"""Kernel schedule autotuner for the fused bitlinear path.

The bitlinear kernel (``repro.kernels.bitlinear``) exposes a small schedule
space — mode (grid / decode / jnp), bit algebra (unpack / bitplane /
dot), token block ``block_t`` and reduction chunking
``r_chunk`` — and the best point depends on (tile geometry, token count,
dtype, device, pallas execution mode) in ways a static heuristic can't
rank: on TPU the decode fast path wins until the column working set
overflows VMEM, while under interpret mode (CPU CI, the committed bench
lane) pallas per-call overhead dwarfs these skinny matmuls and the jnp
formulations win outright.

This module mirrors the RD autotuner's probe-then-serve split
(``compression/autotune.py`` searches (K, tile) per tensor; this searches
the kernel schedule per call signature):

  * :func:`tune` — timed best-of-N trials over the candidate schedules for
    one concrete call; :func:`tune_artifact` sweeps every distinct
    (geometry, T-bucket) a compression manifest can produce and persists
    the winners into ``manifest["kernel_schedules"]``.
  * :func:`resolve` — cache lookup by :func:`schedule_key` with a
    heuristic cost-model fallback, called at trace time by the ops-layer
    adapters (``ops.apply_compressed_fused`` / ``_grouped_fused``) so
    serving never re-tunes: ``Engine`` restores the manifest's schedule
    table via :func:`load_schedules` before enabling kernels.

Keys embed ``device`` and ``pallas_mode``, so a manifest tuned on TPU
hardware coexists with the interpret-mode entries and a compiled-mode
lane lands as new rows without schema changes (docs/kernels.md).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Iterable, Sequence

import jax
import numpy as np

from repro.kernels import bitlinear as _bl

__all__ = [
    "Schedule",
    "SCHEDULES_FORMAT",
    "schedule_key",
    "t_bucket",
    "device_kind",
    "pallas_mode",
    "resolve",
    "resolve_fused",
    "resolve_grouped",
    "heuristic",
    "candidates",
    "tune",
    "tune_artifact",
    "load_schedules",
    "export_schedules",
    "clear_schedules",
    "last_resolutions",
    "clear_log",
]

SCHEDULES_FORMAT = "repro.kernel_schedules/v1"

_T_BUCKET_CAP = 512
_LOG_CAP = 512


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One point of the bitlinear schedule space.  ``math`` "dot" is only
    meaningful for mode "jnp" (the pallas kernels coerce it to unpack)."""

    mode: str
    math: str = "unpack"
    block_t: int = 128
    r_chunk: int = 1

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Schedule":
        return cls(
            mode=d["mode"],
            math=d.get("math", "unpack"),
            block_t=int(d.get("block_t", 128)),
            r_chunk=int(d.get("r_chunk", 1)),
        )

    def kwargs(self) -> dict:
        return {
            "mode": self.mode,
            "math": self.math,
            "block_t": self.block_t,
            "r_chunk": self.r_chunk,
        }


# ---------------------------------------------------------------------------
# keys and environment
# ---------------------------------------------------------------------------


def device_kind() -> str:
    """The chip as JAX names it (``jax.devices()[0].device_kind``, e.g.
    "TPU v5 lite"), so tuned keys separate chip generations."""
    return jax.devices()[0].device_kind


def pallas_mode() -> str:
    """"compiled" on TPU, "interpret" elsewhere — matches
    ``ops.default_interpret()`` and the BENCH_* row schema."""
    return "compiled" if jax.default_backend() == "tpu" else "interpret"


def t_bucket(T: int) -> int:
    """Token counts are bucketed to the next power of two (capped) so a
    tuned table covers nearby batch sizes instead of exact T only."""
    b = 1
    while b < min(int(T), _T_BUCKET_CAP):
        b *= 2
    return b


def schedule_key(
    kind: str,
    *,
    n_r: int,
    n_c: int,
    tn: int,
    K: int,
    td: int,
    T: int,
    dtype,
    E: int = 0,
    device: str | None = None,
    mode: str | None = None,
) -> str:
    """Cache key for one call signature.  ``kind`` is "bitlinear" or
    "bitlinear_grouped" (E = expert count, 0 for 2D)."""
    device = device_kind() if device is None else device
    mode = pallas_mode() if mode is None else mode
    return (
        f"v1|{kind}|{device}|{mode}|r{n_r}c{n_c}n{tn}k{K}d{td}"
        f"|E{E}|T{t_bucket(T)}|{np.dtype(dtype).name}"
    )


# ---------------------------------------------------------------------------
# cache + resolution log
# ---------------------------------------------------------------------------

_CACHE: dict[str, Schedule] = {}
_LOG: list[dict] = []


def load_schedules(table: dict) -> int:
    """Install a ``manifest["kernel_schedules"]`` table into the process
    cache (returns the number of entries).  Called by ``Engine`` before
    ``enable_kernels`` so tuned schedules apply at first trace."""
    fmt = table.get("format")
    if fmt != SCHEDULES_FORMAT:
        raise ValueError(
            f"unsupported kernel schedule format {fmt!r} "
            f"(expected {SCHEDULES_FORMAT!r})"
        )
    entries = table.get("entries", {})
    for key, d in entries.items():
        _CACHE[key] = Schedule.from_dict(d)
    return len(entries)


def export_schedules(extra: dict | None = None) -> dict:
    """The process cache as a manifest-embeddable table."""
    out = {
        "format": SCHEDULES_FORMAT,
        "tuned_on": {"device": device_kind(), "pallas_mode": pallas_mode()},
        "entries": {k: s.to_dict() for k, s in sorted(_CACHE.items())},
    }
    if extra:
        out.update(extra)
    return out


def clear_schedules() -> None:
    _CACHE.clear()


def last_resolutions() -> list[dict]:
    """Trace-time resolution log: one entry per :func:`resolve` call,
    ``{"key", "schedule", "source"}`` with source "cache" or "heuristic".
    The schedule-cache round-trip test asserts on this."""
    return list(_LOG)


def clear_log() -> None:
    _LOG.clear()


# ---------------------------------------------------------------------------
# heuristic cost model (defaults when no cache entry matches)
# ---------------------------------------------------------------------------


def _largest_divisor_leq(n: int, cap: int) -> int:
    d = max(1, min(cap, n))
    while n % d:
        d -= 1
    return d


def heuristic(
    kind: str,
    *,
    n_r: int,
    n_c: int,
    tn: int,
    kb: int,
    K: int,
    td: int,
    T: int,
    x_itemsize: int,
    c_itemsize: int,
    interpret: bool | None = None,
) -> Schedule:
    """Static cost-model default.  Interpret mode (non-TPU): pallas per-call
    overhead (~50-100us) exceeds the whole matmul at serving shapes, so the
    jnp schedule wins everywhere; the batched-dot formulation has the
    cheapest CPU lowering.  Compiled mode: decode when one output column's
    M/C working set fits VMEM (bitplane pays off when the token block is
    skinnier than the tile rows), else the pipelined grid with the
    r-reduction chunked toward ~1k rows per grid step."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret:
        return Schedule(mode="jnp", math="dot")
    bt = min(128, -(-T // 8) * 8)
    Tp = -(-T // bt) * bt
    if Tp <= bt and _bl._decode_path_ok(
        Tp, n_r * tn, n_r, tn, kb, K, td, x_itemsize, c_itemsize,
        _bl._vmem_budget(None),
    ):
        math = "bitplane" if Tp < tn else "unpack"
        return Schedule(mode="decode", math=math)
    r_chunk = _largest_divisor_leq(n_r, max(1, 1024 // tn))
    return Schedule(mode="grid", math="unpack", block_t=128, r_chunk=r_chunk)


def resolve(
    kind: str,
    *,
    n_r: int,
    n_c: int,
    tn: int,
    kb: int,
    K: int,
    td: int,
    T: int,
    dtype,
    E: int = 0,
    c_itemsize: int | None = None,
) -> Schedule:
    """Schedule for one call signature: tuned cache entry when one matches
    the current (device, pallas_mode), heuristic default otherwise.  Pure
    python on static shapes — safe to call at trace time."""
    key = schedule_key(
        kind, n_r=n_r, n_c=n_c, tn=tn, K=K, td=td, T=T, dtype=dtype, E=E
    )
    sched = _CACHE.get(key)
    source = "cache"
    if sched is None:
        source = "heuristic"
        itemsize = np.dtype(dtype).itemsize
        sched = heuristic(
            kind, n_r=n_r, n_c=n_c, tn=tn, kb=kb, K=K, td=td, T=T,
            x_itemsize=itemsize,
            c_itemsize=itemsize if c_itemsize is None else c_itemsize,
        )
    if len(_LOG) >= _LOG_CAP:
        del _LOG[: _LOG_CAP // 2]
    _LOG.append({"key": key, "schedule": sched.to_dict(), "source": source})
    return sched


def resolve_fused(x, m_packed, C) -> Schedule:
    """Trace-time resolution for ``ops.apply_compressed_fused`` operands
    (x already flattened to (T, d_in))."""
    n_r, n_c, tn, kb = m_packed.shape
    K, td = C.shape[-2:]
    return resolve(
        "bitlinear", n_r=n_r, n_c=n_c, tn=tn, kb=kb, K=K, td=td,
        T=x.shape[0], dtype=x.dtype, c_itemsize=C.dtype.itemsize,
    )


def resolve_grouped(x, m_packed, C) -> Schedule:
    E, n_r, n_c, tn, kb = m_packed.shape
    K, td = C.shape[-2:]
    return resolve(
        "bitlinear_grouped", n_r=n_r, n_c=n_c, tn=tn, kb=kb, K=K, td=td,
        T=x.shape[1], dtype=x.dtype, E=E, c_itemsize=C.dtype.itemsize,
    )


# ---------------------------------------------------------------------------
# candidate generation + timed search
# ---------------------------------------------------------------------------


def candidates(
    kind: str,
    *,
    n_r: int,
    n_c: int,
    tn: int,
    kb: int,
    K: int,
    td: int,
    T: int,
    x_itemsize: int,
    c_itemsize: int,
) -> list[Schedule]:
    """The schedule points :func:`tune` times for one call signature.
    Invalid points (decode working set over budget) are filtered here, and
    grid chunks are deduplicated by the chunk the kernel actually runs
    (``bitlinear._resolve_r_chunk`` raises a chunk to Mosaic's 128-lane x
    block), so the search never times a schedule serving would refuse or
    the same kernel twice."""
    out = [Schedule(mode="jnp", math=m) for m in ("unpack", "dot", "bitplane")]
    r_chunks = sorted({
        _bl._resolve_r_chunk(n_r, tn, c) for c in (1, 2, 4, 8)
    })
    block_ts = [128] if T <= 64 else [64, 128, 256]
    for math in _bl.MATHS:
        for bt in block_ts:
            for rc in r_chunks:
                out.append(Schedule("grid", math, bt, rc))
        btk = min(128, -(-T // 8) * 8)
        Tp = -(-T // btk) * btk
        if Tp <= btk and _bl._decode_path_ok(
            Tp, n_r * tn, n_r, tn, kb, K, td, x_itemsize, c_itemsize,
            _bl._vmem_budget(None),
        ):
            out.append(Schedule("decode", math))
    return out


def _bench_once(fn, repeats: int, iters: int) -> float:
    """Best-of-``repeats`` wall time of ``iters`` back-to-back calls
    (seconds per call).  First call compiles and is excluded."""
    fn().block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            y = fn()
        y.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def tune(
    x,
    m_packed,
    C,
    *,
    interpret: bool | None = None,
    schedules: Iterable[Schedule] | None = None,
    repeats: int = 3,
    iters: int = 10,
) -> tuple[Schedule, list[dict]]:
    """Timed best-of-N search over the candidate schedules for one concrete
    call; returns (best, trials).  Grouped operands (x.ndim == 3) route to
    ``bitlinear_grouped``.  Every schedule must name a kernel mode; one
    that fails to lower raises — a tuned table never records a fallback
    in place of the kernel it could not compile."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    grouped = x.ndim == 3
    kind = "bitlinear_grouped" if grouped else "bitlinear"
    if grouped:
        E, T, _ = x.shape
        _, n_r, n_c, tn, kb = m_packed.shape
    else:
        E = 0
        T, _ = x.shape
        n_r, n_c, tn, kb = m_packed.shape
    K, td = C.shape[-2:]
    if schedules is None:
        schedules = candidates(
            kind, n_r=n_r, n_c=n_c, tn=tn, kb=kb, K=K, td=td, T=T,
            x_itemsize=x.dtype.itemsize, c_itemsize=C.dtype.itemsize,
        )
    schedules = list(schedules)
    unknown = [s for s in schedules if s.mode not in _bl.MODES]
    if unknown:
        raise ValueError(f"unknown bitlinear mode(s) for {kind}: {unknown}")
    call = _bl.bitlinear_grouped if grouped else _bl.bitlinear

    trials = []
    best: Schedule | None = None
    best_t = float("inf")
    for s in schedules:
        # time a jitted closure: serving calls the kernel from inside a
        # jitted step, so the python wrapper's static dispatch must not
        # count against fast schedules
        jfn = jax.jit(
            functools.partial(call, interpret=interpret, **s.kwargs())
        )
        dt = _bench_once(lambda: jfn(x, m_packed, C), repeats, iters)
        trials.append({"schedule": s.to_dict(), "seconds": dt})
        if dt < best_t:
            best, best_t = s, dt
    if best is None:
        raise ValueError(f"no bitlinear schedule to time for {kind}")
    return best, trials


# ---------------------------------------------------------------------------
# manifest-level tuning (probe once, serve forever)
# ---------------------------------------------------------------------------


def _entry_geometry(entry: dict):
    """(E, n_r, n_c, tn, kb, K, td, dtype) of the call signature a manifest
    tensor actually serves through; E = 0 for the 2D kernel.  The layer
    scan slices off the *first* lead dim at trace time, so a plain layer
    stack (one lead dim) serves 2D and only a layer x expert stack keeps a
    group axis for the grouped kernel (cf. Engine's grouped_tensors)."""
    mp_shape = tuple(entry["m_packed"]["shape"])
    c_shape = tuple(entry["C"]["shape"])
    lead = mp_shape[:-4]
    E = int(np.prod(lead[1:])) if len(lead) >= 2 else 0
    n_r, n_c, tn, kb = mp_shape[-4:]
    K, td = c_shape[-2:]
    return E, n_r, n_c, tn, kb, K, td, np.dtype(entry["dtype"])


def tune_artifact(
    manifest_or_artifact,
    *,
    T_values: Sequence[int] = (1, 4, 16, 128),
    seed: int = 0,
    repeats: int = 3,
    iters: int = 10,
    schedules: Iterable[Schedule] | None = None,
    verbose: bool = False,
) -> dict:
    """Probe every distinct (kind, geometry, T-bucket, dtype) signature a
    compression manifest can produce, time the candidate schedules, and
    persist the winners into ``manifest["kernel_schedules"]`` (also
    installed into the process cache).  Operands are synthesized from the
    manifest shapes — timing depends on shapes, not checkpoint values — so
    tuning needs no params tree.  Returns the schedule table."""
    manifest = getattr(manifest_or_artifact, "manifest", manifest_or_artifact)
    if schedules is not None:
        schedules = list(schedules)   # reused across signatures
    rng = np.random.default_rng(seed)
    seen: set[str] = set()
    n_tuned = 0
    for path, entry in manifest.get("tensors", {}).items():
        if entry.get("method") == "int8":
            # int8-baseline tensors serve via dequant-einsum only (no
            # {"m_packed", "C"} factors, no fused kernel to schedule)
            continue
        E, n_r, n_c, tn, kb, K, td, dtype = _entry_geometry(entry)
        kind = "bitlinear_grouped" if E else "bitlinear"
        for T in T_values:
            key = schedule_key(
                kind, n_r=n_r, n_c=n_c, tn=tn, K=K, td=td, T=T, dtype=dtype,
                E=E,
            )
            if key in seen:
                continue
            seen.add(key)
            Tb = t_bucket(T)
            xsh = (E, Tb, n_r * tn) if E else (Tb, n_r * tn)
            x = jax.numpy.asarray(
                rng.standard_normal(xsh).astype(np.float32), dtype=dtype
            )
            mpsh = (E, n_r, n_c, tn, kb) if E else (n_r, n_c, tn, kb)
            mp = jax.numpy.asarray(
                rng.integers(0, 256, mpsh).astype(np.uint8)
            )
            csh = (E, n_r, n_c, K, td) if E else (n_r, n_c, K, td)
            C = jax.numpy.asarray(
                rng.standard_normal(csh).astype(np.float32), dtype=dtype
            )
            best, trials = tune(
                x, mp, C, repeats=repeats, iters=iters, schedules=schedules
            )
            _CACHE[key] = best
            n_tuned += 1
            if verbose:
                dt = min(t["seconds"] for t in trials)
                print(
                    f"[autotune] {key} -> {best.mode}/{best.math}"
                    f" bt={best.block_t} rc={best.r_chunk}"
                    f" ({dt * 1e6:.1f} us)"
                )
    table = export_schedules()
    manifest["kernel_schedules"] = table
    return table
