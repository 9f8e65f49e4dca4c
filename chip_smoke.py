#!/usr/bin/env python3
"""Chip smoke: the main path once on one TPU, end to end.

    python chip_smoke.py

Runs granite-moe-1b-a400m at its full published config (24 layers, d_model
1024, 32 experts top-8; random weights from ``--seed``) through the entry
points a user calls, in one process:

  (a) device    fail unless JAX's first device is a TPU;
  (b) solver    ``ising.solve_many`` SA/SQ/QA on the compiled Pallas
                kernels vs the jnp oracle on the same keys, at n = tile_n*K
                spins (the size compression hands it);
  (c) compress  plan + execute the whole model with the default policy and
                one BBO rule (the paper's solver inside ``execute_plan``),
                save checkpoint + manifest, restore them as
                ``launch/serve.py`` does;
  (d) serve     ``Engine`` on the fused Pallas bitlinear path, every kernel
                resolution checked compiled, requests through ``Scheduler``
                and ``ServeFrontend``; prefill and first decode-step logits
                of the fused path vs the unpack+einsum path on the same
                weights.

Each phase prints its wall time with compile time apart.  No phase catches
its own failure; the last line of stdout is one JSON object naming the
device, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "granite-moe-1b-a400m"
# The default policy (launch/compress.py flags): tile 32x128, K = 4.
TILE_N, TILE_D, RANK_RATIO, MIN_SIZE = 32, 128, 0.125, 1 << 16
# One tensor through the paper's BBO at its own scale: tile_n = 8 rows
# (the paper's N = 8), K = 2 -> n = 16 spins, whose nBOCS surrogate is
# ~0.23 MB per tile (execute.surrogate_tile_bytes); the default geometry's
# n = 128 would need ~0.8 GB per tile.
BBO_RULE = dict(pattern=r"attn/wk/w$", method="bbo", tile_n=8, tile_d=512,
                rank_ratio=0.25, bbo_iters=8)
PROMPT_LEN, NEW_TOKENS, REQUESTS = 128, 32, 8
# Solver agreement, pallas vs jnp on the same uniforms.  Where the spins
# match, the energies differ only by summation order (f32, n = 128 terms).
# Where an accept flipped — u within an ulp of exp(-dE/T), whose Mosaic
# and XLA lowerings may differ in the last bit — the chain took another
# path to another local minimum: its best energy must stay within 5%, a
# loose bound for two anneals of one instance that share their start and
# all but the flipped uniform.
SOLVER_MATCH_RTOL = 1e-4
SOLVER_FLIP_RTOL = 5e-2
SOLVER_MIN_MATCH = 0.9      # share of problems whose spins must match
# Fused vs unpack+einsum logits, both f32 at HIGHEST precision: the same
# products summed in another order (~1e-7 relative per layer), through 24
# layers.  Bound: 1e-3 of the largest |logit|, far above that noise and far
# below the O(1) error of a wrong tile, column or expert.
LOGIT_RTOL = 1e-3

# Lowering to StableHLO and the XLA/Mosaic compile, once per program.
# (Tracing is left out: its events nest, one per inner jit.)
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Seconds JAX spent lowering and compiling since the last ``lap``,
    from its own monitoring events."""

    def __init__(self):
        self._secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self._secs += duration

    def lap(self) -> float:
        secs, self._secs = self._secs, 0.0
        return secs


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke failed: {what}")


def phase_time(name: str, t0: float, clock: CompileClock) -> None:
    wall = time.perf_counter() - t0
    compile_s = clock.lap()
    print(f"[{name}] wall {wall:.1f}s, of which compile {compile_s:.1f}s, "
          f"run {wall - compile_s:.1f}s", flush=True)


def check_device():
    """Phase (a)."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip smoke needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        sys.exit(1)
    print(f"[device] {dev.device_kind} x{len(devices)}", flush=True)
    return dev, len(devices)


def run_solvers(seed: int) -> None:
    """Phase (b): every solver on the compiled Pallas kernels against the
    jnp oracle, on the batch shape compression hands it."""
    from repro.core import ising

    n, P = TILE_N * int(RANK_RATIO * TILE_N), 64
    probs = ising.random_problems(jax.random.PRNGKey(seed), P, n)
    key = jax.random.PRNGKey(seed + 1)
    for solver in ("sa", "sq", "qa"):
        xp, ep = ising.solve_many(solver, key, probs, backend="pallas",
                                  interpret=False)
        xj, ej = ising.solve_many(solver, key, probs, backend="jnp")
        xp, ep, xj, ej = (np.asarray(a) for a in (xp, ep, xj, ej))
        match = np.all(xp == xj, axis=1)
        rel = np.abs(ep - ej) / np.maximum(np.abs(ej), 1.0)
        print(f"[solver] {solver}: P={P} n={n}: spins match on "
              f"{int(match.sum())}/{P}; max rel energy diff "
              f"{float(rel[match].max(initial=0.0)):.2e} where they match, "
              f"{float(rel[~match].max(initial=0.0)):.2e} where they differ",
              flush=True)
        require(match.mean() >= SOLVER_MIN_MATCH,
                f"{solver}: pallas spins match jnp on {match.mean():.0%}")
        require(bool(np.all(rel[match] <= SOLVER_MATCH_RTOL)),
                f"{solver}: energies of matching spins differ")
        require(bool(np.all(rel[~match] <= SOLVER_FLIP_RTOL)),
                f"{solver}: a diverged chain's energy is off by > 5%")


def compress(cfg, out_dir: str, seed: int):
    """Phase (c): plan + execute the full model, save, restore.  Returns
    (restored values, artifact)."""
    from repro.compression import (
        CompressionPolicy, CompressionRule, plan_compression,
    )
    from repro.launch.compress import execute_and_save
    from repro.launch.serve import restore_checkpoint
    from repro.models import init_model
    from repro.models.params import split

    values, _ = split(init_model(jax.random.PRNGKey(seed), cfg))
    policy = CompressionPolicy(
        method="alternating", tile_n=TILE_N, tile_d=TILE_D,
        rank_ratio=RANK_RATIO, min_size=MIN_SIZE, solver_backend="pallas",
        rules=(CompressionRule(**BBO_RULE),),
    )
    plan = plan_compression(values, policy)
    print(plan.summary(), flush=True)
    require(any(t.method == "bbo" for t in plan.tensors),
            "the BBO rule matched no tensor")
    shutil.rmtree(out_dir, ignore_errors=True)
    _, artifact, dt = execute_and_save(plan, values, out_dir, seed=seed,
                                       verbose=False)
    totals = artifact.manifest["totals"]
    print(f"[compress] {len(artifact.manifest['tensors'])} tensors "
          f"compressed in {dt:.1f}s: {totals['orig_bytes'] / 2**20:.1f} -> "
          f"{totals['new_bytes'] / 2**20:.1f} MiB, byte ratio "
          f"x{totals['ratio']:.3f}", flush=True)
    for pool in artifact.manifest["pools"]:
        print(f"[compress] pool {pool['method']} {pool['tile_n']}x"
              f"{pool['tile_d']} K={pool['K']}: {pool['num_tiles']} tiles "
              f"in {pool['chunks']} chunk(s)", flush=True)
    restored, restored_art = restore_checkpoint(out_dir, values)
    require(restored_art is not None, "no compressed checkpoint restored")
    require(restored_art.fingerprint() == artifact.fingerprint(),
            "restored manifest differs from the one saved")
    return restored, restored_art


def as_float32(values, artifact):
    """An f32 copy of a compressed model: every float leaf upcast
    (``m_packed`` stays packed) and the manifest's C dtypes to match."""
    manifest = copy.deepcopy(artifact.manifest)
    for entry in manifest["tensors"].values():
        entry["C"]["dtype"] = "float32"
    values = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        values,
    )
    return values, manifest


def serve(cfg, values, artifact, seed: int) -> float:
    """Phase (d): fused serving through the scheduler and front end, and
    fused vs unpack+einsum logits.  Returns the largest logit difference."""
    from repro.kernels import autotune, ops
    from repro.launch.serve import serve_load_curve
    from repro.models import init_cache
    from repro.serving.engine import Engine

    max_len = PROMPT_LEN + NEW_TOKENS
    autotune.clear_log()
    fused = Engine(cfg, values, max_len=max_len, batch=REQUESTS,
                   artifact=artifact)
    ops.enable_kernels(interpret=False)
    require(fused.fused_bitlinear, "engine is not on the fused path")
    print(f"[serve] engine: {fused.compression}", flush=True)

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(REQUESTS, PROMPT_LEN),
                           dtype=np.int32)
    (res,) = serve_load_curve(fused, list(prompts), max_tokens=NEW_TOKENS,
                              rates=[64.0], num_slots=REQUESTS)
    print(f"[serve] requests completed {res.completed}/{REQUESTS}, "
          f"{res.total_tokens} tokens, p50 {res.p50_latency_s * 1e3:.1f} ms, "
          f"p99 {res.p99_latency_s * 1e3:.1f} ms", flush=True)
    require(res.completed == REQUESTS, "not every request completed")
    require(res.total_tokens == REQUESTS * NEW_TOKENS,
            "a request ended short of its new tokens")

    # The comparison runs an f32 copy of the same compressed weights,
    # dropless (capacity E/k), every matmul at HIGHEST precision.  In bf16
    # the forward is chaotic through routing: one bf16 ulp can flip a token's
    # top-k or reorder an expert's queue under capacity and drop another
    # token.  (On the CPU, a 2^-7 nudge of one wq entry per layer moved one
    # of 8 sequences' last logits by 1.63 of 4.72 at 4 layers.)  The served
    # engine above keeps bf16 and the config's capacity factor.
    values32, manifest32 = as_float32(values, artifact)
    cmp_cfg = dataclasses.replace(
        cfg, dtype="float32",
        capacity_factor=cfg.num_experts / cfg.experts_per_token,
    )

    def first_steps(fused_path: bool):
        eng = Engine(cmp_cfg, values32, max_len=max_len, batch=REQUESTS,
                     artifact=manifest32,
                     use_fused_bitlinear=None if fused_path else False)
        if fused_path:
            ops.enable_kernels(interpret=False)
        with jax.default_matmul_precision("highest"):
            cache = init_cache(cmp_cfg, REQUESTS, max_len)
            last, cache = eng.prefill(
                eng.params, {"tokens": jnp.asarray(prompts)}, cache
            )
            tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
            step, _ = eng.decode(eng.params, tok, cache, PROMPT_LEN)
        return np.asarray(last), np.asarray(step)

    fused_logits = first_steps(True)
    resolutions = autotune.last_resolutions()
    for key, mode, math, source in sorted({
        (r["key"], r["schedule"]["mode"], r["schedule"]["math"], r["source"])
        for r in resolutions
    }):
        print(f"[serve] kernel {key} -> {mode}/{math} ({source})")
    require(bool(resolutions), "no bitlinear resolution was traced")
    require(all(r["schedule"]["mode"] != "jnp" and "|compiled|" in r["key"]
                for r in resolutions),
            "a kernel resolved to jnp or interpret mode")

    einsum_logits = first_steps(False)
    worst = 0.0
    for name, a, b in zip(("prefill", "decode"), fused_logits, einsum_logits):
        require(bool(np.all(np.isfinite(a))), f"{name} logits not finite")
        per_seq = np.max(np.abs(a - b), axis=-1)
        diff = float(per_seq.max())
        scale = float(np.max(np.abs(b)))
        print(f"[serve] {name} logits {a.shape} f32: max |fused - einsum| "
              f"{diff:.3g} of max |logit| {scale:.3f} (bound "
              f"{LOGIT_RTOL * scale:.3g}); per sequence "
              f"{np.array2string(per_seq, precision=2, max_line_width=200)}",
              flush=True)
        require(diff <= LOGIT_RTOL * scale,
                f"{name} logits: fused and einsum paths disagree")
        worst = max(worst, diff)
    return worst


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", default=os.path.join(ROOT, "experiments",
                                                      "chip_smoke"),
                    help="compressed checkpoint + manifest (removed after)")
    args = ap.parse_args()

    clock = CompileClock()
    t0 = time.perf_counter()
    dev, count = check_device()
    print(f"[cache] {enable_compile_cache()}")
    from repro.configs import get_config

    cfg = get_config(ARCH)
    print(f"[model] {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads} heads / {cfg.num_kv_heads} kv, "
          f"{cfg.num_experts} experts top-{cfg.experts_per_token}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}", flush=True)
    phase_time("device", t0, clock)

    t0 = time.perf_counter()
    run_solvers(args.seed)
    phase_time("solver", t0, clock)

    t0 = time.perf_counter()
    values, artifact = compress(cfg, args.out_dir, args.seed)
    phase_time("compress", t0, clock)

    t0 = time.perf_counter()
    worst = serve(cfg, values, artifact, args.seed)
    phase_time("serve", t0, clock)
    shutil.rmtree(args.out_dir, ignore_errors=True)

    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"[memory] peak_bytes_in_use {peak}")
    print(f"[done] largest fused-vs-einsum logit difference {worst:.3g}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
