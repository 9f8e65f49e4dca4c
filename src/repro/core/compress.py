"""Tile-wise model compression — the paper's technique as a production
feature (DESIGN.md §2).

A weight matrix W (d_in, d_out) is cut into (tile_n x tile_d) tiles; each
tile is an independent integer-decomposition problem W_t ~ M_t C_t with
K = rank_ratio * tile_n.  Tiles are optimised *in parallel* (vmap; sharded
over the mesh under pjit) with one of three back-ends:

  greedy       the paper's original algorithm (Eq. 5)            [fastest]
  alternating  greedy init + exact per-row block-coordinate descent
  bbo          alternating init + nBOCS/SA refinement — the paper's
               contribution; tile_n defaults to 8 so each tile is exactly
               the paper's n = 8K-spin problem scale (BOCS is O(n^5): the
               tiling is what makes the technique deployable on real
               matrices, answering the paper's closing scalability concern)

This module holds the per-tile numerical core (``compress_tile_batch``) and
the single-matrix entry point (``compress_matrix``).  Whole-model
compression lives in :mod:`repro.compression` — a plan/execute API that
pools tiles across tensors into large batched solves; ``compress_params``
below is kept as a thin back-compat wrapper over it.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import CompressionConfig, ModelConfig
from repro.core import bbo as bbo_lib
from repro.core import decomposition as dec

__all__ = [
    "compress_matrix",
    "compress_params",
    "compress_tile_batch",
    "quantize_tile_batch",
    "CompressionReport",
    "tile_matrix",
    "pick_tile",
]


class CompressionReport(NamedTuple):
    compressed: list          # [(path, orig_bytes, new_bytes, rel_err)]
    skipped: list             # [(path, reason)]

    @property
    def total_ratio(self) -> float:
        ob = sum(c[1] for c in self.compressed)
        nb = sum(c[2] for c in self.compressed)
        return ob / max(nb, 1)


def pick_tile(dim: int, want: int, max_tile: int | None = None) -> int | None:
    """The divisor of ``dim`` (>= 4) whose log-ratio to ``want`` is smallest.

    Searching *all* divisors (rather than a fixed {want, want//2, want//4,
    want*2} ladder) means awkward dimensions like 48, 100 or 12 still get a
    sensible tile instead of falling into ``skipped``.  Candidates stay
    within the legacy ladder's envelope [want/4, want*4] (log distance
    <= 2): a divisor far from ``want`` is worse than skipping — e.g. a
    prime-ish dim like 1018 only divides by 509, whose K = ratio*509 would
    blow up alternating's 2^K row enumeration.  Ties prefer the smaller
    divisor (finer tiles pool better and keep BBO instances small);
    ``max_tile`` caps the search (the BBO path caps at 16 so the per-tile
    Ising problem stays at the paper's n = 8K scale).
    """
    best, best_d = None, None
    hi = dim if max_tile is None else min(dim, max_tile)
    for t in range(4, hi + 1):
        if dim % t:
            continue
        d = abs(math.log2(t / want))
        if d > 2.0 + 1e-9:          # outside the [want/4, want*4] envelope
            continue
        if best is None or d < best_d - 1e-12:
            best, best_d = t, d
    return best


_pick_tile = pick_tile  # back-compat alias (pre-plan-API name)


def tile_matrix(W: jax.Array, tn: int, td: int) -> jax.Array:
    """(d_in, d_out) -> (r*c, tn, td) tile stack (row-major over (r, c))."""
    d_in, d_out = W.shape
    r, c = d_in // tn, d_out // td
    t = W.reshape(r, tn, c, td).transpose(0, 2, 1, 3)
    return t.reshape(r * c, tn, td)


def _untile_meta(W_shape, tn, td):
    return W_shape[0] // tn, W_shape[1] // td


@functools.partial(
    jax.jit, static_argnames=("K", "method", "bbo_iters", "backend")
)
def compress_tile_batch(
    tiles: jax.Array,
    keys: jax.Array,
    pool_key: jax.Array,
    K: int,
    method: str,
    bbo_iters: int = 64,
    backend: str = "auto",
    M0: jax.Array | None = None,
):
    """tiles (T, tn, td), per-tile ``keys`` (T,) -> (M (T, tn, K),
    C (T, K, td), rel_err (T,)).

    The per-tile keys drive the greedy/alternating init, so a batch built by
    concatenating tile stacks from *different* tensors (the pooled execute
    path in :mod:`repro.compression.execute`) is bit-identical to running
    each stack separately with the same keys.  ``pool_key`` seeds the BBO
    refinement, which runs all T tiles in lock-step through
    ``bbo_lib.run_bbo_many``: per iteration the T surrogates are fitted
    under vmap and the T Ising instances are solved by one batched
    ``ising.solve_many`` call (``backend`` selects jnp vs Pallas).

    ``M0`` (T, tn, K), when given, warm-starts each tile from a previous
    solution (delta recompression, docs/delta.md).  The cold init still
    runs with the same per-tile keys — so a warm solve can never end worse
    than the cold solve of the same tile — and a second candidate descends
    from ``M0`` (greedy keeps ``M0`` as-is; alternating/bbo run the
    block-coordinate descent from it); the per-tile better of the two by
    ``dec.objective`` proceeds.  For BBO the winner additionally seeds the
    surrogate dataset and the per-iteration Ising solves
    (``run_bbo_many(warm_x=...)``).  ``M0=None`` is the cold path,
    bit-identical to the pre-warm-start function.
    """
    tiles = tiles.astype(jnp.float32)
    T, tn, _ = tiles.shape

    def init_one(W_t, k):
        M = dec.greedy_decompose(W_t, K, k).M
        if method in ("alternating", "bbo"):
            M, _, _ = dec.alternating_decompose(W_t, K, M0=M)
        return M

    # Named scopes label each stage's ops in a profiler trace
    # (docs/compression_api.md, "Tracing a job"); they change no op.
    with jax.named_scope("compress.init"):
        M = jax.vmap(init_one)(tiles, keys)
        if M0 is not None:
            M0 = jnp.where(M0.astype(jnp.float32) < 0.0, -1.0, 1.0)
            if method in ("alternating", "bbo"):
                M_warm = jax.vmap(
                    lambda W_t, m0: dec.alternating_decompose(W_t, K, M0=m0)[0]
                )(tiles, M0)
            else:
                M_warm = M0
            obj = jax.vmap(dec.objective)
            better = obj(M_warm, tiles) < obj(M, tiles)
            M = jnp.where(better[:, None, None], M_warm, M)

    if method == "bbo":
        cfg = bbo_lib.BBOConfig(
            n=tn * K, N=tn, K=K,
            algo="nbocs", solver="sq", iters=bbo_iters,
            init_points=tn * K, num_sweeps=24, num_reads=4,
            backend=backend,
        )

        def f_batch(xs):                                   # (T, n) -> (T,)
            return jax.vmap(lambda W_t, x: dec.objective_from_x(x, W_t, K))(
                tiles, xs
            )

        with jax.named_scope("compress.bbo"):
            res = bbo_lib.run_bbo_many(
                pool_key, cfg, f_batch, T,
                warm_x=M.reshape(T, tn * K) if M0 is not None else None,
            )
            x_bbo = res.best_x.reshape(T, tn, K)
            better = res.best_y < jax.vmap(
                lambda M_t, W_t: dec.objective(M_t, W_t)
            )(M, tiles)
            M = jnp.where(better[:, None, None], x_bbo, M)

    with jax.named_scope("compress.lstsq"):
        C = jax.vmap(dec.least_squares_C)(M, tiles)
        err = jax.vmap(
            lambda M_t, W_t: jnp.sqrt(jnp.maximum(dec.objective(M_t, W_t), 0.0))
            / jnp.maximum(jnp.linalg.norm(W_t), 1e-30)
        )(M, tiles)
    return M, C, err


@jax.jit
def quantize_tile_batch(tiles: jax.Array):
    """tiles (T, tn, td) -> (q (T, tn, td) int8, scale (T, 1, 1) f32,
    rel_err (T,)).

    Symmetric per-tile int8 rounding: ``scale = max|W_t| / 127``,
    ``q = clip(round(W_t / scale), -127, 127)``.  No solver, no keys —
    the closed form is the allocator's executable baseline column (the
    plain integer quantisation the paper's M·C decomposition competes
    against).  ``rel_err`` matches :func:`compress_tile_batch` semantics:
    ``||W_t - scale·q||_F / max(||W_t||_F, 1e-30)``.
    """
    tiles = tiles.astype(jnp.float32)
    amax = jnp.max(jnp.abs(tiles), axis=(1, 2), keepdims=True)
    scale = amax / 127.0
    safe = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(tiles / safe), -127.0, 127.0).astype(jnp.int8)
    recon = q.astype(jnp.float32) * scale
    resid = tiles - recon
    err = jnp.sqrt(jnp.sum(resid * resid, axis=(1, 2))) / jnp.maximum(
        jnp.sqrt(jnp.sum(tiles * tiles, axis=(1, 2))), 1e-30
    )
    return q, scale, err


def _compress_tiles(
    tiles: jax.Array, K: int, method: str, key, bbo_iters: int = 64,
    backend: str = "auto",
):
    """Back-compat single-tensor form: derives per-tile keys from ``key``."""
    keys = jax.random.split(key, tiles.shape[0])
    return compress_tile_batch(
        tiles, keys, jax.random.fold_in(key, 1), K, method,
        bbo_iters=bbo_iters, backend=backend,
    )


def compress_matrix(
    W: jax.Array,
    ccfg: CompressionConfig,
    key=None,
    method: str | None = None,
):
    """Returns ({"m_packed", "C"}, rel_err mean) or (None, reason)."""
    method = method or ccfg.optimizer
    if W.ndim != 2:
        return None, "not 2D"
    if W.size < ccfg.min_size:
        return None, "below min_size"
    tn_want = 8 if method == "bbo" else ccfg.tile_n
    tn = pick_tile(W.shape[0], tn_want, max_tile=16 if method == "bbo" else None)
    td = pick_tile(W.shape[1], ccfg.tile_d)
    if tn is None or td is None:
        return None, f"indivisible dims {tuple(W.shape)}"
    K = max(int(round(ccfg.rank_ratio * tn)), 1)
    if K >= tn:
        return None, "K >= tile_n (no compression)"
    if key is None:
        key = jax.random.PRNGKey(0)

    tiles = tile_matrix(W, tn, td)
    M, C, errs = _compress_tiles(
        tiles, K, method, key, ccfg.bbo_iters, backend=ccfg.solver_backend
    )
    r, c = _untile_meta(W.shape, tn, td)
    packed = jax.vmap(dec.pack_bits)(M).reshape(r, c, tn, -1)
    Cw = C.reshape(r, c, K, td).astype(W.dtype)
    return {"m_packed": packed, "C": Cw}, float(jnp.mean(errs))


# ---------------------------------------------------------------------------
# Whole-model compression (back-compat wrapper over repro.compression)
# ---------------------------------------------------------------------------


def compress_params(
    values: dict,
    cfg: ModelConfig,
    ccfg: CompressionConfig | None = None,
    key=None,
    verbose: bool = False,
):
    """Walk the model values tree; compress eligible linear weights.

    Thin wrapper over the plan/execute API: the ``CompressionConfig`` becomes
    a one-rule :class:`repro.compression.CompressionPolicy`, the tree is
    planned, and the plan executes with tiles *pooled across tensors* into
    batched solves (bit-identical per tensor to the old one-tensor-at-a-time
    walk for greedy/alternating; see tests/test_compression_api.py).
    Returns (new_values, CompressionReport).
    """
    from repro import compression as comp

    ccfg = ccfg or cfg.compression
    plan = comp.plan_compression(values, ccfg.to_policy())
    new_values, artifact = comp.execute_plan(
        plan, values, key=key, verbose=verbose
    )
    return new_values, artifact.report
