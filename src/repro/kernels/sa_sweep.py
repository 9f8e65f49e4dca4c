"""Pallas TPU kernel: batched simulated-annealing sweeps for Ising solves.

The BBO inner loop (repro/core) solves thousands of small Ising problems —
one per matrix tile x restart chain.  At the compression sizes (n = tile_n
* K <= 128 spins) one coupling matrix B (n x n f32 <= 64 KiB) sits in VMEM,
so whole annealing runs execute on-chip with no HBM traffic beyond the
initial block load.

Two entry points:

``sa_sweep_many``
    The batched backend used by ``repro.core.ising.solve_many``: a block of
    ``block_p`` problems per grid cell, every (problem, chain) pair updated
    in lock-step vectorised Metropolis sweeps.  grid = (P // block_p,);
    within a cell the state is x (bp, C, n), f (bp, C, n) and a spin update
    is a masked lane pick plus a rank-3 FMA with row i of B — no scatter,
    which is what makes this the fast path (the pure-jnp oracle pays a
    batched scatter per spin).  Every block keeps the last two dims of its
    array whole, so any ``block_p`` meets Mosaic's (8, 128) block rule.
``sq_sweep_many``
    The constant-temperature simulated-quench path: same kernel, the
    (P, S) schedule is just filled with one temperature; a trace names
    its kernel ``sq_sweep_many``, SA's ``sa_sweep_many``.
``sa_sweep``
    Backward-compatible single-problem wrapper.

Randomness: pre-drawn uniforms are streamed in (P, chains, sweeps, n) —
this keeps the kernel bit-exact against the pure-jnp oracles in ref.py
(and avoids pltpu PRNG in interpret mode).  Spin update i uses
    dE = -2 x_i (h_i + 2 (B x)_i);  accept iff  dE < 0 or u < exp(-dE / T_s).

The initial state ``x0`` is likewise caller-supplied, which makes it the
warm-start surface: ``solve_many(init_state=...)`` (docs/delta.md) simply
replaces chain 0's random x0 before invoking the kernel — the kernel
itself has no cold/warm distinction and stays bit-exact vs the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["sa_sweep", "sa_sweep_many", "sq_sweep_many"]


def _pick(a, mask):
    """``a[..., i]`` as a (..., 1) column, for the lane ``i`` where ``mask``
    is set.  A masked lane sum: Mosaic lowers no dynamic slice on the lane
    axis, and adding zeros keeps the pick exact."""
    return jnp.sum(jnp.where(mask, a, 0.0), axis=-1, keepdims=True)


def _bx(x, B):
    """B x per (problem, chain): x (bp, C, n), B (bp, n, n) -> (bp, C, n),
    at full f32 precision on every backend (the TPU default would round B
    to bf16 and drift from the oracle)."""
    return jax.lax.dot_general(
        x, B, (((2,), (2,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _energies(h, x, B):
    """Ising energy h.x + x.Bx of every chain: h (bp, 1, n) -> (bp, C, 1)."""
    return jnp.sum(x * h, axis=2, keepdims=True) + jnp.sum(
        x * _bx(x, B), axis=2, keepdims=True
    )


def _many_kernel(h_ref, b_ref, x0_ref, rand_ref, temps_ref, x_ref, e_ref):
    """Lock-step Metropolis anneal of a block of problems.

    h (bp, 1, n) · B (bp, n, n) · x0 (bp, C, n) · rand (bp, S, C, n) ·
    temps (bp, 1, S)  ->  x (bp, C, n), e (bp, C, 1).  The independent
    oracle ``ref.sa_sweep_ref`` consumes the same uniforms in the same
    (sweep, spin) order — keep the two in lock-step.  Spin ``i`` is picked
    by a lane mask and row ``i`` of B is a dynamic sublane load, so nothing
    slices the lane axis at a traced offset.
    """
    h = h_ref[...]
    temps = temps_ref[...]
    bp, C, n = x0_ref.shape
    S = temps.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n), 2)
    sweep_lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, S), 2)
    x = x0_ref[...]
    f = h + 2.0 * _bx(x, b_ref[...])                     # local fields

    def sweep_body(s, carry):
        t = jnp.maximum(_pick(temps, sweep_lane == s), 1e-12)   # (bp, 1, 1)
        u_s = rand_ref[:, s]                                      # (bp, C, n)

        def spin_body(i, carry):
            x, f = carry
            m = lane == i
            xi = _pick(x, m)                                      # (bp, C, 1)
            dE = -2.0 * xi * _pick(f, m)
            accept = (dE < 0.0) | (_pick(u_s, m) < jnp.exp(-dE / t))
            delta = jnp.where(accept, -2.0 * xi, 0.0)
            brow = b_ref[:, pl.ds(i, 1), :]          # row i == col i, (bp, 1, n)
            f = f + 2.0 * brow * delta
            x = x + jnp.where(m, delta, 0.0)
            return x, f

        return jax.lax.fori_loop(0, n, spin_body, carry)

    x, _ = jax.lax.fori_loop(0, S, sweep_body, (x, f))
    x_ref[...] = x
    e_ref[...] = _energies(h, x, b_ref[...])


# VMEM held by one grid cell's blocks, double-buffered: half the 16 MiB
# scoped default, so the kernel's own temporaries fit beside them.
_VMEM_BLOCK_BUDGET = 8 * 1024 * 1024


def _tile_bytes(*shape: int) -> int:
    """f32 bytes of one block once Mosaic pads its last two dims to the
    (8, 128) vreg tile."""
    lead = 1
    for d in shape[:-2]:
        lead *= d
    return 4 * lead * -(-shape[-2] // 8) * 8 * -(-shape[-1] // 128) * 128


def auto_block_p(P: int, per_problem_bytes: int, interpret: bool) -> int:
    """Largest divisor of P whose double-buffered blocks fit the VMEM
    budget.  Interpret mode has no VMEM: one cell (fewest grid steps)."""
    if interpret:
        return P
    bp = min(P, max(1, _VMEM_BLOCK_BUDGET // (2 * per_problem_bytes)))
    while P % bp:
        bp -= 1
    return bp


def _sweep_many(h, B, x0, rand, temps, block_p, interpret, name):
    """The batched kernel call behind ``sa_sweep_many`` and
    ``sq_sweep_many``; ``name`` is the kernel's name in a profiler trace."""
    P, C, n = x0.shape
    S = temps.shape[1]
    per_problem = (
        _tile_bytes(1, n) + _tile_bytes(n, n) + 3 * _tile_bytes(C, n)
        + _tile_bytes(S, C, n) + _tile_bytes(1, S) + _tile_bytes(C, 1)
    )
    bp = block_p or auto_block_p(P, per_problem, interpret)
    if P % bp != 0:
        raise ValueError(f"block_p={bp} must divide problems={P}")

    x, e = pl.pallas_call(
        _many_kernel,
        grid=(P // bp,),
        in_specs=[
            pl.BlockSpec((bp, 1, n), lambda p: (p, 0, 0)),
            pl.BlockSpec((bp, n, n), lambda p: (p, 0, 0)),
            pl.BlockSpec((bp, C, n), lambda p: (p, 0, 0)),
            pl.BlockSpec((bp, S, C, n), lambda p: (p, 0, 0, 0)),
            pl.BlockSpec((bp, 1, S), lambda p: (p, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bp, C, n), lambda p: (p, 0, 0)),
            pl.BlockSpec((bp, C, 1), lambda p: (p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, C, n), jnp.float32),
            jax.ShapeDtypeStruct((P, C, 1), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(
        h.astype(jnp.float32)[:, None, :],
        B.astype(jnp.float32),
        x0.astype(jnp.float32),
        # sweep-major uniforms: sweep s is one leading-axis load in-kernel
        rand.astype(jnp.float32).transpose(0, 2, 1, 3),
        temps.astype(jnp.float32)[:, None, :],
    )
    return x, e[..., 0]


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def sa_sweep_many(
    h: jax.Array,       # (P, n)
    B: jax.Array,       # (P, n, n) symmetric, zero diag
    x0: jax.Array,      # (P, chains, n) initial +-1 spins
    rand: jax.Array,    # (P, chains, sweeps, n) uniforms in [0, 1)
    temps: jax.Array,   # (P, sweeps) per-problem temperature schedules
    block_p: int | None = None,
    interpret: bool = False,
):
    """Batched SA: P problems x chains in one program.  Returns
    (x (P, chains, n), energy (P, chains))."""
    return _sweep_many(h, B, x0, rand, temps, block_p, interpret,
                       "sa_sweep_many")


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def sq_sweep_many(
    h: jax.Array,       # (P, n)
    B: jax.Array,       # (P, n, n)
    x0: jax.Array,      # (P, chains, n)
    rand: jax.Array,    # (P, chains, sweeps, n)
    temperature: float = 0.1,
    block_p: int | None = None,
    interpret: bool = False,
):
    """Simulated quench: constant-temperature path through the SA kernel."""
    P, _, S, _ = rand.shape
    temps = jnp.full((P, S), temperature, jnp.float32)
    return _sweep_many(h, B, x0, rand, temps, block_p, interpret,
                       "sq_sweep_many")


@functools.partial(jax.jit, static_argnames=("interpret",))
def sa_sweep(
    h: jax.Array,       # (n,)
    B: jax.Array,       # (n, n) symmetric, zero diag
    x0: jax.Array,      # (chains, n) initial +-1 spins
    rand: jax.Array,    # (chains, sweeps, n) uniforms in [0, 1)
    temps: jax.Array,   # (sweeps,) temperature schedule
    interpret: bool = False,
):
    """Single-problem wrapper.  Returns (x (chains, n), energy (chains,))."""
    x, e = sa_sweep_many(
        h[None], B[None], x0[None], rand[None], temps[None], interpret=interpret
    )
    return x[0], e[0]
