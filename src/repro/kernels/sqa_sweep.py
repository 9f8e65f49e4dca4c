"""Pallas TPU kernel: batched simulated-quantum-annealing (path-integral
Monte Carlo) sweeps — the Trotter-replica quench behind ``solver="qa"``.

Each chain carries ``n_trotter`` coupled replicas of the n-spin system.  A
sweep visits (slice p, spin i) in sequence at transverse-field coupling
``jperp_s`` (pre-computed per sweep from the annealed Gamma schedule, so the
kernel and the ref.py oracle share exact values):

    dE = -2 X[p,i] ( F[p,i]/T + jperp_s (X[p+1,i] + X[p-1,i]) )
    accept iff dE < 0 or u < exp(-dE / temperature)

with F the per-replica local field h + 2 B X_p, maintained incrementally.
grid = (P // bp,), bp as many problems as VMEM holds (``auto_block_p``);
within a cell each Trotter slice holds X (bp, C, n) and F (bp, C, n), and
all chains of all problems in the block update in lock-step.  Pre-drawn
uniforms (P, C, S, T, n) keep the kernel bit-exact against
``ref.sqa_sweep_many_ref``.

The kernel returns every replica and its Ising energy; the caller
(``repro.core.ising.solve_many``) reduces best-of over (reads x replicas).
The initial replica stack ``X0`` is caller-supplied — the warm-start
surface: ``solve_many(init_state=...)`` (docs/delta.md) broadcasts the
warm spins across read 0's Trotter replicas before invoking the kernel,
which itself has no cold/warm distinction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sa_sweep import (
    _bx, _energies, _pick, _tile_bytes, auto_block_p,
)

__all__ = ["sqa_sweep_many"]


def _sqa_kernel(h_ref, b_ref, x0_ref, rand_ref, jperps_ref, temp_ref, x_ref,
                e_ref):
    """Lock-step PIMC quench of a block of problems.

    h (bp, 1, n) · B (bp, n, n) · X0 (bp, T, C, n) · rand (bp, S, T, C, n) ·
    jperps (1, 1, S) · temperature (1, 1, 1)  ->  X (bp, T, C, n),
    E (bp, T, C, 1).  The Trotter slices are unrolled (T is static), so
    slice p and its neighbours are whole (bp, C, n) values; spin ``i`` is a
    lane-mask pick and row ``i`` of B a dynamic sublane load.  The
    independent oracle ``ref.sqa_sweep_ref`` consumes the same uniforms in
    the same (sweep, slice, spin) order — keep the two in lock-step.
    """
    h = h_ref[...]
    jperps = jperps_ref[...]
    temp = jnp.maximum(temp_ref[...], 1e-12)                      # (1, 1, 1)
    bp, T, C, n = x0_ref.shape
    S = jperps.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n), 2)
    sweep_lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, S), 2)
    B = b_ref[...]
    X = [x0_ref[:, p] for p in range(T)]
    F = [h + 2.0 * _bx(Xp, B) for Xp in X]

    def sweep_body(s, carry):
        X, F = list(carry[0]), list(carry[1])
        jperp = _pick(jperps, sweep_lane == s)                    # (1, 1, 1)
        for p in range(T):
            x_up, x_dn = X[(p + 1) % T], X[(p - 1) % T]
            u_p = rand_ref[:, s, p]                               # (bp, C, n)

            def spin_body(i, carry, x_up=x_up, x_dn=x_dn, u_p=u_p):
                x, f = carry
                m = lane == i
                xi = _pick(x, m)                                  # (bp, C, 1)
                dE = -2.0 * xi * (
                    _pick(f, m) / T + jperp * (_pick(x_up, m) + _pick(x_dn, m))
                )
                accept = (dE < 0.0) | (_pick(u_p, m) < jnp.exp(-dE / temp))
                delta = jnp.where(accept, -2.0 * xi, 0.0)
                brow = b_ref[:, pl.ds(i, 1), :]      # row i == col i, (bp, 1, n)
                return x + jnp.where(m, delta, 0.0), f + 2.0 * brow * delta

            X[p], F[p] = jax.lax.fori_loop(0, n, spin_body, (X[p], F[p]))
        return tuple(X), tuple(F)

    X, _ = jax.lax.fori_loop(0, S, sweep_body, (tuple(X), tuple(F)))
    B = b_ref[...]
    for p in range(T):
        x_ref[:, p] = X[p]
        e_ref[:, p] = _energies(h, X[p], B)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sqa_sweep_many(
    h: jax.Array,       # (P, n)
    B: jax.Array,       # (P, n, n) symmetric, zero diag
    X0: jax.Array,      # (P, chains, n_trotter, n) initial +-1 spins
    rand: jax.Array,    # (P, chains, sweeps, n_trotter, n) uniforms in [0, 1)
    jperps: jax.Array,  # (sweeps,) inter-replica couplings J_perp(Gamma_s)
    temperature: float = 0.05,
    interpret: bool = False,
):
    """Batched SQA: P problems x chains x Trotter replicas in one program.
    Returns (X (P, chains, n_trotter, n), energy (P, chains, n_trotter))."""
    P, C, T, n = X0.shape
    S = jperps.shape[0]
    per_problem = (
        _tile_bytes(1, n) + _tile_bytes(n, n) + 2 * _tile_bytes(T, C, n)
        + _tile_bytes(S, T, C, n) + _tile_bytes(T, C, 1)
    )
    bp = auto_block_p(P, per_problem, interpret)

    X, E = pl.pallas_call(
        _sqa_kernel,
        grid=(P // bp,),
        in_specs=[
            pl.BlockSpec((bp, 1, n), lambda p: (p, 0, 0)),
            pl.BlockSpec((bp, n, n), lambda p: (p, 0, 0)),
            pl.BlockSpec((bp, T, C, n), lambda p: (p, 0, 0, 0)),
            pl.BlockSpec((bp, S, T, C, n), lambda p: (p, 0, 0, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda p: (0, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda p: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bp, T, C, n), lambda p: (p, 0, 0, 0)),
            pl.BlockSpec((bp, T, C, 1), lambda p: (p, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, T, C, n), jnp.float32),
            jax.ShapeDtypeStruct((P, T, C, 1), jnp.float32),
        ],
        interpret=interpret,
        name="sqa_sweep_many",
    )(
        h.astype(jnp.float32)[:, None, :],
        B.astype(jnp.float32),
        # slice-major state and (sweep, slice)-major uniforms: both become
        # leading-axis loads in-kernel
        X0.astype(jnp.float32).transpose(0, 2, 1, 3),
        rand.astype(jnp.float32).transpose(0, 2, 3, 1, 4),
        jperps.astype(jnp.float32).reshape(1, 1, S),
        jnp.full((1, 1, 1), temperature, jnp.float32),
    )
    return X.transpose(0, 2, 1, 3), E[..., 0].transpose(0, 2, 1)
