"""Pure-jnp oracles for every Pallas kernel (tests assert_allclose against
these across shape/dtype sweeps; they are also the CPU/dry-run fallbacks)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "bitlinear_ref",
    "bitlinear_grouped_ref",
    "flash_attention_ref",
    "sa_sweep_ref",
    "sa_sweep_many_ref",
    "sq_sweep_many_ref",
    "sqa_sweep_ref",
    "sqa_sweep_many_ref",
]


# The Ising oracles contract at full f32 precision, as the kernels do: the
# TPU's default would round B to bf16 and the two backends would drift.
_HIGHEST = jax.lax.Precision.HIGHEST


def _unpack(m_packed: jax.Array, K: int, dtype) -> jax.Array:
    bits = (m_packed[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    bits = bits.reshape(*m_packed.shape[:-1], m_packed.shape[-1] * 8)[..., :K]
    return 2 * bits.astype(dtype) - 1


def bitlinear_ref(x: jax.Array, m_packed: jax.Array, C: jax.Array) -> jax.Array:
    """y = (x @ M) @ C, dense reference."""
    n_r, n_c, tn, kb = m_packed.shape
    K = C.shape[2]
    M = _unpack(m_packed, K, jnp.float32)
    xt = x.reshape(x.shape[0], n_r, tn).astype(jnp.float32)
    z = jnp.einsum("trn,rcnk->trck", xt, M)
    y = jnp.einsum("trck,rckd->tcd", z, C.astype(jnp.float32))
    return y.reshape(x.shape[0], n_c * C.shape[3]).astype(x.dtype)


def bitlinear_grouped_ref(
    x: jax.Array, m_packed: jax.Array, C: jax.Array
) -> jax.Array:
    """y_e = (x_e @ M_e) @ C_e per group slice, dense reference.
    x (E, T, d_in), m_packed (E, r, c, tn, kb), C (E, r, c, K, td)."""
    return jax.vmap(bitlinear_ref)(x, m_packed, C)


def flash_attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, window: int = 0
) -> jax.Array:
    """Plain masked softmax attention. q (B,H,S,hd), k/v (B,KV,S,hd)."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    rep = H // KV
    kr = jnp.repeat(k, rep, axis=1)
    vr = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kr).astype(jnp.float32) / math.sqrt(hd)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), vr)


def sa_sweep_ref(h, B, x0, rand, temps):
    """Sequential-sweep Metropolis SA consuming the same uniforms as the
    kernel — bit-exact reference."""
    hf = h.astype(jnp.float32)
    Bf = B.astype(jnp.float32)

    def one_chain(x0c, randc):
        x = x0c.astype(jnp.float32)
        f = hf + 2.0 * jnp.matmul(Bf, x, precision=_HIGHEST)

        def sweep(carry, su):
            x, f = carry
            t, u = su

            def spin(i, carry):
                x, f = carry
                dE = -2.0 * x[i] * f[i]
                accept = jnp.logical_or(
                    dE < 0.0, u[i] < jnp.exp(-dE / jnp.maximum(t, 1e-12))
                )
                delta = jnp.where(accept, -2.0 * x[i], 0.0)
                f = f + 2.0 * Bf[:, i] * delta
                x = x.at[i].add(delta)
                return x, f

            x, f = jax.lax.fori_loop(0, x.shape[0], spin, (x, f))
            return (x, f), None

        (x, _), _ = jax.lax.scan(sweep, (x, f), (temps, randc))
        e = x @ hf + x @ jnp.matmul(Bf, x, precision=_HIGHEST)
        return x, e

    return jax.vmap(one_chain)(x0, rand)


def sa_sweep_many_ref(h, B, x0, rand, temps):
    """Multi-problem SA oracle (the jnp backend of ``ising.solve_many``):
    h (P, n), B (P, n, n), x0 (P, C, n), rand (P, C, S, n), temps (P, S)
    -> (x (P, C, n), e (P, C)).  Idiomatic vmap-of-scan over the bit-exact
    single-problem reference; the Pallas kernel replaces the per-spin
    scatter with lock-step rank-3 updates but consumes the same uniforms."""
    return jax.vmap(sa_sweep_ref)(h, B, x0, rand, temps)


def sq_sweep_many_ref(h, B, x0, rand, temperature=0.1):
    """Constant-temperature (simulated quench) path of the SA oracle."""
    P, _, S, _ = rand.shape
    temps = jnp.full((P, S), temperature, jnp.float32)
    return sa_sweep_many_ref(h, B, x0, rand, temps)


def sqa_sweep_ref(h, B, X0, rand, jperps, temperature=0.05):
    """Sequential path-integral SQA consuming the same uniforms as the
    kernel — bit-exact reference for one problem.

    X0 (C, T, n) replica spins per chain, rand (C, S, T, n), jperps (S,)
    pre-computed inter-replica couplings -> (X (C, T, n), E (C, T))."""
    hf = h.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    T = X0.shape[1]
    n = X0.shape[2]

    def one_chain(X0c, randc):
        X = X0c.astype(jnp.float32)
        F = hf[None] + 2.0 * jax.lax.dot_general(
            X, Bf, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32,
        )

        def sweep(carry, su):
            X, F = carry
            jperp, u = su

            def slice_body(p, carry):
                X, F = carry
                up = (p + 1) % T
                dn = (p - 1) % T

                def spin(i, carry):
                    X, F = carry
                    xi = X[p, i]
                    dE = -2.0 * xi * (
                        F[p, i] / T + jperp * (X[up, i] + X[dn, i])
                    )
                    accept = jnp.logical_or(
                        dE < 0.0,
                        u[p, i]
                        < jnp.exp(-dE / jnp.maximum(temperature, 1e-12)),
                    )
                    delta = jnp.where(accept, -2.0 * xi, 0.0)
                    F = F.at[p].add(2.0 * Bf[:, i] * delta)
                    X = X.at[p, i].add(delta)
                    return X, F

                return jax.lax.fori_loop(0, n, spin, (X, F))

            X, F = jax.lax.fori_loop(0, T, slice_body, (X, F))
            return (X, F), None

        (X, _), _ = jax.lax.scan(sweep, (X, F), (jperps, randc))
        E = jax.vmap(
            lambda x: x @ hf + x @ jnp.matmul(Bf, x, precision=_HIGHEST)
        )(X)
        return X, E

    return jax.vmap(one_chain)(X0, rand)


def sqa_sweep_many_ref(h, B, X0, rand, jperps, temperature=0.05):
    """Multi-problem SQA oracle: leading problem axis on h/B/X0/rand."""
    return jax.vmap(
        lambda hp, Bp, Xp, rp: sqa_sweep_ref(hp, Bp, Xp, rp, jperps, temperature)
    )(h, B, X0, rand)
