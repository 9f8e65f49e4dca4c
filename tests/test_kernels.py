"""Per-kernel shape/dtype sweeps vs. the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decomposition import pack_bits
from repro.kernels import ops, ref


def _pack_tiles(M):
    nr, nc = M.shape[:2]
    return jnp.stack([
        jnp.stack([pack_bits(M[r, c]) for c in range(nc)]) for r in range(nr)
    ])


@pytest.mark.parametrize("T,nr,nc,tn,K,td", [
    (8, 2, 3, 16, 4, 32),
    (128, 4, 2, 32, 8, 128),
    (32, 1, 1, 8, 3, 64),     # paper-scale tile (N=8, K=3)
    (64, 2, 2, 32, 12, 256),
    (3, 2, 3, 16, 4, 32),     # decode batch: T prime, padded inside
    (13, 2, 2, 16, 5, 64),    # multi-block with a ragged tail
    (1, 1, 2, 8, 3, 32),      # single sequence decode
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bitlinear_matches_ref(T, nr, nc, tn, K, td, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(T + K), 3)
    M = jnp.sign(jax.random.normal(k1, (nr, nc, tn, K)))
    M = jnp.where(M == 0, 1.0, M)
    Mp = _pack_tiles(M)
    C = (jax.random.normal(k2, (nr, nc, K, td)) * 0.2).astype(dtype)
    x = jax.random.normal(k3, (T, nr * tn)).astype(dtype)
    y_r = ref.bitlinear_ref(x, Mp, C)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    # every schedule point the autotuner can pick must agree with the
    # oracle: all pallas modes x both bit algebras —
    # bitplane (z = 2 x@B - rowsum) vs unpack is an exactness check on the
    # bit-plane algebra across the whole sweep, not a tolerance artifact
    for mode in ("auto", "grid", "decode"):
        for math in ("unpack", "bitplane"):
            y_k = ops.bitlinear(x, Mp, C, block_t=min(128, max(T, 8)),
                                interpret=True, mode=mode, math=math)
            np.testing.assert_allclose(
                np.asarray(y_k, np.float32), np.asarray(y_r, np.float32),
                rtol=tol, atol=tol, err_msg=f"mode={mode} math={math}",
            )


@pytest.mark.parametrize("B,H,KV,S,hd,win,bq", [
    (2, 4, 2, 128, 32, 0, 64),
    (1, 8, 8, 256, 64, 64, 64),    # MHA + sliding window
    (2, 4, 1, 128, 16, 0, 32),     # MQA
    (1, 2, 2, 64, 128, 32, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, KV, S, hd, win, bq, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * S + hd), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd)).astype(dtype)
    o_k = ops.flash_attention(q, k, v, window=win, interpret=True,
                              block_q=bq, block_k=bq)
    o_r = ref.flash_attention_ref(q, k, v, win)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("S", [558, 939])
def test_flash_attention_at_any_length_matches_the_jnp_path(S):
    """A prompt length that is no multiple of the 512 block (the serving
    mix's 558 and 939) is padded inside the kernel and the pad rows are
    dropped; the causal mask hides the pad keys.  Against the model's own
    jnp path at a scale that is not 1/sqrt(hd); both in float32, so only
    the order of summation differs."""
    from repro.models.attention import _chunked_attention

    B, KV, rep, hd, scale = 1, 2, 2, 32, 0.0078125
    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(ks[0], (B, S, KV, rep, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    o_k = ops.flash_attention(
        q.reshape(B, S, KV * rep, hd).transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), interpret=True,
        scale=scale)
    o_j = _chunked_attention(q, k, v, window=0, q_chunk=512, scale=scale)
    np.testing.assert_allclose(
        np.asarray(o_k.transpose(0, 2, 1, 3)),
        np.asarray(o_j.reshape(B, S, KV * rep, hd)), rtol=2e-5, atol=2e-5)


def _rand_problems(key, P, n, scale=0.2):
    from repro.core.ising import random_problems

    return random_problems(key, P, n, scale)


@pytest.mark.parametrize("P,n,chains,sweeps,block_p", [
    (4, 8, 2, 8, None),        # single grid cell (block_p = P)
    (6, 24, 4, 16, 2),         # multi-cell grid
    (3, 48, 3, 8, 1),          # one problem per cell
])
def test_sa_sweep_many_bit_exact_vs_ref(P, n, chains, sweeps, block_p):
    ks = jax.random.split(jax.random.PRNGKey(P * n), 3)
    h, B = _rand_problems(ks[0], P, n)
    x0 = jax.random.rademacher(ks[1], (P, chains, n), dtype=jnp.float32)
    rand = jax.random.uniform(ks[2], (P, chains, sweeps, n))
    temps = jnp.broadcast_to(jnp.linspace(2.0, 0.05, sweeps)[None], (P, sweeps))
    xk, ek = ops.sa_sweep_many(h, B, x0, rand, temps, block_p=block_p,
                               interpret=True)
    xr, er = ref.sa_sweep_many_ref(h, B, x0, rand, temps)
    np.testing.assert_array_equal(np.asarray(xk), np.asarray(xr))
    np.testing.assert_allclose(np.asarray(ek), np.asarray(er), rtol=1e-4, atol=1e-4)


def test_sq_sweep_many_bit_exact_vs_ref():
    P, n, chains, sweeps = 5, 16, 3, 12
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    h, B = _rand_problems(ks[0], P, n)
    x0 = jax.random.rademacher(ks[1], (P, chains, n), dtype=jnp.float32)
    rand = jax.random.uniform(ks[2], (P, chains, sweeps, n))
    xk, ek = ops.sq_sweep_many(h, B, x0, rand, temperature=0.1, interpret=True)
    xr, er = ref.sq_sweep_many_ref(h, B, x0, rand, temperature=0.1)
    np.testing.assert_array_equal(np.asarray(xk), np.asarray(xr))
    np.testing.assert_allclose(np.asarray(ek), np.asarray(er), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("P,chains,trotter,sweeps,n", [
    (3, 2, 4, 8, 8),
    (2, 3, 8, 12, 24),
])
def test_sqa_sweep_many_bit_exact_vs_ref(P, chains, trotter, sweeps, n):
    ks = jax.random.split(jax.random.PRNGKey(P + n), 3)
    h, B = _rand_problems(ks[0], P, n)
    X0 = jax.random.rademacher(ks[1], (P, chains, trotter, n), dtype=jnp.float32)
    rand = jax.random.uniform(ks[2], (P, chains, sweeps, trotter, n))
    temperature = 0.05
    gammas = 3.0 * (1e-2 / 3.0) ** jnp.linspace(0.0, 1.0, sweeps)
    PT = trotter * temperature
    jperps = -0.5 * PT * jnp.log(jnp.tanh(jnp.maximum(gammas / PT, 1e-7)))
    Xk, Ek = ops.sqa_sweep_many(h, B, X0, rand, jperps,
                                temperature=temperature, interpret=True)
    Xr, Er = ref.sqa_sweep_many_ref(h, B, X0, rand, jperps,
                                    temperature=temperature)
    np.testing.assert_array_equal(np.asarray(Xk), np.asarray(Xr))
    np.testing.assert_allclose(np.asarray(Ek), np.asarray(Er), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,chains,sweeps", [(8, 2, 8), (24, 4, 16), (48, 3, 8)])
def test_sa_sweep_bit_exact_vs_ref(n, chains, sweeps):
    ks = jax.random.split(jax.random.PRNGKey(n), 4)
    h = jax.random.normal(ks[0], (n,))
    B = jax.random.normal(ks[1], (n, n)) * 0.2
    B = (B + B.T) / 2
    B = B - jnp.diag(jnp.diag(B))
    x0 = jnp.sign(jax.random.normal(ks[2], (chains, n)))
    x0 = jnp.where(x0 == 0, 1.0, x0)
    rand = jax.random.uniform(ks[3], (chains, sweeps, n))
    temps = jnp.linspace(2.0, 0.05, sweeps)
    xk, ek = ops.sa_sweep(h, B, x0, rand, temps, interpret=True)
    xr, er = ref.sa_sweep_ref(h, B, x0, rand, temps)
    np.testing.assert_array_equal(np.asarray(xk), np.asarray(xr))
    np.testing.assert_allclose(np.asarray(ek), np.asarray(er), rtol=1e-4, atol=1e-4)


def test_fused_compressed_apply_matches_layer_path():
    from repro.core import quantized
    from repro.kernels.ops import apply_compressed_fused

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    M = jnp.sign(jax.random.normal(k1, (2, 2, 16, 4)))
    M = jnp.where(M == 0, 1.0, M)
    w = {"m_packed": _pack_tiles(M), "C": jax.random.normal(k2, (2, 2, 4, 32)) * 0.3}
    x = jax.random.normal(k3, (4, 8, 32))
    y_layer = quantized.apply_compressed(x, w)
    y_fused = apply_compressed_fused(x, w, block_t=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y_layer), np.asarray(y_fused), rtol=2e-5, atol=2e-5
    )
