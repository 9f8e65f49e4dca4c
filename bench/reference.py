"""The plain float32 reference that decides ``correct`` for compression
cells.  Nothing here imports the program.

A tensor (..., d_in, d_out) is cut into tiles of tn x td, slice-major then
row-block, column-block; the program's answer is ``m_packed`` uint8
(..., r, c, tn, ceil(K/8)) little-endian bits (bit 1 = +1, bit 0 = -1),
``C`` (..., r, c, K, td) and the recorded per-tile residuals
``||W_t - M_t C_t||_F``.  For a binary M the best real factor is the
least-squares C*(M) = pinv(M) W; the best M of a two-column tile of up to
10 rows is found by trying every pair of sign columns.

Per tile the reference reads:
- ``c_gap``: ||C - C*(M)|| / ||C*(M)||;
- ``resid_gap``: |recorded - ||W - M C||| / ||W - M C||;
- ``excess`` (two-column tiles only): the least-squares residual of the
  returned M above the best M's, over ||W||^2.

``control_answer`` is the control: the same compression in float8 e4m3
(per-tensor absmax scale) put in the program's place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0
# A tile is off the best M when its residual lies more than this share of
# ||W_t||^2 above the best two-column M's (``off_best``: the share of such
# tiles in a job).
OFF_BEST = 0.015


def _q8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def tiles_of(W, tn: int, td: int) -> jax.Array:
    W = W.reshape(-1, *W.shape[-2:]).astype(jnp.float32)
    G, d_in, d_out = W.shape
    t = W.reshape(G, d_in // tn, tn, d_out // td, td).transpose(0, 1, 3, 2, 4)
    return t.reshape(-1, tn, td)


def decode_M(m_packed, K: int) -> jax.Array:
    m = m_packed.reshape(-1, *m_packed.shape[-2:])
    bits = (m[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    bits = bits.reshape(*m.shape[:-1], m.shape[-1] * 8)[..., :K]
    return 2.0 * bits.astype(jnp.float32) - 1.0              # (T, tn, K)


@jax.jit
def _lstsq_C(M, W):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda m, w: jnp.linalg.pinv(m) @ w)(M, W)


@jax.jit
def _sq_resid(M, C, W):
    d = W - jnp.einsum("tnk,tkd->tnd", M, C,
                       precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(d * d, axis=(1, 2))


def _signs(n: int) -> jax.Array:
    idx = np.arange(2 ** n)[:, None] >> np.arange(n)[None, :]
    return jnp.asarray(2.0 * (idx & 1) - 1.0, jnp.float32)    # (2^n, n)


@functools.partial(jax.jit, static_argnames=("fp8",))
def _best_pairs(W, fp8: bool = False):
    """Least squared residual over every M with two sign columns, and the
    (i, j) of the sign vectors that reach it, per tile."""
    tn = W.shape[1]
    S = _signs(tn)
    with jax.default_matmul_precision("highest"):
        A = _mm("tnd,tmd->tnm", W, W, fp8)                     # W W^T
        Q = jnp.einsum("in,tnm,jm->tij", S, A, S)              # s_i^T A s_j
        g = S @ S.T                                            # s_i . s_j
    diag = jnp.diagonal(Q, axis1=1, axis2=2)                   # (T, 2^n)
    full = tn * tn - g * g
    proj2 = (tn * (diag[:, :, None] + diag[:, None, :]) - 2 * g * Q) / \
        jnp.where(full > 0, full, 1.0)
    proj1 = jnp.maximum(diag[:, :, None], diag[:, None, :]) / tn
    proj = jnp.where(full > 0, proj2, proj1)
    flat = proj.reshape(proj.shape[0], -1)
    best = jnp.argmax(flat, axis=1)
    total = jnp.sum(W * W, axis=(1, 2))
    return total - jnp.max(flat, axis=1), best // S.shape[0], best % S.shape[0]


def best_pair_M(W, fp8: bool = False):
    """(least squared residual, its M (T, tn, 2)) over all two-column M."""
    opt, i, j = _best_pairs(W, fp8)
    S = _signs(W.shape[1])
    return opt, jnp.stack([S[i], S[j]], axis=-1)


def tile_readings(W, M, C, recorded) -> dict:
    """Per-tile readings (module doc) of one compressed tensor's answer:
    W (T, tn, td), M (T, tn, K), C (T, K, td), recorded (T,)."""
    C = C.astype(jnp.float32)
    Cs = _lstsq_C(M, W)
    c_gap = jnp.linalg.norm((C - Cs).reshape(C.shape[0], -1), axis=1) / \
        jnp.maximum(jnp.linalg.norm(Cs.reshape(C.shape[0], -1), axis=1), 1e-30)
    true_r = jnp.sqrt(_sq_resid(M, C, W))
    rec = jnp.asarray(recorded, jnp.float32)
    resid_gap = jnp.abs(rec - true_r) / jnp.maximum(true_r, 1e-30)
    out = {"c_gap": np.asarray(c_gap), "resid_gap": np.asarray(resid_gap)}
    if M.shape[-1] == 2 and M.shape[1] <= 10:
        opt, _ = best_pair_M(W)
        total = jnp.sum(W * W, axis=(1, 2))
        out["excess"] = np.asarray((_sq_resid(M, Cs, W) - opt) / total)
    return out


def job_numbers(readings: list) -> dict:
    """The numbers one job is compared on, from the per-tile readings of
    its tensors: the worst tile's ``c_gap`` and ``resid_gap``; over every
    two-column tile, ``off_best`` (the share off the best M by more than
    ``OFF_BEST``) and ``excess`` (the mean)."""
    tiles = {k: np.concatenate([r[k] for r in readings])
             for k in readings[0]}
    out = {"c_gap": float(tiles["c_gap"].max()),
           "resid_gap": float(tiles["resid_gap"].max())}
    if "excess" in tiles:
        out["off_best"] = float(np.mean(tiles["excess"] > OFF_BEST))
        out["excess"] = float(np.mean(tiles["excess"]))
    return out


def control_answer(W):
    """The reference compression in float8 (the control): the best
    two-column M of the float8-rounded tiles, its least-squares C rounded
    to float8, and the residual it records against the rounded tiles."""
    Wq = _q8(W)
    _, M = best_pair_M(Wq, fp8=True)
    C = _q8(_lstsq_C(M, Wq))
    return M, C, jnp.sqrt(_sq_resid(M, C, Wq))
