"""Surrogate models for black-box optimisation (BOCS variants + FM).

Every surrogate consumes *sufficient statistics* of the acquired dataset and
produces a Thompson sample of a quadratic pseudo-Boolean model, returned as
Ising terms ``(h, B)`` via :func:`repro.core.features.coeffs_to_ising`.

Beyond-paper optimisation: the paper refits the Bayesian regression from
scratch each iteration (their complexity analysis: O(n^2) iterations x
O(p^3) solve).  We maintain the Gram matrix ``G = Phi^T Phi``, the moment
vector ``F = Phi^T y`` and scalar moments incrementally (rank-1 update per
acquired point).  For the two conjugate priors (nBOCS, gBOCS) the posterior
precision ``A = G + I/prior_var`` does not depend on ``y``, so we also carry
a square root ``S`` of its inverse (``S S^T = A^{-1}``) and update it per
point by Potter's rank-1 form of Sherman-Morrison, O(p^2).  A Thompson draw
is then two matrix-vector products, with no factorisation or triangular
solve.  This is exact algebra, not an approximation.  vBOCS keeps a p x p
Cholesky per Gibbs step: its prior precision changes every step.

Surrogates:
  * ``nbocs``  — normal prior  alpha_k ~ N(0, sigma2)           (conjugate)
  * ``gbocs``  — normal-gamma prior, NIG posterior              (conjugate)
  * ``vbocs``  — horseshoe prior, Makalic–Schmidt Gibbs sampler (vanilla BOCS)
  * ``fm``     — factorisation machine of rank k_FM, Adam-trained
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import features as feat

__all__ = [
    "SuffStats",
    "init_stats",
    "update_stats",
    "posterior_residual",
    "sample_nbocs",
    "sample_gbocs",
    "HorseshoeState",
    "init_horseshoe",
    "sample_vbocs",
    "FMState",
    "init_fm",
    "train_fm",
    "fm_to_ising",
]


# ---------------------------------------------------------------------------
# Incremental sufficient statistics
# ---------------------------------------------------------------------------

class SuffStats(NamedTuple):
    G: jax.Array       # (p, p)  Phi^T Phi
    F: jax.Array       # (p,)    Phi^T y
    Sy: jax.Array      # ()      sum y
    Syy: jax.Array     # ()      sum y^2
    count: jax.Array   # ()      number of points (float for jit arithmetic)
    # (p, p) square root of the conjugate posterior covariance,
    # S S^T = (G + I/prior_var)^{-1}; None when no prior variance was given
    S: jax.Array | None = None


def init_stats(n: int, dtype=jnp.float32, prior_var: float | None = None) -> SuffStats:
    """Empty statistics for n spins.  ``prior_var`` (the conjugate prior's
    coefficient variance) starts the carried posterior square root at
    ``sqrt(prior_var) I``; without it ``S`` stays None and nothing more is
    carried."""
    p = feat.num_features(n)
    S = None
    if prior_var is not None:
        S = jnp.sqrt(jnp.asarray(prior_var, dtype)) * jnp.eye(p, dtype=dtype)
    return SuffStats(
        G=jnp.zeros((p, p), dtype),
        F=jnp.zeros((p,), dtype),
        Sy=jnp.zeros((), dtype),
        Syy=jnp.zeros((), dtype),
        count=jnp.zeros((), dtype),
        S=S,
    )


# Matrix-vector products with S as multiply-and-sum: exact float32 on every
# backend.  A TPU dot at default precision rounds its operands to bfloat16,
# which would compound over the rank-1 updates into a wrong posterior.
def _mv(S, v):
    """S @ v."""
    return jnp.sum(S * v[None, :], axis=1)


def _mtv(S, v):
    """S^T @ v."""
    return jnp.sum(S * v[:, None], axis=0)


def _potter_update(S: jax.Array, phi: jax.Array) -> jax.Array:
    """The square root after one more point: S' S'^T = ((S S^T)^{-1} +
    phi phi^T)^{-1} for S' = S - (S v) v^T / (r (r + 1)), with v = S^T phi
    and r = sqrt(1 + v.v)."""
    v = _mtv(S, phi)
    r = jnp.sqrt(1.0 + jnp.sum(v * v))
    return S - jnp.outer(_mv(S, v) / (r * (r + 1.0)), v)


def update_stats(stats: SuffStats, x: jax.Array, y: jax.Array) -> SuffStats:
    phi = feat.featurize(x)
    return SuffStats(
        G=stats.G + jnp.outer(phi, phi),
        F=stats.F + phi * y,
        Sy=stats.Sy + y,
        Syy=stats.Syy + y * y,
        count=stats.count + 1.0,
        S=None if stats.S is None else _potter_update(stats.S, phi),
    )


def posterior_residual(stats: SuffStats, prior_var: float) -> jax.Array:
    """max |(G + I/prior_var) S S^T - I|: how far the carried square root
    has drifted from the posterior it stands for (0 in exact arithmetic)."""
    p = stats.G.shape[0]
    eye = jnp.eye(p, dtype=stats.G.dtype)
    hi = jax.lax.Precision.HIGHEST
    AS = jnp.matmul(stats.G + eye / prior_var, stats.S, precision=hi)
    return jnp.max(jnp.abs(jnp.matmul(AS, stats.S.T, precision=hi) - eye))


def _standardised(stats: SuffStats):
    """Moments of the regression against standardised targets.

    Features are raw (+-1 products are already scale-free); targets are
    centred/scaled, which only applies an affine map to the coefficients and
    leaves the Ising argmin unchanged while conditioning the solve.
    Note Phi^T 1 = G[:, 0] because feature 0 is the constant 1.
    """
    m = jnp.maximum(stats.count, 1.0)
    ybar = stats.Sy / m
    var = jnp.maximum(stats.Syy / m - ybar**2, 1e-12)
    s = jnp.sqrt(var)
    F_std = (stats.F - ybar * stats.G[:, 0]) / s
    yty_std = jnp.maximum((stats.Syy - m * ybar**2) / var, 0.0)
    return F_std, yty_std


def _chol_gaussian_sample(key, mean, precision_chol):
    """Sample N(mean, P^{-1}) given the lower Cholesky factor L of P."""
    z = jax.random.normal(key, mean.shape, mean.dtype)
    return mean + jax.scipy.linalg.solve_triangular(
        precision_chol, z, trans="T", lower=True
    )


def _require_root(stats: SuffStats, prior: str) -> jax.Array:
    if stats.S is None:
        raise ValueError(
            f"{prior} samples from the carried posterior square root: build "
            f"the statistics with init_stats(..., prior_var=...)"
        )
    return stats.S


# ---------------------------------------------------------------------------
# nBOCS — normal prior (paper's best performer; sigma2 = 0.1 from Fig. 6)
# ---------------------------------------------------------------------------

def sample_nbocs(key: jax.Array, stats: SuffStats):
    """Thompson sample alpha ~ posterior under alpha_k ~ N(0, sigma2),
    unit observation noise on standardised targets.  ``stats`` must come
    from ``init_stats(..., prior_var=sigma2)``: the draw
    ``S (S^T F + z)`` is N(A^{-1} F, A^{-1}) with A = G + I/sigma2."""
    S = _require_root(stats, "nBOCS")
    F_std, _ = _standardised(stats)
    z = jax.random.normal(key, F_std.shape, F_std.dtype)
    return _mv(S, _mtv(S, F_std) + z)


# ---------------------------------------------------------------------------
# gBOCS — normal-gamma prior NG(0, 1, a0=1, b0=beta); beta = 0.001 (Fig. 6)
# ---------------------------------------------------------------------------

def sample_gbocs(
    key: jax.Array, stats: SuffStats, a0: float = 1.0, b0: float = 0.001
):
    """Thompson sample under the normal-gamma prior with V0 = I: ``stats``
    must come from ``init_stats(..., prior_var=1.0)``, so S S^T = (G + I)^{-1}."""
    S = _require_root(stats, "gBOCS")
    F_std, yty = _standardised(stats)
    mu = _mv(S, _mtv(S, F_std))
    a_n = a0 + stats.count / 2.0
    b_n = b0 + 0.5 * jnp.maximum(yty - mu @ F_std, 0.0)
    k1, k2 = jax.random.split(key)
    prec = jax.random.gamma(k1, a_n) / b_n             # sigma^{-2}
    sigma = jnp.sqrt(1.0 / jnp.maximum(prec, 1e-12))
    z = jax.random.normal(k2, mu.shape, mu.dtype)
    return mu + sigma * _mv(S, z)


# ---------------------------------------------------------------------------
# vBOCS — horseshoe prior, Makalic–Schmidt auxiliary-variable Gibbs sampler
# ---------------------------------------------------------------------------

class HorseshoeState(NamedTuple):
    alpha: jax.Array    # (p,)
    beta2: jax.Array    # (p,) local scales
    nu: jax.Array       # (p,) auxiliaries
    tau2: jax.Array     # ()   global scale
    xi: jax.Array       # ()   auxiliary
    sigma2: jax.Array   # ()   noise variance


def init_horseshoe(n: int, dtype=jnp.float32) -> HorseshoeState:
    p = feat.num_features(n)
    one = jnp.ones((), dtype)
    return HorseshoeState(
        alpha=jnp.zeros((p,), dtype),
        beta2=jnp.ones((p,), dtype),
        nu=jnp.ones((p,), dtype),
        tau2=one,
        xi=one,
        sigma2=one,
    )


def _inv_gamma(key, shape_param, scale):
    """Sample InvGamma(shape, scale): scale / Gamma(shape, rate=1)."""
    g = jax.random.gamma(key, shape_param)
    return scale / jnp.maximum(g, 1e-30)


def sample_vbocs(
    key: jax.Array,
    stats: SuffStats,
    state: HorseshoeState,
    gibbs_steps: int = 4,
):
    """One (or a few) Gibbs sweeps of the horseshoe regression; returns the
    current alpha draw (Thompson sample) and the carried chain state.

    All conditionals only need (G, F, y^T y): the residual norm expands as
    y^T y - 2 alpha^T F + alpha^T G alpha, so no data matrix is rebuilt.
    """
    F_std, yty = _standardised(stats)
    G = stats.G
    p = G.shape[0]
    m = stats.count

    def gibbs(state: HorseshoeState, key):
        k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
        d_inv = 1.0 / jnp.maximum(state.tau2 * state.beta2, 1e-12)
        A = G / state.sigma2 + jnp.diag(d_inv) / state.sigma2
        L = jnp.linalg.cholesky(A + 1e-8 * jnp.eye(p, dtype=G.dtype))
        mu = jax.scipy.linalg.cho_solve((L, True), F_std / state.sigma2)
        alpha = _chol_gaussian_sample(k1, mu, L)

        a2 = alpha * alpha
        beta2 = _inv_gamma(
            k2, jnp.ones((p,), G.dtype),
            1.0 / state.nu + a2 / (2.0 * state.tau2 * state.sigma2),
        )
        nu = _inv_gamma(k3, jnp.ones((p,), G.dtype), 1.0 + 1.0 / beta2)
        tau2 = _inv_gamma(
            k4, jnp.asarray((p + 1.0) / 2.0, G.dtype),
            1.0 / state.xi + jnp.sum(a2 / beta2) / (2.0 * state.sigma2),
        )
        xi = _inv_gamma(k5, jnp.ones((), G.dtype), 1.0 + 1.0 / tau2)
        rss = jnp.maximum(yty - 2.0 * alpha @ F_std + alpha @ (G @ alpha), 0.0)
        pen = jnp.sum(a2 / (tau2 * beta2))
        sigma2 = _inv_gamma(
            k6, jnp.asarray((m + p) / 2.0, G.dtype), 0.5 * (rss + pen)
        )
        sigma2 = jnp.clip(sigma2, 1e-6, 1e6)
        return HorseshoeState(alpha, beta2, nu, tau2, xi, sigma2), None

    state, _ = jax.lax.scan(gibbs, state, jax.random.split(key, gibbs_steps))
    return state.alpha, state


# ---------------------------------------------------------------------------
# FM — factorisation machine surrogate (FMQA; k_FM in {8, 12})
# ---------------------------------------------------------------------------

class FMState(NamedTuple):
    w0: jax.Array      # ()
    w: jax.Array       # (n,)
    V: jax.Array       # (n, k)
    opt_m: jax.Array   # Adam first moment  (flattened params)
    opt_v: jax.Array   # Adam second moment
    step: jax.Array


def _fm_flat(w0, w, V):
    return jnp.concatenate([w0[None], w, V.reshape(-1)])


def init_fm(key: jax.Array, n: int, k: int, dtype=jnp.float32) -> FMState:
    V = 0.01 * jax.random.normal(key, (n, k), dtype)
    w0 = jnp.zeros((), dtype)
    w = jnp.zeros((n,), dtype)
    flat = _fm_flat(w0, w, V)
    return FMState(w0, w, V, jnp.zeros_like(flat), jnp.zeros_like(flat), jnp.zeros((), dtype))


def fm_predict(w0, w, V, X):
    """FM of degree 2 on +-1 inputs (Eq. 11-12)."""
    lin = X @ w
    XV = X @ V                               # (m, k)
    x2V2 = (X * X) @ (V * V)                 # (m, k)
    pair = 0.5 * jnp.sum(XV * XV - x2V2, axis=-1)
    return w0 + lin + pair


@functools.partial(jax.jit, static_argnames=("steps",))
def train_fm(
    state: FMState,
    X: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    key: jax.Array,
    steps: int = 50,
    lr: float = 0.05,
):
    """Full-batch Adam on masked MSE; warm-started across BBO iterations."""
    m_eff = jnp.maximum(jnp.sum(mask), 1.0)
    ybar = jnp.sum(y * mask) / m_eff
    ystd = jnp.sqrt(jnp.maximum(jnp.sum(mask * (y - ybar) ** 2) / m_eff, 1e-12))
    yn = (y - ybar) / ystd

    def loss_fn(params):
        w0, w, V = params
        pred = fm_predict(w0, w, V, X)
        return jnp.sum(mask * (pred - yn) ** 2) / m_eff

    def adam_step(carry, _):
        (w0, w, V), mom, vel, t = carry
        g = jax.grad(loss_fn)((w0, w, V))
        gflat = _fm_flat(*g)
        t = t + 1.0
        mom = 0.9 * mom + 0.1 * gflat
        vel = 0.999 * vel + 0.001 * gflat * gflat
        mhat = mom / (1.0 - 0.9**t)
        vhat = vel / (1.0 - 0.999**t)
        upd = lr * mhat / (jnp.sqrt(vhat) + 1e-8)
        flat = _fm_flat(w0, w, V) - upd
        n, k = V.shape
        w0n = flat[0]
        wn = flat[1 : 1 + n]
        Vn = flat[1 + n :].reshape(n, k)
        return ((w0n, wn, Vn), mom, vel, t), None

    carry = ((state.w0, state.w, state.V), state.opt_m, state.opt_v, state.step)
    carry, _ = jax.lax.scan(adam_step, carry, None, length=steps)
    (w0, w, V), mom, vel, t = carry
    return FMState(w0, w, V, mom, vel, t)


def fm_to_ising(state: FMState):
    """FM -> Ising terms: h = w, B_ij = <v_i, v_j>/2 (i != j), zero diag."""
    B = state.V @ state.V.T / 2.0
    B = B - jnp.diag(jnp.diag(B))
    return state.w, B
