"""Ising solvers + BBO loop: the paper's optimisation machinery."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import linalg as scipy_linalg

from repro.core import bbo as bbo_lib
from repro.core import decomposition as dec
from repro.core import features, ising, surrogate, symmetry
from repro.core.bruteforce import brute_force


def small_ising(seed, n=8):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    h = jax.random.normal(k1, (n,))
    B = jax.random.normal(k2, (n, n)) * 0.3
    B = (B + B.T) / 2
    B = B - jnp.diag(jnp.diag(B))
    return h, B


def exhaustive_min(h, B):
    n = h.shape[0]
    X = dec.sign_enumeration(n)
    E = jax.vmap(lambda x: ising.ising_energy(x, h, B))(X)
    return float(jnp.min(E))


@pytest.mark.parametrize("solver", ["sa", "sq", "qa"])
def test_solvers_reach_ground_state_small(solver):
    hits = 0
    for seed in range(5):
        h, B = small_ising(seed)
        e0 = exhaustive_min(h, B)
        kw = dict(num_sweeps=64, num_reads=10) if solver != "qa" else dict(num_sweeps=48, num_reads=10)
        _, e = ising.solve(solver, jax.random.PRNGKey(seed), h, B, **kw)
        assert float(e) >= e0 - 1e-4  # never below the true minimum
        hits += float(e) <= e0 + 1e-4
    # stochastic heuristics: require a strong majority, not perfection
    assert hits >= 3, f"{solver} found ground state only {hits}/5 times"


def test_sa_energy_decreases_from_start():
    h, B = small_ising(42, n=16)
    key = jax.random.PRNGKey(0)
    x0 = jax.random.rademacher(key, (16,), dtype=h.dtype)
    e0 = ising.ising_energy(x0, h, B)
    _, e = ising.solve_sa(key, h, B, num_sweeps=32, num_reads=4)
    assert float(e) <= float(e0)


def test_features_and_ising_roundtrip():
    n = 5
    alpha = jax.random.normal(jax.random.PRNGKey(1), (features.num_features(n),))
    h, B = features.coeffs_to_ising(alpha, n)
    # quadratic model value == feature dot product for random x
    for seed in range(5):
        x = jax.random.rademacher(jax.random.PRNGKey(seed), (n,), dtype=jnp.float32)
        lhs = float(alpha @ features.featurize(x))
        rhs = float(alpha[0] + x @ h + x @ (B @ x))
        assert np.isclose(lhs, rhs, rtol=1e-4, atol=1e-5)


def test_incremental_stats_match_batch():
    n = 6
    X = jax.random.rademacher(jax.random.PRNGKey(0), (20, n), dtype=jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(1), (20,))
    stats = surrogate.init_stats(n)
    for i in range(20):
        stats = surrogate.update_stats(stats, X[i], y[i])
    Phi = jax.vmap(features.featurize)(X)
    np.testing.assert_allclose(np.asarray(stats.G), np.asarray(Phi.T @ Phi), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(stats.F), np.asarray(Phi.T @ y), rtol=1e-4, atol=1e-4)
    assert np.isclose(float(stats.count), 20)


def test_nbocs_recovers_known_quadratic():
    """Sampling posterior mean should approach the generating coefficients.

    ``sample_nbocs`` standardises the targets internally (subtracts the
    mean, divides by the std): the division is a global rescale that
    preserves direction, but the mean shift is absorbed entirely by the
    *constant* feature's coefficient, which is therefore not recoverable.
    Compare directions over the non-constant coefficients only — with an
    800-point budget the cosine is deterministic at > 0.99 on CPU."""
    n = 5
    npts = 800
    p = features.num_features(n)
    alpha_true = jax.random.normal(jax.random.PRNGKey(7), (p,))
    X = jax.random.rademacher(jax.random.PRNGKey(8), (npts, n), dtype=jnp.float32)
    Phi = jax.vmap(features.featurize)(X)
    y = Phi @ alpha_true
    stats = surrogate.init_stats(n, prior_var=10.0)
    for i in range(npts):
        stats = surrogate.update_stats(stats, X[i], y[i])
    draws = jnp.stack([
        surrogate.sample_nbocs(jax.random.PRNGKey(i), stats)
        for i in range(16)
    ])
    mean = jnp.mean(draws, axis=0)[1:]        # drop the constant feature
    at = alpha_true[1:]
    cos = float(mean @ at / (jnp.linalg.norm(mean) * jnp.linalg.norm(at)))
    assert cos > 0.98, cos


def _posterior_rows(rows: str, N: int = 4, K: int = 2, m: int = 12):
    """The rows a BBO run appends: m random init points, then the warm
    start's row and / or one point's augmented orbit."""
    n = N * K
    X = jax.random.rademacher(jax.random.PRNGKey(20), (m, n), dtype=jnp.float32)
    if "warm" in rows:
        X = jnp.concatenate([X, -X[:1]])
    if "orbit" in rows:
        x = jax.random.rademacher(jax.random.PRNGKey(21), (n,), dtype=jnp.float32)
        X = jnp.concatenate([X, symmetry.orbit_flat(x, N, K)])
    y = jax.random.normal(jax.random.PRNGKey(22), (X.shape[0],))
    return X, y


@pytest.mark.parametrize("rows", ["init", "init+warm", "init+orbit",
                                  "init+warm+orbit"])
@pytest.mark.parametrize("prior_var", [0.1, 1.0], ids=["nbocs", "gbocs"])
def test_square_root_posterior_matches_float64(prior_var, rows):
    """After m rank-1 updates the carried S gives S S^T = (G + I/v)^{-1}
    and S S^T F = cho_solve(A, F), both computed here in float64."""
    X, y = _posterior_rows(rows)
    stats = surrogate.init_stats(X.shape[1], prior_var=prior_var)
    update = jax.jit(surrogate.update_stats)
    for i in range(X.shape[0]):
        stats = update(stats, X[i], y[i])
    Phi = np.asarray(jax.vmap(features.featurize)(X), np.float64)
    A = Phi.T @ Phi + np.eye(Phi.shape[1]) / prior_var
    cov = np.linalg.inv(A)
    S = np.asarray(stats.S, np.float64)
    np.testing.assert_allclose(S @ S.T, cov, rtol=0, atol=1e-6 * np.abs(cov).max())
    F_std = np.asarray(surrogate._standardised(stats)[0], np.float64)
    mu_ref = scipy_linalg.cho_solve(scipy_linalg.cho_factor(A), F_std)
    np.testing.assert_allclose(S @ (S.T @ F_std), mu_ref, rtol=0,
                               atol=1e-5 * np.abs(mu_ref).max())


def test_nbocs_draws_have_the_posterior_covariance():
    """4,096 Thompson draws at fixed keys: their mean and covariance are the
    posterior's, N(A^{-1} F, A^{-1}) with A = G + I/sigma2."""
    X, y = _posterior_rows("init", N=2, K=2, m=10)
    stats = surrogate.init_stats(X.shape[1], prior_var=0.1)
    for i in range(X.shape[0]):
        stats = surrogate.update_stats(stats, X[i], y[i])
    keys = jax.random.split(jax.random.PRNGKey(23), 4096)
    draws = np.asarray(jax.vmap(lambda k: surrogate.sample_nbocs(k, stats))(keys),
                       np.float64)
    Phi = np.asarray(jax.vmap(features.featurize)(X), np.float64)
    cov = np.linalg.inv(Phi.T @ Phi + np.eye(Phi.shape[1]) / 0.1)
    mu = cov @ np.asarray(surrogate._standardised(stats)[0], np.float64)
    scale = np.sqrt(np.diag(cov))
    assert np.abs(draws.mean(0) - mu).max() / scale.max() < 0.1
    emp = np.cov(draws, rowvar=False)
    assert np.abs(emp - cov).max() / np.abs(cov).max() < 0.1


@pytest.mark.parametrize("algo,augment,warm", [
    ("nbocs", False, False), ("nbocs", True, False), ("nbocs", False, True),
    ("gbocs", False, False),
])
def test_run_bbo_many_posterior_residual(algo, augment, warm):
    """A lock-step chunk's carried square roots still invert their final
    posterior precision; algorithms without one report None."""
    N, K, P = 4, 2, 6
    W = jax.random.normal(jax.random.PRNGKey(24), (P, N, 16))
    cfg = bbo_lib.BBOConfig(n=N * K, N=N, K=K, algo=algo, solver="sa",
                            iters=12, init_points=8, augment=augment,
                            backend="jnp")

    def f_batch(xs):
        return jax.vmap(lambda w, x: dec.objective_from_x(x, w, K))(W, xs)

    warm_x = (jax.random.rademacher(jax.random.PRNGKey(25), (P, N * K),
                                    dtype=jnp.float32) if warm else None)
    res = bbo_lib.run_bbo_many(jax.random.PRNGKey(26), cfg, f_batch, P,
                               warm_x=warm_x)
    assert res.posterior_residual.shape == (P,)
    assert float(jnp.max(res.posterior_residual)) <= 1e-5
    rs = bbo_lib.run_bbo_many(jax.random.PRNGKey(26),
                              bbo_lib.BBOConfig(n=N * K, N=N, K=K, algo="rs",
                                                iters=2, init_points=8),
                              f_batch, P)
    assert rs.posterior_residual is None


def test_fm_surrogate_learns():
    n = 6
    X = jax.random.rademacher(jax.random.PRNGKey(0), (64, n), dtype=jnp.float32)
    y = jnp.sum(X[:, :2], axis=1) * X[:, 3]
    mask = jnp.ones((64,))
    fm = surrogate.init_fm(jax.random.PRNGKey(1), n, 4)
    pred0 = surrogate.fm_predict(fm.w0, fm.w, fm.V, X)
    fm = surrogate.train_fm(fm, X, y, mask, jax.random.PRNGKey(2), steps=300)
    pred1 = surrogate.fm_predict(fm.w0, fm.w, fm.V, X)
    ystd = (y - y.mean()) / y.std()
    assert float(jnp.mean((pred1 - ystd) ** 2)) < float(jnp.mean((pred0 - ystd) ** 2)) * 0.5


@pytest.mark.slow
def test_bbo_finds_exact_solution_small_instance():
    """End-to-end paper validation at reduced scale: N=4, K=2 (n=8 spins,
    256 candidates) — nBOCS must find the brute-force optimum."""
    W = jax.random.normal(jax.random.PRNGKey(3), (4, 20))
    res = brute_force(np.asarray(W), K=2, chunk=256)
    f = dec.make_objective(W, 2)
    cfg = bbo_lib.BBOConfig(n=8, N=4, K=2, algo="nbocs", solver="sa",
                            iters=60, init_points=8)
    out = bbo_lib.run_bbo_batch(jax.random.PRNGKey(0), cfg, f, 3)
    assert float(jnp.min(out.best_y)) <= res.best_cost * (1 + 1e-5)


@pytest.mark.slow
def test_bbo_nbocs_beats_random_search():
    """At an 80-iteration budget the comparison is a coin flip on this tiny
    instance (both methods hover near the optimum); at 160 iterations x 8
    seeded runs every nBOCS run reaches the optimum (67.6866) while RS's
    mean stays ~1.3 above it — deterministic on CPU with these keys."""
    W = jax.random.normal(jax.random.PRNGKey(4), (5, 30))
    f = dec.make_objective(W, 2)
    base = dict(n=10, N=5, K=2, iters=160, init_points=10)
    nb = bbo_lib.run_bbo_batch(
        jax.random.PRNGKey(1), bbo_lib.BBOConfig(algo="nbocs", **base), f, 8
    )
    rs = bbo_lib.run_bbo_batch(
        jax.random.PRNGKey(1), bbo_lib.BBOConfig(algo="rs", **base), f, 8
    )
    assert float(jnp.mean(nb.best_y)) <= float(jnp.mean(rs.best_y)) + 1e-6, (
        float(jnp.mean(nb.best_y)), float(jnp.mean(rs.best_y)),
    )


def test_augmentation_appends_orbit_with_equal_costs():
    W = jax.random.normal(jax.random.PRNGKey(5), (4, 12))
    f = dec.make_objective(W, 2)
    cfg = bbo_lib.BBOConfig(n=8, N=4, K=2, algo="rs", iters=3, init_points=4,
                            augment=True)
    out = bbo_lib.run_bbo(jax.random.PRNGKey(2), cfg, f)
    count = int(out.count)
    assert count == 4 + 3 * 8  # K! * 2^K = 2 * 4 = 8 per iteration
    X, y = np.asarray(out.X)[:count], np.asarray(out.y)[:count]
    # each appended orbit shares the evaluated cost
    for i in range(4, count, 8):
        np.testing.assert_allclose(y[i : i + 8], y[i], rtol=1e-5)
        costs = [float(f(jnp.asarray(x))) for x in X[i : i + 8]]
        np.testing.assert_allclose(costs, y[i], rtol=1e-3, atol=1e-5)
