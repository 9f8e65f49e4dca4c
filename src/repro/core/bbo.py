"""Black-box optimisation loop for the integer decomposition (paper core).

One BBO iteration = Thompson-sample a quadratic surrogate -> minimise it with
an Ising solver -> de-duplicate -> evaluate the true pseudo-Boolean cost ->
append to the dataset.  The whole run (init + iters) compiles to a single
``lax.scan`` program; independent runs (the paper uses 25) and independent
matrix tiles (the production compression path) are ``vmap`` axes.

Algorithms (paper naming):
  RS       random search                         algo="rs"
  vBOCS    horseshoe-prior BOCS                  algo="vbocs"
  nBOCS    normal-prior BOCS (best performer)    algo="nbocs"
  gBOCS    normal-gamma-prior BOCS               algo="gbocs"
  FMQA08 / FMQA12  factorisation machine, k_FM   algo="fmqa", fm_rank=8/12
  nBOCSa   nBOCS + K!*2^K data augmentation      algo="nbocs", augment=True
Solvers: "sa" | "sq" | "qa" (simulated QA) — paper's nBOCS / nBOCSsq / nBOCSqa.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import features as feat
from repro.core import ising, surrogate, symmetry

__all__ = [
    "BBOConfig",
    "BBOResult",
    "run_bbo",
    "run_bbo_batch",
    "run_bbo_many",
    "paper_iterations",
]


@dataclasses.dataclass(frozen=True)
class BBOConfig:
    """Static configuration (hashable: used as a jit static argument)."""

    n: int                      # number of spins = N*K
    N: int                      # rows of W
    K: int                      # decomposition rank
    algo: str = "nbocs"         # rs | nbocs | gbocs | vbocs | fmqa
    solver: str = "sa"          # sa | sq | qa
    iters: int = 0              # 0 -> paper default 2 n^2
    init_points: int = 0        # 0 -> paper default n
    augment: bool = False       # nBOCSa
    sigma2: float = 0.1         # nBOCS prior variance (paper Fig. 6)
    beta: float = 0.001         # gBOCS inverse scale (paper Fig. 6)
    fm_rank: int = 8            # FMQA08 / FMQA12
    fm_steps: int = 50          # Adam steps per iteration (warm-started)
    gibbs_steps: int = 4        # horseshoe Gibbs sweeps per iteration
    num_reads: int = 10         # Ising restarts per iteration (paper: 10)
    num_sweeps: int = 64        # Ising sweeps per read
    backend: str = "auto"       # Ising solver backend: auto | pallas | jnp
    dtype: object = jnp.float32

    def resolved(self) -> "BBOConfig":
        it = self.iters if self.iters > 0 else 2 * self.n * self.n
        ip = self.init_points if self.init_points > 0 else self.n
        return dataclasses.replace(self, iters=it, init_points=ip)

    @property
    def points_per_iter(self) -> int:
        return symmetry.orbit_size(self.K) if self.augment else 1

    @property
    def max_points(self) -> int:
        c = self.resolved()
        return c.init_points + c.iters * self.points_per_iter


def paper_iterations(n: int) -> int:
    """Paper: n initial points followed by 2 n^2 iterations."""
    return 2 * n * n


class BBOResult(NamedTuple):
    best_x: jax.Array        # (n,) best spin vector found
    best_y: jax.Array        # () its cost
    traj: jax.Array          # (iters,) best-so-far cost after each iteration
    proposed: jax.Array      # (iters, n) candidate evaluated at each iteration
    X: jax.Array             # (max_points, n) acquired dataset (padded)
    y: jax.Array             # (max_points,)
    count: jax.Array         # () number of valid rows in X / y
    # () max |(G + I/prior_var) S S^T - I| of the final carried posterior
    # square root (nBOCS/gBOCS); None for the algorithms that carry none
    posterior_residual: jax.Array | None = None


def _prior_var(cfg: BBOConfig) -> float | None:
    """Coefficient variance of the conjugate prior (nBOCS: sigma2; gBOCS:
    V0 = I), or None for the algorithms without one."""
    return {"nbocs": cfg.sigma2, "gbocs": 1.0}.get(cfg.algo)


def _residual(stats: surrogate.SuffStats, cfg: BBOConfig):
    pv = _prior_var(cfg)
    return None if pv is None else surrogate.posterior_residual(stats, pv)


class _State(NamedTuple):
    X: jax.Array
    y: jax.Array
    count: jax.Array
    stats: surrogate.SuffStats
    hs: surrogate.HorseshoeState
    fm: surrogate.FMState
    best_x: jax.Array
    best_y: jax.Array


def _append(state: _State, x: jax.Array, yv: jax.Array, cfg: BBOConfig) -> _State:
    """Append one evaluated point (plus its symmetry orbit when augmenting)."""
    if cfg.augment:
        xs = symmetry.orbit_flat(x, cfg.N, cfg.K)            # (orbit, n)
        ys = jnp.full((xs.shape[0],), yv, state.y.dtype)
    else:
        xs = x[None]
        ys = yv[None]

    def put(state: _State, row):
        xi, yi = row
        c = state.count
        X = jax.lax.dynamic_update_slice(state.X, xi[None], (c, 0))
        y = jax.lax.dynamic_update_slice(state.y, yi[None], (c,))
        stats = surrogate.update_stats(state.stats, xi, yi)
        return state._replace(X=X, y=y, count=c + 1, stats=stats), None

    state, _ = jax.lax.scan(put, state, (xs, ys))
    better = yv < state.best_y
    return state._replace(
        best_x=jnp.where(better, x, state.best_x),
        best_y=jnp.where(better, yv, state.best_y),
    )


def _dedupe(key, state: _State, x: jax.Array) -> jax.Array:
    """If x (or -x as a whole column-flip need not be checked: orbit handled
    by augmentation only) is already in the dataset, flip one random spin —
    the FMQA convention, which keeps the iteration budget honest."""
    valid = jnp.arange(state.X.shape[0]) < state.count
    dup = jnp.any(valid & jnp.all(state.X == x[None], axis=-1))
    i = jax.random.randint(key, (), 0, x.shape[0])
    return jnp.where(dup, x.at[i].multiply(-1.0), x)


def _sample_ising(key, state: _State, cfg: BBOConfig):
    """Surrogate fit + Thompson sample -> one Ising instance (h, B).

    Pure per-problem function: ``run_bbo_many`` vmaps it over the problem
    axis and hands the stacked (h, B) to one batched ``ising.solve_many``."""
    hs, fm = state.hs, state.fm
    if cfg.algo == "nbocs":
        alpha = surrogate.sample_nbocs(key, state.stats)
        h, B = feat.coeffs_to_ising(alpha, cfg.n)
    elif cfg.algo == "gbocs":
        alpha = surrogate.sample_gbocs(key, state.stats, b0=cfg.beta)
        h, B = feat.coeffs_to_ising(alpha, cfg.n)
    elif cfg.algo == "vbocs":
        alpha, hs = surrogate.sample_vbocs(key, state.stats, state.hs, cfg.gibbs_steps)
        h, B = feat.coeffs_to_ising(alpha, cfg.n)
    elif cfg.algo == "fmqa":
        mask = (jnp.arange(state.X.shape[0]) < state.count).astype(cfg.dtype)
        fm = surrogate.train_fm(state.fm, state.X, state.y, mask, key, cfg.fm_steps)
        h, B = surrogate.fm_to_ising(fm)
    else:  # pragma: no cover - guarded by config validation
        raise ValueError(f"unknown algo {cfg.algo}")
    return (h, B), state._replace(hs=hs, fm=fm)


def _propose(key, state: _State, cfg: BBOConfig):
    """Surrogate fit + Thompson sample + Ising solve -> candidate x."""
    k_fit, k_solve = jax.random.split(key)
    if cfg.algo == "rs":
        with jax.named_scope("bbo.ising"):
            x = jax.random.rademacher(k_solve, (cfg.n,), dtype=cfg.dtype)
        return x, state
    with jax.named_scope("bbo.surrogate"):
        (h, B), state = _sample_ising(k_fit, state, cfg)
    with jax.named_scope("bbo.ising"):
        x, _ = ising.solve_many(
            cfg.solver,
            k_solve,
            ising.IsingProblem(h[None], B[None]),
            num_sweeps=cfg.num_sweeps,
            num_reads=cfg.num_reads,
            backend=cfg.backend,
        )
    return x[0].astype(cfg.dtype), state


@functools.partial(jax.jit, static_argnames=("cfg", "f"))
def run_bbo(key: jax.Array, cfg: BBOConfig, f: Callable) -> BBOResult:
    """Run one BBO optimisation of the black-box ``f: x (n,) -> cost``.

    ``cfg`` must be `resolved()`; ``f`` must be jit-traceable (for the integer
    decomposition use ``repro.core.decomposition.make_objective``).
    """
    cfg = cfg.resolved()
    n, dtype = cfg.n, cfg.dtype
    mp = cfg.max_points

    k_init, k_loop = jax.random.split(key)

    def put_init(state, row):
        return _append(state, row[0], row[1], dataclasses.replace(cfg, augment=False)), None

    with jax.named_scope("bbo.init"):
        X0 = jax.random.rademacher(k_init, (cfg.init_points, n), dtype=dtype)
        y0 = jax.vmap(f)(X0)
        state = _State(
            X=jnp.zeros((mp, n), dtype),
            y=jnp.full((mp,), jnp.inf, dtype),
            count=jnp.zeros((), jnp.int32),
            stats=surrogate.init_stats(n, dtype, _prior_var(cfg)),
            hs=surrogate.init_horseshoe(n, dtype),
            fm=surrogate.init_fm(jax.random.fold_in(k_init, 1), n, cfg.fm_rank, dtype),
            best_x=X0[0],
            best_y=jnp.asarray(jnp.inf, dtype),
        )
        state, _ = jax.lax.scan(put_init, state, (X0, y0))

    def iteration(state: _State, key):
        k1, k2 = jax.random.split(key)
        x, state = _propose(k1, state, cfg)
        with jax.named_scope("bbo.evaluate"):
            x = _dedupe(k2, state, x)
            yv = f(x)
        with jax.named_scope("bbo.append"):
            state = _append(state, x, yv, cfg)
        return state, (state.best_y, x)

    state, (traj, proposed) = jax.lax.scan(
        iteration, state, jax.random.split(k_loop, cfg.iters)
    )
    return BBOResult(
        best_x=state.best_x,
        best_y=state.best_y,
        traj=traj,
        proposed=proposed,
        X=state.X,
        y=state.y,
        count=state.count,
        posterior_residual=_residual(state.stats, cfg),
    )


def run_bbo_batch(key: jax.Array, cfg: BBOConfig, f: Callable, num_runs: int) -> BBOResult:
    """The paper's protocol: ``num_runs`` independent randomised runs (25; 100
    for RS), vmapped into one XLA program."""
    keys = jax.random.split(key, num_runs)
    return jax.vmap(lambda k: run_bbo(k, cfg, f))(keys)


def run_bbo_many(
    key: jax.Array,
    cfg: BBOConfig,
    f_batch: Callable,
    num_problems: int,
    warm_x: jax.Array | None = None,
) -> BBOResult:
    """Optimise ``num_problems`` independent instances in lock-step — the
    production tile fan-out (core/compress.py).

    ``f_batch`` maps a stacked candidate batch ``(P, n) -> (P,)`` costs.
    Unlike ``vmap(run_bbo)``, each iteration fits the P surrogates under
    vmap but issues a *single* batched ``ising.solve_many`` call, so all
    P x num_reads annealing chains run as one flattened chain axis (one
    Pallas program on TPU) instead of P sequential per-spin loops.

    ``warm_x`` (P, n), when given, warm-starts every problem from a prior
    solution (delta recompression, docs/delta.md): the point is evaluated
    and appended to the surrogate training data before the first iteration
    (so the surrogate fits through it and best-so-far starts at its cost),
    and each iteration's Ising solve seeds read 0 from the current
    best-so-far spins via ``solve_many(init_state=...)``.  ``warm_x=None``
    is the cold path, bit-identical to the pre-warm-start loop.

    Returns a ``BBOResult`` with a leading problem axis.  Traces eagerly;
    callers on a hot path should wrap it (with ``cfg``/``f_batch``/
    ``num_problems`` static) in ``jax.jit``.
    """
    cfg = cfg.resolved()
    P, n, dtype = num_problems, cfg.n, cfg.dtype
    # the warm observation occupies one extra dataset row per problem
    mp = cfg.max_points + (1 if warm_x is not None else 0)

    k_init, k_fm, k_loop = jax.random.split(key, 3)

    def bcast(tree):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (P,) + a.shape), tree)

    append_plain = jax.vmap(
        functools.partial(_append, cfg=dataclasses.replace(cfg, augment=False))
    )
    append_cfg = jax.vmap(functools.partial(_append, cfg=cfg))
    sample_many = jax.vmap(functools.partial(_sample_ising, cfg=cfg))
    dedupe_many = jax.vmap(_dedupe)

    def put_init(state, row):
        return append_plain(state, row[0], row[1]), None

    # Named scopes label the ops of each stage for a profiler trace
    # (docs/compression_api.md, "Tracing a job"); they change no op.
    with jax.named_scope("bbo.init"):
        X0 = jax.random.rademacher(k_init, (P, cfg.init_points, n), dtype=dtype)
        y0 = jax.vmap(f_batch, in_axes=1, out_axes=1)(X0)      # (P, init_points)
        state = _State(
            X=jnp.zeros((P, mp, n), dtype),
            y=jnp.full((P, mp), jnp.inf, dtype),
            count=jnp.zeros((P,), jnp.int32),
            stats=bcast(surrogate.init_stats(n, dtype, _prior_var(cfg))),
            hs=bcast(surrogate.init_horseshoe(n, dtype)),
            fm=jax.vmap(lambda k: surrogate.init_fm(k, n, cfg.fm_rank, dtype))(
                jax.random.split(k_fm, P)
            ),
            best_x=X0[:, 0],
            best_y=jnp.full((P,), jnp.inf, dtype),
        )
        state, _ = jax.lax.scan(
            put_init, state, (jnp.swapaxes(X0, 0, 1), jnp.swapaxes(y0, 0, 1))
        )
        if warm_x is not None:
            xw = warm_x.astype(dtype)
            state = append_plain(state, xw, f_batch(xw))

    def iteration(state: _State, key):
        k_fit, k_solve, k_dupe = jax.random.split(key, 3)
        if cfg.algo == "rs":
            with jax.named_scope("bbo.ising"):
                x = jax.random.rademacher(k_solve, (P, n), dtype=dtype)
        else:
            with jax.named_scope("bbo.surrogate"):
                (h, B), state = sample_many(jax.random.split(k_fit, P), state)
            with jax.named_scope("bbo.ising"):
                x, _ = ising.solve_many(
                    cfg.solver,
                    k_solve,
                    ising.IsingProblem(h, B),
                    num_sweeps=cfg.num_sweeps,
                    num_reads=cfg.num_reads,
                    backend=cfg.backend,
                    init_state=state.best_x if warm_x is not None else None,
                )
                x = x.astype(dtype)
        with jax.named_scope("bbo.evaluate"):
            x = dedupe_many(jax.random.split(k_dupe, P), state, x)
            yv = f_batch(x)
        with jax.named_scope("bbo.append"):
            state = append_cfg(state, x, yv)
        return state, (state.best_y, x)

    state, (traj, proposed) = jax.lax.scan(
        iteration, state, jax.random.split(k_loop, cfg.iters)
    )
    return BBOResult(
        best_x=state.best_x,
        best_y=state.best_y,
        traj=jnp.swapaxes(traj, 0, 1),             # (P, iters)
        proposed=jnp.swapaxes(proposed, 0, 1),     # (P, iters, n)
        X=state.X,
        y=state.y,
        count=state.count,
        posterior_residual=jax.vmap(lambda st: _residual(st, cfg))(state.stats),
    )
