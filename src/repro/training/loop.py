"""Distributed training step: microbatched grad accumulation + sharded
optimiser + activation sharding rules.

The step is a single pjit program:

    for each microbatch (lax.scan):       # gradient accumulation, fp32
        loss, grads += grad(train_loss)   # remat inside the model scan
    grads /= n_micro
    params, opt_state = optimizer.update(...)

Parameter/optimiser shardings come from the logical-axis rules
(distributed/sharding.py): FSDP over ``data`` x TP over ``model``; batch over
``(pod, data)``; the scanned activation carry is sequence-sharded over
``model`` (SP) so the per-device live set stays small (DESIGN.md §5).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.distributed import sharding as shd
from repro.models import init_model, train_loss
from repro.models.params import split
from repro.optim import adafactor, adamw

__all__ = [
    "TrainState",
    "init_train_state",
    "make_train_step",
    "state_shardings",
    "batch_sharding",
]


class TrainState(NamedTuple):
    step: jax.Array      # () int32
    params: dict         # model values tree
    opt: dict            # optimiser state tree


def make_optimizer(pcfg: ParallelConfig):
    return {"adamw": adamw, "adafactor": adafactor}[pcfg.optimizer]()


def _axes_trees(cfg: ModelConfig):
    """(ShapeDtypeStruct values tree, logical-axes tree) without allocating.

    The axes tree is static metadata captured during the eval_shape trace
    (Param.axes holds strings, which eval_shape cannot return)."""
    box = {}

    def shapes_only():
        values, axes = split(init_model(jax.random.PRNGKey(0), cfg))
        box["axes"] = axes
        return values

    shapes = jax.eval_shape(shapes_only)
    return shapes, box["axes"]


def state_shardings(cfg: ModelConfig, pcfg: ParallelConfig, mesh: Mesh):
    """NamedSharding tree matching TrainState."""
    shapes, axes = _axes_trees(cfg)
    rules = shd.make_rules(pcfg)
    p_sh = shd.param_shardings(axes, shapes, rules, mesh)

    opt = make_optimizer(pcfg)
    opt_shapes = jax.eval_shape(opt.init, shapes)

    # Optimiser state mirrors the params tree one level down ({"m": tree,
    # "v": tree} for adamw; per-param {"v"} / {"vr","vc"} dicts for
    # adafactor).  Same-shape moments inherit the param sharding; factored
    # (lower-rank, tiny) adafactor moments are replicated.
    def match(shape_tree, sh_tree, opt_tree):
        def one(pshape, psh, osub):
            def leafmap(o):
                if tuple(o.shape) == tuple(pshape.shape):
                    return psh
                return NamedSharding(mesh, P())
            return jax.tree.map(leafmap, osub)
        return jax.tree.map(
            one, shape_tree, sh_tree, opt_tree,
            is_leaf=lambda x: hasattr(x, "shape"),
        )

    if pcfg.optimizer == "adamw":
        opt_sh = {k: match(shapes, p_sh, opt_shapes[k]) for k in opt_shapes}
    else:
        opt_sh = match(shapes, p_sh, opt_shapes)

    return TrainState(
        step=NamedSharding(mesh, P()),
        params=p_sh,
        opt=opt_sh,
    )


def batch_sharding(mesh: Mesh, ndim: int = 2):
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return NamedSharding(mesh, P(dp, *([None] * (ndim - 1))))


def init_train_state(key, cfg: ModelConfig, pcfg: ParallelConfig, mesh: Mesh) -> TrainState:
    """Sharded initialisation: runs under jit with out_shardings so no
    device ever materialises a full replica of a big tensor."""
    sh = state_shardings(cfg, pcfg, mesh)
    opt = make_optimizer(pcfg)

    def init():
        values, _ = split(init_model(key, cfg))
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=values,
            opt=opt.init(values),
        )

    with jax.set_mesh(mesh):
        return jax.jit(init, out_shardings=sh)()


def make_train_step(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    lr_schedule,
    *,
    unroll: bool = False,
    donate: bool = True,
):
    """Returns train_step(state, batch) -> (state, metrics); NOT yet jitted —
    callers jit/lower with explicit shardings (launch/train.py, dryrun.py)."""
    opt = make_optimizer(pcfg)
    n_micro = pcfg.microbatches

    def train_step(state: TrainState, batch: dict):
        def micro_slices(x):
            b = x.shape[0]
            return x.reshape((n_micro, b // n_micro) + x.shape[1:])

        micro = jax.tree.map(micro_slices, batch)

        def loss_fn(params, mb):
            return train_loss(params, mb, cfg, unroll=unroll)[0]

        def one_micro(acc, mb):
            mb = jax.tree.map(lambda x: shd.constrain(x, "batch") if x.ndim >= 1 else x, mb)
            loss, g = jax.value_and_grad(loss_fn)(state.params, mb)
            gacc, lacc = acc
            gacc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), gacc, g)
            return (gacc, lacc + loss), None

        accum_dtype = jnp.dtype(pcfg.accum_dtype)
        g0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, accum_dtype), state.params
        )
        if n_micro == 1:
            mb = jax.tree.map(lambda x: x[0], micro)
            loss, grads = jax.value_and_grad(loss_fn)(state.params, mb)
            grads = jax.tree.map(lambda g: g.astype(accum_dtype), grads)
        else:
            (grads, loss_sum), _ = jax.lax.scan(one_micro, (g0, 0.0), micro)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = loss_sum / n_micro

        lr = lr_schedule(state.step)
        new_params, new_opt, gnorm = opt.update(
            grads, state.opt, state.params, state.step, lr
        )
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return TrainState(state.step + 1, new_params, new_opt), metrics

    return train_step
