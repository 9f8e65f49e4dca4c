"""A seeded dense model, made on the device in one jitted call: the
compression cells' input.

Values: norm scales one, vectors zero, every matrix normal at
1/sqrt(fan-in) (the embedding table at 1/sqrt(d_model)), in the served
dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any seed a run is given (more than 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def paths(tree) -> list:
    """[(``a/b/c`` path, leaf)] of a tree, in flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path), leaf) for path, leaf in flat]


def _fill(key, path: str, sds):
    shape, dtype = sds.shape, sds.dtype
    if "norm" in path:
        return jnp.ones(shape, dtype)
    if len(shape) == 1:
        return jnp.zeros(shape, dtype)
    name = path.rsplit("/", 1)[-1]
    std = shape[-1] ** -0.5 if name == "table" else shape[-2] ** -0.5
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def dense(cfg, seed: int):
    """The dense model for ``cfg`` in its served dtype, from ``seed``."""
    from repro.models import init_model
    from repro.models.params import split

    shapes = jax.eval_shape(
        lambda: split(init_model(jax.random.PRNGKey(0), cfg))[0]
    )
    treedef = jax.tree_util.tree_structure(shapes)
    named = paths(shapes)

    @jax.jit
    def make(key):
        leaves = [_fill(jax.random.fold_in(key, i), path, s)
                  for i, (path, s) in enumerate(named)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make(seed_key(seed))
