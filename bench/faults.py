"""Faults planted in the program's compression path, each of which a
compression cell's comparison must catch (or, for ``random_spins``, whose
reading shows how far the answer sees the Ising kernel).  ``plant`` patches
the program in this process before anything is traced; the benchmark's own
runs never call it.

- ``altered_C``: one tile's C is scaled by 1.1 where ``execute_plan`` packs
  it (an answer altered where it is produced);
- ``keep_init``: the BBO stage returns its state unchanged, so every tile
  keeps its greedy + alternating init (a step that returns its state
  unchanged);
- ``half_tiles``: the second half of every pooled chunk is left out and
  copies the first half's answer;
- ``random_spins``: the Ising solver returns uniform random spins.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("altered_C", "keep_init", "half_tiles", "random_spins")


def plant(name: str, setattr_=setattr) -> None:
    """Patch the program with fault ``name``; ``setattr_`` may be a test's
    ``monkeypatch.setattr`` so the patch is undone after the test.  JAX's
    in-memory caches are cleared, so that no program traced before the
    patch is reused (clear them again once the patch is undone)."""
    from repro.compression import execute
    from repro.core import bbo, ising

    if name == "altered_C":
        orig_pack = execute._pack_tensor

        def altered(t, M_seg, C_seg, dtype):
            return orig_pack(t, M_seg, C_seg.at[0].multiply(1.1), dtype)

        setattr_(execute, "_pack_tensor", altered)
    elif name == "keep_init":
        orig_bbo = bbo.run_bbo_many

        def unchanged(*args, **kwargs):
            res = orig_bbo(*args, **kwargs)
            return res._replace(best_y=jnp.full_like(res.best_y, jnp.inf))

        setattr_(bbo, "run_bbo_many", unchanged)
    elif name == "half_tiles":
        orig_batch = execute.compress_tile_batch

        def half(tiles, *args, **kwargs):
            outs = orig_batch(tiles, *args, **kwargs)
            h = tiles.shape[0] // 2
            return tuple(o.at[h:2 * h].set(o[:h]) for o in outs)

        setattr_(execute, "compress_tile_batch", half)
    elif name == "random_spins":
        orig_solve = ising.solve_many

        def random(solver, key, problem, *args, **kwargs):
            x, e = orig_solve(solver, key, problem, *args, **kwargs)
            return jax.random.rademacher(
                jax.random.fold_in(key, 0x5EED), x.shape, x.dtype), e

        setattr_(ising, "solve_many", random)
    else:
        raise KeyError(f"no fault {name!r}; have {FAULTS}")
    jax.clear_caches()
