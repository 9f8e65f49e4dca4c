"""Faults planted in the program, each of which a cell's comparison must
catch (or, for ``random_spins``, whose reading shows how far the answer
sees the Ising kernel).  ``plant`` patches the program in this process
before anything is traced; the benchmark's own runs never call it.

Compression path:

- ``altered_C``: one tile's C is scaled by 1.1 where ``execute_plan`` packs
  it (an answer altered where it is produced);
- ``keep_init``: the BBO stage returns its state unchanged, so every tile
  keeps its greedy + alternating init (a step that returns its state
  unchanged);
- ``half_tiles``: the second half of every pooled chunk is left out and
  copies the first half's answer;
- ``random_spins``: the Ising solver returns uniform random spins.

Serving path:

- ``wrong_kv_page``: the decode step reads the scratch page in place of
  each slot's first KV page (``PagePool.gather``);
- ``dropped_expert``: expert 0's output is dropped from every MoE layer
  (``models/moe._expert_linear``);
- ``altered_token``: the scheduler serves the token after the one its
  logits put first, for each request's first token (a token altered where
  it is produced).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("altered_C", "keep_init", "half_tiles", "random_spins",
          "wrong_kv_page", "dropped_expert", "altered_token")


def plant(name: str, setattr_=setattr) -> None:
    """Patch the program with fault ``name``; ``setattr_`` may be a test's
    ``monkeypatch.setattr`` so the patch is undone after the test.  JAX's
    in-memory caches are cleared, so that no program traced before the
    patch is reused (clear them again once the patch is undone)."""
    from repro.compression import execute
    from repro.core import bbo, ising
    from repro.models import moe
    from repro.serving import kv_pages, scheduler

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
    elif name == "wrong_kv_page":
        orig_gather = kv_pages.PagePool.gather

        def wrong_page(self, pools, resident, tables):
            return orig_gather(self, pools, resident, tables.at[:, 0].set(0))

        setattr_(kv_pages.PagePool, "gather", wrong_page)
    elif name == "dropped_expert":
        orig_linear = moe._expert_linear

        def dropped(x, w):
            return orig_linear(x, w).at[0].set(0)

        setattr_(moe, "_expert_linear", dropped)
    elif name == "altered_token":
        orig_sample = scheduler.Scheduler._sample

        def altered(self, req, logits_row, index):
            tok = orig_sample(self, req, logits_row, index)
            return (tok + 1) % logits_row.shape[-1] if index == 0 else tok

        setattr_(scheduler.Scheduler, "_sample", altered)
    else:
        raise KeyError(f"no fault {name!r}; have {FAULTS}")
    jax.clear_caches()
