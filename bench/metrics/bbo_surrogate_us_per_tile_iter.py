"""Device time of the nBOCS surrogate (the ``bbo.surrogate`` scope of
``core/bbo.py``: the Thompson sample's Gram and prior, Cholesky,
``cho_solve``, triangular solve and ``coeffs_to_ising``) per tile and BBO
iteration of the traced window's jobs.

The tile-iterations are the program's own count (tiles of each BBO pool's
chunks times its ``bbo_iters``, from the job's manifest), so a change of
chunk size does not move the reading unless the cost per tile moves."""

from bench.trace_scopes import us_per_tile_iteration


def read(ctx):
    return us_per_tile_iteration(ctx, "bbo.surrogate")
