"""Device time of the BBO dataset update (the ``bbo.append`` scope of
``core/bbo.py``: each new point written into the dataset and folded into
the surrogate's sufficient statistics) per tile and BBO iteration of the
traced window's jobs, counted as for ``bbo_surrogate_us_per_tile_iter``."""

from bench.trace_scopes import us_per_tile_iteration


def read(ctx):
    return us_per_tile_iteration(ctx, "bbo.append")
