"""Device idle share of a serving window: the reduction of
``device_idle.compress`` (1 - busy / window), read from this cell's
trace."""

from bench import cells


def read(ctx):
    return cells.metric_reader(ctx["cell"].root, "device_idle.compress")(ctx)
