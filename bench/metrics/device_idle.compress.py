"""Device idle share of a compression window: 1 - busy / window, busy the union
of device operation intervals in the trace."""


def read(ctx):
    red = ctx["trace"]
    if red.window_s <= 0 or red.devices == 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
