"""Share of the traced training window in which no operation ran on the
device: 1 - (union of device operation intervals) / window, averaged over
the cell's chips."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t.window_s <= 0 or not t.ops:
        return None
    return 100.0 * (1.0 - t.mean_busy_s / t.window_s)
