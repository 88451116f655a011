"""Share of the traced window's device-idle time (summed over the cell's
chips) that lies inside ``feed/pool_wait`` spans and outside the decode
nested in them: the chip idle while the feed waited on the sampling
service, %."""
from bench import feed_trace


def read(ctx):
    ft = feed_trace.load(ctx)
    if ft is None or not ft.named("feed/pool_wait"):
        return None
    idle = ft.idle_seconds()
    if idle <= 0:
        return None
    return 100.0 * ft.idle_inside(
        ("feed/pool_wait",), minus=feed_trace.OWN_WORK) / idle
