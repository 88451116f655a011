"""Share of the traced window's device-idle time (summed over the cell's
chips) that lies inside ``feed/decode`` or ``feed/assemble`` spans: the
chip idle while the training process did its own feed work, %."""
from bench import feed_trace


def read(ctx):
    ft = feed_trace.load(ctx)
    if ft is None or not ft.named("feed/assemble"):
        return None
    idle = ft.idle_seconds()
    if idle <= 0:
        return None
    return 100.0 * ft.idle_inside(feed_trace.OWN_WORK) / idle
