"""Milliseconds per step that the main thread spent handing the step to
the device (``step/dispatch``: placing the stacked batch and launching the
jitted step, up to its return)."""
from bench import feed_trace


def read(ctx):
    ft = feed_trace.load(ctx)
    steps = ft.named("step/dispatch") if ft is not None else []
    if not steps:
        return None
    return 1e3 * sum(s.dur for s in steps) / len(steps)
