"""Mean device milliseconds of one served micro-batch's forward program
(the launches of the serving runtime's jitted ``fwd``) in the traced
window."""
from bench.kernels import SERVE_FORWARD


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    launches = t.module_events(SERVE_FORWARD)
    if not launches:
        return None
    return 1e3 * sum(e.dur for e in launches) / len(launches)
