"""Device milliseconds per batch in the aggregation kernels (forward and
backward, every chip), from the traced window's operations."""
from bench.kernels import AGG_KERNELS


def read(ctx):
    t, rec = ctx["trace"], ctx["record"]
    if t is None or not rec.get("batches"):
        return None
    seconds = t.op_seconds(AGG_KERNELS)
    if seconds <= 0:
        return None
    return 1e3 * seconds / rec["batches"]
