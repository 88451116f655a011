"""Share of the training window's epoch time that the prefetch thread
spent waiting on the sampling service for its next item (``feed/pool_wait``
spans, less the ring decode nested inside them) over the sum of
``run_epoch``'s ``epoch_time_s``, %."""
from bench import feed_trace


def read(ctx):
    ft, rec = feed_trace.load(ctx), ctx["record"]
    if ft is None or not ft.named("feed/pool_wait") \
            or not rec.get("epoch_time_s"):
        return None
    return 100.0 * ft.pool_wait_self_seconds() / rec["epoch_time_s"]
