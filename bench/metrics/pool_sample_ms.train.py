"""Milliseconds per batch of the sampling service's sampling
(``NeighborSampler.batch_at``), summed over its workers: the ``sample_s``
arguments of the window's ``feed/stages`` spans, plus ``feed/sample``
spans where the stage ran in the training process, over the window's
batches. Worker busy time, not a share of the wall clock."""
from bench import feed_trace


def read(ctx):
    ft, rec = feed_trace.load(ctx), ctx["record"]
    if ft is None or not rec.get("batches") or not (
            ft.named("feed/stages") or ft.named("feed/sample")):
        return None
    return 1e3 * ft.stage_seconds("sample") / rec["batches"]
