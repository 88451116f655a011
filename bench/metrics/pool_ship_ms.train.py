"""Milliseconds per batch of the sampling service's row selection and ring
encode (``select_ship_rows`` and ``PayloadCodec.encode``), summed over its
workers: the ``ship_s`` arguments of the window's ``feed/stages`` spans,
plus ``feed/ship`` spans where the stage ran in the training process, over
the window's batches. Worker busy time, not a share of the wall clock."""
from bench import feed_trace


def read(ctx):
    ft, rec = feed_trace.load(ctx), ctx["record"]
    if ft is None or not rec.get("batches") or not (
            ft.named("feed/stages") or ft.named("feed/ship")):
        return None
    return 1e3 * ft.stage_seconds("ship") / rec["batches"]
