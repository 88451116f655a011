"""Milliseconds per batch of the training process's own feed work: the
ring decode (``feed/decode``) and the iteration's assembly
(``feed/assemble``: placement, stacking, cache hooks), less the sampling
stages that ran inside the assembly when there is no sampling service."""
from bench import feed_trace


def read(ctx):
    ft, rec = feed_trace.load(ctx), ctx["record"]
    if ft is None or not ft.named("feed/assemble") or not rec.get("batches"):
        return None
    return 1e3 * ft.own_work_seconds() / rec["batches"]
