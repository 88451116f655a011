"""The whole training step's share of the chips' peak: the operations the
window's batches need (``bench/work.step_flops`` on each batch's real
vertex and edge counts, forward and backward, nothing recomputed) over
window seconds x chips x the bf16 peak. The f32 step's matmuls run as
bf16 passes on the MXU, so bf16 is the peak they can reach."""
from bench import work


def read(ctx):
    rec = ctx["record"]
    sizes = rec.get("batch_sizes")
    if not sizes or rec["window_s"] <= 0 or not ctx["peak"]:
        return None
    flops = sum(work.step_flops(rec["model"], s, rec["dims"]) for s in sizes)
    return 100.0 * flops / (rec["window_s"] * ctx["chips"]
                            * ctx["peak"]["bf16_flops"])
