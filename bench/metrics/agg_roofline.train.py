"""The aggregation kernels' share of their roofline: for each batch of the
traced window the least time the chip could take for the aggregation the
batch needs (the larger of ``bench/work.agg_bytes`` over HBM bandwidth
and ``bench/work.agg_flops`` over the bf16 peak, from the batch's real
counts), summed, over the kernels' device time. The fused kernels also
apply the update matmul, whose work is not counted here."""
from bench import work
from bench.kernels import AGG_KERNELS


def read(ctx):
    t, rec, peak = ctx["trace"], ctx["record"], ctx["peak"]
    sizes = rec.get("batch_sizes")
    if t is None or not sizes or not peak:
        return None
    seconds = t.op_seconds(AGG_KERNELS)
    if seconds <= 0:
        return None
    least = sum(max(work.agg_bytes(s, rec["dims"]) / peak["hbm_bytes_per_s"],
                    work.agg_flops(s, rec["dims"]) / peak["bf16_flops"])
                for s in sizes)
    return 100.0 * least / seconds
