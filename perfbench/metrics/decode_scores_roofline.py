"""decode kernel: share of its roofline that the streaming (token, score)
decode reaches, in percent.  The kernel's device time is the mean of the
trace's operations named after it; its least time is the larger of its
operations over the bf16 peak and its bytes over HBM bandwidth, counted
from the op's shapes (``flops.decode_scores_work``: max_batch x canvas x
vocab, logits in the configuration's dtype, the f32 Gumbel slab in sample
mode), not from the kernel's padded blocks.  At these shapes the bytes
bound it."""

KERNEL = "decode_scores"


def read(ctx):
    if ctx.trace is None:
        return None
    total_ns, count = ctx.devtrace.op_time_ns(ctx.trace, KERNEL)
    if not count:
        return None
    t = ctx.traffic
    work = ctx.flops.decode_scores_work(
        t["max_batch"], t["canvas"], ctx.conf["vocab_size"],
        ctx.conf["torch_dtype"], t["x0_mode"] == "sample")
    least, _ = ctx.flops.roofline_seconds(*work, ctx.peaks)
    return 100.0 * least / (total_ns * 1e-9 / count)
