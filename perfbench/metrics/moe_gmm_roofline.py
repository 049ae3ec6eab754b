"""expert kernel: share of its roofline that one step's grouped matmuls
reach, in percent.  Their device time is ``moe_gmm_device_ms``'s per
step; their least time is the larger of their FLOPs over the bf16 peak
and their bytes over HBM bandwidth, from the configuration's
``moe_gmm_work`` at the cell's batch (``max_batch`` rows, which a
backlog keeps live) and canvas: every held expert's weights once, and
the routed rows in and out at the expected pairs.  At 128 tokens per
expert the weights' bytes bound it."""
from perfbench import cell, expert_kernel


def read(ctx):
    ms = expert_kernel.ms_per_step(ctx)
    if ms is None:
        return None
    t = ctx.traffic
    work = cell.model_module(ctx.conf).moe_gmm_work(
        ctx.conf, t["max_batch"], t["canvas"])
    least, _ = ctx.flops.roofline_seconds(*work, ctx.peaks)
    return 100.0 * least / (ms * 1e-3)
