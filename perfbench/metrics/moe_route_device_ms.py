"""denoiser: routing: mean device milliseconds per execution of the step
program of everything in the MoE sub-layer but the expert matmuls: the
``mlp`` scope's time (pre-norm, router, top-k, sort, gathers, the grouped
matmuls, combine, residual add; ``progtrace.scope_ms_per_step``) less the
grouped-matmul kernel's (``moe_gmm_device_ms``), both counted over the
same executions."""
from perfbench import expert_kernel, progtrace


def read(ctx):
    prog = getattr(ctx, "program", None)
    if getattr(ctx, "trace", None) is None or prog is None:
        return None
    kernel = expert_kernel.ms_per_step(ctx)
    mlp = progtrace.scope_ms_per_step(ctx.trace, prog, "mlp")
    if kernel is None or mlp is None:
        return None
    return mlp - kernel
