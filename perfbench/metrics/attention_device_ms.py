"""denoiser: attention: mean device milliseconds, per execution of the step
program in the traced window, of the operations under the ``attention``
named scope (q/k/v/o projections and the attention core, with the
block's pre-norm and residual add; ``progtrace.scope_ms_per_step``)."""
from perfbench import progtrace


def read(ctx):
    prog = getattr(ctx, "program", None)
    if getattr(ctx, "trace", None) is None or prog is None:
        return None
    return progtrace.scope_ms_per_step(ctx.trace, prog, "attention")
