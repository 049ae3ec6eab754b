"""engine: admission: device-idle milliseconds per execution of the step
program in the traced window, counting the idle time during which the
innermost open program span is ``engine.admit``
(``progtrace.idle_ms_per_step``)."""
from perfbench import progtrace

SPANS = ("engine.admit",)


def read(ctx):
    prog = getattr(ctx, "program", None)
    if getattr(ctx, "trace", None) is None or prog is None:
        return None
    return progtrace.idle_ms_per_step(ctx.trace, prog, SPANS)
