"""scheduler: batching: share of the positions the step computed that lie
past their request's length, in percent: the sum of the
``padded_positions`` attribute of the ``engine.stepwise`` spans that
began in the traced window over the sum of their ``rows`` times the
canvas (``progtrace.padded_position_share``)."""
from perfbench import progtrace


def read(ctx):
    prog = getattr(ctx, "program", None)
    if prog is None:
        return None
    return progtrace.padded_position_share(prog, ctx.traffic["canvas"])
