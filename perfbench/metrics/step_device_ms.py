"""denoiser + decode step: mean device milliseconds of one execution of
the batched step program (the program that took most device time in the
traced window)."""


def read(ctx):
    if ctx.trace is None:
        return None
    prog = ctx.devtrace.main_program(ctx.trace)
    runs = ctx.devtrace.executions(ctx.trace, prog) if prog else []
    return 1e-6 * sum(e - s for s, e in runs) / len(runs) if runs else None
