"""engine: mean device-idle gap, in milliseconds, between consecutive
executions of the batched step program while a row is live
(``devtrace.mean_step_gap_ms``)."""


def read(ctx):
    return (None if ctx.trace is None
            else ctx.devtrace.mean_step_gap_ms(ctx.trace))
