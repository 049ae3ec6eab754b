"""scheduler: 95th percentile (nearest rank) of the wait from a request's
scheduled arrival to its admission into a row (``Request.t_admit``), in
milliseconds, over the requests scheduled in the window that finished."""


def read(ctx):
    waits = [1e3 * (r.req.t_admit - ctx.clock_offset - r.scheduled)
             for r in ctx.scheduled if r.req is not None]
    return ctx.nearest_rank(waits, 95) if waits else None
