"""sampler: mean number of batched network calls (``plan.nfe``) of the
requests completed in the window: the transition times a request's
predetermined schedule visits."""


def read(ctx):
    nfe = [r.req.plan.nfe for r in ctx.completed]
    return sum(nfe) / len(nfe) if nfe else None
