"""scheduler: mean host-clock milliseconds of one ``submit()`` call over
the requests scheduled in the window.  ``submit()`` samples the request's
call schedule (``plan_request``), which waits for the device."""


def read(ctx):
    times = [r.submit_s for r in ctx.scheduled]
    return 1e3 * sum(times) / len(times) if times else None
