"""whole step: model FLOPs of the batched calls the device ran in the
traced window over the window's seconds times the chip's bf16 peak, in
percent.  A call is one execution of the step program (the program that
took most device time) that started inside the window; its work is one
denoiser forward over one canvas (``flops.denoiser_flops``) per row that
was live in it.  Executions and the harness's dispatches of the step
pair up in order: the profiler starts before the first dispatch, and the
cells that report this run one method at one canvas, so one program."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_s <= 0:
        return None
    prog = ctx.devtrace.main_program(tr)
    runs = ctx.devtrace.executions(tr, prog) if prog else []
    first = tr.modules_before.get(prog, 0)
    if first + len(runs) > len(ctx.live_rows):
        return None         # more executions than dispatches: no pairing
    rows = sum(ctx.live_rows[first + i] for i, (s, _) in enumerate(runs)
               if s > tr.window[0])
    if not rows:
        return None
    work = ctx.flops.denoiser_flops(ctx.conf, ctx.traffic["canvas"]) * rows
    return 100.0 * work / (tr.window_s * ctx.peaks["bf16_flops"])
