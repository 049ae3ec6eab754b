"""The grouped-matmul kernel's device time per step, which the expert
layer's readers share (``metrics/moe_gmm_*.py``, ``moe_route_device_ms``).

Its operations are named ``moe_gmm`` or ``moe_gmm.<n>`` (the held
experts' gate, up and down matmuls of every layer); they are counted
where they start inside an execution of the step program, as
``step_device_ms`` counts them."""

KERNEL = "moe_gmm"


def ms_per_step(ctx):
    """The kernel's device ms per step execution, or ``None`` where the
    trace holds no step program or none of the kernel's operations."""
    tr = getattr(ctx, "trace", None)
    if tr is None:
        return None
    prog = ctx.devtrace.main_program(tr)
    runs = ctx.devtrace.executions(tr, prog) if prog else []
    ops = [(s, e) for n, s, e in tr.ops
           if (n == KERNEL or n.rsplit(".", 1)[0] == KERNEL)
           and any(a <= s < b for a, b in runs)]
    if not ops:
        return None
    return 1e-6 * ctx.devtrace.union_ns(ops) / len(runs)
