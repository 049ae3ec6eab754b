"""denoiser: experts: mean device milliseconds, per execution of the step
program in the traced window, of the grouped-matmul kernel's operations
(``perfbench/expert_kernel.py``)."""
from perfbench import expert_kernel


def read(ctx):
    return expert_kernel.ms_per_step(ctx)
