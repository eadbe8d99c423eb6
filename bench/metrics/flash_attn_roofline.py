"""The prefill flash attention kernel's least time for the causal FLOPs
and the Q/K/V/O bytes of the actual prompt lengths (bench/work.py:
flash_work), at the peaks, over the kernel's device time."""
from bench.cell import FLASH_KERNEL


def read(ctx):
    return ctx.roofline(FLASH_KERNEL, "flash")
