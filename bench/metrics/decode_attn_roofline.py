"""The decode attention kernel's least time for the K/V bytes and FLOPs
of the valid contexts of every useful decode step (bench/work.py:
decode_attn_work), at the peaks, over the kernel's device time."""
from bench.cell import DECODE_KERNEL


def read(ctx):
    return ctx.roofline(DECODE_KERNEL, "decode")
