"""Useful model FLOPs of the window's requests (bench/work.py:
request_flops) over the device time of the engine's step programs
(prefill, insert, decode) times the peak FLOP/s."""


def read(ctx):
    return ctx.step_mfu()
