"""Decode step's share of its roofline, in %: the least time of the traced
window's decode steps (the larger of their operations over the bf16 peak and
their least bytes over HBM bandwidth, counted by the configuration's
architecture module) over the device time of the decode programs
(``decode_one_row``) in the trace."""
from bench import trace as TR
from bench.flops import least_seconds

PROGRAM = "decode_one_row"


def read(ctx):
    if ctx.trace is None:
        return None
    n, device_s = TR.module_seconds(ctx.trace, PROGRAM)
    steps = [s for s in ctx.rec.decode
             if ctx.trace.t0 <= s.t0 and s.t1 <= ctx.trace.t1]
    if not n or not steps or device_s <= 0:
        return None
    A = ctx.arch
    least = sum(least_seconds(A.step_flops(ctx.D, s.tokens, s.ctx, s.out),
                              A.decode_bytes(ctx.D, s.ctx), ctx.peaks)[0]
                for s in steps)
    return 100.0 * least / device_s
