"""Model FLOP utilization of the whole served step, in %: operations the
model needs for every token the window's prefill and decode steps processed
(counted by the configuration's architecture module), per second of the
window, over the chip's bf16 peak."""
from bench import window as Wn


def read(ctx):
    steps = Wn.steps_in_window(ctx.rec.decode + ctx.rec.prefill, ctx)
    if not steps:
        return None
    total = sum(ctx.arch.step_flops(ctx.D, s.tokens, s.ctx, s.out)
                for s in steps)
    return 100.0 * total / ctx.seconds / ctx.peaks["bf16_flops"]
