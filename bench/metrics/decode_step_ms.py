"""Host ms per M2CacheEngine.decode_step call in the window (engine)."""
from bench import window as Wn


def read(ctx):
    steps = Wn.steps_in_window(ctx.rec.decode, ctx)
    return sum(s.t1 - s.t0 for s in steps) * 1e3 / len(steps) \
        if steps else None
