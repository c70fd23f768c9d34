"""Requests per decode step, averaged over the window's decode steps
(scheduler)."""
from bench import window as Wn


def read(ctx):
    steps = Wn.steps_in_window(ctx.rec.decode, ctx)
    return sum(s.rows for s in steps) / len(steps) if steps else None
