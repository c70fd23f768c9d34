"""Median time to first token over every request due in the window: host
seconds from its due time to its first token on the host. A request never
served counts with the time it had waited when the run stopped, which is at
least the grace the harness gives past the window. The median, not a tail:
a window holds too few requests for a percentile with ten beyond it."""
from bench import window as Wn
from bench.harness import percentile


def read(ctx):
    stop = ctx.stopped_at
    vals = [t if t != float("inf") else stop - (ctx.origin + r.arrival_s)
            for r, t in zip(Wn.due(ctx), Wn.ttfts(ctx))]
    return percentile(vals, 50) if vals else None
