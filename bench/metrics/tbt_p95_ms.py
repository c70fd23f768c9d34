"""95th percentile, in ms, of every gap between consecutive output tokens
of one request that ends in the window."""
from bench import window as Wn
from bench.harness import percentile


def read(ctx):
    gaps = Wn.gaps_in_window(ctx)
    return percentile(gaps, 95) * 1e3 if gaps else None
