"""Host ms inside the KV tiers' entry points (ensure_resident, swap_out,
extend, append_token, prefetch_resident, payloads_for) and the prefix-KV
restore into a fresh session cache, each fenced on queued device work, per
request admitted."""
from bench import window as Wn


def read(ctx):
    n = len(Wn.admitted(ctx))
    if ctx.tier is None or not n:
        return None
    return ctx.tier.seconds * 1e3 / n
