"""What the measured window saw, from the harness's host stamps.

Pure functions of the recorder and the requests, so that each metric reader
computes from the same numbers and the CPU tests can check them.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def due(ctx) -> list:
    """Requests due in the window (all that the traffic sent)."""
    return [r for r in ctx.traffic.requests
            if ctx.origin + r.arrival_s < ctx.window_end]


def ttfts(ctx) -> List[float]:
    """Seconds from each due request's due time to its first token on the
    host; infinite for one that never got it."""
    out = []
    for r in due(ctx):
        t = ctx.rec.tokens.get(r.rid)
        out.append(t[0] - (ctx.origin + r.arrival_s) if t else math.inf)
    return out


def gaps_in_window(ctx) -> List[float]:
    """Every gap between consecutive tokens of one request that ends in
    the window."""
    out = []
    for times in ctx.rec.tokens.values():
        for a, b in zip(times, times[1:]):
            if ctx.origin <= b < ctx.window_end:
                out.append(b - a)
    return out


def tokens_in_window(ctx) -> int:
    return sum(1 for times in ctx.rec.tokens.values() for t in times
               if ctx.origin <= t < ctx.window_end)


def slo_met(ctx) -> List[bool]:
    """Per due request: first token within the limit and the mean gap
    between its tokens within the limit (a request never served misses)."""
    slo = ctx.mix["slo"]
    out = []
    for r, ttft in zip(due(ctx), ttfts(ctx)):
        times = ctx.rec.tokens.get(r.rid) or []
        mean_gap = (times[-1] - times[0]) / (len(times) - 1) \
            if len(times) > 1 else 0.0
        out.append(ttft <= slo["ttft_s"] and mean_gap <= slo["mean_gap_s"])
    return out


def steps_in_window(steps, ctx) -> list:
    return [s for s in steps if ctx.origin <= s.t1 < ctx.window_end]


def lateness(ctx) -> Dict[str, float]:
    """How late the scheduler's arrival loop released the due requests."""
    late = [ctx.released[r.rid] - (ctx.origin + r.arrival_s)
            for r in due(ctx) if r.rid in ctx.released]
    if not late:
        return {"released": 0}
    return {"released": len(late), "median_s": float(np.median(late)),
            "max_s": float(np.max(late))}


def admitted(ctx) -> list:
    """Due requests the scheduler admitted."""
    return [r for r in ctx.requests if r.admitted_s is not None]
