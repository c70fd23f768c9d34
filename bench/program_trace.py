"""Put a profiler trace's device idle down to the program's own host spans.

While a ``repro.obs.TraceRecorder`` is attached to the scheduler, the served
path holds a ``jax.profiler.TraceAnnotation`` named ``m2c:<track>.<name>``
over each host span (scheduler iteration, engine step, M2 neuron-cache
bookkeeping, KV tier moves), so a profiler trace holds them on the device
trace's clock. ``load`` reads those spans and the device's ops from the
``.xplane.pb``; ``reduce`` works on that list alone, so it is checked on a
recorded slice (``bench/tests/data/program_trace_slice.json``).

    python bench/program_trace.py <trace dir or .xplane.pb>

prints the reduction as JSON.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from types import SimpleNamespace
from typing import Dict, Iterable, List

PREFIX = "m2c:"
OPS_LINE = "XLA Ops"
UNCOVERED = "uncovered"
DECODE_STEP = "engine.decode_step"


def load(path: str) -> List[dict]:
    """Device ops and the program's host spans, as dicts with plane,
    line, name (host spans without the prefix), start_ns and dur_ns."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device:
                    name = ev.name
                elif ev.name.startswith(PREFIX):
                    name = ev.name[len(PREFIX):]
                else:
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns})
    return out


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def idle_gaps(events: List[dict]) -> List[tuple]:
    """(start_ns, end_ns) between consecutive runs of the op union of each
    device plane."""
    gaps = []
    for p in sorted({e["plane"] for e in events
                     if e["plane"].startswith("/device:")}):
        merged = _union((e["start_ns"], e["start_ns"] + e["dur_ns"])
                        for e in events
                        if e["plane"] == p and e["line"] == OPS_LINE)
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    return gaps


def host_spans(events: List[dict]) -> List[tuple]:
    """(start_ns, end_ns, name) of the program's spans, by start."""
    return sorted((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                  for e in events if not e["plane"].startswith("/device:"))


def _innermost_cover(a, b, spans) -> Dict[str, float]:
    """ns of [a, b) under each innermost span (the latest-started of the
    spans open at a moment; spans nest on the host), and uncovered."""
    cuts = sorted({a, b} | {t for s, e, _ in spans for t in (s, e)
                            if a < t < b})
    out: Dict[str, float] = {}
    for lo, hi in zip(cuts, cuts[1:]):
        inner = None
        for s, e, name in spans:
            if s <= lo and hi <= e and (inner is None or s >= inner[0]):
                inner = (s, name)
        label = inner[1] if inner else UNCOVERED
        out[label] = out.get(label, 0.0) + (hi - lo)
    return out


def reduce(events: List[dict]) -> SimpleNamespace:
    """idle_by_program: seconds of device idle by the innermost program
    span that covers most of each gap ("uncovered" where no span does);
    idle_s: all idle seconds between ops; spans: {name: [count, s]}."""
    spans = host_spans(events)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    idle: Dict[str, float] = {}
    total = 0.0
    for a, b in idle_gaps(events):
        lo = bisect.bisect_left(starts, a - longest)
        near = [sp for sp in spans[lo:bisect.bisect_left(starts, b)]
                if sp[1] > a]
        cover = _innermost_cover(a, b, near)
        label = max(cover, key=cover.get)
        idle[label] = idle.get(label, 0.0) + (b - a) / 1e9
        total += (b - a) / 1e9
    counts: Dict[str, list] = {}
    for s, e, name in spans:
        c = counts.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) / 1e9
    return SimpleNamespace(idle_by_program=idle, idle_s=total, spans=counts,
                           events=spans)


def top(idle_by_program: Dict[str, float], n: int = 10) -> list:
    """The ``n`` largest labels and ``uncovered``, as [label, s] pairs."""
    rows = sorted(((k, v) for k, v in idle_by_program.items()
                   if k != UNCOVERED), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows] + [
        [UNCOVERED, idle_by_program.get(UNCOVERED, 0.0)]]


def outermost_seconds(spans: List[tuple], names: Iterable[str]) -> float:
    """Seconds in spans named in ``names`` that no other such span
    encloses."""
    names = set(names)
    total, reach = 0.0, None
    for s, e, name in spans:
        if name not in names:
            continue
        if reach is not None and e <= reach:
            continue                    # nested in an outer one
        total += (e - s) / 1e9
        reach = e if reach is None else max(reach, e)
    return total


def per_step_ms(red, name: str, step: str = DECODE_STEP):
    """Host ms in spans ``name`` per ``step`` span; None without steps."""
    n = red.spans.get(step, [0, 0.0])[0]
    return red.spans.get(name, [0, 0.0])[1] * 1e3 / n if n else None


def child_cover(spans: List[tuple], parent: str, prefix: str):
    """Share of the ``parent`` spans' time covered by spans whose name
    starts with ``prefix`` inside them; None without parents."""
    parents = [(s, e) for s, e, n in spans if n == parent]
    kids = [(s, e) for s, e, n in spans if n.startswith(prefix)]
    whole = sum(e - s for s, e in parents)
    if not whole:
        return None
    starts = [s for s, _ in parents]
    inside = 0
    for s, e in kids:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= parents[i][1]:
            inside += e - s
    return inside / whole


def reduce_dir(trace_dir: str) -> SimpleNamespace:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return reduce(load(paths[0]))


def summary(red) -> dict:
    return {"idle_s": red.idle_s,
            "idle_by_program": top(red.idle_by_program),
            "spans": red.spans,
            "decode_children_cover": child_cover(red.events, DECODE_STEP,
                                                 "engine.decode."),
            "m2_manager_ms": per_step_ms(red, "engine.decode.m2_manager"),
            "decode_dispatch_ms": per_step_ms(red, "engine.decode.dispatch"),
            "kv_move_s": outermost_seconds(red.events, (
                "kv.demote", "kv.promote", "engine.restore_prefix"))}


if __name__ == "__main__":
    target = sys.argv[1]
    red = reduce(load(target)) if target.endswith(".pb") \
        else reduce_dir(target)
    print(json.dumps(summary(red)))
