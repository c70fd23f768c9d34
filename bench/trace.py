"""Reduce a profiler trace to device busy time and program time (the host
activity behind the device's idle gaps is ``bench/program_trace.py``'s).

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a flat
list of events; ``reduce`` works on that list alone, so it can be checked
on a small recorded trace (``bench/tests/data``).
"""
from __future__ import annotations

import glob
import json
import os
from types import SimpleNamespace
from typing import Dict, List

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(path: str) -> List[dict]:
    """Device ops and modules as dicts with plane, line, name, start_ns
    and dur_ns."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": ev.start_ns,
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


def reduce(events: List[dict], window_s: float) -> SimpleNamespace:
    """busy_s: union of op intervals, averaged over the device planes that
    ran anything; modules: {name: [count, seconds]}."""
    planes = sorted({e["plane"] for e in events
                     if e["plane"].startswith("/device:")})
    busy = 0.0
    for p in planes:
        ops = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
               if e["plane"] == p and e["line"] == OPS_LINE]
        merged = _union(ops)
        busy += sum(b - a for a, b in merged) / 1e9
    busy /= max(len(planes), 1)
    modules: Dict[str, list] = {}
    for e in events:
        if e["line"] == MODULES_LINE:
            m = modules.setdefault(e["name"], [0, 0.0])
            m[0] += 1
            m[1] += e["dur_ns"] / 1e9
    return SimpleNamespace(busy_s=busy, window_s=window_s, modules=modules,
                           planes=len(planes))


def module_seconds(red, key: str) -> tuple:
    """(executions, seconds) of the programs whose name contains ``key``."""
    n, s = 0, 0.0
    for name, (c, sec) in red.modules.items():
        if key in name:
            n += c
            s += sec
    return n, s


def reduce_dir(trace_dir: str, t0: float, t1: float) -> SimpleNamespace:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    red = reduce(load(paths[0]), t1 - t0)
    red.t0, red.t1 = t0, t1
    return red


def dump(events: List[dict], path: str):
    with open(path, "w") as f:
        json.dump(events, f)
