"""Device idle put down to the program's own host spans, the per-layer
metrics that read those spans, and the traced run that records them."""
import importlib
import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import program_trace as PT
from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _op(start, dur):
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "op",
            "start_ns": start, "dur_ns": dur}


def _span(name, start, dur):
    return {"plane": "/host:CPU", "line": "python", "name": name,
            "start_ns": start, "dur_ns": dur}


def test_gaps_go_to_the_innermost_span_covering_most():
    ev = [_op(0, 100), _op(400, 100), _op(1000, 100), _op(1500, 100),
          _span("engine.decode_step", 90, 400),
          _span("engine.decode.m2_manager", 120, 230),
          _span("kv.demote", 520, 100)]
    red = PT.reduce(ev)
    # 100-400: m2_manager covers 230 of 300; 500-1000: kv.demote covers
    # 100 of 500, nothing the rest; 1100-1500: nothing
    assert red.idle_by_program == {"engine.decode.m2_manager": 300e-9,
                                   "uncovered": 900e-9}
    assert abs(red.idle_s - 1200e-9) < 1e-15
    assert red.spans["engine.decode_step"] == [1, 400e-9]
    assert PT.top(red.idle_by_program, 1) == [
        ["engine.decode.m2_manager", 300e-9], ["uncovered", 900e-9]]
    assert PT.per_step_ms(red, "engine.decode.m2_manager") == 230e-6
    assert PT.child_cover(red.events, "engine.decode_step",
                          "engine.decode.") == 230 / 400


def test_outermost_seconds_counts_nested_moves_once():
    spans = PT.host_spans([
        _span("kv.promote", 0, 100), _span("kv.demote", 10, 50),
        _span("kv.demote", 200, 30), _span("engine.restore_prefix", 300, 5),
        _span("engine.prefill_step", 290, 40)])
    got = PT.outermost_seconds(spans, ("kv.promote", "kv.demote",
                                       "engine.restore_prefix"))
    assert abs(got - 135e-9) < 1e-15


def test_recorded_program_trace_slice():
    """A 0.35-second slice of a traced qwen14b-docqa run on one v5e with a
    recorder attached: device ops and the program's m2c: spans."""
    ev = json.loads((DATA / "program_trace_slice.json").read_text())
    red = PT.reduce(ev)
    names = set(red.spans)
    assert {"sched.iteration", "sched.admit", "engine.decode_step",
            "engine.decode.dispatch", "engine.decode.m2_manager",
            "engine.restore_prefix", "kv.demote", "kv.capture", "kv.spill",
            "kv.promote"} <= names
    # the gaps between the device's ops: their span less its busy time
    ops = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ev
           if e["line"] == TR.OPS_LINE]
    span = (max(b for _, b in ops) - min(a for a, _ in ops)) / 1e9
    assert abs(red.idle_s - (span - TR.reduce(ev, 0.35).busy_s)) < 1e-9
    assert abs(sum(red.idle_by_program.values()) - red.idle_s) < 1e-9
    assert red.idle_by_program.get(PT.UNCOVERED, 0.0) <= 0.15 * red.idle_s
    assert PT.child_cover(red.events, PT.DECODE_STEP,
                          "engine.decode.") >= 0.9


def _slice_ctx(admitted=5):
    ev = json.loads((DATA / "program_trace_slice.json").read_text())
    reqs = [SimpleNamespace(admitted_s=float(i)) for i in range(admitted)] \
        + [SimpleNamespace(admitted_s=None)]
    return ev, SimpleNamespace(program_trace=PT.reduce(ev), requests=reqs)


def _span_sums(ev, name):
    durs = [e["dur_ns"] for e in ev if e["name"] == name
            and not e["plane"].startswith("/device:")]
    return len(durs), sum(durs) / 1e9


@pytest.mark.parametrize("metric,span", [
    ("m2_manager_ms", "engine.decode.m2_manager"),
    ("decode_dispatch_ms", "engine.decode.dispatch")])
def test_per_step_readers_on_the_recorded_slice(metric, span):
    ev, ctx = _slice_ctx()
    steps, _ = _span_sums(ev, "engine.decode_step")
    n, sec = _span_sums(ev, span)
    assert steps and n
    got = importlib.import_module(f"bench.metrics.{metric}").read(ctx)
    assert abs(got - sec * 1e3 / steps) < 1e-9
    ctx.program_trace.spans.pop(span)
    assert importlib.import_module(f"bench.metrics.{metric}").read(ctx) \
        is None


def test_kv_move_reader_on_the_recorded_slice():
    from bench.metrics import kv_move_ms_per_req as K
    ev, ctx = _slice_ctx(admitted=4)
    spans = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                   for e in ev if e["name"] in K.MOVES)
    # the union of the move spans, which nest inside one another only
    whole, reach = 0, None
    for s, e in spans:
        if reach is None or s >= reach:
            whole += e - s
            reach = e
        elif e > reach:
            raise AssertionError("move spans overlap without nesting")
    got = K.read(ctx)
    assert got > 0 and abs(got - whole / 1e6 / 4) < 1e-9
    ctx.requests = [SimpleNamespace(admitted_s=None)]
    assert K.read(ctx) is None


@pytest.mark.parametrize("metric", ["m2_manager_ms", "decode_dispatch_ms",
                                    "kv_move_ms_per_req"])
def test_program_span_readers_read_nothing_untraced(metric):
    ctx = SimpleNamespace(program_trace=None,
                          requests=[SimpleNamespace(admitted_s=0.0)])
    assert importlib.import_module(f"bench.metrics.{metric}").read(ctx) \
        is None


def test_traced_run_attaches_a_recorder_and_reduces_its_spans():
    """A traced tiny run on the CPU attaches a TraceRecorder to the window's
    scheduler and reduces the program's spans from the profiler's trace;
    an untraced one attaches none and reads no program span."""
    from repro.obs import TraceRecorder
    from bench import harness as H
    from bench import run as RUN
    from bench.metrics import kv_move_ms_per_req, m2_manager_ms
    from bench.tests import tiny

    def run(trace):
        return H.run(tiny.config(), tiny.DOCQA, seed=2 ** 32 + 79,
                     seconds=2.0, trace=trace, t_start=time.perf_counter(),
                     limits=tiny.LIMITS, peaks=PEAKS)

    ctx = run(True)
    assert isinstance(ctx.recorder, TraceRecorder)
    assert ctx.recorder.total_events > 0
    assert {"sched.iteration", "engine.decode_step",
            "engine.decode.m2_manager", "engine.decode.dispatch",
            "engine.restore_prefix"} <= set(ctx.program_trace.spans)
    assert m2_manager_ms.read(ctx) > 0
    assert kv_move_ms_per_req.read(ctx) > 0
    idle = RUN.breakdown(ctx)["idle_gaps"]
    assert idle[-1][0] == PT.UNCOVERED and len(idle) <= 10
    assert H.correct(ctx.checks), ctx.checks

    ctx = run(False)
    assert ctx.recorder is None and ctx.program_trace is None
    assert m2_manager_ms.read(ctx) is None
    assert H.correct(ctx.checks), ctx.checks
