"""Device idle put down to the program's own host spans."""
import json
from pathlib import Path

from bench import program_trace as PT
from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"


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
    # the same gaps as the harness's reduction of the same events
    assert abs(red.idle_s - sum(TR.reduce(ev, 0.35).idle_by_host.values())) \
        < 1e-9
    assert abs(sum(red.idle_by_program.values()) - red.idle_s) < 1e-9
    assert red.idle_by_program.get(PT.UNCOVERED, 0.0) <= 0.15 * red.idle_s
    assert PT.child_cover(red.events, PT.DECODE_STEP,
                          "engine.decode.") >= 0.9
