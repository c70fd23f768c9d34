"""Trace reduction and the shape-based operations and bytes."""
import json
from pathlib import Path

from bench import harness as H
from bench import trace as TR
from bench.arch import qwen2 as Q
from bench.flops import least_seconds

DATA = Path(__file__).resolve().parent / "data"
V5E = H.load_peaks("TPU v5 lite")


def _ev(line, name, start, dur, plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start, "dur_ns": dur}


def test_busy_is_the_union_and_gaps_go_to_host_spans():
    ev = [_ev("XLA Ops", "a", 0, 100), _ev("XLA Ops", "b", 50, 100),
          _ev("XLA Ops", "c", 400, 100), _ev("XLA Ops", "d", 1000, 100),
          _ev("XLA Modules", "jit_decode_one_row(1)", 0, 150),
          _ev("XLA Modules", "jit_decode_one_row(1)", 400, 100),
          _ev("XLA Modules", "jit_prefill_block_one_row", 1000, 100),
          _ev("python", "bench:kv.swap_out", 150, 250, plane="/host:CPU")]
    red = TR.reduce(ev, window_s=2e-6)
    assert abs(red.busy_s - 350e-9) < 1e-15
    assert TR.module_seconds(red, "decode_one_row") == (2, 250e-9)
    # host events are the program trace's to reduce (bench/program_trace.py)
    assert red.planes == 1 and set(red.modules) == {
        "jit_decode_one_row(1)", "jit_prefill_block_one_row"}


def test_recorded_trace_slice():
    """A 0.3-second slice of a traced qwen32b-chat run on one v5e."""
    path = DATA / "trace_slice.json"
    ev = json.loads(path.read_text())
    red = TR.reduce(ev, window_s=0.3)
    assert red.planes == 1
    assert 0 < red.busy_s <= 0.3
    n, sec = TR.module_seconds(red, "decode_one_row")
    assert n > 0 and 0 < sec <= red.busy_s + 1e-9


def _d14():
    return Q.dims(H.load_config("qwen2.5-14b"))


def test_decode_bytes_of_the_14b():
    D = _d14()
    head = 152064 * 5120 * 4
    attn = 5120 * (40 + 16) * 128 * 4 + 40 * 128 * 5120 * 4
    ffn = 3 * 5120 * (1037 * 4 + 1037 + 2073 * 0.5) + 3 * 3110 * 4
    pred = (5120 * 64 + 64 * 13824) * 4
    assert (D["k"], D["k_fp"], D["k_i8"], D["k_i4"]) == (4147, 1037, 1037,
                                                          2073)
    want = head + 2 * (attn + ffn + pred) + 100 * 2 * 2 * 8 * 128 * 4
    assert abs(Q.decode_bytes(D, 100) - want) < 1
    t, bound = least_seconds(Q.step_flops(D, 4, 100, 4),
                             Q.decode_bytes(D, 100), V5E)
    assert bound == "bytes" and 4e-3 < t < 6e-3


def test_token_flops_count_the_head_once():
    D = _d14()
    a = Q.token_flops(D, 10, logits=False)
    b = Q.token_flops(D, 10, logits=True)
    assert b - a == 2 * 5120 * 152064
    assert Q.step_flops(D, 3, 30, 1) == 3 * a + (b - a)


def test_roofline_and_mfu_stay_under_100():
    """With device time no shorter than the least time, the shares stay
    within 100%."""
    import importlib
    from types import SimpleNamespace
    D = _d14()
    steps = [H.Step(t0=0.1 * i, t1=0.1 * i + 0.05, rows=4, tokens=4,
                    ctx=8000, out=4) for i in range(10)]
    least = sum(least_seconds(Q.step_flops(D, 4, 8000, 4),
                              Q.decode_bytes(D, 8000), V5E)[0]
                for _ in steps)
    trace = SimpleNamespace(t0=0.0, t1=1.0, window_s=1.0, busy_s=least,
                            modules={"jit_decode_one_row": [10, least]})
    rec = SimpleNamespace(decode=steps, prefill=[])
    ctx = SimpleNamespace(arch=Q, D=D, peaks=V5E, trace=trace, rec=rec,
                          origin=0.0, window_end=1.0, seconds=1.0)
    roof = importlib.import_module("bench.metrics.decode_roofline").read(ctx)
    mfu = importlib.import_module("bench.metrics.mfu").read(ctx)
    assert abs(roof - 100.0) < 1e-6
    assert 0 < mfu < 100
