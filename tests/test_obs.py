"""Observability subsystem: trace recorder, metrics, block traces,
summary schema, prefix-persistence checksums, and end-to-end tracing.

Acceptance properties:

* spans nest and order correctly on the modeled clock; the ring buffer
  truncates oldest-first with exact drop accounting;
* ``to_chrome`` emits valid Chrome ``trace_event`` JSON (complete
  spans, instants, counters, thread-name metadata, µs timestamps);
* the KV block-access trace round-trips its JSONL replay format;
* the ``ServingReport.summary()`` schema rejects key drift both ways;
* a persisted prefix tree with a corrupted/missing payload or an old
  format version is rejected whole — the cache stays empty and the
  rejection is traced;
* a traced scheduler run reconstructs every request's TTFT from the
  trace alone (matching the report), attributes each iteration's gCO2
  to the requests that did the work, and never perturbs the modeled
  clock (tracing on/off spans are identical; real-tiny tokens are
  byte-identical);
* host-clock spans nest with their parent and rid, share the ring's
  drop accounting, export as their own process, cover every served
  layer of a real-tiny run, and the ``engine.decode.*`` children lie
  inside their step; every request's host stamps are ordered; the
  served programs keep the names the device-trace reductions match.
"""
import json
import pathlib
import sys

import numpy as np
import pytest

from repro.core.engine import M2CacheEngine
from repro.obs import (BlockAccessEvent, BlockTraceCollector, MetricsRegistry,
                       PeriodicSnapshotter, TraceRecorder, events_from_chrome,
                       events_from_recorder, read_block_trace)
from repro.obs.trace import HOST, NO_SPAN, host_span
from repro.serving import (ContinuousBatchScheduler, PrefixCache,
                           requests_from_trace)
from repro.serving.kv_cache import TieredKVCache
from repro.serving.schema import (SUMMARY_REQUIRED, looks_like_summary,
                                  validate_summary)
from repro.serving.workload import ArrivalEvent

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                       / "scripts"))
import trace_report  # noqa: E402


# ---------------------------------------------------------------------------
# TraceRecorder


def test_span_nesting_and_ordering_on_modeled_clock():
    tr = TraceRecorder()
    outer = tr.span_begin("sched", "outer", t=1.0, tag="a")
    inner = tr.span_begin("sched", "inner", t=2.0)
    assert tr.open_spans() == 2
    tr.span_end(inner, t=3.0)
    tr.span_end(outer, t=5.0, result="ok")   # end args merge with begin's
    assert tr.open_spans() == 0
    evs = tr.events()
    # closes emit in end order; both carry modeled begin time + duration
    assert [e.name for e in evs] == ["inner", "outer"]
    assert evs[0].t == 2.0 and evs[0].dur == 1.0
    assert evs[1].t == 1.0 and evs[1].dur == 4.0
    assert evs[1].args == {"tag": "a", "result": "ok"}
    # nesting: inner lies inside outer on the modeled timeline
    assert evs[1].t <= evs[0].t and \
        evs[0].t + evs[0].dur <= evs[1].t + evs[1].dur
    # ending an unknown/already-ended span is a no-op, not an error
    tr.span_end(inner, t=9.0)
    assert len(tr.events()) == 2


def test_default_clock_and_explicit_timestamps():
    t = [0.0]
    tr = TraceRecorder(clock=lambda: t[0])
    t[0] = 2.5
    tr.instant("x", "a")                     # stamped from the clock
    tr.instant("x", "b", t=9.0)              # explicit t wins
    assert [e.t for e in tr.events()] == [2.5, 9.0]
    # a clockless recorder stamps 0.0 rather than failing
    tr2 = TraceRecorder()
    tr2.instant("x", "c")
    assert tr2.events()[0].t == 0.0


def test_ring_buffer_truncation_accounting():
    tr = TraceRecorder(capacity=10)
    for i in range(25):
        tr.instant("x", f"e{i}", t=float(i))
    s = tr.stats()
    assert s["trace_events"] == 10
    assert s["trace_total_events"] == 25
    assert s["trace_dropped_events"] == 15
    # oldest dropped, newest kept, order preserved
    assert [e.name for e in tr.events()] == [f"e{i}" for i in range(15, 25)]
    # the export records the drop so a truncated trace is never mistaken
    # for a complete one
    chrome = tr.to_chrome()
    assert chrome["otherData"]["dropped_events"] == 15
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_chrome_trace_json_valid(tmp_path):
    tr = TraceRecorder()
    tr.span("req:0", "prefill", 1.0, 2.5, tokens=16)
    tr.instant("sched", "admit", t=1.0, rid=0)
    tr.counter("kv", "kv_bytes", t=2.0, hbm=1024, dram=0)
    with tr.host_span("sched", "admit", rid=0, waiting=1):
        pass
    path = tmp_path / "t.trace.json"
    tr.export_chrome(str(path))
    doc = json.loads(path.read_text())       # valid JSON round-trip
    evs = doc["traceEvents"]
    host_pid = doc["otherData"]["host_pid"]
    by_ph = {e["ph"]: e for e in evs if e["pid"] != host_pid}
    assert set(by_ph) == {"M", "X", "i", "C"}
    # complete span: µs timestamps + duration, args preserved
    x = by_ph["X"]
    assert x["ts"] == pytest.approx(1.0e6)
    assert x["dur"] == pytest.approx(1.5e6)
    assert x["args"] == {"tokens": 16}
    # the host span rides in its own process, on the host clock
    (h,) = [e for e in evs if e["pid"] == host_pid and e["ph"] == "X"]
    assert h["name"] == "admit" and h["args"] == {"rid": 0, "waiting": 1}
    assert h["ts"] > 0 and h["dur"] >= 0
    # instant scope + counter series (args are the series alone)
    assert by_ph["i"]["s"] == "t"
    assert by_ph["C"]["args"] == {"hbm": 1024.0, "dram": 0.0}
    # every referenced tid has thread_name metadata
    named = {e["tid"] for e in evs if e["ph"] == "M"}
    assert {e["tid"] for e in evs} <= named
    tracks = {(e["pid"], e["args"]["name"]) for e in evs
              if e["name"] == "thread_name"}
    assert tracks == {(1, "req:0"), (1, "sched"), (1, "kv"),
                      (host_pid, "sched")}


def test_host_spans_nest_with_parent_and_rid():
    tr = TraceRecorder()
    with tr.host_span("engine", "decode_step", rows=2) as outer:
        with tr.host_span("engine", "decode.dispatch", rid=7) as inner:
            inner.note(bytes=64)
        assert tr.open_spans() == 1
    assert tr.open_spans() == 0
    inner_ev, outer_ev = tr.events()     # closes emit in end order
    assert inner_ev.clock == outer_ev.clock == HOST
    assert inner_ev.args == {"rid": 7, "bytes": 64,
                             "parent": "engine.decode_step"}
    assert outer_ev.args == {"rows": 2} and outer is not inner
    # host-clock nesting: the child lies inside its parent
    assert outer_ev.t <= inner_ev.t
    assert inner_ev.t + inner_ev.dur <= outer_ev.t + outer_ev.dur
    # a span closed by an exception still leaves the stack clean
    with pytest.raises(KeyError):
        with tr.host_span("kv", "demote"):
            raise KeyError
    assert tr.open_spans() == 0 and tr.events()[-1].name == "demote"
    # no recorder: one shared no-op, nothing recorded anywhere
    with host_span(None, "kv", "demote", rid=1) as off:
        off.note(bytes=1)
    assert off is NO_SPAN


def test_ring_overflow_counts_host_spans():
    tr = TraceRecorder(capacity=4)
    for i in range(3):
        tr.instant("x", f"e{i}", t=float(i))
        with tr.host_span("sched", f"s{i}"):
            pass
    s = tr.stats()
    assert s["trace_total_events"] == 6
    assert s["trace_dropped_events"] == 2 and s["trace_events"] == 4
    assert [e.name for e in tr.events()] == ["e1", "s1", "e2", "s2"]


def test_export_puts_host_spans_in_own_process():
    tr = TraceRecorder()
    tr.span("sched", "decode_step", 1.0, 2.0, batch=2)
    with tr.host_span("sched", "iteration"):
        with tr.host_span("engine", "decode_step", rows=2):
            pass
    doc = tr.to_chrome()
    host_pid = doc["otherData"]["host_pid"]
    procs = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
             if e["name"] == "process_name"}
    assert procs == {1: "modeled clock", host_pid: "host clock"}
    host = [e for e in doc["traceEvents"]
            if e["pid"] == host_pid and e["ph"] == "X"]
    assert sorted(e["name"] for e in host) == ["decode_step", "iteration"]
    # a track name shared by both clocks is two threads, never one
    sched_tids = {e["tid"] for e in doc["traceEvents"]
                  if e["name"] == "thread_name"
                  and e["args"]["name"] == "sched"}
    assert len(sched_tids) == 2
    # the modeled-clock readers never see a host span
    for evs in (events_from_recorder(tr), events_from_chrome(doc)):
        assert [(e["track"], e["name"]) for e in evs] == \
            [("sched", "decode_step")]


# ---------------------------------------------------------------------------
# block-access trace (replay format)


def test_block_event_record_roundtrip_exact():
    ev = BlockAccessEvent(t=1.25, op="promote", bid=7, rid=-3,
                          tier="hbm", prev_tier="ssd", nbytes=16384,
                          tok0=32, cause="prefetch")
    assert BlockAccessEvent.from_record(ev.to_record()) == ev
    # defaults survive a sparse record too
    sparse = BlockAccessEvent.from_record(
        {"t": 0.0, "op": "alloc", "bid": 1, "rid": 0, "tier": "hbm"})
    assert sparse.prev_tier is None and sparse.nbytes == 0


def test_block_trace_collector_and_jsonl_roundtrip(tmp_path):
    bt = BlockTraceCollector()
    bt.emit(0.0, "alloc", 0, 0, "hbm", nbytes=1024)
    bt.emit(1.0, "demote", 0, 0, "dram", prev_tier="hbm", nbytes=1024,
            cause="preempt")
    bt.emit(2.0, "free", 0, 0, "dram")
    with pytest.raises(ValueError):
        bt.emit(3.0, "teleport", 0, 0, "hbm")
    s = bt.stats()
    assert s["block_events"] == 3 and s["block_demote"] == 1
    path = tmp_path / "blocks.jsonl"
    bt.export_jsonl(str(path))
    back = list(read_block_trace(str(path)))
    assert back == bt.events()
    # header validation: wrong format and future version both refuse
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"format": "something-else", "version": 1}\n')
    with pytest.raises(ValueError, match="not a kv-block-trace"):
        list(read_block_trace(str(bad)))
    newer = tmp_path / "newer.jsonl"
    newer.write_text('{"format": "kv-block-trace", "version": 99}\n')
    with pytest.raises(ValueError, match="newer"):
        list(read_block_trace(str(newer)))


def test_block_trace_capacity_drops_accounted():
    bt = BlockTraceCollector(capacity=2)
    for i in range(5):
        bt.emit(float(i), "touch", i, 0, "hbm")
    assert len(bt) == 2 and bt.stats()["block_dropped"] == 3
    assert bt.stats()["block_touch"] == 5    # per-op counts stay lifetime


# ---------------------------------------------------------------------------
# metrics registry


def test_metrics_counters_gauges_histograms():
    reg = MetricsRegistry()
    c = reg.counter("toks", "tokens")
    c.inc(3)
    c.inc(2, tier="hbm")
    assert c.get() == 3 and c.get(tier="hbm") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("active")
    g.set(4)
    g.dec()
    assert g.get() == 3
    h = reg.histogram("lat", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 3 and h.sum() == pytest.approx(55.5)
    # create-or-get returns the same object; kind conflicts refuse
    assert reg.counter("toks") is c
    with pytest.raises(TypeError):
        reg.gauge("toks")
    text = reg.to_prometheus()
    assert "# TYPE toks counter" in text
    assert 'toks{tier="hbm"} 2.0' in text
    # histogram buckets are cumulative, with +Inf == count
    assert 'lat_bucket{le="1.0"} 1' in text
    assert 'lat_bucket{le="10.0"} 2' in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_count 3" in text
    snap = reg.snapshot(now=1.5)
    assert snap["t_modeled_s"] == 1.5
    assert snap["lat"]["_"]["count"] == 3


def test_periodic_snapshotter_modeled_time(tmp_path):
    reg = MetricsRegistry()
    c = reg.counter("n")
    path = tmp_path / "m.jsonl"
    snap = PeriodicSnapshotter(reg, str(path), interval_s=1.0)
    snap.tick(0.0)                           # arms the first interval
    c.inc()
    snap.tick(0.5)                           # not due yet
    snap.tick(1.5)                           # due -> one snapshot
    snap.tick(50.0)                          # long idle jump -> ONE more
    snap.tick(50.1)
    c.inc()
    snap.close(60.0)                         # final snapshot on close
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert len(lines) == 3
    assert [x["t_modeled_s"] for x in lines] == [1.5, 50.0, 60.0]
    assert lines[0]["n"]["_"] == 1.0 and lines[-1]["n"]["_"] == 2.0
    snap.close()                             # idempotent
    with pytest.raises(ValueError):
        PeriodicSnapshotter(reg, str(path), interval_s=0.0)


# ---------------------------------------------------------------------------
# summary schema (single source of truth for the bench gate)


def _minimal_summary():
    out = {k: 0.0 for k in SUMMARY_REQUIRED}
    out["policy"] = "fcfs"
    return out


def test_summary_schema_catches_drift_both_ways():
    ok = _minimal_summary()
    assert validate_summary(ok) is ok
    # optional + per-class family keys are allowed
    ok2 = dict(ok, prefix_hit_rate=0.5, slo_attainment_interactive=1.0)
    validate_summary(ok2)
    # a renamed (missing) required key fails
    broken = dict(ok)
    broken["throughput_tok_s"] = broken.pop("tokens_per_s")
    with pytest.raises(ValueError, match="missing required"):
        validate_summary(broken)
    with pytest.raises(ValueError, match="unknown keys"):
        validate_summary(dict(ok, brand_new_metric=1.0))
    assert looks_like_summary(ok)
    assert not looks_like_summary({"tokens_per_s": 1.0})


def test_scheduler_summary_passes_schema(tmp_path):
    eng = M2CacheEngine(paper_model="llama-7b", dram_capacity_gb=6.0,
                        ssd_dir=str(tmp_path / "w"))
    sched = ContinuousBatchScheduler(eng, max_batch=2)
    reqs = requests_from_trace(
        [ArrivalEvent(rid=i, arrival_s=0.0, prompt_len=8,
                      max_new_tokens=4) for i in range(2)])
    s = sched.run(reqs).summary()            # validate_summary runs inside
    assert looks_like_summary(s)
    assert "mean_intensity_g_kwh" in s


# ---------------------------------------------------------------------------
# prefix-persistence checksum + version handshake


class _Prov:
    def __init__(self, bt):
        self.bt = bt

    def _arr(self, tok0):
        rng = np.random.default_rng(tok0 + 1)
        return rng.standard_normal((self.bt, 8)).astype(np.float32)

    def export(self, tok0, ntokens, *, scrub=False):
        return {"k": self._arr(tok0), "v": self._arr(tok0) * -1.0}

    def import_(self, tok0, payload):
        pass


def _payload_prefix(tmp_path, tag):
    bt, bpt = 4, 256.0
    kv = TieredKVCache(
        num_layers=2, d_model=8,
        hbm_capacity_bytes=64 * bt * bpt,
        dram_capacity_bytes=64 * bt * bpt,
        ssd_dir=str(tmp_path / tag / "kv"), block_tokens=bt,
        bytes_per_token=bpt, store_payloads=True)
    return kv, PrefixCache(kv)


def _build_and_save(tmp_path, persist):
    kv, pc = _payload_prefix(tmp_path, "src")
    kv.register_provider(0, _Prov(kv.block_tokens))
    toks = tuple(range(13))                  # 3 whole blocks + 1 tail
    pc.lock(0, toks)
    kv.extend(0, len(toks))
    assert pc.insert(0, toks, prefix_hit=0) == 12
    pc.release(0)
    saved = pc.save(str(persist))
    assert saved["payload_blocks"] == 3
    return toks, saved


def test_prefix_load_verifies_checksums_ok(tmp_path):
    persist = tmp_path / "tree"
    toks, _ = _build_and_save(tmp_path, persist)
    kv2, pc2 = _payload_prefix(tmp_path, "dst")
    res = pc2.load(str(persist))
    assert "rejected" not in res
    assert res == {"nodes": 1, "payload_blocks": 3}
    assert pc2.match(toks).hit_tokens == 12
    assert pc2.stats()["prefix_load_rejects"] == 0


def test_prefix_load_rejects_corrupted_payload(tmp_path):
    """A flipped byte in one persisted payload file must reject the
    whole tree: nothing adopted, cache empty, rejection traced."""
    import os
    persist = tmp_path / "tree"
    toks, _ = _build_and_save(tmp_path, persist)
    epoch = PrefixCache.latest_epoch_dir(str(persist))
    target = os.path.join(
        epoch, sorted(f for f in os.listdir(epoch)
                      if f.endswith(".bin"))[0])
    with open(target, "r+b") as f:
        f.seek(8)
        b = f.read(1)
        f.seek(8)
        f.write(bytes([b[0] ^ 0xFF]))
    kv2, pc2 = _payload_prefix(tmp_path, "dst")
    tr = TraceRecorder()
    pc2.attach_obs(tr, clock=lambda: 0.0)
    res = pc2.load(str(persist))
    assert "checksum mismatch" in res["rejected"]
    assert res["nodes"] == 0
    assert pc2.nodes == 0 and pc2.match(toks).hit_tokens == 0
    assert not kv2.blocks                    # nothing adopted
    assert pc2.stats()["prefix_load_rejects"] == 1
    rejected = [e for e in tr.events() if e.name == "load_rejected"]
    assert len(rejected) == 1
    assert "checksum" in rejected[0].args["reason"]


def test_prefix_load_rejects_missing_payload_and_old_version(tmp_path):
    import os
    from pathlib import Path
    persist = tmp_path / "tree"
    toks, _ = _build_and_save(tmp_path, persist)
    epoch = Path(PrefixCache.latest_epoch_dir(str(persist)))
    # deleting a payload file -> unreadable/missing -> whole-tree reject
    target = sorted(f for f in os.listdir(epoch) if f.endswith(".bin"))[0]
    os.unlink(epoch / target)
    kv2, pc2 = _payload_prefix(tmp_path, "dst")
    res = pc2.load(str(persist))
    assert "rejected" in res and pc2.nodes == 0
    # a pre-checksum (v1) tree is unverifiable -> reject
    spec = json.loads((epoch / "tree.json").read_text())
    spec["format_version"] = 1
    (epoch / "tree.json").write_text(json.dumps(spec))
    kv3, pc3 = _payload_prefix(tmp_path, "dst2")
    res = pc3.load(str(persist))
    assert "format_version" in res["rejected"]
    assert pc3.nodes == 0 and not kv3.blocks


# ---------------------------------------------------------------------------
# end-to-end: traced scheduler run (analytic engine, fast)


def _traced_run(tmp_path, tag, *, trace=None, metrics=None,
                block_trace=None):
    eng = M2CacheEngine(paper_model="llama-7b", dram_capacity_gb=6.0,
                        ssd_dir=str(tmp_path / tag))
    sched = ContinuousBatchScheduler(
        eng, max_batch=2, hbm_kv_gb=2e-4, dram_kv_gb=1e-4,
        prefill_chunk=8, trace=trace, metrics=metrics,
        block_trace=block_trace)
    reqs = requests_from_trace(
        [ArrivalEvent(rid=i, arrival_s=0.3 * i, prompt_len=12 + 4 * i,
                      max_new_tokens=4 + i) for i in range(4)])
    return sched.run(reqs)


def test_traced_run_ttft_and_phases_match_report(tmp_path):
    tr = TraceRecorder()
    met = MetricsRegistry()
    bt = BlockTraceCollector()
    rep = _traced_run(tmp_path, "on", trace=tr, metrics=met,
                      block_trace=bt)
    assert tr.open_spans() == 0              # every phase span closed
    chrome_path = tmp_path / "run.trace.json"
    tr.export_chrome(str(chrome_path))
    events = trace_report.load_trace(str(chrome_path))
    timelines = trace_report.request_timelines(events)
    assert sorted(timelines) == [r.rid for r in sorted(
        rep.requests, key=lambda r: r.rid)]
    for r in rep.requests:
        tl = timelines[r.rid]
        # TTFT and latency reconstructed from the trace alone must match
        # the scheduler's own accounting (same clock, pure differences)
        assert tl["ttft_s"] == pytest.approx(r.ttft_s, abs=1e-9)
        assert tl["latency_s"] == pytest.approx(r.latency_s, abs=1e-9)
        assert tl["queue_wait_s"] == pytest.approx(
            r.admitted_s - r.arrival_s, abs=1e-9)
        assert tl["phases"].get("prefill", 0.0) >= 0.0
        assert "decode" in tl["phases"]
        # the finish instant carries the request's attributed carbon
        assert tl["gco2_g"] == pytest.approx(r.gco2_g, abs=1e-12)
    # per-request carbon attribution: phases sum to the request total,
    # and request totals never exceed the run total (idle stays unsplit)
    for r in rep.requests:
        assert r.gco2_prefill_g + r.gco2_decode_g == \
            pytest.approx(r.gco2_g, abs=1e-12)
    total_attr = sum(r.gco2_g for r in rep.requests)
    assert 0.0 < total_attr <= rep.carbon["total_g"] + 1e-12
    # metrics agree with the report
    assert met.counter("serving_requests_finished_total").get() == \
        len(rep.requests)
    assert met.histogram("serving_ttft_seconds").count() == \
        len(rep.requests)
    assert met.counter("serving_gco2_total").get() == \
        pytest.approx(total_attr, abs=1e-9)
    # KV pressure left tier transitions in the replay stream
    assert bt.stats()["block_alloc"] > 0
    ops = {e.op for e in bt.events()}
    assert "free" in ops and "touch" in ops


def test_tracing_never_perturbs_modeled_clock(tmp_path):
    rep_off = _traced_run(tmp_path, "off")
    rep_on = _traced_run(tmp_path, "on", trace=TraceRecorder(),
                         metrics=MetricsRegistry(),
                         block_trace=BlockTraceCollector())
    assert rep_on.modeled_span_s == rep_off.modeled_span_s
    assert rep_on.decode_steps == rep_off.decode_steps
    assert [r.ttft_s for r in rep_on.requests] == \
        [r.ttft_s for r in rep_off.requests]


# ---------------------------------------------------------------------------
# real-tiny: token identity with tracing on/off


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.models import transformer as T
    cfg = get_config("qwen2.5-14b", tiny=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32,
                           m2=True)
    return cfg, params


@pytest.mark.slow
def test_real_tiny_tokens_identical_tracing_on_off(tmp_path, tiny_model):
    cfg, params = tiny_model

    def run(tag, **obs):
        eng = M2CacheEngine(cfg=cfg, params=params, dram_capacity_gb=0.5,
                            ssd_dir=str(tmp_path / tag))
        sched = ContinuousBatchScheduler(eng, max_batch=2,
                                         hbm_kv_gb=6e-5,
                                         dram_kv_gb=1.6e-5, **obs)
        reqs = requests_from_trace(
            [ArrivalEvent(rid=i, arrival_s=0.0, prompt_len=pl,
                          max_new_tokens=gl)
             for i, (pl, gl) in enumerate(zip((18, 16, 12, 19),
                                              (6, 10, 8, 7)))],
            vocab_size=cfg.vocab_size)
        rep = sched.run(reqs)
        return rep, {r.rid: list(r.session.tokens) for r in rep.requests}

    rep_off, toks_off = run("off")
    rep_on, toks_on = run("on", trace=TraceRecorder(),
                          block_trace=BlockTraceCollector())
    assert toks_on == toks_off               # byte-identical generation
    assert rep_on.modeled_span_s == rep_off.modeled_span_s
    assert rep_on.preemptions == rep_off.preemptions > 0


# ---------------------------------------------------------------------------
# real-tiny: host spans on the served path


@pytest.fixture(scope="module")
def served_with_and_without_recorder(tmp_path_factory, tiny_model):
    """One real-tiny run of a shared-prefix trace under tight KV budgets
    (preemption, DRAM and SSD demotion, prefix restores, an idle gap),
    once with a recorder attached and once without."""
    cfg, params = tiny_model
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, 32).tolist()
    events = [ArrivalEvent(rid=i, arrival_s=at, prompt_len=32 + sfx,
                           max_new_tokens=gl,
                           prompt_tokens=shared + rng.integers(
                               0, cfg.vocab_size, sfx).tolist())
              for i, (sfx, gl, at) in enumerate(
                  [(5, 6, 0.0), (9, 8, 0.0), (3, 7, 5.0), (12, 5, 5.0)])]
    out = {}
    for tag, trace in (("on", TraceRecorder()), ("off", None)):
        eng = M2CacheEngine(cfg=cfg, params=params, dram_capacity_gb=0.5,
                            ssd_dir=str(tmp_path_factory.mktemp(tag)))
        per_block = 16 * eng.kv_bytes_per_token() / 2 ** 30
        sched = ContinuousBatchScheduler(
            eng, max_batch=2, hbm_kv_gb=4 * per_block,
            dram_kv_gb=2 * per_block, prefill_chunk=16,
            prefix_caching=True, trace=trace)
        reqs = requests_from_trace(events)
        rep = sched.run(reqs)
        out[tag] = (rep, reqs, trace)
    return out


def test_real_tiny_host_spans_cover_the_served_layers(
        served_with_and_without_recorder):
    (rep_on, _, tr), (rep_off, _, _) = (
        served_with_and_without_recorder[k] for k in ("on", "off"))
    toks = {k: {r.rid: list(r.session.tokens) for r in rep.requests}
            for k, rep in (("on", rep_on), ("off", rep_off))}
    assert toks["on"] == toks["off"] and len(toks["on"]) == 4
    assert rep_on.modeled_span_s == rep_off.modeled_span_s
    # the trace exercised every layer the host spans cover
    names = {f"{e.track}.{e.name}" for e in tr.events() if e.clock == HOST}
    assert names >= {
        "sched.iteration", "sched.admit", "sched.idle", "prefix.lock",
        "prefix.insert", "engine.decode_step", "engine.decode.sync",
        "engine.decode.dispatch", "engine.decode.m2_manager",
        "engine.prefill_step", "engine.prefill.dispatch",
        "engine.prefill.m2_manager", "engine.restore_prefix", "kv.demote",
        "kv.capture", "kv.spill", "kv.promote", "kv.ssd_read",
        "kv.deliver"}
    assert tr.open_spans() == 0 and tr.dropped_events == 0


def test_host_stamps_ordered_for_every_request(
        served_with_and_without_recorder):
    for tag in ("on", "off"):
        rep, reqs, _ = served_with_and_without_recorder[tag]
        assert len(rep.requests) == len(reqs) == 4
        for r in reqs:
            assert r.host_released_s <= r.host_admitted_s \
                <= r.host_first_token_s


def test_decode_children_lie_inside_their_step(
        served_with_and_without_recorder):
    _, _, tr = served_with_and_without_recorder["on"]
    host = [e for e in tr.events() if e.clock == HOST]
    steps = [e for e in host if e.name == "decode_step"]
    children = [e for e in host if e.name.startswith("decode.")]
    assert steps and len(children) == 3 * len(steps)
    for c in children:
        assert c.args["parent"] == "engine.decode_step"
        assert len([p for p in steps
                    if p.t <= c.t and c.t + c.dur <= p.t + p.dur]) == 1
    assert sum(c.dur for c in children) <= sum(p.dur for p in steps)
    assert {(p.args or {}).get("parent") for p in steps} == \
        {"sched.iteration"}


# ---------------------------------------------------------------------------
# stable names of the served programs: a profiler trace finds the decode
# and prefill steps by these substrings of their lowered module names


@pytest.mark.parametrize("program,name", [
    ("_decode_batched", "decode_one_row"),
    ("_prefill_block", "prefill_block_one_row"),
    ("_prefill_block_rows", "prefill_block_one_row"),
])
def test_served_program_names_are_stable(tiny_model, program, name):
    import re
    import jax.numpy as jnp
    from repro.core.engine_model import RealModelRunner, _stack_rows
    from repro.models import transformer as T
    cfg, params = tiny_model
    runner = RealModelRunner(cfg, params, max_seq=32)
    one = T.init_cache(cfg, 1, max_seq=32, dtype=runner.dtype)
    stack = _stack_rows([one] * 2)
    args = {
        "_decode_batched": (stack, jnp.zeros((2, cfg.vocab_size),
                                             jnp.float32)),
        "_prefill_block": (one, jnp.zeros((16,), jnp.int32),
                           jnp.asarray(1, jnp.int32)),
        "_prefill_block_rows": (stack, jnp.zeros((2, 16), jnp.int32),
                                jnp.ones((2,), jnp.int32)),
    }[program]
    text = getattr(runner, program).lower(params, *args).as_text()
    module = re.search(r"module @(\S+)", text).group(1)
    assert name in module, module


def test_recorder_outliving_its_scheduler_frees_the_engine(tmp_path):
    import gc
    import weakref
    tr = TraceRecorder()
    eng = M2CacheEngine(paper_model="llama-7b", dram_capacity_gb=6.0,
                        ssd_dir=str(tmp_path))
    sched = ContinuousBatchScheduler(eng, max_batch=2, trace=tr)
    sched.run(requests_from_trace([ArrivalEvent(
        rid=0, arrival_s=0.0, prompt_len=8, max_new_tokens=2)]))
    assert tr.events()
    dead = weakref.ref(eng)
    del sched, eng
    gc.collect()
    assert dead() is None            # the recorder held on to nothing
