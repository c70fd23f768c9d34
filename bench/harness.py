"""One run of one benchmark cell: set-up, the measured window, the check.

The window drives the served path as a library: ``M2CacheEngine`` in real
mode under ``ContinuousBatchScheduler.run``, built as ``launch/server.py``
builds them, with weights and traffic made here from the seed. The
scheduler reads time only through ``engine.clock`` and charges it through
``engine.advance_clock``; it is handed ``HostClockEngine``, which delegates
everything to the real engine but reads ``clock`` from the host's
``perf_counter`` and adds nothing in ``advance_clock``. Arrivals are
therefore released when they are due on the host clock, an idle scheduler
waits on the host clock, and no modeled second enters a timestamp. The
adapter's ``decode_step`` and ``prefill_step`` stamp the host time at which
each call returned: both read their results back to the host, so a token
stamped there has reached it.

The harness knows no model: the configuration's architecture module
(``bench/arch``) makes the program's config, the weights and the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from bench import generator
from bench import program_trace as PT
from bench.arch import resolve

BENCH = Path(__file__).resolve().parent
GRACE_S = 90.0      # past the window: the most a due request may wait


class StopServing(Exception):
    """Raised from a step wrapper to end ``ContinuousBatchScheduler.run``."""


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    rows: int           # sessions in the call
    tokens: int         # tokens the call processed
    ctx: int            # sum over those tokens of the context each attends
    out: int = 0        # logits rows the call turned into output tokens


class Recorder:
    """Host stamps of the served path's steps and tokens."""

    def __init__(self):
        self.tokens: Dict[int, List[float]] = {}
        self.decode: List[Step] = []
        self.prefill: List[Step] = []
        self.stop_check = None          # callable(t) run after each step

    def on_decode(self, t0, t1, sessions):
        ctx = 0
        for s in sessions:
            self.tokens.setdefault(s.rid, []).append(t1)
            ctx += s.prompt_len + len(s.tokens)
        self.decode.append(Step(t0, t1, len(sessions), len(sessions), ctx,
                                out=len(sessions)))

    def on_prefill(self, t0, t1, before, sessions):
        tok = ctx = out = 0
        for s in sessions:
            n = s.prompt_done - before[id(s)]
            tok += n
            ctx += n * before[id(s)] + n * (n + 1) // 2
            out += int(n > 0 and s.prompt_done >= s.prompt_len)
        self.prefill.append(Step(t0, t1, len(sessions), tok, ctx, out=out))


class HostClockEngine:
    """The real engine, seen by the scheduler on the host clock."""

    def __init__(self, engine, recorder: Recorder):
        self._engine = engine
        self._rec = recorder
        self.origin = time.perf_counter()

    def __getattr__(self, name):
        return getattr(self._engine, name)

    @property
    def clock(self) -> float:
        return time.perf_counter() - self.origin

    def advance_clock(self, dt: float):
        """Modeled seconds (transfer stalls, idle jumps) are not time."""

    def decode_step(self, sessions):
        t0 = time.perf_counter()
        rep = self._engine.decode_step(sessions)
        t1 = time.perf_counter()
        self._rec.on_decode(t0, t1, sessions)
        if self._rec.stop_check is not None:
            self._rec.stop_check(t1)
        return rep

    def prefill_step(self, sessions, max_tokens=None):
        before = {id(s): s.prompt_done for s in sessions}
        t0 = time.perf_counter()
        rep = self._engine.prefill_step(sessions, max_tokens)
        t1 = time.perf_counter()
        self._rec.on_prefill(t0, t1, before, sessions)
        if self._rec.stop_check is not None:
            self._rec.stop_check(t1)
        return rep


class ReleaseRecorder:
    """The scheduling policy, noting when each request was first offered
    for admission: its release by the scheduler's arrival loop."""

    def __init__(self, policy):
        self._policy = policy
        self.released: Dict[int, float] = {}

    def __getattr__(self, name):
        return getattr(self._policy, name)

    def admission_order(self, waiting, now):
        t = time.perf_counter()
        for r in waiting:
            self.released.setdefault(r.rid, t)
        return self._policy.admission_order(waiting, now)


class CompileCounter:
    """Counts jaxpr traces and backend compiles while ``active``, and keeps
    the names JAX logs for them then."""

    def __init__(self):
        import logging
        import jax
        self._active = False
        self.traces = 0
        self.compiles = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        counter = self

        class _Names(logging.Handler):
            def emit(self, record):
                if counter._active:
                    counter.names.append(record.getMessage()[:160])
        logging.getLogger("jax").addHandler(_Names(logging.WARNING))

    @property
    def active(self) -> bool:
        return self._active

    @active.setter
    def active(self, on: bool):
        import jax
        if on != self._active:
            jax.config.update("jax_log_compiles", on)
        self._active = on

    def _on(self, name, _dur, **_kw):
        if not self._active:
            return
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


# ---------------------------------------------------------------------------
# set-up


def load_spec(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def kv_budget_tokens(mix: dict, max_batch: int) -> tuple:
    kv = mix["kv_tokens"]
    hbm = kv["hbm"] if "hbm" in kv else kv["hbm_per_slot"] * max_batch
    return hbm, kv["dram"]


def build_scheduler(engine, mix: dict, max_batch: int, recorder: Recorder,
                    *, hbm_tokens: int, dram_tokens: int, trace=None):
    """The scheduler ``launch/server.py`` builds (FCFS, lossless KV tiers,
    KV prefetch, the mix's prefix cache and chunk), over the host-clock
    adapter, with ``trace`` (a ``repro.obs.TraceRecorder``) attached.
    Returns (scheduler, adapter, release recorder)."""
    from repro.core.carbon import CarbonIntensityTrace
    from repro.serving import ContinuousBatchScheduler, make_policy
    adapter = HostClockEngine(engine, recorder)
    policy = ReleaseRecorder(make_policy("fcfs"))
    per_tok = engine.kv_bytes_per_token()
    sched = ContinuousBatchScheduler(
        adapter, max_batch=max_batch,
        hbm_kv_gb=hbm_tokens * per_tok / 2 ** 30,
        dram_kv_gb=dram_tokens * per_tok / 2 ** 30,
        policy=policy, prefill_chunk=mix["prefill_chunk"],
        carbon_trace=CarbonIntensityTrace.constant(), kv_prefetch=True,
        kv_precision=None, prefix_caching=mix["prefix_cache"],
        prefix_capacity_tokens=65536, trace=trace)
    return sched, adapter, policy


def serving_requests(reqs):
    from repro.serving.request import ServingRequest
    return [ServingRequest(rid=r.rid, prompt_len=len(r.prompt),
                           max_new_tokens=r.max_new_tokens,
                           arrival_s=r.arrival_s,
                           prompt=np.asarray(r.prompt, np.int32))
            for r in reqs]


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def compile_programs(engine, params, buckets, max_batch: int, block: int):
    """Run every (rows, bucket) decode and prefill program of the cell once
    on zeros, with the row stacking, scatter and gather around them, so
    that the window finds them compiled."""
    import jax
    import jax.numpy as jnp
    from repro.core import kv_payload as KP
    from repro.core.engine_model import _gather_row, _scatter_row, _stack_rows
    from repro.models import transformer as T
    cap = _pow2(max_batch)
    for b in buckets:
        runner = engine._runner_for(b - 1)
        assert runner.max_seq == b, (runner.max_seq, b)
        one = T.init_cache(engine.cfg, 1, max_seq=b, dtype=runner.dtype)
        toks = jnp.zeros((block,), jnp.int32)
        last1, one, _ = runner._prefill_block(params, one, toks,
                                              jnp.asarray(1, jnp.int32))
        jax.block_until_ready(last1[None])
        # a block's KV out of and back into a session cache (tier moves,
        # prefix restores)
        block_kv = KP.extract(one, 0, block)
        jax.block_until_ready((KP.inject(one, block_kv, 0),
                               KP.scrub(one, 0, block)))
        rows = 1
        while rows <= cap:
            stack = _stack_rows([one] * rows)
            last = jnp.zeros((rows, engine.cfg.vocab_size), jnp.float32)
            jax.block_until_ready(runner._decode_batched(params, stack, last))
            if rows > 1:
                last, out, _ = runner._prefill_block_rows(
                    params, stack, jnp.zeros((rows, block), jnp.int32),
                    jnp.ones((rows,), jnp.int32))
                jax.block_until_ready((_gather_row(out, 0), last[0][None]))
            stack = _scatter_row(stack, one, 0)
            jax.block_until_ready((_gather_row(stack, 0), last[0][None],
                                   last.at[0].set(last1),
                                   KP.inject(stack, KP.extract(
                                       stack, 0, block, row=0), 0, row=0),
                                   KP.scrub(stack, 0, block, row=0)))
            rows *= 2
        del stack, last, one


def warmup_traffic(buckets, max_batch: int, vocab: int, seed: int,
                   block: int) -> list:
    """Requests that reach every bucket with a full batch, share a prefix
    (restores) and grow past a small KV budget (demotions, promotions).
    Their length sets the bucket; they are stopped after a few steps."""
    rng = np.random.default_rng([int(seed), 0x3A7])
    shared = rng.integers(0, vocab, 2 * block).astype(np.int32)
    out = []
    rid = 2_000_000
    for b in buckets:
        for _ in range(2 * max_batch):
            prompt = np.concatenate([shared, rng.integers(
                0, vocab, block).astype(np.int32)])
            out.append(generator.Request(
                rid=rid, arrival_s=0.0, prompt=prompt,
                max_new_tokens=b - len(prompt) - 1))
            rid += 1
    return out


def run_warmup(engine, mix, max_batch, buckets, vocab, seed, block,
               steps: int = 12):
    """Drive a throwaway scheduler over ``warmup_traffic`` bucket by bucket
    until each batch has decoded ``steps`` times."""
    for b in buckets:
        reqs = [r for r in warmup_traffic([b], max_batch, vocab, seed, block)]
        rec = Recorder()

        def stop(_t):
            if len(rec.decode) >= steps:
                raise StopServing
        rec.stop_check = stop
        # room for a full batch of three-block prompts and two blocks more:
        # growing past the fourth block preempts, demotes to DRAM and SSD
        # and resumes
        sched, adapter, _ = build_scheduler(
            engine, mix, max_batch, rec,
            hbm_tokens=(3 * max_batch + 2) * block, dram_tokens=2 * block)
        try:
            sched.run(serving_requests(reqs))
        except StopServing:
            pass
        del sched, adapter


# ---------------------------------------------------------------------------
# the run


def run(cfg: dict, mix: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float, limits: dict, faults: Optional[dict] = None,
        peaks: Optional[dict] = None):
    """Set up, measure for ``seconds`` and check one cell. Returns what
    the window saw (a namespace the metric readers take). ``faults``
    plants a fault in the timed path and ``peaks`` stands in for the
    device's row of bench/peaks.json (tests only). A traced run attaches a
    ``repro.obs.TraceRecorder`` to the window's scheduler, so that the
    profiler's trace holds the program's own spans."""
    import jax
    from repro.core.engine import M2CacheEngine
    from repro.launch.jax_cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      cfg["assumed"]["matmul_precision"].split(":")[0])
    counter = CompileCounter()
    arch = resolve(cfg)
    D = arch.dims(cfg)
    block = mix["prefill_chunk"]
    max_batch = cfg["max_batch"]
    pcfg = arch.program_config(cfg)
    vocab = pcfg.vocab_size
    params = arch.program_params(seed, D)
    jax.block_until_ready(params)
    ssd_dir = tempfile.mkdtemp(prefix="bench_ssd_")
    try:
        engine = M2CacheEngine(cfg=pcfg, params=params, mode="m2cache",
                               hbm_policy="atu", use_ssd=True,
                               dram_capacity_gb=6.0, seed=seed % 2 ** 31,
                               batched_decode=True, prefill_bucket=8,
                               ssd_dir=ssd_dir, kv_block_tokens=block)
        if faults:
            _plant(engine, faults)
        buckets = generator.buckets(mix)
        compile_programs(engine, params, buckets, max_batch, block)
        run_warmup(engine, mix, max_batch, buckets, vocab, seed, block)
        traffic = generator.build(mix, vocab, seed, seconds)

        rec = Recorder()
        hbm_t, dram_t = kv_budget_tokens(mix, max_batch)
        recorder = None
        if trace:
            from repro.obs import TraceRecorder
            recorder = TraceRecorder()
        sched, adapter, policy = build_scheduler(
            engine, mix, max_batch, rec, hbm_tokens=hbm_t,
            dram_tokens=dram_t, trace=recorder)
        if traffic.documents:
            docs = [generator.Request(rid=1_000_000 + i, arrival_s=0.0,
                                      prompt=d, max_new_tokens=1)
                    for i, d in enumerate(traffic.documents)]
            sched.run(serving_requests(docs))
        rec = Recorder()
        adapter._rec = rec
        reqs = serving_requests(traffic.requests)
        kv0 = dict(sched.kv.stats())
        pre0 = dict(sched.prefix.stats()) if sched.prefix else None
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace \
            else None
        tr = SimpleNamespace(on=False, t0=None, t1=None)
        window_end = [0.0]

        def stop_trace(t):
            if tr.on:
                jax.profiler.stop_trace()
                tr.on, tr.t1 = False, t

        def stop_check(t):
            if t < window_end[0]:
                return
            stop_trace(t)
            counter.active = False
            due = all(rec.tokens.get(r.rid) for r in reqs
                      if r.state.name != "FAILED")
            if due or t >= window_end[0] + GRACE_S:
                raise StopServing
        rec.stop_check = stop_check

        if trace:
            jax.profiler.start_trace(trace_dir)
            tr.on, tr.t0 = True, time.perf_counter()
        counter.active = True
        adapter.origin = time.perf_counter()
        window_end[0] = adapter.origin + seconds
        setup_s = adapter.origin - t_start
        try:
            sched.run(reqs)
        except StopServing:
            pass
        t_stop = time.perf_counter()
        stop_trace(t_stop)
        counter.active = False
        stats = jax.devices()[0].memory_stats() or {}
        kv1 = dict(sched.kv.stats())
        pre1 = dict(sched.prefix.stats()) if sched.prefix else None

        ctx = SimpleNamespace(
            arch=arch, D=D, cfg=cfg, mix=mix, seconds=seconds,
            origin=adapter.origin, window_end=window_end[0],
            setup_s=setup_s, rec=rec, requests=reqs, traffic=traffic,
            released=policy.released, kv0=kv0, kv1=kv1, pre0=pre0,
            pre1=pre1, recorder=sched.trace, peaks=None,
            trace=None, program_trace=None, device=None)
        ctx.stopped_at = t_stop
        served = {r.rid: list(r.session.tokens) if r.session else []
                  for r in reqs}
        reqs_by_rid = {r.rid: r for r in reqs}
        sample = _sample(traffic, served, mix["check"]["requests"], seed)
        last = {r.rid: np.asarray(engine.last_logits(reqs_by_rid[r.rid]
                                                     .session))
                for r in sample}
        # free the program's device state before the reference runs
        for r in reqs:
            r.session = None
        del sched, adapter, policy, engine, params
        gc.collect()
        ctx.bytes_in_use_after_free = (
            jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    finally:
        shutil.rmtree(ssd_dir, ignore_errors=True)

    if trace:
        from bench import trace as TR
        t0 = time.perf_counter()
        try:
            ctx.trace = TR.reduce_dir(trace_dir, tr.t0, tr.t1)
            ctx.program_trace = PT.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.trace_reduce_s = time.perf_counter() - t0
    ctx.peaks = peaks or load_peaks(jax.devices()[0].device_kind)
    ctx.compiles = counter
    ctx.memory_peak_bytes = stats.get("peak_bytes_in_use")
    ctx.checked = [(r.prompt, served[r.rid]) for r in sample]
    ctx.last_logits = [last[r.rid] for r in sample]
    ctx.checks = check_outputs(arch, seed, D, vocab, ctx.checked,
                               ctx.last_logits, block, limits)
    return ctx


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table["devices"][kind]


def _sample(traffic, served, n: int, seed: int) -> list:
    """Requests to check: drawn from the seed among those served at least
    one token, with the one served most tokens always in."""
    done = [r for r in traffic.requests if served.get(r.rid)]
    if not done:
        return []
    longest = max(done, key=lambda r: len(served[r.rid]))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rest = [r for r in done if r.rid != longest.rid]
    pick = [rest[i] for i in rng.permutation(len(rest))[:max(n - 1, 0)]]
    return [longest] + pick


def check_outputs(arch, seed, D, vocab, pairs, last_logits, block,
                  limits) -> dict:
    """Compare the sampled requests with the architecture's plain
    reference.

    ``pairs`` are (prompt, served tokens) and ``last_logits`` the logits
    each request's last decode step left. Two numbers: the widest gap by
    which a served token's reference logit lies below the reference's best
    at its position, and the median over the requests of the RMS deviation
    of the last logits from the reference's."""
    n_tok = sum(len(s) for _, s in pairs)
    out_of_vocab = sum(int(t < 0 or t >= vocab) for _, s in pairs
                       for t in s)
    gap = rms = float("inf")
    if pairs and not out_of_vocab:
        ref = arch.logits(seed, D, pairs, block=block)
        gap = max(float(arch.served_gaps(lg, s).max())
                  for lg, (_, s) in zip(ref, pairs))
        rms = float(np.median([arch.rms_deviation(p, lg[len(s)]) for p, lg,
                               (_, s) in zip(last_logits, ref, pairs)]))
    return {"requests_checked": len(pairs), "tokens_checked": n_tok,
            "served_gap": {"value": gap,
                           "limit": limits["served_gap"]["limit"]},
            "last_logits_rms": {"value": rms,
                                "limit": limits["last_logits_rms"]["limit"]},
            "out_of_vocab": {"value": out_of_vocab, "limit": 0}}


def correct(checks: dict) -> bool:
    return checks["tokens_checked"] > 0 and all(
        v["value"] <= v["limit"] for v in checks.values()
        if isinstance(v, dict))


def _plant(engine, faults: dict):
    """Break the timed path underneath (tests of the check only)."""
    kind = faults["kind"]
    if kind == "token":
        # a token altered where it is produced
        orig = engine.decode_step

        def decode_step(sessions):
            rep = orig(sessions)
            for s in sessions:
                if len(s.tokens) == faults.get("at", 2):
                    s.tokens[-1] = (s.tokens[-1] + 1) % engine.cfg.vocab_size
            return rep
        engine.decode_step = decode_step
    elif kind == "stale_state":
        # a step that returns its state unchanged: the decode step hands
        # back the KV cache it was given, so no step sees the last token
        orig_runner_for = engine._runner_for

        def runner_for(max_seq):
            r = orig_runner_for(max_seq)
            if not getattr(r, "_bench_frozen", False):
                fn = r._decode_batched

                def frozen(params, stack, last):
                    new_last, _, nxt, aux = fn(params, stack, last)
                    return new_last, stack, nxt, aux
                r._decode_batched = frozen
                r._bench_frozen = True
            return r
        engine._runner_for = runner_for
    else:
        raise ValueError(kind)


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))
