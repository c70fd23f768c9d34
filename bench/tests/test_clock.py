"""The host-clock adapter: arrivals are released on the host clock, no
modeled second enters a timestamp, and waiting idle for an arrival costs
the served work nothing."""
import time

from bench import harness as H


def _serve(arrivals, gen=4, prompt=32):
    from repro.core.carbon import CarbonIntensityTrace
    from repro.core.engine import M2CacheEngine
    from repro.serving import ContinuousBatchScheduler, make_policy
    from repro.serving.request import ServingRequest
    eng = M2CacheEngine(paper_model="llama-7b", dram_capacity_gb=6.0)
    rec = H.Recorder()
    adapter = H.HostClockEngine(eng, rec)
    policy = H.ReleaseRecorder(make_policy("fcfs"))
    sched = ContinuousBatchScheduler(
        adapter, max_batch=4, policy=policy, prefill_chunk=16,
        carbon_trace=CarbonIntensityTrace.constant())
    reqs = [ServingRequest(rid=i, prompt_len=prompt, max_new_tokens=gen,
                           arrival_s=a) for i, a in enumerate(arrivals)]
    modeled0 = eng.clock
    adapter.origin = time.perf_counter()
    sched.run(reqs)
    t_end = time.perf_counter()
    return adapter, rec, policy, reqs, eng.clock - modeled0, t_end


def test_no_modeled_second_enters_a_timestamp():
    adapter, rec, policy, reqs, modeled, t_end = _serve([0.0, 0.0, 0.2])
    host = t_end - adapter.origin
    # the engine's modeled clock ran ahead of the host's: had its seconds
    # entered a timestamp, the last finish would lie past the host's end
    assert modeled > 2 * host
    for r in reqs:
        stamps = rec.tokens[r.rid]
        assert len(stamps) == r.max_new_tokens
        assert adapter.origin + r.arrival_s <= policy.released[r.rid]
        assert policy.released[r.rid] <= stamps[0] <= stamps[-1] <= t_end
        # the scheduler's own timestamps are host seconds from the origin
        assert abs(r.first_token_s - (stamps[0] - adapter.origin)) < 0.05
        assert r.finish_s <= host


def test_idle_wait_costs_the_served_work_nothing():
    gap = 2.0
    _serve([0.0])                       # compile first, time after
    adapter, rec, _, reqs, _, t_end = _serve([0.0, gap])
    first = [rec.tokens[r.rid][0] - (adapter.origin + r.arrival_s)
             for r in reqs]
    span = [rec.tokens[r.rid][-1] - rec.tokens[r.rid][0] for r in reqs]
    # the second request waited for nobody: it was released when due and
    # served as fast as the first, not charged the idle second
    assert rec.tokens[reqs[0].rid][-1] < adapter.origin + gap
    assert first[1] < gap / 2
    assert first[1] < 2 * first[0] + 0.05
    assert span[1] < 2 * span[0] + 0.05
    assert t_end - adapter.origin >= gap
