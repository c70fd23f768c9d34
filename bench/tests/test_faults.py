"""The output check fails a run whose timed path is broken underneath.
These drive the whole harness on a tiny configuration on the CPU (the
harness's look for a chip is left out) with each fault a served cell can
have planted in the served path."""
import time

import pytest

from bench import harness as H
from bench.tests import tiny

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _run(mix, faults=None):
    ctx = H.run(tiny.config(), mix, seed=2 ** 32 + 77, seconds=2.0,
                trace=False, t_start=time.perf_counter(),
                limits=tiny.LIMITS, faults=faults, peaks=PEAKS)
    return ctx.checks


@pytest.mark.parametrize("mix", [tiny.DOCQA, tiny.CHAT],
                         ids=["docqa", "chat"])
def test_sound_run_is_correct(mix):
    checks = _run(mix)
    assert checks["tokens_checked"] > 0
    assert H.correct(checks), checks


@pytest.mark.parametrize("fault", ["token", "stale_state"])
@pytest.mark.parametrize("mix", [tiny.DOCQA, tiny.CHAT],
                         ids=["docqa", "chat"])
def test_broken_path_is_not_correct(mix, fault):
    checks = _run(mix, {"kind": fault})
    assert not H.correct(checks), checks
