"""The control: the program at its own default matmul precision, in place of
the configuration's ``highest``, must come out not correct through the
harness's own check, where the program as configured comes out correct.
Matmul precision changes nothing on the CPU, so this runs on a TPU only (by
hand, on the chip):

    python -m pytest -q bench/tests/test_control.py
"""
import time

import jax
import pytest

from bench import control as C
from bench import harness as H
from bench.tests import tiny

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def tpu():
    if jax.devices()[0].platform != "tpu":
        pytest.skip("precision steps differ only on a TPU")


def _checks(cfg, seed):
    mix = dict(tiny.CHAT, answer_tokens={"median": 48, "sigma": 0.3,
                                         "min": 32, "max": 64})
    ctx = H.run(cfg, mix, seed=seed, seconds=3.0, trace=False,
                t_start=time.perf_counter(), limits=tiny.LIMITS,
                peaks=PEAKS)
    return ctx.checks


@pytest.mark.parametrize("seed", [2 ** 31 + 5, 2 ** 31 + 6, 2 ** 31 + 7])
def test_control_fails_where_the_program_passes(tpu, seed):
    cfg = tiny.config()
    program = _checks(cfg, seed)
    assert H.correct(program), program
    control = _checks(C.as_control(cfg), seed)
    assert not H.correct(control), control
