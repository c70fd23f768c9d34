"""A configuration brings its architecture: ``bench.arch.resolve`` finds the
module by the file's ``architectures`` name, the Qwen2 module gives what the
harness computed before it had one, and the harness, the output check and
the readers call nothing else for weights, reference and counts."""
import dataclasses
import sys
import time
import types
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness as H
from bench import reference as R
from bench import weights as W
from bench.arch import UnknownArchitecture, resolve
from bench.arch import qwen2 as Q
from bench.metrics import decode_roofline, mfu
from bench.tests import tiny

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CONFIGS = ["qwen2.5-14b", "qwen2.5-32b", "tiny"]


def _config(name):
    return tiny.config() if name == "tiny" else H.load_config(name)


# -- the harness's Qwen2 code before the architecture modules, verbatim ------


def _old_program_config(cfg: dict):
    import dataclasses as dc
    from repro.configs.base import get_config
    a = cfg["assumed"]
    return dc.replace(
        get_config(cfg["program_arch"]),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=a["head_dim"], ffn_act=cfg["hidden_act"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], qkv_bias=True,
        m2_enabled=True, m2_active_ratio=a["m2_active_ratio"],
        m2_ratio_fp16=a["m2_ratio_fp"], m2_ratio_int8=a["m2_ratio_int8"],
        m2_ratio_int4=a["m2_ratio_int4"],
        m2_predictor_rank=a["m2_predictor_rank"])


def _old_dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hd = cfg["assumed"]["head_dim"]
    f = cfg["intermediate_size"]
    k = min(max(int(round(f * cfg["assumed"]["m2_active_ratio"])), 8), f)
    k_fp = int(round(k * cfg["assumed"]["m2_ratio_fp"]))
    k_i8 = int(round(k * cfg["assumed"]["m2_ratio_int8"]))
    return {"d": d, "f": f, "hq": hq, "hkv": cfg["num_key_value_heads"],
            "hd": hd, "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "r": cfg["assumed"]["m2_predictor_rank"],
            "k": k, "k_fp": k_fp, "k_i8": k_i8, "k_i4": k - k_fp - k_i8,
            "eps": cfg["assumed"]["rms_norm_eps"],
            "theta": cfg["rope_theta"]}


F32 = 4


def _old_attn_weight_params(D):
    d, hq, hkv, hd = D["d"], D["hq"], D["hkv"], D["hd"]
    return d * (hq + 2 * hkv) * hd + hq * hd * d


def _old_ffn_active_bytes(D):
    d = D["d"]
    per_neuron = 3 * d
    return per_neuron * (D["k_fp"] * F32 + D["k_i8"] * 1 + D["k_i4"] * 0.5) \
        + 3 * (D["k_i8"] + D["k_i4"]) * F32


def _old_predictor_params(D):
    return D["d"] * D["r"] + D["r"] * D["f"]


def _old_token_flops(D, ctx_tokens, *, logits):
    per_layer = 2 * (_old_attn_weight_params(D) + 3 * D["d"] * D["k"]
                     + _old_predictor_params(D))
    flops = D["L"] * per_layer + 4 * D["L"] * D["hq"] * D["hd"] * ctx_tokens
    if logits:
        flops += 2 * D["d"] * D["V"]
    return flops


def _old_step_flops(D, tokens, ctx_sum, outputs):
    per_layer = 2 * (_old_attn_weight_params(D) + 3 * D["d"] * D["k"]
                     + _old_predictor_params(D))
    return (tokens * D["L"] * per_layer
            + 4 * D["L"] * D["hq"] * D["hd"] * ctx_sum
            + outputs * 2 * D["d"] * D["V"])


def _old_decode_bytes(D, ctx_sum):
    d, L = D["d"], D["L"]
    weights = L * ((_old_attn_weight_params(D) + _old_predictor_params(D))
                   * F32 + _old_ffn_active_bytes(D)) + D["V"] * d * F32
    kv = ctx_sum * L * 2 * D["hkv"] * D["hd"] * F32
    return weights + kv


# -- the Qwen2 module gives the same ------------------------------------------


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_and_dims_as_before(name):
    cfg = _config(name)
    arch = resolve(cfg)
    assert arch is Q
    got, want = arch.program_config(cfg), _old_program_config(cfg)
    assert dataclasses.asdict(got) == dataclasses.asdict(want)
    assert got.qkv_bias and got.m2_enabled
    assert arch.dims(cfg) == _old_dims(cfg)


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_as_before(name):
    cfg = _config(name)
    arch = resolve(cfg)
    D = arch.dims(cfg)
    for ctx_sum in (0, 1, 100, 2047, 8000, 65536):
        assert arch.decode_bytes(D, ctx_sum) == _old_decode_bytes(D, ctx_sum)
        for logits in (False, True):
            assert arch.token_flops(D, ctx_sum, logits=logits) == \
                _old_token_flops(D, ctx_sum, logits=logits)
        for tokens in (1, 3, 4, 16, 64):
            for outputs in (0, 1, 4):
                assert arch.step_flops(D, tokens, ctx_sum, outputs) == \
                    _old_step_flops(D, tokens, ctx_sum, outputs)


def test_tiny_weights_and_reference_logits_as_before():
    cfg = tiny.config()
    arch = resolve(cfg)
    seed = 2 ** 31 + 11
    D, old = arch.dims(cfg), _old_dims(cfg)
    ours = arch.program_params(seed, D)
    theirs = W.program_params(seed, old)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rng = np.random.default_rng(3)
    pairs = [(rng.integers(0, D["V"], 21), rng.integers(0, D["V"], 5)),
             (rng.integers(0, D["V"], 7), rng.integers(0, D["V"], 2))]
    for a, b in zip(arch.logits(seed, D, pairs, block=16),
                    R.logits(seed, old, pairs, block=16)):
        np.testing.assert_array_equal(a, b)


# -- the harness goes through the module --------------------------------------

ENTRY_POINTS = ("program_config", "dims", "program_params", "logits",
                "served_gaps", "rms_deviation", "step_flops", "token_flops",
                "decode_bytes")


def _forbidden(name):
    def call(*a, **kw):
        raise AssertionError(f"the harness called {name} directly")
    return call


def test_a_stub_architecture_is_served_through_its_module(monkeypatch):
    """A module that only this test defines, found by its name, makes the
    program's config, the weights, the reference and the counts of a tiny
    run on the CPU; the Qwen2 code is reached only through it."""
    calls = []
    stub = types.ModuleType("bench.arch.stubarch")
    stub.ARCHITECTURES = ("StubArchForCausalLM",)
    for name in ENTRY_POINTS:
        def rec(*a, _fn=getattr(Q, name), _name=name, **kw):
            calls.append(_name)
            return _fn(*a, **kw)
        setattr(stub, name, rec)
    monkeypatch.setitem(sys.modules, "bench.arch.stubarch", stub)
    for name in ENTRY_POINTS:
        monkeypatch.setattr(Q, name, _forbidden(f"qwen2.{name}"))
    monkeypatch.setattr(W, "program_params", _forbidden("program_params"))
    for name in ("logits", "served_gaps", "rms_deviation"):
        monkeypatch.setattr(R, name, _forbidden(f"reference.{name}"))

    cfg = tiny.config(architectures=["StubArchForCausalLM"])
    assert resolve(cfg) is stub
    ctx = H.run(cfg, tiny.CHAT, seed=2 ** 32 + 78, seconds=2.0, trace=False,
                t_start=time.perf_counter(), limits=tiny.LIMITS,
                peaks=PEAKS)
    assert ctx.arch is stub
    assert H.correct(ctx.checks), ctx.checks
    assert {"program_config", "dims", "program_params", "logits",
            "served_gaps", "rms_deviation"} <= set(calls)

    calls.clear()
    assert mfu.read(ctx) > 0
    ctx.trace = SimpleNamespace(t0=0.0, t1=float("inf"),
                                modules={"jit_decode_one_row": [1, 1.0]})
    assert decode_roofline.read(ctx) > 0
    assert {"step_flops", "decode_bytes"} <= set(calls)


@pytest.mark.parametrize("names", [
    ["LlamaForCausalLM"],                   # no bench/arch/llama.py
    ["Qwen2ForSequenceClassification"],     # qwen2.py does not claim it
    ["Qwen2ForCausalLM", "Qwen2ForCausalLM"],
    [],
    None,
])
def test_an_unknown_architecture_is_an_error(names):
    cfg = tiny.config(architectures=names)
    if names is None:
        del cfg["architectures"]
    with pytest.raises(UnknownArchitecture):
        resolve(cfg)
