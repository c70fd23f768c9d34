"""Qwen2 decoder layers as the served path runs them: RMSNorm, GQA attention
with q/k/v bias and RoPE, and M2Cache's sparse mixed-precision GLU as the
FFN (Qwen2.5-14B and -32B).

The weights are ``bench/weights.py``'s and the plain reference is
``bench/reference.py``; this module maps a configuration file onto them and
onto the program's ``ModelConfig``, and counts a served step's work.

The counts are what the model needs, not what the program happens to do: a
decode step reads every weight once (the FFN only in its active neurons,
each at its bank's width) and the KV of the live tokens, whatever the number
of rows; a token's operations are two per weight it multiplies plus the
attention over its context. So a time computed from them is a least time,
and a share of it cannot pass 100% unless the time leaves out work.
"""
from __future__ import annotations

from bench.flops import F32
from bench.reference import logits, rms_deviation, served_gaps  # noqa: F401
from bench.weights import (embed_weights, layer_weights,  # noqa: F401
                           program_params)

ARCHITECTURES = ("Qwen2ForCausalLM",)


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
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


def dims(cfg: dict) -> dict:
    """The sizes the weights, the reference and the counts need."""
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


def attn_weight_params(D: dict) -> int:
    d, hq, hkv, hd = D["d"], D["hq"], D["hkv"], D["hd"]
    return d * (hq + 2 * hkv) * hd + hq * hd * d


def ffn_active_bytes(D: dict) -> float:
    """One layer's active neurons at their bank widths (float32, int8,
    packed int4) across gate, up and down, with their int scales."""
    d = D["d"]
    per_neuron = 3 * d
    return per_neuron * (D["k_fp"] * F32 + D["k_i8"] * 1 + D["k_i4"] * 0.5) \
        + 3 * (D["k_i8"] + D["k_i4"]) * F32


def predictor_params(D: dict) -> int:
    return D["d"] * D["r"] + D["r"] * D["f"]


def token_flops(D: dict, ctx_tokens: float, *, logits: bool) -> float:
    """One token through every layer (attention over ``ctx_tokens``
    positions), plus the output head when its logits are needed."""
    per_layer = 2 * (attn_weight_params(D) + 3 * D["d"] * D["k"]
                     + predictor_params(D))
    flops = D["L"] * per_layer + 4 * D["L"] * D["hq"] * D["hd"] * ctx_tokens
    if logits:
        flops += 2 * D["d"] * D["V"]
    return flops


def step_flops(D: dict, tokens: int, ctx_sum: int, outputs: int) -> float:
    """A step that processes ``tokens`` tokens whose contexts sum to
    ``ctx_sum`` and turns ``outputs`` of them into logits."""
    per_layer = 2 * (attn_weight_params(D) + 3 * D["d"] * D["k"]
                     + predictor_params(D))
    return (tokens * D["L"] * per_layer
            + 4 * D["L"] * D["hq"] * D["hd"] * ctx_sum
            + outputs * 2 * D["d"] * D["V"])


def decode_bytes(D: dict, ctx_sum: int) -> float:
    """Least HBM bytes of one decode step: every weight once and the KV of
    the ``ctx_sum`` live tokens of its rows."""
    d, L = D["d"], D["L"]
    weights = L * ((attn_weight_params(D) + predictor_params(D)) * F32
                   + ffn_active_bytes(D)) + D["V"] * d * F32
    kv = ctx_sum * L * 2 * D["hkv"] * D["hd"] * F32
    return weights + kv
