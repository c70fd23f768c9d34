"""Operations and bytes of the served path's work, from the shapes alone.

These count what the model needs, not what the program happens to do:
a decode step reads every weight once (the FFN only in its active neurons,
each at its bank's width) and the KV of the live tokens, whatever the number
of rows; a token's operations are two per weight it multiplies plus the
attention over its context. So a time computed from them is a least time,
and a share of it cannot pass 100% unless the time leaves out work.
"""
from __future__ import annotations

F32 = 4


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


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, which bound binds): the larger of operations over peak
    and bytes over HBM bandwidth."""
    tf = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tb, "bytes") if tb >= tf else (tf, "flops")
