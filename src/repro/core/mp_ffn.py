"""Dynamic sparse mixed-precision FFN — the in-graph (jit/pjit) form of the
paper's MP Inference (§5.2), used by the serving path and the dry-run.

Per decode step:
  1. predictor scores every FFN neuron from the block input,
  2. the top ``k = active_ratio·f`` neurons form the active set (batch-shared,
     see DESIGN.md), *sorted by score*,
  3. a tier code per neuron marks the top ``r_fp16·k`` ranks FP16(bf16), the
     next ``r_int8·k`` ranks INT8 and the rest INT4 (0: inactive),
  4. the input is multiplied against each tier's whole gate and up bank and
     every neuron keeps its own tier's value (0 if inactive); the down
     projection sums, over the tiers, that tier's neurons against its bank.
     Under the batched runner's vmap the banks are unbatched operands, so
     each bank is read once a step for all rows: no per-row weight copy.

Sharding: the banks are sharded on the *d_model* axis (opposite of a dense
FFN); the contraction over d produces one all-reduce, identical in shape to
a row-parallel dense FFN.

FLOP/byte accounting: the device reads every bank whole, once a step for
the whole batch, and computes each tier's product over all f neurons.
``bytes_weights`` stays the paper's count for the active set,
k·(r16·2 + r8·1 + r4·0.5)·3·d against 3·d·f·2 dense: what the cache
manager and the modeled clock price for moving neurons between tiers.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.core.predictor import predictor_scores, shared_topk_indices
from repro.core.quantize import dequantize_int8, int4_nibbles
from repro.models.common import activation


def tier_sizes(f: int, cfg) -> Dict[str, int]:
    k = max(int(round(f * cfg.m2_active_ratio)), 8)
    k = min(k, f)
    k16 = int(round(k * cfg.m2_ratio_fp16))
    k8 = int(round(k * cfg.m2_ratio_int8))
    k4 = max(k - k16 - k8, 0)
    return {"k": k16 + k8 + k4, "fp16": k16, "int8": k8, "int4": k4}


def _int4_halves(banks, m: str, axis: int, dtype):
    """The int4 bank of matrix ``m``, scaled, as the even and odd halves of
    its packed axis ``axis`` (the low nibble holds the even index)."""
    s = jnp.expand_dims(banks[f"{m}_i4_s"], axis).astype(dtype)
    return tuple(n.astype(dtype) * s for n in int4_nibbles(banks[f"{m}_i4"]))


def _bank_product(banks, m: str, tier: int, v):
    """``v`` against the whole bank of tier ``tier`` (1 fp, 2 int8, 3 int4)
    of matrix ``m``, scaled before the product: ``v`` (B, S, d) against a
    (d, f) gate or up bank, or (B, S, f) against the (f, d) down bank.
    The int4 product runs on the nibble halves, so no unpacked,
    interleaved copy of the bank is built."""
    c = v.dtype
    if tier == 1:
        return v @ banks[f"{m}_fp"].astype(c)
    axis = 1 if m == "wd" else 0               # the non-neuron axis
    if tier == 2:
        return v @ dequantize_int8(banks[f"{m}_i8"], banks[f"{m}_i8_s"],
                                   axis).astype(c)
    lo, hi = _int4_halves(banks, m, axis, c)
    if m == "wd":                              # even and odd output columns
        return jnp.stack([v @ lo, v @ hi], -1).reshape(v.shape[:-1] + (-1,))
    return v[..., 0::2] @ lo + v[..., 1::2] @ hi


def mp_ffn_apply(cfg, banks, pred, x):
    """x: (B, S, d) — serving activations. banks/pred: one layer's params.

    Returns (y, info) where info carries the active indices (for the cache
    manager / ATU policy) and per-tier byte counts.
    """
    B, S, d = x.shape
    f = banks["wg_i8_s"].shape[-1]
    sizes = tier_sizes(f, cfg)
    k, k16, k8, k4 = sizes["k"], sizes["fp16"], sizes["int8"], sizes["int4"]

    scores = predictor_scores(x, pred["A"], pred["B"])        # (B,S,f)
    idx = shared_topk_indices(scores, k)                      # (k,) rank-sorted

    # tier code per neuron, by rank: 1 fp, 2 int8, 3 int4; 0 inactive
    tier = np.repeat(np.int8([1, 2, 3]), [k16, k8, k4])
    code = jnp.zeros((f,), jnp.int8).at[idx].set(tier)
    tiers = [t for t, n in ((1, k16), (2, k8), (3, k4)) if n]

    def pick(m):
        # each neuron's value from its own tier's product, 0 if inactive
        out = jnp.zeros((B, S, f), x.dtype)
        for t in tiers:
            out = jnp.where(code == t, _bank_product(banks, m, t, x), out)
        return out

    act = activation(cfg.ffn_act)
    h = act(pick("wg")) * pick("wu")
    y = sum(_bank_product(banks, "wd", t, jnp.where(code == t, h, 0))
            for t in tiers)

    bytes_moved = 3 * d * (k16 * 2 + k8 * 1 + k4 * 0.5)
    info = {"active_idx": idx, "bytes_weights": bytes_moved,
            "sizes": sizes}
    return y, info


def mp_ffn_reference(cfg, wg, wu, wd, pred, x):
    """Oracle: dense FFN masked to the same active set at full precision —
    used by tests to bound the quantization error of mp_ffn_apply."""
    f = wg.shape[-1]
    sizes = tier_sizes(f, cfg)
    scores = predictor_scores(x, pred["A"], pred["B"])
    idx = shared_topk_indices(scores, sizes["k"])
    mask = jnp.zeros((f,), bool).at[idx].set(True)
    act = activation(cfg.ffn_act)
    h = act(jnp.einsum("bsd,df->bsf", x, wg))
    h = h * jnp.einsum("bsd,df->bsf", x, wu)
    h = jnp.where(mask, h, 0).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, wd)
