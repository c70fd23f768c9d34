"""Seeded weights for the Qwen2 family (``bench/arch/qwen2.py``, which maps a
configuration file onto the sizes ``D`` taken here), made on the device.

The benchmark makes the weights, not the program: ``program_params`` returns
the served path's parameter pytree (float32 weights, the M2 FFN as its three
precision banks) from one jitted call, and ``layer_weights`` /
``embed_weights`` return the same numbers one layer at a time for the plain
reference. Both derive every array from ``base_key(seed)`` by the same
``fold_in`` path, so they agree bit for bit.

Quantization (per neuron, symmetric): int8 with scale max|w|/127, int4 with
scale max|w|/7, int4 values packed two to a byte along the non-neuron axis,
low nibble at the even index. A neuron is a column of the gate and up
projections and the matching row of the down projection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int):
    """A PRNG key from any non-negative integer seed (also past 2**32)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _normal(key, i, shape, std):
    # the barrier keeps XLA from folding the scale into the sampler's own
    # constants differently in differently fused programs: the per-layer
    # and the stacked weights must agree bit for bit
    z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return jax.lax.optimization_barrier(z) * jnp.float32(std)


def quantize(w, axis: int, qmax: float):
    """Per-neuron symmetric quantization over ``axis`` (the non-neuron
    axis). Returns (int8 values, float32 scales)."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis) / qmax, 1e-8)
    q = jnp.clip(jnp.round(w / jnp.expand_dims(scale, axis)), -qmax, qmax)
    return q.astype(jnp.int8), scale


def pack_int4(q, axis: int):
    """Two int4 values to a byte along ``axis``, low nibble = even index."""
    lo = jnp.take(q, jnp.arange(0, q.shape[axis], 2), axis=axis)
    hi = jnp.take(q, jnp.arange(1, q.shape[axis], 2), axis=axis)
    return ((lo & 0x0F) | (hi << 4)).astype(jnp.int8)


def layer_weights(key, D: dict, layer: int) -> dict:
    """One decoder layer: attention, norms as offsets from a gain of 1, the
    predictor, and the FFN's float32 bank with its int8 and int4 banks."""
    key = jax.random.fold_in(key, 1000 + layer)
    d, f, hq, hkv, hd, r = D["d"], D["f"], D["hq"], D["hkv"], D["hd"], D["r"]
    e = (hq + 2 * hkv) * hd
    wg = _normal(key, 0, (d, f), d ** -0.5)
    wu = _normal(key, 1, (d, f), d ** -0.5)
    wd = _normal(key, 2, (f, d), f ** -0.5)
    g8, g8s = quantize(wg, 0, 127.0)
    u8, u8s = quantize(wu, 0, 127.0)
    d8, d8s = quantize(wd, 1, 127.0)
    g4, g4s = quantize(wg, 0, 7.0)
    u4, u4s = quantize(wu, 0, 7.0)
    d4, d4s = quantize(wd, 1, 7.0)
    return {
        "norm1": _normal(key, 3, (d,), 0.1),
        "norm2": _normal(key, 4, (d,), 0.1),
        "wqkv": _normal(key, 5, (d, e), d ** -0.5),
        "bqkv": _normal(key, 6, (e,), 0.1),
        "wo": _normal(key, 7, (hq * hd, d), (hq * hd) ** -0.5),
        "ffn": {
            "banks": {
                "wg_fp": wg, "wu_fp": wu, "wd_fp": wd,
                "wg_i8": g8, "wg_i8_s": g8s, "wu_i8": u8, "wu_i8_s": u8s,
                "wd_i8": d8, "wd_i8_s": d8s,
                "wg_i4": pack_int4(g4, 0), "wg_i4_s": g4s,
                "wu_i4": pack_int4(u4, 0), "wu_i4_s": u4s,
                "wd_i4": pack_int4(d4, 1), "wd_i4_s": d4s,
            },
            "pred": {"A": _normal(key, 8, (d, r), d ** -0.5),
                     "B": _normal(key, 9, (r, f), r ** -0.5)},
        },
    }


def embed_weights(key, D: dict) -> dict:
    d, V = D["d"], D["V"]
    return {"embed": _normal(key, 10, (V, d), 1.0),
            "unembed": _normal(key, 11, (V, d), d ** -0.5),
            "final_norm": _normal(key, 12, (d,), 0.1)}


def _program_params(key, D: dict) -> dict:
    layers = jax.lax.map(lambda l: layer_weights(key, D, l),
                         jnp.arange(D["L"]))
    out = embed_weights(key, D)
    out["layers"] = {"pattern": [layers], "remainder": []}
    return out


def program_params(seed: int, D: dict) -> dict:
    """The served path's parameter pytree, made on the default device in
    one jitted call (layers stacked on a leading axis)."""
    fn = jax.jit(_program_params, static_argnums=1)
    return fn(base_key(seed), _Frozen(D))


class _Frozen(dict):
    """A hashable dict, so the sizes can be a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
