"""Full flash-attention Pallas TPU kernel (prefill/train forward).

Addresses the §Roofline finding that the XLA-level chunked attention
materialises fp32 score tiles to HBM (~16 TB/step on qwen prefill_32k):
here scores, running max/denominator and the output accumulator live in
VMEM scratch; HBM traffic is Q/K/V/O only.

Grid (B·Hq, n_q_tiles, n_kv_tiles); the kv axis is the accumulation
("arbitrary") dimension. Causal + sliding-window masking via absolute
positions. GQA: each query head reads the K/V rows of its KV head through
the index map (query head h -> KV head h // G), so every block is a
(rows, D) tile aligned to the TPU's (8, 128) tiling and the MXU sees
(bq, D) × (D, bk) matmuls.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, n_kv_tiles: int, scale: float, window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale       # (bq, D)
    k = k_ref[0].astype(jnp.float32)               # (bk, D)
    v = v_ref[0].astype(jnp.float32)               # (bk, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos <= q_pos
    if window:
        mask &= k_pos > (q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv_tiles - 1)
    def _finish():
        # fully-masked rows (window gaps) have l == 0 -> emit zeros
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bk", "window", "interpret"))
def flash_attention(q, k, v, *, window: int = 0, bq: int = 256,
                    bk: int = 256, interpret: bool):
    """Causal (+optional sliding-window) flash attention.

    q: (B, S, Hq, D); k, v: (B, S, Hkv, D) with Hq % Hkv == 0.
    ``interpret`` runs the kernel in the Pallas interpreter (CPU tests)
    instead of compiling it for the TPU.
    Returns (B, S, Hq, D) in q.dtype. S must divide by bq and bk.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0
    scale = 1.0 / math.sqrt(D)

    # heads leading: query row b*Hq + h reads KV row b*Hkv + h // G, which
    # is (b*Hq + h) // G because Hq = Hkv * G
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)

    grid = (B * Hq, S // bq, S // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, n_kv_tiles=S // bk,
                          scale=scale, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // G, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
