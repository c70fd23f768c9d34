"""Flash-decoding attention Pallas TPU kernel.

One new token (per sequence) attends to a long KV cache: online-softmax
accumulation over KV tiles so the (S)-length score row never materialises in
HBM. Grid (B, Hkv, S/bs); the S axis is the accumulation dimension with
running (m, l, acc) carried in VMEM scratch. GQA handled by folding the G
query heads of each KV head into the tile ((G, D) @ (D, bs) on the MXU).

The cache keeps the model's (B, S, Hkv, D) layout. The wrapper views it as
(B, S, Hkv·D), which is free, so a K/V block is (bs, D): sequence slots on
sublanes and one head's D lanes, aligned to the TPU's (8, 128) tiling when
bs % 8 == 0 and D % 128 == 0. Slot positions ride as (B, 1, S) and lengths
as (B, 1, 1), so their blocks span whole trailing dims for any batch size.

The ``lengths`` input masks invalid cache slots (decode position + ring-
buffer wrap handled by the caller via per-slot validity, passed as absolute
slot positions).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, pos_ref, len_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, n_s_tiles: int, scale: float):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale     # (G, D)
    k = k_ref[0].astype(jnp.float32)                # (bs, D)
    v = v_ref[0].astype(jnp.float32)                # (bs, D)
    slot_pos = pos_ref[0]                           # (1, bs) absolute positions
    valid = (slot_pos >= 0) & (slot_pos <= len_ref[0])

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bs)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                             # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                          # (G, bs)
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(s_idx == n_s_tiles - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def flash_decode(q, k, v, slot_positions, lengths, *, bs: int = 512,
                 interpret: bool):
    """q: (B, Hkv, G, D); k, v: (B, S, Hkv, D);
    slot_positions: (B, S) int32 absolute position per cache slot (-1 =
    empty); lengths: (B,) int32 current decode position (inclusive).
    ``interpret`` runs the kernel in the Pallas interpreter (CPU tests)
    instead of compiling it for the TPU. Returns (B, Hkv, G, D) f32."""
    B, Hkv, G, D = q.shape
    S = k.shape[1]
    bs = min(bs, S)
    assert S % bs == 0

    grid = (B, Hkv, S // bs)
    scale = 1.0 / math.sqrt(D)
    kf = k.reshape(B, S, Hkv * D)
    vf = v.reshape(B, S, Hkv * D)
    pos3d = slot_positions.reshape(B, 1, S).astype(jnp.int32)
    len3d = lengths.reshape(B, 1, 1).astype(jnp.int32)

    return pl.pallas_call(
        functools.partial(_flash_kernel, n_s_tiles=S // bs, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, bs, D), lambda b, h, s: (b, s, h)),
            pl.BlockSpec((1, bs, D), lambda b, h, s: (b, s, h)),
            pl.BlockSpec((1, 1, bs), lambda b, h, s: (b, 0, s)),
            pl.BlockSpec((1, 1, 1), lambda b, h, s: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, kf, vf, pos3d, len3d)
