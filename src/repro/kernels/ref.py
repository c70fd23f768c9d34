"""Pure-jnp oracles for every Pallas kernel (tests assert_allclose against
these across shape/dtype sweeps)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def qmatmul_ref(x, w, scale=None, *, precision: str = "fp"):
    x = x.astype(jnp.float32)
    if precision == "int4":
        lo = jnp.int8(w << 4) >> 4
        hi = w >> 4
        half, n = w.shape
        wf = jnp.stack([lo, hi], axis=1).reshape(half * 2, n)
        wf = wf.astype(jnp.float32)
    else:
        wf = w.astype(jnp.float32)
    y = x @ wf
    if precision in ("int8", "int4") and scale is not None:
        y = y * scale[None, :].astype(jnp.float32)
    return y


def flash_decode_ref(q, k, v, slot_positions, lengths):
    """q: (B,Hkv,G,D); k,v: (B,S,Hkv,D); slot_positions: (B,S); lengths: (B,)."""
    B, Hkv, G, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, kf)
    valid = (slot_positions >= 0) & \
        (slot_positions <= lengths[:, None])
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", p, vf)


def atu_update_ref(bank, unit, src_idx, dst_idx, *, bg: int = 8):
    """Block-group row copies: groups of bg neuron rows move together.
    bank: (f, d); unit: (k, d)."""
    out = jnp.asarray(unit)
    m = src_idx.shape[0]
    for g in range(m // bg):
        sbase = int(src_idx[g * bg]) // bg * bg
        dbase = int(dst_idx[g * bg]) // bg * bg
        out = out.at[dbase:dbase + bg].set(
            bank[sbase:sbase + bg].astype(unit.dtype))
    return out


def mp_glu_ffn_ref(x, banks_compact, act_name: str = "silu"):
    """Oracle for the composed mixed-precision GLU FFN over compact banks
    (same per-tier layout as kernels/ops.make_compact_banks)."""
    from repro.models.common import activation
    act = activation(act_name)
    y = 0.0
    for tier, t in banks_compact.items():
        prec = "fp" if tier == "fp" else tier
        hg = qmatmul_ref(x, t["wg"], t.get("sg"), precision=prec)
        hu = qmatmul_ref(x, t["wu"], t.get("su"), precision=prec)
        h = act(hg) * hu
        y = y + qmatmul_ref(h, t["wd"], t.get("sd"), precision=prec)
    return y


def flash_attention_ref(q, k, v, *, window: int = 0):
    """Oracle for the prefill flash-attention kernel: dense causal
    (+window) attention. q: (B,S,Hq,D); k,v: (B,S,Hkv,D)."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(D))
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask &= pos[None, :] > (pos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, Hq, D).astype(q.dtype)
