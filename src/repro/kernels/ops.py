"""jit'd wrappers composing the Pallas kernels into M2Cache operations.

``mp_glu_ffn`` is the serving hot path: the HBM cache unit holds *compact*
per-tier banks (fp | int8 | int4, neurons contiguous per tier, built by the
cache manager's ATU updates); the FFN is six qmatmul kernel calls + the GLU
glue. Per-neuron scales of the down-projection are applied to the
activations (the contraction axis), keeping the kernel's scale semantics
per-output-channel.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.kernels.qmatmul import qmatmul
from repro.kernels.flash_decode import flash_decode
from repro.kernels.atu_update import atu_update
from repro.models.common import activation


def make_compact_banks(wg, wu, wd, sizes: Dict[str, int], idx) -> Dict:
    """Build the compact per-tier bank layout from dense fp weights + the
    rank-sorted active index set (host/manager-side helper; in production
    the SSD tier stores this layout per precision).

    Packing: int4 packs along the *contraction* axis of each matmul
    (d for up/gate, k_tier for down), so kernel tiles stay byte-aligned.
    """
    from repro.core.quantize import quantize_int8, quantize_int4
    k16, k8, k4 = sizes["fp16"], sizes["int8"], sizes["int4"]
    i16, i8, i4 = idx[:k16], idx[k16:k16 + k8], idx[k16 + k8:k16 + k8 + k4]
    out = {}
    if k16:
        out["fp"] = {"wg": wg[:, i16], "wu": wu[:, i16], "wd": wd[i16, :]}
    if k8:
        g8, sg = quantize_int8(wg[:, i8], 0)
        u8, su = quantize_int8(wu[:, i8], 0)
        # down-proj: scale per *output* channel (d) — matches the kernel's
        # per-N scale natively (the neuron axis is the contraction here)
        d8, sd = quantize_int8(wd[i8, :], 0)
        out["int8"] = {"wg": g8, "wu": u8, "wd": d8,
                       "sg": sg, "su": su, "sd": sd}
    if k4:
        g4, sg = quantize_int4(wg[:, i4], 0)
        u4, su = quantize_int4(wu[:, i4], 0)
        d4, sd = quantize_int4(wd[i4, :], 0)     # packed (k4//2, d), scale (d,)
        out["int4"] = {"wg": g4, "wu": u4, "wd": d4,
                       "sg": sg, "su": su, "sd": sd}
    return out


def mp_glu_ffn(x, banks: Dict, *, act_name: str = "silu", interpret: bool):
    """x: (B, d). banks: output of make_compact_banks. ``interpret`` is
    passed to every kernel (True on CPU). Returns (B, d) f32."""
    B, d = x.shape
    y = jnp.zeros((B, d), jnp.float32)
    act = activation(act_name)
    for tier, t in banks.items():
        prec = "fp" if tier == "fp" else tier
        hg = qmatmul(x, t["wg"], t.get("sg"), precision=prec,
                     interpret=interpret)
        hu = qmatmul(x, t["wu"], t.get("su"), precision=prec,
                     interpret=interpret)
        h = act(hg) * hu                                   # (B, k_t) f32
        y = y + qmatmul(h, t["wd"], t.get("sd"), precision=prec,
                        interpret=interpret)
    return y


__all__ = ["qmatmul", "flash_decode", "atu_update", "mp_glu_ffn",
           "make_compact_banks"]
