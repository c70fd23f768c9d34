"""Quantized matmul Pallas TPU kernel — the M2Cache compute hot-spot.

Computes ``y[B, N] = x[B, K] @ dequant(w)[K, N]`` where ``w`` is one of the
three M2Cache precision banks:

  * ``fp``   — bf16/f32 weights as-is,
  * ``int8`` — sym-quantized, per-output-channel scale (N,),
  * ``int4`` — packed two-per-int8 along K (K//2 rows), same scale layout.

Tiling: grid (N/bn, K/bk); the K axis is the accumulation ("arbitrary")
dimension, N is parallel. Per step the kernel holds an (B, bk) x-tile, a
(bk, bn) weight tile (or (bk//2, bn) packed) and the (B, bn) f32 accumulator
in VMEM. The int4 path takes x as its even and odd columns, (B, bk//2) each,
one per nibble of the packed tile. Dequantization happens in-register right
before the MXU dot, so HBM traffic is the *quantized* bytes — exactly the
paper's bandwidth saving, mapped to the HBM→VMEM hierarchy (DESIGN.md §2).

MXU alignment: pick bk, bn multiples of 128 (callers use 256×256 by
default); B stays un-tiled (decode batches are small).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack_int4(packed):
    """(bk//2, bn) int8 -> (low, high) nibbles as (bk//2, bn) int32 each.

    The shifts run in int32 (Mosaic does not legalise int8 shifts): the
    sign-extended byte carries the high nibble's sign in its upper bits,
    so one arithmetic shift recovers it."""
    p = packed.astype(jnp.int32)
    return (p << 28) >> 28, p >> 4


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, *, precision: str):
    j = pl.program_id(1)                      # accumulation step over K

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)        # (B, bk)
    wf = w_ref[...].astype(jnp.float32)       # (bk, bn)
    part = jnp.dot(x, wf, preferred_element_type=jnp.float32)   # (B, bn)
    if precision == "int8":
        part = part * s_ref[...]              # (1, bn) per-channel scale
    o_ref[...] += part


def _qmm_int4_kernel(xe_ref, xo_ref, w_ref, s_ref, o_ref):
    # packed row i holds K rows 2i (low nibble) and 2i+1 (high nibble), so
    # x @ W = x[:, 0::2] @ low + x[:, 1::2] @ high — the wrapper splits x
    # into its even and odd columns and no row interleave happens here
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    lo, hi = _unpack_int4(w_ref[...])         # (bk//2, bn) int32 each
    part = jnp.dot(xe_ref[...].astype(jnp.float32), lo.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    part += jnp.dot(xo_ref[...].astype(jnp.float32), hi.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o_ref[...] += part * s_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("precision", "bk", "bn", "interpret"))
def qmatmul(x, w, scale=None, *, precision: str = "fp", bk: int = 256,
            bn: int = 256, interpret: bool):
    """x: (B, K); w: (K, N) [or (K//2, N) int8-packed for int4];
    scale: (N,) f32 for int8/int4. ``interpret`` runs the kernel in the
    Pallas interpreter (CPU tests) instead of compiling it for the TPU.
    Returns (B, N) f32."""
    B, K = x.shape
    if precision == "int4":
        K2, N = w.shape
        assert K2 * 2 == K, (w.shape, x.shape)
    else:
        Kw, N = w.shape
        assert Kw == K
    bk = min(bk, K)
    bn = min(bn, N)
    assert K % bk == 0 and N % bn == 0, (K, N, bk, bn)
    if scale is None:
        scale = jnp.ones((N,), jnp.float32)
    scale2d = scale.reshape(1, N).astype(jnp.float32)

    grid = (N // bn, K // bk)
    w_spec = pl.BlockSpec((bk // 2, bn) if precision == "int4" else (bk, bn),
                          lambda i, j: (j, i))
    s_spec = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    if precision == "int4":
        kernel = _qmm_int4_kernel
        x_spec = pl.BlockSpec((B, bk // 2), lambda i, j: (0, j))
        operands = (x[:, 0::2], x[:, 1::2], w, scale2d)
        in_specs = [x_spec, x_spec, w_spec, s_spec]
    else:
        kernel = functools.partial(_qmm_kernel, precision=precision)
        operands = (x, w, scale2d)
        in_specs = [pl.BlockSpec((B, bk), lambda i, j: (0, j)), w_spec,
                    s_spec]

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
