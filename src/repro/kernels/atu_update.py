"""ATU cache-unit update Pallas kernel (paper §5.3, TPU form).

The HBM isolated cache unit is a *compacted* neuron bank ``(k, d)``; the
Adjacent-Token-Update policy replaces only the neurons that changed between
tokens. On GPU that is a per-neuron cudaMemcpy storm (paper Fig. 5 shows the
small-copy penalty); the TPU-native form is one kernel launch that copies
``m`` changed source rows into ``m`` destination slots, with the
(src, dst) index pairs scalar-prefetched so each grid step's BlockSpec
index_map selects the right source row block.

Neurons are rows (sublanes) and d_model is the lane axis, so a group of
``bg`` (default 8) neurons is one ``(bg, d)`` tile aligned to the TPU's
(8, 128) tiling; the cache manager pads the change-list to a multiple of
``bg`` with identity copies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _atu_kernel(src_idx_ref, dst_idx_ref, bank_ref, unit_in_ref,
                unit_ref, *, bg: int):
    # bank_ref: (bg, d) gathered source rows (BlockSpec did the gather via
    # the scalar-prefetched src indices); unit_ref: (bg, d) dst slot view
    del unit_in_ref  # aliased with the output; untouched blocks persist
    unit_ref[...] = bank_ref[...].astype(unit_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bg", "interpret"))
def atu_update(bank, unit, src_idx, dst_idx, *, bg: int = 8,
               interpret: bool):
    """bank: (f, d) source neuron bank (any precision, neurons in rows);
    unit: (k, d) compacted HBM cache unit; src_idx/dst_idx: (m,) int32,
    m % bg == 0, *block-group* aligned: entries are neuron ids grouped so
    src_idx[i*bg:(i+1)*bg] are consecutive slots of a gathered group (the
    manager builds these). ``interpret`` runs the kernel in the Pallas
    interpreter (CPU tests) instead of compiling it for the TPU. Returns
    the updated unit.

    Implementation note: TPU gathers are block-granular, so the manager
    groups changed neurons into ``bg``-row groups; the index arrays here
    carry the *group base* per grid step (entries i*bg).
    """
    f, d = bank.shape
    k, _ = unit.shape
    (m,) = src_idx.shape
    assert m % bg == 0 and m <= k, (m, bg, k)
    n_groups = m // bg

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_groups,),
        in_specs=[
            # gather: block g reads bank[src_idx[g*bg]//bg *bg : +bg, :]
            pl.BlockSpec(
                (bg, d), lambda g, src, dst: (src[g * bg] // bg, 0)),
            pl.BlockSpec(
                (bg, d), lambda g, src, dst: (dst[g * bg] // bg, 0)),
        ],
        out_specs=pl.BlockSpec(
            (bg, d), lambda g, src, dst: (dst[g * bg] // bg, 0)),
    )
    return pl.pallas_call(
        functools.partial(_atu_kernel, bg=bg),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(unit.shape, unit.dtype),
        input_output_aliases={3: 0},   # unit (after 2 prefetch + bank) -> out
        interpret=interpret,
    )(src_idx.astype(jnp.int32), dst_idx.astype(jnp.int32), bank, unit)
