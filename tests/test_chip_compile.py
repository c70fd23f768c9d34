"""Compile rehearsals for one TPU v5e chip, run without the chip.

The TPU compiler is installed with jaxlib and compiles for a described
topology, so these tests refuse what the chip's compiler would refuse (block
shapes off the (8, 128) tiling, operations Mosaic cannot legalise, programs
that do not fit HBM) at no chip time. Nothing runs: a compile that passes
here is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file. Keep every such compile in this one file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.core.mp_ffn import tier_sizes
from repro.models import transformer as T

HBM_BYTES = 16 * 2**30        # one v5e chip
ROWS = 4                      # decode rows / prefill rows, as the server runs
CHUNK = 16                    # prefill block (the engine's kv_block_tokens)
MAX_SEQ = 128                 # the engine's bucket for these requests


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip, so the next compile would warn and recompile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _rows(tree, n):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)


def _served_args(one_chip, step, arch):
    """Abstract arguments of a served jitted step at ``arch``'s widths,
    depth cut to one layer, float32 as the server builds them."""
    import dataclasses
    from repro.core.engine_model import RealModelRunner
    cfg = dataclasses.replace(get_config(arch), num_layers=1)
    runner = RealModelRunner(cfg, None, max_seq=MAX_SEQ)
    params = _on(one_chip, T.abstract_params(cfg, dtype=jnp.float32,
                                             m2=True))
    caches = _on(one_chip, _rows(T.cache_specs(cfg, 1, max_seq=MAX_SEQ,
                                               dtype=jnp.float32), ROWS))
    if step == "decode":
        last = jax.ShapeDtypeStruct((ROWS, cfg.vocab_size), jnp.float32,
                                    sharding=one_chip)
        return runner._decode_batched, (params, caches, last)
    toks = jax.ShapeDtypeStruct((ROWS, CHUNK), jnp.int32, sharding=one_chip)
    n_valid = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    return runner._prefill_block_rows, (params, caches, toks, n_valid)


# the 32B is the widest FFN the benchmark serves and the tightest in HBM
@pytest.mark.parametrize("arch", ["qwen2.5-14b", "qwen2.5-32b"])
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_served_step_fits_one_chip(one_chip, step, arch):
    fn, args = _served_args(one_chip, step, arch)
    mem = fn.lower(*args).compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, (step, arch, used)


def _kernel_case(name):
    """(kernel, args, static kwargs) at the served widths."""
    from repro.kernels.atu_update import atu_update
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_decode import flash_decode
    from repro.kernels.qmatmul import qmatmul
    cfg = get_config("qwen2.5-14b")
    d, f = cfg.d_model, cfg.d_ff
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // hkv
    sds = jax.ShapeDtypeStruct
    if name == "flash_decode":
        kv = sds((ROWS, MAX_SEQ, hkv, hd), jnp.float32)
        return flash_decode, (sds((ROWS, hkv, g, hd), jnp.float32), kv, kv,
                              sds((ROWS, MAX_SEQ), jnp.int32),
                              sds((ROWS,), jnp.int32)), {"bs": MAX_SEQ}
    if name == "flash_attention":
        q = sds((1, MAX_SEQ, cfg.num_heads, hd), jnp.float32)
        kv = sds((1, MAX_SEQ, hkv, hd), jnp.float32)
        return flash_attention, (q, kv, kv), {"bq": MAX_SEQ, "bk": MAX_SEQ}
    if name == "atu_update":
        k = tier_sizes(f, cfg)["k"]
        idx = sds((64,), jnp.int32)
        return atu_update, (sds((f, d), jnp.float32),
                            sds((k, d), jnp.float32), idx, idx), {}
    precision = name.split("_")[1]
    x = sds((ROWS, d), jnp.float32)
    w = {"fp": sds((d, f), jnp.float32), "int8": sds((d, f), jnp.int8),
         "int4": sds((d // 2, f), jnp.int8)}[precision]
    return qmatmul, (x, w, sds((f,), jnp.float32)), {"precision": precision}


@pytest.mark.parametrize("name", ["flash_decode", "flash_attention",
                                  "atu_update", "qmatmul_fp",
                                  "qmatmul_int8", "qmatmul_int4"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args, kw = _kernel_case(name)
    compiled = fn.lower(*_on(one_chip, args), interpret=False,
                        **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
