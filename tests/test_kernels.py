"""Pallas kernel validation (interpret mode): shape/dtype sweeps against the
pure-jnp oracles in kernels/ref.py. That the same kernels compile for the
TPU is checked in test_chip_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import quantize_int4, quantize_int8
from repro.kernels import ref as R
from repro.kernels.atu_update import atu_update
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ops import make_compact_banks, mp_glu_ffn
from repro.kernels.qmatmul import qmatmul


@pytest.mark.parametrize("B,K,N,bk,bn", [
    (1, 256, 128, 128, 128),
    (4, 512, 256, 256, 256),
    (8, 256, 512, 128, 256),
    (3, 384, 384, 128, 128),
])
@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_qmatmul_fp_sweep(B, K, N, bk, bn, xdtype, key):
    ks = jax.random.split(key, 2)
    x = jax.random.normal(ks[0], (B, K), jnp.float32).astype(xdtype)
    w = (jax.random.normal(ks[1], (K, N), jnp.float32)
         / np.sqrt(K)).astype(xdtype)
    y = qmatmul(x, w, precision="fp", bk=bk, bn=bn, interpret=True)
    yr = R.qmatmul_ref(x, w, precision="fp")
    tol = 1e-5 if xdtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("B,K,N", [(2, 256, 128), (4, 512, 512)])
@pytest.mark.parametrize("precision", ["int8", "int4"])
def test_qmatmul_quantized_sweep(B, K, N, precision, key):
    ks = jax.random.split(key, 2)
    x = jax.random.normal(ks[0], (B, K), jnp.float32)
    w = jax.random.normal(ks[1], (K, N), jnp.float32) / np.sqrt(K)
    if precision == "int8":
        wq, s = quantize_int8(w, 0)
    else:
        wq, s = quantize_int4(w, 0)
    y = qmatmul(x, wq, s, precision=precision, interpret=True)
    yr = R.qmatmul_ref(x, wq, s, precision=precision)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    # dequantized result approximates the fp matmul within quant noise
    y_fp = np.asarray(x @ w)
    rel = np.linalg.norm(np.asarray(y) - y_fp) / np.linalg.norm(y_fp)
    # int4 quant noise on N(0,1) weights: per-element err ≈ scale/√12 with
    # scale = max|w|/7 ≈ 0.5σ → rel ≈ 0.13–0.15
    assert rel < (0.02 if precision == "int8" else 0.18)


@pytest.mark.parametrize("B,Hkv,G,D,S,bs", [
    (1, 1, 1, 64, 512, 128),
    (2, 2, 4, 64, 1024, 256),
    (2, 4, 5, 32, 512, 512),   # odd G (qwen-style 40/8)
])
def test_flash_decode_sweep(B, Hkv, G, D, S, bs, key):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    lens = jnp.asarray(np.random.default_rng(0).integers(1, S, (B,)))
    o = flash_decode(q, k, v, pos, lens, bs=bs, interpret=True)
    orf = R.flash_decode_ref(q, k, v, pos, lens)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               atol=1e-5, rtol=1e-5)


def test_flash_decode_ring_buffer_positions(key):
    """Ring-buffer slot positions (wrap-around) mask correctly."""
    B, Hkv, G, D, S = 1, 1, 2, 32, 256
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    pos_now = 300                       # wrapped past S=256
    slots = jnp.arange(S)
    slot_pos = pos_now - jnp.mod(pos_now - slots, S)
    slot_pos = jnp.broadcast_to(slot_pos[None], (B, S))
    lens = jnp.array([pos_now])
    o = flash_decode(q, k, v, slot_pos, lens, bs=128, interpret=True)
    orf = R.flash_decode_ref(q, k, v, slot_pos, lens)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), atol=1e-5)


@pytest.mark.parametrize("d,f,k,bg", [(32, 64, 32, 8), (16, 128, 64, 16)])
def test_atu_update_sweep(d, f, k, bg, key):
    bank = jax.random.normal(key, (f, d), jnp.float32)
    unit = jnp.zeros((k, d), jnp.float32)
    rng = np.random.default_rng(0)
    n_groups = 2
    src, dst = [], []
    sgroups = rng.choice(f // bg, n_groups, replace=False)
    dgroups = rng.choice(k // bg, n_groups, replace=False)
    for sg, dg in zip(sgroups, dgroups):
        src.extend(range(sg * bg, sg * bg + bg))
        dst.extend(range(dg * bg, dg * bg + bg))
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    u = atu_update(bank, unit, src, dst, bg=bg, interpret=True)
    ur = R.atu_update_ref(np.asarray(bank), np.asarray(unit),
                          np.asarray(src), np.asarray(dst), bg=bg)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ur))


def test_atu_update_preserves_untouched_slots(key):
    d, f, k, bg = 16, 64, 32, 8
    bank = jax.random.normal(key, (f, d))
    unit = jax.random.normal(jax.random.PRNGKey(7), (k, d))
    src = jnp.arange(bg, dtype=jnp.int32)
    dst = jnp.arange(bg, dtype=jnp.int32) + 8
    u = atu_update(bank, unit, src, dst, bg=bg, interpret=True)
    np.testing.assert_allclose(np.asarray(u[:8]), np.asarray(unit[:8]))
    np.testing.assert_allclose(np.asarray(u[16:]), np.asarray(unit[16:]))
    np.testing.assert_allclose(np.asarray(u[8:16]), np.asarray(bank[:8]))


@pytest.mark.parametrize("act", ["silu", "gelu", "relu"])
def test_mp_glu_ffn_composed(act, key):
    dm, ff = 256, 512
    ks = jax.random.split(key, 4)
    wg = jax.random.normal(ks[0], (dm, ff)) / np.sqrt(dm)
    wu = jax.random.normal(ks[1], (dm, ff)) / np.sqrt(dm)
    wd = jax.random.normal(ks[2], (ff, dm)) / np.sqrt(ff)
    sizes = {"fp16": 128, "int8": 128, "int4": 128}
    idx = jnp.argsort(-jax.random.normal(ks[3], (ff,)))[:384]
    banks = make_compact_banks(wg, wu, wd, sizes, idx)
    x = jax.random.normal(key, (4, dm))
    y = mp_glu_ffn(x, banks, act_name=act, interpret=True)
    yr = R.mp_glu_ffn_ref(x, banks, act_name=act)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    # and it approximates the dense-masked fp FFN within quant noise
    from repro.models.common import activation
    mask = jnp.zeros((ff,), bool).at[idx].set(True)
    h = activation(act)(x @ wg) * (x @ wu)
    y_dense = (jnp.where(mask, h, 0) @ wd)
    rel = float(jnp.linalg.norm(y - y_dense) / jnp.linalg.norm(y_dense))
    assert rel < 0.15


@pytest.mark.parametrize("B,S,Hq,Hkv,D,w,bq,bk", [
    (1, 256, 4, 2, 32, 0, 64, 64),
    (2, 512, 8, 2, 64, 128, 128, 128),   # sliding window
    (1, 256, 5, 1, 32, 0, 64, 128),      # MQA, odd G
    (1, 128, 4, 4, 64, 0, 128, 32),      # MHA, uneven tiles
])
def test_flash_attention_sweep(B, S, Hq, Hkv, D, w, bq, bk, key):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import flash_attention_ref
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    o = flash_attention(q, k, v, window=w, bq=bq, bk=bk,
                        interpret=True)
    orf = flash_attention_ref(q, k, v, window=w)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_matches_model_chunked_attention(key):
    """The Pallas kernel and the model's XLA-level chunked attention are the
    same mathematical function."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models.common import chunked_attention
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ref = chunked_attention(q, k, v, pos, pos, q_chunk=32)
    out = flash_attention(q, k, v, bq=32, bk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
