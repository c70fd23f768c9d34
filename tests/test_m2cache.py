"""M2Cache numerics: quantization properties (hypothesis), predictor
training, mixed-precision FFN accuracy ordering, Algorithm 1 ratio search."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.base import get_config
from repro.core import mp_ffn, predictor, quantize, ratio_search
from repro.models import transformer as T


# ---------------------------------------------------------------------------
# quantization round-trip properties


@settings(max_examples=25, deadline=None)
@given(d=st.integers(2, 16).map(lambda x: x * 2),
       f=st.integers(1, 12),
       seed=st.integers(0, 2**31 - 1),
       axis=st.integers(0, 1))
def test_int8_roundtrip_bounded(d, f, seed, axis):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((d, f)).astype(np.float32))
    q, s = quantize.quantize_int8(w, axis)
    wr = quantize.dequantize_int8(q, s, axis)
    amax = jnp.max(jnp.abs(w), axis=axis)
    # error per element bounded by scale/2 = amax/254
    bound = (amax / 127.0 / 2.0 + 1e-6)
    err = jnp.max(jnp.abs(wr - w), axis=axis)
    assert bool(jnp.all(err <= bound * 1.01))


@settings(max_examples=25, deadline=None)
@given(d=st.integers(1, 12).map(lambda x: x * 2),
       f=st.integers(1, 12).map(lambda x: x * 2),
       seed=st.integers(0, 2**31 - 1),
       axis=st.integers(0, 1))
def test_int4_pack_unpack_exact(d, f, seed, axis):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((d, f)).astype(np.float32))
    packed, s = quantize.quantize_int4(w, axis)
    # unpack must invert packing exactly (int domain)
    q = quantize.unpack_int4(packed, axis)
    assert q.shape == w.shape
    assert int(jnp.max(q)) <= 7 and int(jnp.min(q)) >= -7
    wr = quantize.dequantize_int4(packed, s, axis)
    amax = jnp.max(jnp.abs(w), axis=axis)
    bound = amax / 7.0 / 2.0 + 1e-6
    err = jnp.max(jnp.abs(wr - w), axis=axis)
    assert bool(jnp.all(err <= bound * 1.01))


def test_int4_precision_worse_than_int8():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))
    e8 = float(jnp.mean(jnp.abs(
        quantize.dequantize_int8(*quantize.quantize_int8(w, 0), 0) - w)))
    e4 = float(jnp.mean(jnp.abs(
        quantize.dequantize_int4(*quantize.quantize_int4(w, 0), 0) - w)))
    assert e4 > e8 > 0


# ---------------------------------------------------------------------------
# predictor


def test_predictor_training_improves_recall(key):
    d, f, r = 32, 128, 16
    ks = jax.random.split(key, 4)
    wg = jax.random.normal(ks[0], (d, f)) / np.sqrt(d)
    wu = jax.random.normal(ks[1], (d, f)) / np.sqrt(d)
    xs = jax.random.normal(ks[2], (256, d))
    A0, B0 = predictor.init_predictor(ks[3], d, f, r)
    k = 32
    rec0 = float(predictor.predictor_recall(A0, B0, xs, wg, wu,
                                            act_name="relu", k=k))
    A, B, loss = predictor.train_predictor(xs, wg, wu, act_name="relu",
                                           A0=A0, B0=B0, steps=300, lr=5e-2)
    rec1 = float(predictor.predictor_recall(A, B, xs, wg, wu,
                                            act_name="relu", k=k))
    assert rec1 > rec0 + 0.1, (rec0, rec1)
    assert rec1 > 0.5


def test_shared_topk_sorted_by_score(key):
    scores = jax.random.normal(key, (2, 3, 64))
    idx = predictor.shared_topk_indices(scores, 16)
    tot = scores.reshape(-1, 64).sum(0)
    vals = tot[idx]
    assert bool(jnp.all(vals[:-1] >= vals[1:]))  # descending


# ---------------------------------------------------------------------------
# mixed-precision FFN: accuracy must be monotone in precision budget


def _mp_err(cfg_ratios, key):
    cfg = dataclasses.replace(
        get_config("qwen2.5-14b", tiny=True),
        m2_ratio_fp16=cfg_ratios[0], m2_ratio_int8=cfg_ratios[1],
        m2_ratio_int4=cfg_ratios[2], m2_active_ratio=0.5)
    d, f = 64, 256
    ks = jax.random.split(key, 5)
    wg = jax.random.normal(ks[0], (d, f)) / np.sqrt(d)
    wu = jax.random.normal(ks[1], (d, f)) / np.sqrt(d)
    wd = jax.random.normal(ks[2], (f, d)) / np.sqrt(f)
    banks = quantize.build_neuron_banks(wg, wu, wd)
    pred = {"A": jax.random.normal(ks[3], (d, 16)),
            "B": jax.random.normal(ks[4], (16, f))}
    x = jax.random.normal(key, (2, 4, d))
    y, _ = mp_ffn.mp_ffn_apply(cfg, banks, pred, x)
    yref = mp_ffn.mp_ffn_reference(cfg, wg, wu, wd, pred, x)
    return float(jnp.linalg.norm(y - yref) / jnp.linalg.norm(yref))


def test_mp_ffn_precision_ordering(key):
    e_fp = _mp_err((1.0, 0.0, 0.0), key)
    e_mix = _mp_err((0.25, 0.25, 0.5), key)
    e_i4 = _mp_err((0.0, 0.0, 1.0), key)
    assert e_fp < 1e-5                      # pure fp16 == masked reference
    assert e_fp < e_mix < e_i4              # monotone in precision


def _gather_form(cfg, banks, pred, x):
    """The per-row gather formulation of the M2 FFN: gather each tier's
    active columns (rows of the down projection), dequantize them,
    concatenate a (d, k) copy per matrix and multiply."""
    from repro.core.predictor import predictor_scores, shared_topk_indices
    from repro.models.common import activation
    d = x.shape[-1]
    f = banks["wg_i8_s"].shape[-1]
    sizes = mp_ffn.tier_sizes(f, cfg)
    k, k16, k8, k4 = sizes["k"], sizes["fp16"], sizes["int8"], sizes["int4"]
    idx = shared_topk_indices(predictor_scores(x, pred["A"], pred["B"]), k)
    i16, i8, i4 = idx[:k16], idx[k16:k16 + k8], idx[k16 + k8:]
    c = x.dtype

    def cols(w, i):
        return jnp.take(w, i, axis=1)

    def rows(w, i):
        return jnp.take(w, i, axis=0)

    wg = jnp.concatenate([
        cols(banks["wg_fp"], i16).astype(c),
        cols(banks["wg_i8"], i8).astype(c) * banks["wg_i8_s"][i8].astype(c),
        quantize.unpack_int4(cols(banks["wg_i4"], i4), 0).astype(c)
        * banks["wg_i4_s"][i4].astype(c)], axis=1)
    wu = jnp.concatenate([
        cols(banks["wu_fp"], i16).astype(c),
        cols(banks["wu_i8"], i8).astype(c) * banks["wu_i8_s"][i8].astype(c),
        quantize.unpack_int4(cols(banks["wu_i4"], i4), 0).astype(c)
        * banks["wu_i4_s"][i4].astype(c)], axis=1)
    wd = jnp.concatenate([
        rows(banks["wd_fp"], i16).astype(c),
        rows(banks["wd_i8"], i8).astype(c)
        * banks["wd_i8_s"][i8].astype(c)[:, None],
        quantize.unpack_int4(rows(banks["wd_i4"], i4), 1).astype(c)
        * banks["wd_i4_s"][i4].astype(c)[:, None]], axis=0)
    act = activation(cfg.ffn_act)
    h = act(jnp.einsum("bsd,dk->bsk", x, wg)) \
        * jnp.einsum("bsd,dk->bsk", x, wu)
    y = jnp.einsum("bsk,kd->bsd", h, wd)
    return y, {"active_idx": idx,
               "bytes_weights": 3 * d * (k16 * 2 + k8 * 1 + k4 * 0.5)}


@pytest.mark.parametrize("ratios", [(1.0, 0.0, 0.0), (0.25, 0.25, 0.5),
                                    (0.0, 0.0, 1.0)],
                         ids=["fp", "mixed", "int4"])
def test_mp_ffn_matches_gather_form(key, ratios):
    """The shared per-bank products with a per-row select compute what the
    per-row column gather computed, to float32 rounding, alone and under
    the batched runner's vmap over rows with different active sets."""
    cfg = dataclasses.replace(
        get_config("qwen2.5-14b", tiny=True), m2_ratio_fp16=ratios[0],
        m2_ratio_int8=ratios[1], m2_ratio_int4=ratios[2],
        m2_active_ratio=0.3)
    d, f = 64, 256
    ks = jax.random.split(key, 7)
    banks = quantize.build_neuron_banks(
        jax.random.normal(ks[0], (d, f)) / np.sqrt(d),
        jax.random.normal(ks[1], (d, f)) / np.sqrt(d),
        jax.random.normal(ks[2], (f, d)) / np.sqrt(f))
    pred = {"A": jax.random.normal(ks[3], (d, 16)),
            "B": jax.random.normal(ks[4], (16, f))}

    def check(got, want):
        (y, info), (y0, info0) = got, want
        tol = 1e-5 * float(jnp.max(jnp.abs(y0)))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                   rtol=1e-5, atol=tol)
        np.testing.assert_array_equal(np.asarray(info["active_idx"]),
                                      np.asarray(info0["active_idx"]))
        np.testing.assert_array_equal(np.asarray(info["bytes_weights"]),
                                      np.asarray(info0["bytes_weights"]))

    x = jax.random.normal(ks[5], (1, 1, d))
    check(mp_ffn.mp_ffn_apply(cfg, banks, pred, x),
          _gather_form(cfg, banks, pred, x))

    xs = jax.random.normal(ks[6], (4, 1, 1, d))

    def rows(fn):
        return jax.jit(jax.vmap(lambda r: fn(cfg, banks, pred, r)))(xs)

    got, want = rows(mp_ffn.mp_ffn_apply), rows(_gather_form)
    assert len({tuple(np.asarray(r)) for r in want[1]["active_idx"]}) == 4
    check(got, want)


def test_tier_sizes_partition():
    cfg = get_config("qwen2.5-14b", tiny=True)
    s = mp_ffn.tier_sizes(cfg.d_ff, cfg)
    assert s["fp16"] + s["int8"] + s["int4"] == s["k"]
    assert s["k"] <= cfg.d_ff


# ---------------------------------------------------------------------------
# Algorithm 1


def test_ratio_search_respects_budget_and_picks_feasible(key):
    cfg = get_config("qwen2.5-14b", tiny=True)
    params = T.init_params(key, cfg, dtype=jnp.float32, m2=True)
    prompts = jax.random.randint(key, (1, 8), 0, cfg.vocab_size)
    res = ratio_search.search(cfg, params, prompts, memory_budget=0.20,
                              gen_len=3)
    assert res.best_ratio is not None
    assert ratio_search.memory_cost(cfg, res.best_ratio) <= 0.20 + 1e-9
    infeasible = [t for t in res.table if not t["feasible"]]
    assert all(np.isinf(t["uq"]) for t in infeasible)
    # all-fp16 active set must be infeasible at this tight budget
    assert any(t["ratio"] == (1.0, 0.0, 0.0) and not t["feasible"]
               for t in res.table)


def test_uq_est_finite(key):
    cfg = get_config("qwen2.5-14b", tiny=True)
    params = T.init_params(key, cfg, dtype=jnp.float32, m2=True)
    prompts = jax.random.randint(key, (2, 6), 0, cfg.vocab_size)
    uq = ratio_search.uq_est(cfg, params, prompts, gen_len=4)
    assert np.isfinite(uq) and uq > 0
