"""Plain reference for the Qwen2.5 configurations as the served path runs
them: Qwen2 decoder layers (RMSNorm, GQA attention with q/k/v bias and
RoPE, residuals) whose FFN is M2Cache's dynamic sparse mixed-precision
GLU. It imports nothing of the program and takes nothing it made: its
weights come from ``bench/weights.py`` and the seed.

What the M2 FFN computes, written out:

- a low-rank predictor scores every neuron: ``(x @ A) @ B``;
- the scores of a *group* of tokens are summed and the top
  ``k = active_ratio * f`` neurons, in order of score, are active; the first
  ``k_fp`` ranks use the float32 bank, the next ``k_i8`` the int8 bank, the
  rest the int4 bank (each dequantized as value * per-neuron scale), and
  inactive neurons contribute nothing;
- ``y = (silu(x Wg) * (x Wu)) Wd`` over the active neurons.

A group is what one forward call of the served path sees: a prompt runs in
blocks of ``block`` tokens from position 0, the last block padded with token
0 (the pads share the block's top-k but no real token attends to them), and
every generated token is fed back alone. Restored prefix blocks and KV moved
between tiers do not appear here: they must leave the answer unchanged.

Departures from the published Qwen2 description: norm gains are stored as
offsets from 1 (the served path's convention; the reference adds the 1), and
RMSNorm's epsilon is the configuration's assumed 1e-6.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

CHUNK = 512          # tokens per attention / FFN chunk; sequences pad to it


def _rms(x, offset, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + offset)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _unpack_int4(p, axis):
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)
    hi = jnp.right_shift(p, 4)
    st = jnp.stack([lo, hi], axis=axis + 1)
    shape = list(p.shape)
    shape[axis] *= 2
    return st.reshape(shape)


def _dequantized(banks):
    """Each FFN matrix at each of its three precisions, as float32."""
    out = {}
    for m, ax in (("wg", 0), ("wu", 0), ("wd", 1)):
        scale = jnp.expand_dims(banks[f"{m}_i8_s"], ax)
        out[m] = (banks[f"{m}_fp"],
                  banks[f"{m}_i8"].astype(jnp.float32) * scale,
                  _unpack_int4(banks[f"{m}_i4"], ax).astype(jnp.float32)
                  * jnp.expand_dims(banks[f"{m}_i4_s"], ax))
    return out


def _tier_codes(scores, groups, n_groups, D):
    """(T, f) int: 1 fp, 2 int8, 3 int4, 0 inactive, from the top-k of each
    group's summed scores."""
    sums = jax.ops.segment_sum(scores, groups, num_segments=n_groups)
    _, idx = jax.lax.top_k(sums, D["k"])                     # (G, k)
    rank = jnp.arange(D["k"])
    tier = jnp.where(rank < D["k_fp"], 1,
                     jnp.where(rank < D["k_fp"] + D["k_i8"], 2, 3))
    codes = jnp.zeros(sums.shape, jnp.int8)
    codes = codes.at[jnp.arange(n_groups)[:, None], idx].set(
        jnp.broadcast_to(tier, idx.shape).astype(jnp.int8))
    return codes[groups]


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(D, lw, x, pos, groups, vis):
    """One decoder layer over a padded sequence. ``vis[i, j]``: query i
    may attend key j."""
    T = x.shape[0]
    d, hq, hkv, hd = D["d"], D["hq"], D["hkv"], D["hd"]
    h = _rms(x, lw["norm1"], D["eps"])
    qkv = h @ lw["wqkv"] + lw["bqkv"]
    q = _rope(qkv[:, :hq * hd].reshape(T, hq, hd), pos, D["theta"])
    k = _rope(qkv[:, hq * hd:(hq + hkv) * hd].reshape(T, hkv, hd), pos,
              D["theta"])
    v = qkv[:, (hq + hkv) * hd:].reshape(T, hkv, hd)
    g = hq // hkv

    def attend(c):
        qc = jax.lax.dynamic_slice_in_dim(q, c * CHUNK, CHUNK)
        mc = jax.lax.dynamic_slice_in_dim(vis, c * CHUNK, CHUNK)
        s = jnp.einsum("qhgd,khd->hgqk", qc.reshape(CHUNK, hkv, g, hd),
                       k) / math.sqrt(hd)
        s = jnp.where(mc[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(CHUNK, hq * hd)

    att = jax.lax.map(attend, jnp.arange(T // CHUNK)).reshape(T, hq * hd)
    x = x + att @ lw["wo"]
    h2 = _rms(x, lw["norm2"], D["eps"])
    scores = (h2 @ lw["ffn"]["pred"]["A"]) @ lw["ffn"]["pred"]["B"]
    codes = _tier_codes(scores, groups, T, D)
    deq = _dequantized(lw["ffn"]["banks"])

    def ffn(c):
        hc = jax.lax.dynamic_slice_in_dim(h2, c * CHUNK, CHUNK)
        cc = jax.lax.dynamic_slice_in_dim(codes, c * CHUNK, CHUNK)

        def pick(mats):
            fp, i8, i4 = (hc @ m for m in mats)
            return jnp.where(cc == 1, fp, jnp.where(
                cc == 2, i8, jnp.where(cc == 3, i4, 0.0)))

        a = jax.nn.silu(pick(deq["wg"])) * pick(deq["wu"])
        return sum(jnp.where(cc == t + 1, a, 0.0) @ deq["wd"][t]
                   for t in range(3))

    y = jax.lax.map(ffn, jnp.arange(T // CHUNK)).reshape(T, d)
    return x + y


@functools.partial(jax.jit, static_argnums=(0,))
def _head(D, ew, x, rows):
    return _rms(x[rows], ew["final_norm"], D["eps"]) @ ew["unembed"].T


@functools.partial(jax.jit, static_argnums=(1, 2))
def _layer_weights(key, D, layer):
    return W.layer_weights(key, D, layer)


def layout(prompt, served, block: int):
    """The padded sequence the reference runs for one request: the prompt
    in ``block``-token groups (last one padded with 0), then every served
    token, each its own group, as the decode steps fed them back.

    Returns (tokens, positions, groups, visibility, logit rows): logit row
    j < n is the position whose logits chose served token j, and row n the
    position after the last served token (the logits the request's last
    decode step left)."""
    plen, n = len(prompt), len(served)
    lp = -(-plen // block) * block
    t_real = lp + n
    T = -(-t_real // CHUNK) * CHUNK
    tok = np.zeros(T, np.int32)
    tok[:plen] = prompt
    tok[lp:t_real] = served
    pos = np.zeros(T, np.int32)
    pos[:lp] = np.arange(lp)
    pos[lp:t_real] = plen + np.arange(n)
    pos[t_real:] = pos[t_real - 1] + 1 + np.arange(T - t_real)
    groups = np.arange(T, dtype=np.int32)
    groups[:lp] = np.arange(lp) // block
    groups[lp:] = lp // block + np.arange(T - lp)
    i = np.arange(T)
    in_p, in_d = i < lp, (i >= lp) & (i < t_real)
    vis = (in_p[:, None] & in_p[None, :] & (i[None, :] <= i[:, None])) \
        | (in_d[:, None] & in_p[None, :] & (i < plen)[None, :]) \
        | (in_d[:, None] & in_d[None, :] & (i[None, :] <= i[:, None]))
    vis |= np.eye(T, dtype=bool)          # pad rows see only themselves
    rows = np.concatenate([[plen - 1], lp + np.arange(n)]).astype(np.int32)
    return tok, pos, groups, vis, rows


def _padded_rows(rows, multiple: int = 64):
    out = np.zeros(-(-len(rows) // multiple) * multiple, np.int32)
    out[:len(rows)] = rows
    return out


def logits(seed: int, D: dict, requests, *, block: int,
           precision: str = "highest"):
    """Reference logits for each (prompt, served tokens) pair at the
    positions that chose the served tokens and after the last one: a list
    of (n + 1, V) float32 numpy arrays. Runs layer by layer over all requests, so only one layer's
    weights are on the device at a time."""
    key = W.base_key(seed)
    Df = W._Frozen(D)
    lays = [layout(np.asarray(p), np.asarray(s), block) for p, s in requests]
    with jax.default_matmul_precision(precision):
        ew = jax.jit(W.embed_weights, static_argnums=1)(key, Df)
        xs = [ew["embed"][jnp.asarray(t)] for t, *_ in lays]
        ew = {k: v for k, v in ew.items() if k != "embed"}
        for layer in range(D["L"]):
            lw = _layer_weights(key, Df, layer)
            xs = [_layer(Df, lw, x, jnp.asarray(p), jnp.asarray(g),
                         jnp.asarray(v))
                  for x, (_, p, g, v, _) in zip(xs, lays)]
            del lw
        return [np.asarray(_head(Df, ew, x, jnp.asarray(
            _padded_rows(rows))))[:len(rows)]
                for x, (*_, rows) in zip(xs, lays)]


def served_gaps(ref_logits, served) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position (0 where it is the best)."""
    s = np.asarray(served)
    ref = np.asarray(ref_logits, np.float64)[:len(s)]
    return ref.max(-1) - ref[np.arange(len(s)), s]


def rms_deviation(logits, ref_logits) -> float:
    """Root mean square over the vocabulary of logits less the
    reference's."""
    d = np.asarray(logits, np.float64) - np.asarray(ref_logits, np.float64)
    return float(np.sqrt(np.mean(d * d)))
