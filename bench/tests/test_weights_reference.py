"""The benchmark's weights feed the program in its own layout, the
reference's per-layer weights are the same numbers, and on a tiny
configuration the served path's tokens agree with the reference."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W
from bench.arch import qwen2 as Q
from bench.tests import tiny


def test_program_layout_matches_the_program():
    from repro.models import transformer as T
    D = Q.dims(tiny.CONFIG)
    ours = jax.eval_shape(lambda k: W._program_params(k, W._Frozen(D)),
                          W.base_key(0))
    theirs = T.abstract_params(Q.program_config(tiny.CONFIG),
                               dtype=jnp.float32, m2=True)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_layer_weights_equal_the_stacked_ones():
    D = Q.dims(tiny.CONFIG)
    seed = 2 ** 33 + 1
    full = W.program_params(seed, D)
    for layer in range(D["L"]):
        one = R._layer_weights(W.base_key(seed), W._Frozen(D), layer)
        stacked = jax.tree.map(lambda x: x[layer],
                               full["layers"]["pattern"][0])
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_int4_packing_round_trips():
    D = Q.dims(tiny.CONFIG)
    lw = W.layer_weights(W.base_key(5), D, 0)
    b = lw["ffn"]["banks"]
    q, _ = W.quantize(b["wg_fp"], 0, 7.0)
    np.testing.assert_array_equal(np.asarray(R._unpack_int4(b["wg_i4"], 0)),
                                  np.asarray(q))
    q, _ = W.quantize(b["wd_fp"], 1, 7.0)
    np.testing.assert_array_equal(np.asarray(R._unpack_int4(b["wd_i4"], 1)),
                                  np.asarray(q))


def test_seeds_past_32_bits_differ():
    a = np.asarray(jax.random.key_data(W.base_key(2 ** 33)))
    b = np.asarray(jax.random.key_data(W.base_key(0)))
    assert not np.array_equal(a, b)


def test_layout_groups_and_visibility():
    prompt = np.arange(1, 21)               # 20 tokens: blocks of 16 + 4
    served = np.array([7, 8, 9])
    tok, pos, groups, vis, rows = R.layout(prompt, served, 16)
    assert list(rows) == [19, 32, 33, 34]
    assert list(tok[32:35]) == [7, 8, 9]
    assert list(groups[:32]) == [0] * 16 + [1] * 16
    assert len(set(groups[32:35])) == 3 and groups[32] > 1
    assert list(pos[32:35]) == [20, 21, 22]
    assert not vis[32, 20] and vis[32, 19]      # pads unseen by decode
    assert vis[20, 19] and not vis[19, 20]      # causal in the prompt
    assert vis[33, 32] and not vis[32, 33]
