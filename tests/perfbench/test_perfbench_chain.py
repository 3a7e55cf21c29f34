"""The weight-sync traffic: the version chain and its ping-pong order."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import chain

SHAPES = {"embed": jax.ShapeDtypeStruct((64, 48), jnp.bfloat16),
          "blocks": ({"w": jax.ShapeDtypeStruct((2, 48, 96), jnp.bfloat16),
                      "norm1": jax.ShapeDtypeStruct((2, 48), jnp.bfloat16)},)}
MIX = dict(versions=3, lr=1e-6, beta1=0.9, beta2=0.999, eps=1e-8,
           weight_decay=0.01, init_std=0.02)


def _bits(tree):
    return [np.asarray(jax.lax.bitcast_convert_type(l, jnp.uint16))
            for l in jax.tree_util.tree_leaves(tree)]


@pytest.fixture(scope="module")
def versions():
    return chain.make_chain(SHAPES, chain.seed_key(2**31 + 17), **MIX)


def test_same_seed_same_chain(versions):
    again = chain.make_chain(SHAPES, chain.seed_key(2**31 + 17), **MIX)
    other = chain.make_chain(SHAPES, chain.seed_key(2**31 + 18), **MIX)
    for v, w in zip(versions, again):
        assert all(np.array_equal(a, b) for a, b in zip(_bits(v), _bits(w)))
    assert not np.array_equal(_bits(versions[0]["embed"]),
                              _bits(other[0]["embed"]))


def test_seeds_past_32_bits_differ():
    keys = [chain.seed_key(s) for s in (5, 2**32 + 5, 2**33 + 5)]
    assert len({tuple(np.asarray(jax.random.key_data(k))) for k in keys}) \
        == 3


def test_ping_pong_pairs_are_one_step_deltas(versions):
    order = list(itertools.islice(chain.ping_pong(len(versions)), 12))
    assert order[:6] == [0, 1, 2, 1, 0, 1]
    bits = [_bits(v) for v in versions]
    for a, b in zip(order, order[1:]):
        assert abs(a - b) == 1
        lo = min(a, b)
        for x, y, p, q in zip(bits[a], bits[b], bits[lo], bits[lo + 1]):
            assert np.array_equal(x ^ y, p ^ q)


def test_rl_steps_change_a_few_percent(versions):
    v0, v1 = _bits(versions[0]), _bits(versions[1])
    changed = sum(int(np.sum(a != b)) for a, b in zip(v0, v1))
    total = sum(a.size for a in v0)
    assert 0 < changed / total < 0.1
    norms = versions[1]["blocks"][0]["norm1"]
    assert np.all(np.asarray(norms, np.float32) == 1.0)
