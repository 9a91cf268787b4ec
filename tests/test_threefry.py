"""The threefry replica must equal jax.random BITWISE — the fused and
Pallas evaluators' PRNG contract rests on it. If jax ever changes its
default PRNG implementation these tests fail loudly instead of letting
golden streams drift silently."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import threefry as tf3


def _kd(key):
    return np.asarray(jax.random.key_data(key))


def _pair_kd(pair):
    """(k1, k2) with trailing singleton axes -> [..., 2] key words."""
    return np.concatenate([np.asarray(pair[0]), np.asarray(pair[1])], -1)


def test_key_data_typed_and_raw():
    key = jax.random.key(42)
    np.testing.assert_array_equal(np.asarray(tf3.key_data(key)), _kd(key))
    raw = jax.random.key_data(key)
    np.testing.assert_array_equal(np.asarray(tf3.key_data(raw)), _kd(key))
    np.testing.assert_array_equal(_pair_kd(tf3.key_pair(key)), _kd(key))


@pytest.mark.parametrize("data", [0, 1, 7, 2**31, 2**32 - 1])
def test_fold_in_matches_jax(data):
    key = jax.random.key(3)
    want = _kd(jax.random.fold_in(key, data))
    got = _pair_kd(tf3.fold_in(*tf3.key_pair(key), jnp.uint32(data)))
    np.testing.assert_array_equal(got, want)


def test_fold_in_batched():
    key = jax.random.key(11)
    ids = jnp.arange(37, dtype=jnp.uint32)
    want = _kd(jax.vmap(lambda d: jax.random.fold_in(key, d))(ids))
    k1, k2 = tf3.key_pair(key)
    got = _pair_kd(tf3.fold_in(k1, k2, ids[:, None]))
    np.testing.assert_array_equal(got, want)


def test_split2_matches_jax():
    for seed in (0, 5, 123456):
        key = jax.random.key(seed)
        k0, k1 = jax.random.split(key)
        g0, g1 = tf3.split2(*tf3.key_pair(key))
        np.testing.assert_array_equal(_pair_kd(g0), _kd(k0))
        np.testing.assert_array_equal(_pair_kd(g1), _kd(k1))


@pytest.mark.parametrize("n", [1, 2, 3, 7, 10, 33, 320])
def test_uniform_halves_matches_jax(n):
    """``uniform(key, (n,))`` at even and odd sizes (the old layout paired
    counter halves and padded odd n; the partitionable one must not)."""
    key = jax.random.key(n * 7 + 1)
    want = np.asarray(jax.random.uniform(key, (n,)))
    got = np.asarray(tf3.uniform(*tf3.key_pair(key), n))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("p,l", [(10, 64), (10, 63), (3, 5), (1, 7)])
def test_uniform_column_matches_jax(p, l):
    """Column i of uniform(key, (p, l)) without drawing the rest —
    including odd p*l."""
    key = jax.random.key(p * l)
    full = np.asarray(jax.random.uniform(key, (p, l)))
    k1, k2 = tf3.key_pair(key)
    for i in range(l):
        got = np.asarray(tf3.uniform_column(k1, k2, p, l, jnp.int32(i)))
        np.testing.assert_array_equal(got, full[:, i], err_msg=f"col {i}")


def test_evaluator_stream_derivation_end_to_end():
    """The exact chain the evaluators use: fold_in(key, doc) ->
    fold_in(doc_key, pos) -> split -> uniform draws, all bit-equal,
    batched over documents like the fused evaluator."""
    key = jax.random.key(9)
    p, l = 10, 16
    docs = jnp.asarray([0, 3, 1000], jnp.uint32)
    k1, k2 = tf3.fold_in(*tf3.key_pair(key), docs[:, None])    # [3, 1]
    dks = jax.vmap(lambda d: jax.random.fold_in(key, d))(docs)
    np.testing.assert_array_equal(_pair_kd((k1, k2)), _kd(dks))
    for pos in (0, 1, l - 1):
        rs, dr = tf3.split2(*tf3.fold_in(k1, k2, jnp.uint32(pos)))
        for d, dk in enumerate(dks):
            k_rs, k_dr = jax.random.split(jax.random.fold_in(dk, pos))
            u_rs = np.asarray(jax.random.uniform(k_rs, (p, l)))
            u_dr = np.asarray(jax.random.uniform(k_dr, (p,)))
            np.testing.assert_array_equal(
                np.asarray(tf3.uniform(*dr, p))[d], u_dr)
            for i in (0, pos, l - 1):
                np.testing.assert_array_equal(
                    np.asarray(tf3.uniform_column(*rs, p, l,
                                                  jnp.int32(i)))[d],
                    u_rs[:, i])
