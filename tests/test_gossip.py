"""Gossip mixing: mass conservation, consensus contraction, schedules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyputil import given, settings, st

from repro.core import gossip
from repro.core.graph import complete_graph, watts_strogatz_graph


def _rand_stats(n, seed=0, shape=(3, 7)):
    return jax.random.normal(jax.random.key(seed), (n, *shape))


def test_mix_edge_preserves_mean_and_averages():
    s = _rand_stats(6)
    out = gossip.mix_edge(s, jnp.asarray(1), jnp.asarray(4))
    np.testing.assert_allclose(np.asarray(out.mean(0)),
                               np.asarray(s.mean(0)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(out[4]))
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(s[0]))


@given(st.integers(2, 16), st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_mix_matching_preserves_mean(n, seed):
    rng = np.random.default_rng(seed)
    # random involution
    p = np.arange(n)
    order = rng.permutation(n)
    for a, b in zip(order[::2], order[1::2]):
        if rng.random() < 0.7:
            p[a], p[b] = b, a
    s = _rand_stats(n, seed)
    out = gossip.mix_matching(s, jnp.asarray(p))
    np.testing.assert_allclose(np.asarray(out.mean(0)),
                               np.asarray(s.mean(0)), atol=1e-5)


def _guarded_involution(n, rng):
    """A random matching with self-partners, then the training round's
    liveness guard: a pair with a down endpoint mixes as self-self."""
    p = np.arange(n)
    order = rng.permutation(n)
    for a, b in zip(order[::2], order[1::2]):
        if rng.random() < 0.8:
            p[a], p[b] = b, a
    live = rng.random(n) < 0.85
    return np.where(live & live[p], p, np.arange(n)).astype(np.int32)


@pytest.mark.parametrize("with_active", [False, True],
                         ids=["all-rows", "active"])
@pytest.mark.parametrize("trailing", [(5,), (3, 11), (100, 131)],
                         ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("n", [2, 7, 32, 128, 129, 200])
def test_partner_mix_bitwise_equals_row_gather(n, trailing, with_active):
    """The partner rows come from a one-hot contraction, at node counts
    on both sides of the 128 rows of an MXU pass: it gives the gather's
    bits."""
    rng = np.random.default_rng(n * 131 + len(trailing))
    p = _guarded_involution(n, rng)
    s = jnp.asarray(rng.standard_normal((n, *trailing))
                    * 10.0 ** rng.uniform(-6, 6, (n, *trailing)),
                    jnp.float32)
    active = jnp.asarray(rng.random(n) < 0.7) if with_active else None
    out = gossip.partner_mix(s, s, jnp.asarray(p), active)
    mixed = 0.5 * (s + s[jnp.asarray(p)])
    want = mixed if active is None else jnp.where(
        active.reshape((-1,) + (1,) * len(trailing)), mixed, s)
    assert jnp.array_equal(out, want)
    if active is None:
        assert jnp.array_equal(gossip.mix_matching(s, jnp.asarray(p)), want)


@pytest.mark.parametrize("bad", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("n", [7, 32])
def test_partner_mix_spreads_non_finite_to_every_active_row(n, bad):
    """0 * NaN and 0 * inf are NaN in the one-hot contraction, so one
    non-finite entry reaches that entry of every active row; every other
    entry, and every inactive row, is the gather's result."""
    rng = np.random.default_rng(n)
    p = _guarded_involution(n, rng)
    s = rng.standard_normal((n, 3, 5)).astype(np.float32)
    s[n // 2, 1, 2] = bad
    active = rng.random(n) < 0.6
    active[:2] = True, False
    out = np.asarray(gossip.partner_mix(jnp.asarray(s), jnp.asarray(s),
                                        jnp.asarray(p), jnp.asarray(active)))
    want = np.where(active[:, None, None], 0.5 * (s + s[p]), s)
    hit = np.zeros((3, 5), bool)
    hit[1, 2] = True
    assert not np.isfinite(out[active][:, hit]).any()
    np.testing.assert_array_equal(out[active][:, ~hit], want[active][:, ~hit])
    np.testing.assert_array_equal(out[~active], s[~active])


def test_hypercube_rounds_reach_exact_consensus():
    n = 8
    s = _rand_stats(n, 3)
    for r in gossip.hypercube_partners(n):
        s = gossip.mix_matching(s, jnp.asarray(r))
    target = np.asarray(_rand_stats(n, 3).mean(0))
    np.testing.assert_allclose(np.asarray(s[0]), target, atol=1e-5)
    for i in range(1, n):
        np.testing.assert_allclose(np.asarray(s[i]), np.asarray(s[0]),
                                   atol=1e-6)


def test_ring_matchings_contract():
    n = 8
    s = _rand_stats(n, 4)
    d0 = float(gossip.consensus_distance(s))
    rounds = gossip.ring_matchings(n)
    for k in range(6):
        s = gossip.mix_matching(s, jnp.asarray(rounds[k % 2]))
    assert float(gossip.consensus_distance(s)) < 0.5 * d0


def test_consensus_contraction_rate_matches_lambda2():
    """E[consensus^2] contracts at least as fast as lambda2 per uniform
    random edge activation (Boyd et al. 2006)."""
    g = complete_graph(10)
    lam2 = g.lambda2()
    rng = np.random.default_rng(0)
    trials = []
    for t in range(30):
        s = _rand_stats(10, seed=t, shape=(4,))
        d0 = float(gossip.consensus_distance(s)) ** 2
        e = g.edges[rng.integers(0, g.n_edges)]
        s2 = gossip.mix_edge(s, jnp.asarray(e[0]), jnp.asarray(e[1]))
        trials.append(float(gossip.consensus_distance(s2)) ** 2 / d0)
    assert np.mean(trials) <= lam2 + 0.05


def test_mixing_matrix_properties():
    w = gossip.mixing_matrix_edge(5, 1, 3)
    np.testing.assert_allclose(w.sum(0), 1.0)
    np.testing.assert_allclose(w @ w, w, atol=1e-12)   # projection
    p = np.array([1, 0, 3, 2, 4])
    wm = gossip.mixing_matrix_matching(p)
    np.testing.assert_allclose(wm.sum(0), 1.0)
    np.testing.assert_allclose(wm, wm.T)


def test_schedules_shapes():
    g = watts_strogatz_graph(12, 4, 0.3, seed=0)
    rng = np.random.default_rng(0)
    edges = gossip.draw_edge_schedule(g, 50, rng)
    assert edges.shape == (50, 2)
    m = gossip.draw_matching_schedule(g, 5, rng)
    assert m.shape == (5, 12)
    for row in m:
        np.testing.assert_array_equal(row[row], np.arange(12))  # involution


def test_matching_schedule_deterministic_valid_maximal():
    g = watts_strogatz_graph(20, 4, 0.3, seed=3)
    m1 = gossip.draw_matching_schedule(g, 40, np.random.default_rng(7))
    m2 = gossip.draw_matching_schedule(g, 40, np.random.default_rng(7))
    np.testing.assert_array_equal(m1, m2)           # same seed, same schedule
    m3 = gossip.draw_matching_schedule(g, 40, np.random.default_rng(8))
    assert (m1 != m3).any()                         # different seed differs
    edge_set = {(int(a), int(b)) for a, b in g.edges}
    edge_set |= {(b, a) for a, b in edge_set}
    ident = np.arange(g.n_nodes)
    for row in m1:
        np.testing.assert_array_equal(row[row], ident)      # involution
        for i, p in enumerate(row):
            if p != i:
                assert (i, int(p)) in edge_set              # real edges only
        unmatched = row == ident
        for a, b in g.edges:                                # maximality
            assert not (unmatched[a] and unmatched[b])


def test_envelope_monotone_in_lambda2():
    rhos = 1.0 / np.arange(1, 101) ** 0.6
    e_fast = gossip.consensus_envelope(0.2, rhos, 1.0)
    e_slow = gossip.consensus_envelope(0.9, rhos, 1.0)
    assert e_fast[-1] < e_slow[-1]
