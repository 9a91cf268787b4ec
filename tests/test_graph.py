"""Graph/topology tests: spectral properties driving eq. (3)."""

import numpy as np
import pytest
from hyputil import given, settings, st

from repro.core import graph as G


def test_complete_graph_counts():
    g = G.complete_graph(50)
    assert g.n_edges == 1225           # the paper's |E|
    assert g.is_connected()


def test_watts_strogatz_paper_setup():
    g = G.watts_strogatz_graph(50, k=4, p=0.3, seed=0)
    assert g.n_edges == 100            # the paper's 100 edges
    assert g.is_connected()


def test_lambda2_ordering_matches_connectivity():
    """Better-connected graphs contract consensus faster (paper §4)."""
    complete = G.complete_graph(20)
    ws = G.watts_strogatz_graph(20, 4, 0.3, seed=1)
    ring = G.ring_graph(20)
    assert complete.lambda2() < ws.lambda2() < ring.lambda2()


@given(st.integers(3, 12), st.integers(0, 1000))
@settings(max_examples=15, deadline=None)
def test_lambda2_in_unit_interval(n, seed):
    g = G.erdos_renyi_graph(n, 0.6, seed=seed)
    lam2 = g.lambda2()
    assert 0.0 <= lam2 < 1.0 + 1e-9


def test_expected_w_doubly_stochastic():
    g = G.watts_strogatz_graph(16, 4, 0.3, seed=2)
    ew = g.expected_w()
    np.testing.assert_allclose(ew.sum(0), 1.0, atol=1e-12)
    np.testing.assert_allclose(ew.sum(1), 1.0, atol=1e-12)
    np.testing.assert_allclose(ew, ew.T, atol=1e-12)


def test_graph_validation():
    with pytest.raises(ValueError):
        G.Graph(3, np.array([[0, 0]]))          # self loop
    with pytest.raises(ValueError):
        G.Graph(3, np.array([[0, 5]]))          # out of range
    with pytest.raises(ValueError):
        G.Graph(3, np.array([[0, 1], [1, 0]]))  # duplicate


def test_erdos_renyi_retries_an_empty_draw():
    # seed 61's first 4-node draw has no edge at p=0.5
    g = G.erdos_renyi_graph(4, 0.5, seed=61)
    assert g.n_edges > 0 and g.is_connected()


def test_is_connected_large_and_disconnected():
    """BFS reachability at n=500 (the old matrix_power overflowed float64
    here) plus explicit negative cases."""
    g = G.watts_strogatz_graph(500, 4, 0.3, seed=0)
    assert g.is_connected()
    # two disjoint cliques
    clique = [(i, j) for i in range(5) for j in range(i + 1, 5)]
    far = [(i + 5, j + 5) for i, j in clique]
    assert not G.Graph(10, np.array(clique + far, np.int32)).is_connected()
    # isolated vertex
    assert not G.Graph(4, np.array([[0, 1], [1, 2]], np.int32)) \
        .is_connected()
    # degenerate sizes
    assert G.Graph(1, np.zeros((0, 2), np.int32)).is_connected()
    assert not G.Graph(3, np.zeros((0, 2), np.int32)).is_connected()
    # path graph: worst-case diameter for the frontier loop
    path = np.array([(i, i + 1) for i in range(499)], np.int32)
    assert G.Graph(500, path).is_connected()


def test_hypercube_and_grid():
    h = G.hypercube_graph(3)
    assert h.n_nodes == 8 and h.n_edges == 12
    gr = G.grid_graph(3, 4)
    assert gr.n_nodes == 12 and gr.is_connected()


@given(st.integers(0, 500))
@settings(max_examples=10, deadline=None)
def test_random_matching_is_matching(seed):
    g = G.watts_strogatz_graph(20, 4, 0.3, seed=3)
    rng = np.random.default_rng(seed)
    m = G.random_matching(g, rng)
    nodes = m.reshape(-1)
    assert len(nodes) == len(set(nodes.tolist()))    # disjoint
    edge_set = {(int(a), int(b)) for a, b in np.sort(g.edges, 1)}
    for i, j in np.sort(m, 1):
        assert (int(i), int(j)) in edge_set          # real edges
