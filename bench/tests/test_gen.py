"""The benchmark's generators: seeded, and shaped as the configurations say."""

import math
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

jax = pytest.importorskip("jax")
from bench import gen  # noqa: E402


def test_key_from_seed_takes_large_seeds():
    a = gen.key_from_seed(2**33 + 5)
    b = gen.key_from_seed(5)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))


def test_same_seed_same_corpus():
    kw = dict(n_nodes=2, docs_per_node=8, doc_len=16, vocab=200, n_topics=5,
              alpha=0.5, mean_len=6.0, sigma=0.5, zipf=1.0,
              concentration=0.1)
    w1, m1 = gen.corpus(gen.key_from_seed(3_000_000_001), **kw)
    w2, m2 = gen.corpus(gen.key_from_seed(3_000_000_001), **kw)
    w3, _ = gen.corpus(gen.key_from_seed(3_000_000_002), **kw)
    assert np.array_equal(w1, w2) and np.array_equal(m1, m2)
    assert not np.array_equal(w1, w3)
    assert int(np.asarray(w1).max()) < 200 and int(np.asarray(w1).min()) >= 0
    assert (np.asarray(m1).sum(-1) >= 2).all()


@pytest.mark.parametrize("mean,sigma,doc_len", [(332.0, 0.6, 1024),
                                                (89.0, 0.5, 256)])
def test_realised_mean_length(mean, sigma, doc_len):
    """Lengths as the corpus makes them: mean within sampling error."""
    n = 20000
    mu = gen.lognormal_mu(mean, sigma, 2, doc_len)
    z = np.asarray(jax.random.normal(gen.key_from_seed(11), (n,)))
    lengths = np.clip(np.round(np.exp(mu + sigma * z)), 2, doc_len)
    se = lengths.std() / math.sqrt(n)
    assert abs(lengths.mean() - mean) < 4 * se


def test_corpus_mean_length_small():
    words, mask = gen.corpus(gen.key_from_seed(7), n_nodes=4,
                             docs_per_node=256, doc_len=256, vocab=500,
                             n_topics=8, alpha=0.5, mean_len=89.0,
                             sigma=0.5, zipf=1.0, concentration=0.1)
    lengths = np.asarray(mask).sum(-1).ravel()
    se = lengths.std() / math.sqrt(lengths.size)
    assert abs(lengths.mean() - 89.0) < 4 * se


def test_matchings_are_matchings():
    rng = np.random.default_rng(1)
    edges = gen.watts_strogatz_edges(32, 4, 0.3, rng)
    assert len(edges) == 64
    es = {tuple(e) for e in edges}
    part = gen.matchings(edges, 32, 50, rng)
    ids = np.arange(32)
    for p in part:
        assert np.array_equal(p[p], ids)
        for i, j in enumerate(p):
            if i != j:
                assert (min(i, j), max(i, j)) in es
        # maximal: no edge with both ends unmatched
        free = p == ids
        assert not any(free[a] and free[b] for a, b in edges)

