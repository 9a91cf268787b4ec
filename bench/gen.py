"""The benchmark's own seeded generators: corpora and gossip schedules.

A copy of the LDA generative process (topics ~ Dirichlet with a Zipf word
envelope, theta ~ Dirichlet(alpha), z ~ theta, w ~ beta*[z]) written for
speed: every draw is an inverse-CDF lookup on the device, so a corpus of
tens of millions of tokens is made in seconds. It shares no code with the
program; the program only ever receives the arrays made here.

Everything is a pure function of the seed: the same seed gives the same
corpus and schedule, bit for bit.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any whole number, including seeds beyond 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def lognormal_mu(mean: float, sigma: float, lo: int, hi: int) -> float:
    """mu such that round(exp(N(mu, sigma))) clipped to [lo, hi] has ``mean``.

    The source corpora state the mean document length; clipping at the
    padded length would otherwise pull the realised mean below it.
    """
    def clipped_mean(mu):
        # E[clip(X, lo, hi)] for X ~ LogNormal(mu, sigma), by quadrature
        z = np.linspace(-8.0, 8.0, 20001)
        w = np.exp(-0.5 * z * z)
        x = np.clip(np.exp(mu + sigma * z), lo, hi)
        return float((x * w).sum() / w.sum())

    a, b = math.log(lo) - 1.0, math.log(hi) + 1.0
    for _ in range(80):
        m = 0.5 * (a + b)
        if clipped_mean(m) < mean:
            a = m
        else:
            b = m
    return 0.5 * (a + b)


def topic_matrix(key: jax.Array, k: int, v: int, concentration: float,
                 zipf: float) -> jax.Array:
    """beta* [K, V]: Dirichlet(concentration) rows times a Zipf envelope."""
    g = jnp.maximum(jax.random.gamma(key, concentration, (k, v)), 1e-30)
    env = (jnp.arange(v, dtype=jnp.float32) + 1.0) ** (-zipf)
    beta = g * env
    return beta / beta.sum(axis=1, keepdims=True)


def _search(cdf_rows: jax.Array, rows: jax.Array, u: jax.Array) -> jax.Array:
    """First column c with cdf_rows[rows, c] > u (binary search, any shape)."""
    v = cdf_rows.shape[1]
    lo = jnp.zeros(u.shape, jnp.int32)
    hi = jnp.full(u.shape, v - 1, jnp.int32)
    for _ in range(max(1, (v - 1).bit_length())):
        mid = (lo + hi) // 2
        go_right = cdf_rows[rows, mid] <= u
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return lo


@partial(jax.jit, static_argnames=("n_docs", "doc_len", "alpha", "mu",
                                   "sigma", "chunk"))
def documents(key, beta, *, n_docs, doc_len, alpha, mu, sigma, chunk):
    """(words [n_docs, L], mask [n_docs, L]) drawn from topics ``beta``,
    ``chunk`` documents at a time; lengths round(exp(N(mu, sigma)))
    clipped to [2, L]."""
    k = beta.shape[0]
    cdf = jnp.cumsum(beta, axis=1)
    cdf = cdf / cdf[:, -1:]
    k_len, k_doc = jax.random.split(key)
    raw = jnp.round(jnp.exp(mu + sigma * jax.random.normal(k_len, (n_docs,))))
    lengths = jnp.clip(raw, 2, doc_len).astype(jnp.int32)

    def one_chunk(keys):
        def doc(kd):
            k_th, k_z, k_w = jax.random.split(kd, 3)
            theta = jax.random.dirichlet(k_th, jnp.full((k,), alpha))
            tcdf = jnp.cumsum(theta)
            uz = jax.random.uniform(k_z, (doc_len,)) * tcdf[-1]
            z = jnp.minimum((tcdf[None, :] <= uz[:, None]).sum(-1), k - 1)
            return z, jax.random.uniform(k_w, (doc_len,))
        z, uw = jax.vmap(doc)(keys)
        return _search(cdf, z, uw)

    keys = jax.random.split(k_doc, n_docs).reshape(n_docs // chunk, chunk)
    words = jax.lax.map(one_chunk, keys).reshape(n_docs, doc_len)
    mask = jnp.arange(doc_len)[None, :] < lengths[:, None]
    return jnp.where(mask, words, 0).astype(jnp.int32), mask


def corpus(key: jax.Array, *, n_nodes: int, docs_per_node: int,
           doc_len: int, vocab: int, n_topics: int, alpha: float,
           mean_len: float, sigma: float, zipf: float,
           concentration: float) -> tuple[jax.Array, jax.Array]:
    """Per-node shards (words [n, D, L] int32, mask [n, D, L] bool)."""
    k_beta, k_docs = jax.random.split(key)
    beta = topic_matrix(k_beta, n_topics, vocab, concentration, zipf)
    n_docs = n_nodes * docs_per_node
    chunk = math.gcd(n_docs, max(1, (64 << 20) // (doc_len * 64)))
    words, mask = documents(
        k_docs, beta, n_docs=n_docs, doc_len=doc_len, alpha=float(alpha),
        mu=lognormal_mu(mean_len, sigma, 2, doc_len), sigma=float(sigma),
        chunk=chunk)
    shape = (n_nodes, docs_per_node, doc_len)
    return words.reshape(shape), mask.reshape(shape)


def watts_strogatz_edges(n: int, k: int, p: float,
                         rng: np.random.Generator) -> np.ndarray:
    """Edges [E, 2] of a connected Watts-Strogatz graph (ring of degree k)."""
    for _ in range(100):
        edges = {(i, (i + d) % n) for i in range(n) for d in range(1, k // 2 + 1)}
        edges = {(min(a, b), max(a, b)) for a, b in edges}
        for a, b in sorted(edges):
            if rng.random() < p:
                for _ in range(50):
                    c = int(rng.integers(0, n))
                    cand = (min(a, c), max(a, c))
                    if c != a and cand not in edges:
                        edges.discard((a, b))
                        edges.add(cand)
                        break
        e = np.array(sorted(edges), np.int32)
        adj = [[] for _ in range(n)]
        for a, b in e:
            adj[a].append(b)
            adj[b].append(a)
        seen, todo = {0}, [0]
        while todo:
            for j in adj[todo.pop()]:
                if j not in seen:
                    seen.add(j)
                    todo.append(j)
        if len(seen) == n:
            return e
    raise RuntimeError("no connected Watts-Strogatz graph drawn")


def matchings(edges: np.ndarray, n: int, n_rounds: int,
              rng: np.random.Generator) -> np.ndarray:
    """[T, n] partner vectors of random maximal matchings (greedy, per round)."""
    out = np.tile(np.arange(n, dtype=np.int32), (n_rounds, 1))
    for t in range(n_rounds):
        used = np.zeros(n, bool)
        for e in rng.permutation(len(edges)):
            a, b = edges[e]
            if not used[a] and not used[b]:
                used[a] = used[b] = True
                out[t, a], out[t, b] = b, a
    return out

