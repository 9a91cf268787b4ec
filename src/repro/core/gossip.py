"""Gossip averaging: schedules, in-simulation mixing, and TPU-mesh collectives.

Two execution substrates for the same communication pattern:

1. **Simulation** (paper-faithful, n arbitrary): the n agents' iterates are
   stacked on a leading axis, ``S`` of shape ``[n, ...]``; a gossip event
   applies the averaging matrix ``W_e = I - (1/2)(e_i - e_j)(e_i - e_j)^T``
   to the node axis. Schedules (random edges / random maximal matchings) are
   pre-drawn host-side so the whole trajectory folds into one ``lax.scan``.

2. **Mesh collectives** (TPU adaptation, n = mesh axis size): a gossip round
   is a ``jax.lax.ppermute``-and-average across a mesh axis inside
   ``shard_map``. Hypercube rounds (partner = rank XOR 2^r) reach *exact*
   consensus in log2(n) rounds — recursive-halving all-reduce re-derived as
   gossip; ring matchings give the partial, bandwidth-cheap variant. This is
   the knob `sync="gossip-hypercube[k]"` exposed by core/decentralized.py.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph, random_matching


# ----------------------------------------------------------------------------
# Host-side schedule generation
# ----------------------------------------------------------------------------

def draw_edge_schedule(graph: Graph, n_steps: int,
                       rng: np.random.Generator) -> np.ndarray:
    """[T, 2] int32: one uniformly-random edge per iteration (Algorithm 1 l.3)."""
    idx = rng.integers(0, graph.n_edges, size=n_steps)
    return graph.edges[idx].astype(np.int32)


def draw_matching_schedule(graph: Graph, n_rounds: int,
                           rng: np.random.Generator) -> np.ndarray:
    """[T, n] int32 partner vectors: p[t, i] = j if (i, j) matched else i.

    Each round is a random maximal matching — the multi-edge synchronous
    gossip round used by the `gossip_mix` kernel and the mesh trainer.

    Vectorized over all T rounds at once (Luby-style): every round draws a
    random edge priority order; an edge joins the matching iff it holds the
    minimum priority among all still-alive edges at both endpoints, which is
    exactly the matching the sequential greedy builds when it processes
    edges in priority order. Each pass settles every locally-minimal edge in
    every round simultaneously, so the loop runs O(log E) passes of [T, E]
    numpy work instead of the former O(T * E) Python double loop.
    """
    n, m = graph.n_nodes, graph.n_edges
    ei, ej = graph.edges[:, 0], graph.edges[:, 1]
    # unique integer priorities per round == a random edge processing order
    pri = rng.permuted(
        np.broadcast_to(np.arange(m, dtype=np.float64), (n_rounds, m)),
        axis=1)
    alive = np.ones((n_rounds, m), bool)
    used = np.zeros((n_rounds, n), bool)
    partners = np.broadcast_to(np.arange(n, dtype=np.int32),
                               (n_rounds, n)).copy()
    rows = np.arange(n_rounds)[:, None]
    while alive.any():
        p = np.where(alive, pri, np.inf)
        node_min = np.full((n_rounds, n), np.inf)
        np.minimum.at(node_min, (rows, np.broadcast_to(ei, (n_rounds, m))),
                      p)
        np.minimum.at(node_min, (rows, np.broadcast_to(ej, (n_rounds, m))),
                      p)
        sel = alive & (p <= node_min[rows, ei]) & (p <= node_min[rows, ej])
        t_idx, e_idx = np.nonzero(sel)
        partners[t_idx, ei[e_idx]] = ej[e_idx]
        partners[t_idx, ej[e_idx]] = ei[e_idx]
        used[t_idx, ei[e_idx]] = True
        used[t_idx, ej[e_idx]] = True
        alive &= ~(used[rows, ei] | used[rows, ej])
    return partners


def hypercube_partners(n: int) -> np.ndarray:
    """[log2(n), n] partner vectors p[r, i] = i XOR 2^r (exact consensus)."""
    if n & (n - 1):
        raise ValueError(f"hypercube gossip needs power-of-two n, got {n}")
    log2n = n.bit_length() - 1
    ranks = np.arange(n, dtype=np.int32)
    return np.stack([ranks ^ (1 << r) for r in range(log2n)], axis=0)


def ring_matchings(n: int) -> np.ndarray:
    """[2, n] even/odd ring matchings: round 0 pairs (0,1)(2,3)..., round 1
    pairs (1,2)(3,4)...; for odd n the leftover node self-pairs."""
    p_even = np.arange(n, dtype=np.int32)
    p_odd = np.arange(n, dtype=np.int32)
    for i in range(0, n - 1, 2):
        p_even[i], p_even[i + 1] = i + 1, i
    for i in range(1, n - 1, 2):
        p_odd[i], p_odd[i + 1] = i + 1, i
    if n % 2 == 0 and n >= 2:
        # close the ring on the odd round: pair (n-1, 0). For n == 2 the
        # "ring" is the single edge (0, 1), so the odd round repeats it —
        # an identity odd round would silently waste half the round budget
        # that decentralized.rounds_per_axis charges for ring schedules.
        p_odd[n - 1], p_odd[0] = 0, n - 1
    return np.stack([p_even, p_odd], axis=0)


# ----------------------------------------------------------------------------
# Simulation-substrate mixing (node axis is a real array axis)
# ----------------------------------------------------------------------------

def mix_edge(stats: jax.Array, i: jax.Array, j: jax.Array) -> jax.Array:
    """Apply W_(i,j) to the node axis: s_i, s_j <- (s_i + s_j)/2.

    stats: [n, ...]; i, j scalar int32 (may be traced). One gossip event.
    """
    avg = 0.5 * (stats[i] + stats[j])
    return stats.at[i].set(avg).at[j].set(avg)


def partner_mix(rows: jax.Array, other: jax.Array, src: jax.Array,
                active: jax.Array | None = None) -> jax.Array:
    """``where(active, (rows + other[src]) / 2, rows)`` over the node axis.

    rows: [m, ...]; other: [n, ...] with the same trailing shape; src: [m]
    int rows of ``other``; active: [m] bool, or None for every row. The
    partner rows come from a contraction with the one-hot matrix of
    ``src`` at ``Precision.HIGHEST`` (under the ``mix.contract`` scope),
    not from a row gather: TPU lays the node axis of an [n, K, V]
    statistic out on sublanes, so a row gather over it is a sublane
    shuffle inside every tile, which XLA expands into loops of chunked
    gathers, while the MXU contracts over sublanes natively. The
    contraction's work grows as n squared. Timed alone on a v5e, it beat
    the gather from 32 to 1,024 rows (6.1x at 32 rows and 2.4x at 128,
    K=100; 1.4x at 1,024, K=4, ``chip_smoke.py``'s shape) and lost from
    2,048 rows on (0.66x to 0.81x; 0.40x at 4,096).

    The contraction agrees with the gather bit for bit on finite values:
    each output sums one row times 1.0 (exact in HIGHEST's three-way
    bf16 split of an f32) with zeros. Non-finite values spread further
    than through a gather: 0 * NaN and 0 * inf are NaN, so a non-finite
    entry in any row of ``other`` makes that entry non-finite in every
    active output row. Inactive rows keep ``rows`` as they are.

    The fetched rows pass an optimization barrier, so that the average
    and what reads it compile as they do after a gather. Without it XLA
    fuses the E-step's sum of each topic row over V into the
    contraction's output, where it sums in another order: the round's
    last bits, and so a sampler's draws, would differ from the gather's.
    """
    with jax.named_scope("mix.contract"):
        onehot = jax.nn.one_hot(src, other.shape[0], dtype=other.dtype)
        fetched = jax.lax.optimization_barrier(jnp.einsum(
            "ij,j...->i...", onehot, other,
            precision=jax.lax.Precision.HIGHEST))
    mixed = 0.5 * (rows + fetched)
    if active is None:
        return mixed
    keep = active.reshape((-1,) + (1,) * (rows.ndim - 1))
    return jnp.where(keep, mixed, rows)


def mix_matching(stats: jax.Array, partners: jax.Array) -> jax.Array:
    """Apply a whole matching at once: s_i <- (s_i + s_{p[i]})/2.

    partners: [n] int32 with p[p[i]] == i (self-partner = no-op). This is the
    pure-jnp oracle for kernels/gossip_mix. Every row is active in
    :func:`partner_mix`'s sense: a non-finite entry of one node's row
    reaches that entry of every node's.
    """
    return partner_mix(stats, stats, partners)


def mixing_matrix_edge(n: int, i: int, j: int) -> np.ndarray:
    """Dense W_e = I - (1/2)(e_i - e_j)(e_i - e_j)^T (for tests/analysis)."""
    v = np.zeros(n)
    v[i], v[j] = 1.0, -1.0
    return np.eye(n) - 0.5 * np.outer(v, v)


def mixing_matrix_matching(partners: np.ndarray) -> np.ndarray:
    """Dense doubly-stochastic W of a matching partner vector."""
    n = len(partners)
    w = np.zeros((n, n))
    for i, p in enumerate(partners):
        if p == i:
            w[i, i] = 1.0
        else:
            w[i, i] = w[i, p] = 0.5
    return w


def consensus_distance(stats: jax.Array,
                       member: jax.Array | None = None,
                       axis_name: str | None = None,
                       vocab_axis: str | None = None) -> jax.Array:
    """||S - mean(S) 1^T||_F — the left side of paper eq. (3).

    ``member`` ([n] bool, lifecycle layer) restricts both the mean and
    the norm to the member nodes: a node that has not yet cold-joined
    (or has permanently left) carries init-only statistics that say
    nothing about the live network's agreement. ``member=None`` is the
    original unmasked computation, bit-for-bit.

    With ``axis_name`` it runs inside ``shard_map`` on one device's
    contiguous block of node rows (``member`` stays the whole [n] row):
    one all-reduce of the node sum gives the mean, and one of a scalar
    the squared norm. ``vocab_axis`` names a second mesh axis that splits
    the rows' last (V) axis into blocks; the squared norm then sums over
    both axes.
    """
    if axis_name is not None:
        return _consensus_distance_across(stats, member, axis_name,
                                          vocab_axis)
    if member is None:
        mean = stats.mean(axis=0, keepdims=True)
        return jnp.linalg.norm((stats - mean).reshape(stats.shape[0], -1))
    w = member.astype(stats.dtype).reshape(
        (-1,) + (1,) * (stats.ndim - 1))                     # [n, 1, ...]
    count = jnp.maximum(jnp.sum(member), 1).astype(stats.dtype)
    mean = (stats * w).sum(axis=0, keepdims=True) / count
    return jnp.linalg.norm(((stats - mean) * w).reshape(stats.shape[0], -1))


def _consensus_distance_across(stats, member, axis_name, vocab_axis):
    n_local = stats.shape[0]
    if member is None:
        w = None
        count = n_local * jax.lax.axis_size(axis_name)
    else:
        lo = jax.lax.axis_index(axis_name) * n_local
        w = jax.lax.dynamic_slice_in_dim(member, lo, n_local).astype(
            stats.dtype).reshape((-1,) + (1,) * (stats.ndim - 1))
        count = jnp.maximum(jnp.sum(member), 1).astype(stats.dtype)
    total = jax.lax.psum((stats if w is None else stats * w).sum(axis=0),
                         axis_name)
    dev = stats - total / count
    if w is not None:
        dev = dev * w
    axes = axis_name if vocab_axis is None else (axis_name, vocab_axis)
    return jnp.sqrt(jax.lax.psum(jnp.sum(dev * dev), axes))


def consensus_envelope(lambda2: float, rhos: np.ndarray,
                       g_norm: float) -> np.ndarray:
    """Paper eq. (3) upper envelope: sum_r rho_r lam2^{(t-r)/2} ||G||.

    rhos: [T] step sizes. Returns [T] envelope values (host-side diagnostic
    against which the measured consensus distance is plotted).
    """
    t_max = len(rhos)
    env = np.zeros(t_max)
    lam_sqrt = np.sqrt(max(lambda2, 0.0))
    acc = 0.0
    for t in range(t_max):
        acc = acc * lam_sqrt + rhos[t] * g_norm
        env[t] = acc
    return env


# ----------------------------------------------------------------------------
# Mesh-substrate gossip (shard_map collectives over a named axis)
# ----------------------------------------------------------------------------

def _ppermute_pairs(partners: np.ndarray) -> list[tuple[int, int]]:
    """ppermute permutation (src, dst) realizing a partner exchange."""
    return [(int(i), int(p)) for i, p in enumerate(partners) if p != i]


def gossip_round_mesh(tree, partners: np.ndarray, axis_name: str):
    """One matching round over a mesh axis, inside shard_map.

    Every leaf x (sharded over `axis_name`) becomes (x + x_partner)/2, where
    the exchange is a single bidirectional ``lax.ppermute`` — i.e. one
    neighbor hop of ICI traffic, vs. a full all-reduce.
    """
    perm = _ppermute_pairs(partners)
    if not perm:
        return tree

    def mix(x):
        other = jax.lax.ppermute(x, axis_name, perm)
        # self-partnered ranks receive nothing (ppermute fills zeros);
        # for them `other` must act as x so the average is a no-op.
        idx = jax.lax.axis_index(axis_name)
        selfp = jnp.asarray(partners, jnp.int32)[idx] == idx
        other = jnp.where(selfp, x, other)
        return 0.5 * (x + other)

    return jax.tree.map(mix, tree)
