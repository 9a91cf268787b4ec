"""Unified gossip communication layer: one schedule object, three backends.

The paper's core move — replace global aggregation of the [K, V] sufficient
statistic with pairwise gossip averaging — used to be implemented three
separate times in this repo (single-edge jnp mixing inside ``run_deleda``'s
scan, an all_gather-then-select in the mesh launcher, and the scalar-prefetch
Pallas kernel that nothing called). This module is the single abstraction
they all now share:

* :class:`GossipSchedule` — a pre-drawn sequence of gossip events, either
  single activated edges (the paper's asynchronous Algorithm 1) or maximal
  matchings (the synchronous multi-edge rounds every SPMD substrate wants).
  Drawn host-side with numpy so a whole trajectory stays reproducible and
  foldable into one ``lax.scan``.

* :class:`Communicator` — the protocol ``mix_matching(stats, partners)`` /
  ``mix_edge(stats, i, j)`` with three interchangeable backends:

  - :class:`DenseSimComm`   pure-jnp oracle (node axis is a real array axis)
  - :class:`PallasSimComm`  the kernels/gossip_mix scalar-prefetch kernel
  - :class:`MeshComm`       ppermute pair exchanges over a device mesh axis;
    documents physically never leave their device (the privacy placement),
    and one matching round moves one local statistics block per device —
    O(K*V) bytes, not the O(n*K*V) of the old all_gather hack.

Statistics enter the consensus linearly (exactly the property exploited by
Campbell & How's approximate decentralized Bayes and by Cyffers & Bellet's
privacy amplification), so all three backends compute the *same* averaging
map and are asserted equivalent in tests/test_comm.py.

**Vocab sharding (the Scale layer).** Gossip averaging is row-linear in
the statistic, so splitting the vocab axis into S blocks splits one
matching round into S *independent* per-shard rounds that may live on
different devices or mix as separate blocks. Every backend accepts
vocab-sharded statistics ``[n, K, S, V/S]`` next to the dense
``[n, K, V]``: the sim backends treat the shard axis as layout (dense is
shard-oblivious; the Pallas kernel streams the flattened contiguous
``[n, K, S*V/S]`` view, identical floats), and :class:`MeshComm` built on
a 2-D node x vocab device grid (``vocab_axis=...``) routes each matching
round as per-shard one-hop ppermutes over the NODE axis — every vocab
shard of a matched pair exchanges its own [K, V/S] block in parallel, so
the per-link payload drops by S while total wire bytes stay put
(``bytes_per_round`` accounts per-shard payloads).
"""

from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.core import gossip
from repro.core.graph import Graph

__all__ = [
    "GossipSchedule", "Communicator", "DenseSimComm", "PallasSimComm",
    "MeshComm", "get_communicator", "make_grid_mesh", "mesh_round",
    "SIM_BACKENDS", "device_passes", "mix_matching_sharded",
]

# One gossip round over a mesh axis, usable *inside* shard_map (this is the
# primitive sync_tree_mesh's hypercube/ring wrappers are built on).
mesh_round = gossip.gossip_round_mesh

EDGE = "edge"
MATCHING = "matching"


# ----------------------------------------------------------------------------
# Schedules
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GossipSchedule:
    """A pre-drawn gossip trajectory as one first-class object.

    ``kind == "edge"``:     data is [T, 2] int32 activated edges.
    ``kind == "matching"``: data is [T, n] int32 partner vectors
                            (involutions: p[p[i]] == i, self-partner = idle).

    ``segments`` is the optional segment axis for time-varying topologies
    (core/scenario.py): [T] int32 ids recording which
    :class:`~repro.core.scenario.GraphSequence` segment each round was
    drawn from. Pure metadata — the consumers scan ``data`` unchanged, so a
    time-varying schedule compiles exactly once, like a static one.
    """

    kind: str
    data: np.ndarray
    n_nodes: int
    segments: np.ndarray | None = None

    def __post_init__(self):
        d = np.asarray(self.data, np.int32)
        if self.kind == EDGE:
            if d.ndim != 2 or d.shape[1] != 2:
                raise ValueError(f"edge schedule must be [T, 2], {d.shape}")
        elif self.kind == MATCHING:
            if d.ndim != 2 or d.shape[1] != self.n_nodes:
                raise ValueError(
                    f"matching schedule must be [T, {self.n_nodes}], "
                    f"got {d.shape}")
            if not (d[np.arange(len(d))[:, None], d]
                    == np.arange(self.n_nodes)).all():
                raise ValueError("matching rows must be involutions")
        else:
            raise ValueError(f"kind must be edge|matching, {self.kind!r}")
        if len(d) and (d.min() < 0 or d.max() >= self.n_nodes):
            raise ValueError("schedule references node out of range")
        object.__setattr__(self, "data", d)
        if self.segments is not None:
            seg = np.asarray(self.segments, np.int32)
            if seg.shape != (len(d),):
                raise ValueError(f"segments must be [T={len(d)}], "
                                 f"got {seg.shape}")
            object.__setattr__(self, "segments", seg)

    @property
    def n_rounds(self) -> int:
        return len(self.data)

    @property
    def n_segments(self) -> int:
        return 1 if self.segments is None else int(self.segments.max()) + 1

    # -- constructors --------------------------------------------------------

    @staticmethod
    def draw_edges(graph: Graph, n_rounds: int,
                   rng: np.random.Generator) -> "GossipSchedule":
        """One uniformly-random activated edge per round (Algorithm 1)."""
        return GossipSchedule(
            EDGE, gossip.draw_edge_schedule(graph, n_rounds, rng),
            graph.n_nodes)

    @staticmethod
    def draw_matchings(graph: Graph, n_rounds: int,
                       rng: np.random.Generator) -> "GossipSchedule":
        """One random maximal matching per round (synchronous rounds)."""
        return GossipSchedule(
            MATCHING, gossip.draw_matching_schedule(graph, n_rounds, rng),
            graph.n_nodes)

    @staticmethod
    def hypercube(n: int) -> "GossipSchedule":
        """log2(n) XOR-partner rounds — exact consensus when run in full."""
        return GossipSchedule(MATCHING, gossip.hypercube_partners(n), n)

    @staticmethod
    def ring(n: int, n_rounds: int = 2) -> "GossipSchedule":
        """Alternating even/odd ring matchings, tiled to n_rounds."""
        base = gossip.ring_matchings(n)
        idx = np.arange(n_rounds) % len(base)
        return GossipSchedule(MATCHING, base[idx], n)

    # -- conversions ---------------------------------------------------------

    def as_matchings(self) -> "GossipSchedule":
        """View an edge schedule as one-pair-per-round matchings.

        This is the bridge between the paper's asynchronous single-edge
        process and the synchronous multi-edge substrates: a round that
        matches exactly the activated pair applies the identical averaging
        matrix W_e, so a matching backend replays an edge schedule exactly.
        """
        if self.kind == MATCHING:
            return self
        t = self.n_rounds
        p = np.broadcast_to(np.arange(self.n_nodes, dtype=np.int32),
                            (t, self.n_nodes)).copy()
        rows = np.arange(t)
        p[rows, self.data[:, 0]] = self.data[:, 1]
        p[rows, self.data[:, 1]] = self.data[:, 0]
        return GossipSchedule(MATCHING, p, self.n_nodes,
                              segments=self.segments)

    def partners(self) -> np.ndarray:
        """[T, n] partner matrix (converting edges if necessary)."""
        return self.as_matchings().data


# ----------------------------------------------------------------------------
# Communicator protocol + simulation backends
# ----------------------------------------------------------------------------

@runtime_checkable
class Communicator(Protocol):
    """Applies gossip averaging rounds to node-stacked statistics [n, ...]."""

    name: str

    def mix_matching(self, stats: jax.Array, partners) -> jax.Array:
        """s_i <- (s_i + s_{p[i]})/2 for a whole matching at once."""
        ...

    def mix_edge(self, stats: jax.Array, i, j) -> jax.Array:
        """s_i, s_j <- (s_i + s_j)/2 for one activated edge."""
        ...

    def bytes_per_round(self, stats_shape, itemsize: int,
                        partners: np.ndarray) -> int:
        """Total bytes on the wire for one matching round (cost model)."""
        ...


def _pair_payload_bytes(stats_shape, itemsize: int) -> int:
    return int(np.prod(stats_shape[1:])) * itemsize


def _n_matched(partners: np.ndarray) -> int:
    partners = np.asarray(partners)
    return int((partners != np.arange(len(partners))).sum())


class DenseSimComm:
    """Pure-jnp oracle: the node axis is a real array axis on one device."""

    name = "dense"

    def mix_matching(self, stats, partners):
        return gossip.mix_matching(stats, jnp.asarray(partners,
                                                      jnp.int32))

    def mix_edge(self, stats, i, j):
        return gossip.mix_edge(stats, i, j)

    def bytes_per_round(self, stats_shape, itemsize, partners):
        # a physical deployment sends each matched node's block both ways
        return _n_matched(partners) * _pair_payload_bytes(stats_shape,
                                                          itemsize)


class PallasSimComm:
    """Routes mixing through the kernels/gossip_mix scalar-prefetch kernel.

    The kernel streams [1, K, V_blk] tiles of [n, K, V]-shaped statistics.
    Vocab-sharded [n, K, S, V/S] statistics are accepted too: the shard
    axis is contiguous layout, so the kernel streams the flattened
    [n, K, S*V/S] view — identical floats, and the V-block tiling already
    never crosses what a shard boundary would be when V/S divides the
    block. ``interpret=None`` auto-detects: compiled on TPU, interpreter
    elsewhere — see kernels/gossip_mix/ops.py.
    """

    name = "pallas"

    def __init__(self, block_v: int = 512, interpret: bool | None = None):
        self.block_v = block_v
        self.interpret = interpret

    def mix_matching(self, stats, partners):
        from repro.kernels.gossip_mix import ops as gossip_mix_ops
        shape = stats.shape
        if stats.ndim == 4:                       # vocab-sharded layout
            stats = stats.reshape(shape[0], shape[1], -1)
        elif stats.ndim != 3:
            raise ValueError(f"pallas mixing wants [n, K, V] or vocab-"
                             f"sharded [n, K, S, V/S] stats, got {shape}")
        out = gossip_mix_ops.mix_matching(
            stats, jnp.asarray(partners, jnp.int32),
            block_v=self.block_v, interpret=self.interpret)
        return out.reshape(shape)

    def mix_edge(self, stats, i, j):
        n = stats.shape[0]
        p = jnp.arange(n, dtype=jnp.int32)
        p = p.at[i].set(jnp.asarray(j, jnp.int32))
        p = p.at[j].set(jnp.asarray(i, jnp.int32))
        return self.mix_matching(stats, p)

    def bytes_per_round(self, stats_shape, itemsize, partners):
        return _n_matched(partners) * _pair_payload_bytes(stats_shape,
                                                          itemsize)


# ----------------------------------------------------------------------------
# Mesh backend: ppermute pair exchanges over a named axis
# ----------------------------------------------------------------------------

def make_grid_mesh(n_node_devices: int, n_vocab_devices: int,
                   axis_names: tuple[str, str] = ("data", "vocab")):
    """A 2-D node x vocab device grid for vocab-sharded MeshComm gossip."""
    return jax.make_mesh((n_node_devices, n_vocab_devices), axis_names,
                         axis_types=(AxisType.Auto,) * 2)


def _route_matching(partners: np.ndarray, n_dev: int):
    """Decompose one matching into intra-device mixing + ppermute passes.

    Nodes are block-contiguous over the axis: device d owns rows
    [d*n_local, (d+1)*n_local). Cross-device pairs are greedily colored into
    *device-level matchings* ("passes"); each pass is one bidirectional
    ppermute of the full local block plus a per-node row fetch from the
    received block (:func:`gossip.partner_mix`). With one node per device
    every matching is a single pass — one [K, V] block per device per
    round.

    Returns ((intra_src, intra_active), [(perm, remote_src, active), ...])
    where intra_src/remote_src are [n] local row indices and perm is
    the static (src, dst) device permutation of the pass.
    """
    partners = np.asarray(partners)
    n = len(partners)
    if n % n_dev:
        raise ValueError(f"n={n} not divisible by n_dev={n_dev}")
    n_local = n // n_dev

    intra_src = (np.arange(n, dtype=np.int32) % n_local)
    intra_active = np.zeros(n, bool)
    cross: list[tuple[int, int]] = []
    for i in range(n):
        j = int(partners[i])
        if j <= i:
            continue
        if i // n_local == j // n_local:
            intra_src[i] = j % n_local
            intra_src[j] = i % n_local
            intra_active[i] = intra_active[j] = True
        else:
            cross.append((i, j))

    passes = []      # [{devmap: {a: b}, nodes: [(i, j)]}]
    for i, j in cross:
        a, b = i // n_local, j // n_local
        for ps in passes:
            pa, pb = ps["devmap"].get(a), ps["devmap"].get(b)
            if (pa is None and pb is None) or (pa == b and pb == a):
                ps["devmap"][a] = b
                ps["devmap"][b] = a
                ps["nodes"].append((i, j))
                break
        else:
            passes.append({"devmap": {a: b, b: a}, "nodes": [(i, j)]})

    routed = []
    for ps in passes:
        perm = tuple(sorted(ps["devmap"].items()))
        remote_src = (np.arange(n, dtype=np.int32) % n_local)
        active = np.zeros(n, bool)
        for i, j in ps["nodes"]:
            remote_src[i] = j % n_local
            remote_src[j] = i % n_local
            active[i] = active[j] = True
        routed.append((perm, remote_src, active))
    return (intra_src, intra_active), routed


class MeshComm:
    """Gossip over a device mesh axis via pairwise ``ppermute`` exchanges.

    Host-level interface over globally-shaped [n, ...] arrays sharded on the
    leading (node) axis: ``mix_matching`` routes the matching as intra-device
    row mixes plus one-hop ppermute passes (see :func:`_route_matching`).
    The routing is host-static (schedules are pre-drawn), so each distinct
    device-permutation compiles once and is cached; the per-node row
    indices stay traced, so two rounds sharing a device permutation share a
    compilation.

    ``vocab_axis`` names the second mesh axis of a 2-D node x vocab device
    grid (:func:`make_grid_mesh`): statistics are then ALSO sharded over
    the vocab axis — the last axis of dense [n, K, V] stats, the shard
    axis of vocab-sharded [n, K, S, V/S] stats — and each ppermute pass
    exchanges every vocab shard's own block over the node axis in
    parallel. Gossip is row-linear, so the per-shard rounds compose to
    exactly the dense averaging map.

    For code already *inside* shard_map, use :func:`mesh_round` directly.
    """

    name = "mesh"

    def __init__(self, mesh=None, axis_name: str = "data",
                 vocab_axis: str | None = None):
        if mesh is None:
            n = len(jax.devices())
            mesh = jax.make_mesh((n,), (axis_name,),
                                 axis_types=(AxisType.Auto,))
        self.mesh = mesh
        self.axis_name = axis_name
        self.vocab_axis = vocab_axis
        self.n_devices = int(dict(mesh.shape)[axis_name])
        self.n_vocab_shards = (1 if vocab_axis is None
                               else int(dict(mesh.shape)[vocab_axis]))
        self._pass_fns: dict[tuple, object] = {}
        self._local_fns: dict[int, object] = {}

    # -- jitted building blocks ---------------------------------------------

    def _node_spec(self):
        return P(self.axis_name)

    def _stats_spec(self, ndim: int):
        """Node axis leading; vocab axis (if any) on V for dense [n, K, V]
        stats and on S for vocab-sharded [n, K, S, V/S] stats."""
        spec = [self.axis_name] + [None] * (ndim - 1)
        if self.vocab_axis is not None:
            if ndim < 3:
                raise ValueError(
                    f"vocab-sharded MeshComm needs [n, K, V] or "
                    f"[n, K, S, V/S] stats, got ndim={ndim}")
            spec[2 if ndim >= 4 else ndim - 1] = self.vocab_axis
        return P(*spec)

    def _get_local_fn(self, ndim: int):
        fn = self._local_fns.get(ndim)
        if fn is None:
            node = self._node_spec()

            def local_mix(stats, src, active):
                with jax.named_scope("deleda.mix"):
                    return gossip.partner_mix(stats, stats, src, active)

            stats_spec = self._stats_spec(ndim)
            fn = jax.jit(jax.shard_map(
                local_mix, mesh=self.mesh,
                in_specs=(stats_spec, node, node), out_specs=stats_spec))
            self._local_fns[ndim] = fn
        return fn

    def _get_pass_fn(self, perm: tuple, ndim: int = 3):
        fn = self._pass_fns.get((perm, ndim))
        if fn is None:
            node = self._node_spec()
            axis = self.axis_name
            perm_list = list(perm)

            def exchange(stats, src, active):
                with jax.named_scope("deleda.mix"):
                    other = jax.lax.ppermute(stats, axis, perm_list)
                    return gossip.partner_mix(stats, other, src, active)

            stats_spec = self._stats_spec(ndim)
            fn = jax.jit(jax.shard_map(
                exchange, mesh=self.mesh,
                in_specs=(stats_spec, node, node), out_specs=stats_spec))
            self._pass_fns[(perm, ndim)] = fn
        return fn

    # -- Communicator interface ---------------------------------------------

    def mix_matching(self, stats, partners):
        partners = np.asarray(partners, np.int32)
        (intra_src, intra_active), passes = _route_matching(
            partners, self.n_devices)
        if intra_active.any():
            stats = self._get_local_fn(stats.ndim)(
                stats, jnp.asarray(intra_src), jnp.asarray(intra_active))
        for perm, remote_src, active in passes:
            stats = self._get_pass_fn(perm, stats.ndim)(
                stats, jnp.asarray(remote_src), jnp.asarray(active))
        return stats

    def mix_edge(self, stats, i, j):
        # host-level routing: i, j must be concrete (schedules are pre-drawn)
        n = stats.shape[0]
        p = np.arange(n, dtype=np.int32)
        p[int(i)], p[int(j)] = int(j), int(i)
        return self.mix_matching(stats, p)

    def bytes_per_round(self, stats_shape, itemsize, partners):
        # each ppermute pass moves one PER-SHARD local block per involved
        # (node-axis) device — all n_vocab_shards shards of a matched pair
        # exchange in parallel, so the per-link payload is 1/S of the dense
        # block while the round total is unchanged
        _, passes = _route_matching(np.asarray(partners), self.n_devices)
        n_local = stats_shape[0] // self.n_devices
        shard_block = (n_local * _pair_payload_bytes(stats_shape, itemsize)
                       // self.n_vocab_shards)
        return sum(len(perm) * self.n_vocab_shards * shard_block
                   for perm, _, _ in passes)


def device_passes(n_dev: int) -> list[tuple[tuple[int, int], ...]]:
    """The static 1-factorization of ``n_dev`` devices into ppermute passes.

    Each pass is a (src, dst) device permutation made of disjoint pairs,
    and every pair of devices meets in exactly one pass: n_dev - 1 passes
    for even n_dev, n_dev for odd (one device sits each pass out). A
    power of two pairs device a with a XOR k in pass k, so four devices
    give {0<->1, 2<->3}, {0<->2, 1<->3}, {0<->3, 1<->2}; other counts use
    the circle method.
    """
    if n_dev & (n_dev - 1) == 0:
        return [tuple((a, a ^ k) for a in range(n_dev))
                for k in range(1, n_dev)]
    m = n_dev + n_dev % 2          # odd: a ghost device, whose peer rests
    passes = []
    for r in range(m - 1):
        pairs = [(m - 1, r)] + [((r + i) % (m - 1), (r - i) % (m - 1))
                                for i in range(1, m // 2)]
        passes.append(tuple(sorted(
            pr for a, b in pairs if max(a, b) < n_dev
            for pr in ((a, b), (b, a)))))
    return passes


def mix_matching_sharded(stats: jax.Array, partners: jax.Array,
                         axis_name: str, n_dev: int) -> jax.Array:
    """One matching round inside ``shard_map``, its routing traced.

    ``stats`` is this device's contiguous block of n/n_dev node rows;
    ``partners`` the whole traced [n] partner vector (replicated). Pairs
    within the block mix through :func:`gossip.partner_mix`; each pass of
    :func:`device_passes` ships the block to the pass's peer device with
    one ``ppermute`` (under the ``mix.permute`` scope), and the rows
    matched to that peer fetch their partner's row from what arrived the
    same way.
    Every node lies in at most one of these steps, so applying them in
    turn reads only rows no earlier step changed: the result is
    ``(s_i + s_p(i)) / 2`` for every row, as :func:`gossip.mix_matching`
    computes it on one device. A non-finite entry of a block reaches that
    entry of every row a step mixes from the block.
    """
    n_local = stats.shape[0]
    dev = jax.lax.axis_index(axis_name)
    lo = dev * n_local
    p = jax.lax.dynamic_slice_in_dim(partners, lo, n_local)
    ids = lo + jnp.arange(n_local, dtype=p.dtype)
    p_dev, src = p // n_local, p % n_local
    stats = gossip.partner_mix(stats, stats, src, (p_dev == dev) & (p != ids))
    for perm in device_passes(n_dev):
        peer_of = dict(perm)
        peer = jnp.asarray([peer_of.get(d, d) for d in range(n_dev)],
                           p.dtype)[dev]
        with jax.named_scope("mix.permute"):
            other = jax.lax.ppermute(stats, axis_name, list(perm))
            stats = gossip.partner_mix(stats, other, src,
                                       (p_dev == peer) & (peer != dev))
    return stats


SIM_BACKENDS = ("dense", "pallas")


def get_communicator(name: str, **kwargs) -> Communicator:
    """Factory: 'dense' | 'pallas' | 'mesh' (kwargs go to the backend)."""
    if name == "dense":
        return DenseSimComm(**kwargs)
    if name == "pallas":
        return PallasSimComm(**kwargs)
    if name == "mesh":
        return MeshComm(**kwargs)
    raise ValueError(f"unknown communicator backend {name!r}; "
                     f"want dense | pallas | mesh")
