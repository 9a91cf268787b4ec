"""Decentralized (gossip) synchronization for arbitrary training state.

The paper's transferable core: *replace global aggregation of a linearly-
entering statistic with pairwise averaging*. For LDA the statistic is the
K x V matrix s; for data-parallel training it is the gradient (or the
parameters themselves, DiLoCo-style local-steps training). This module makes
that a first-class trainer knob usable by every assigned architecture:

    sync = "allreduce"               exact mean, one psum (baseline)
    sync = "gossip-hypercube[k]"     k XOR-partner rounds; k = log2(n) exact
    sync = "gossip-ring[k]"          k even/odd ring-matching rounds

Gossip variants replace the all-reduce with k ppermute+average rounds inside
``shard_map``: each round moves 1x the payload over ONE ICI hop, so k rounds
cost k*B bytes vs. the ring all-reduce's 2*B*(n-1)/n — cheaper for
k < 2(n-1)/n... i.e. k=1 — but the real win is *latency/straggler*
decoupling and partial synchrony: consensus error decays as lambda2^{k/2}
per step and the optimizer tolerates it (exactly the paper's argument).

Both substrates are thin wrappers over the unified ``repro.core.comm``
layer — the same schedules (`GossipSchedule.hypercube` / `.ring`) and the
same mixing backends the LDA reproduction uses:
  * `sync_tree_mesh`   — inside shard_map, over named mesh axes (TPU);
                         rounds are `comm.mesh_round` ppermute exchanges.
  * `sync_tree_sim`    — stacked leading node axis (CPU simulation /
                         tests); rounds go through a sim `Communicator`.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import comm as comm_mod


@dataclasses.dataclass(frozen=True)
class SyncSpec:
    """Parsed synchronization strategy."""

    kind: str                 # "allreduce" | "hypercube" | "ring"
    rounds: int | None = None  # None => exact (log2 n for hypercube)

    def __post_init__(self):
        if self.kind not in ("allreduce", "hypercube", "ring"):
            raise ValueError(f"unknown sync kind {self.kind!r}")


_SPEC_RE = re.compile(r"^(allreduce|gossip-hypercube|gossip-ring)"
                      r"(?:\[(\d+)\])?$")


def parse_sync(spec: str) -> SyncSpec:
    """Parse 'allreduce' | 'gossip-hypercube[k]' | 'gossip-ring[k]'."""
    m = _SPEC_RE.match(spec)
    if not m:
        raise ValueError(
            f"bad sync spec {spec!r}; want allreduce | gossip-hypercube[k] "
            f"| gossip-ring[k]")
    kind = m.group(1).replace("gossip-", "")
    rounds = int(m.group(2)) if m.group(2) else None
    return SyncSpec(kind=kind, rounds=rounds)


def rounds_per_axis(spec: SyncSpec, axis_sizes: Sequence[int]) -> list[int]:
    """How many gossip rounds each axis runs under the spec's TOTAL budget.

    ``spec.rounds`` is a budget over ALL axes, spent in axis order:
    hypercube axes take up to their exact count (log2 size), ring axes take
    the whole remaining budget (or the nominal 2 even/odd rounds when the
    budget is unlimited). This is the single source of truth shared by
    sync_tree_mesh, sync_tree_sim and collective_bytes_per_sync — the mesh
    path used to skip decrementing the budget for ring rounds, silently
    over-spending on multi-axis specs.
    """
    out: list[int] = []
    budget = spec.rounds
    for size in axis_sizes:
        if spec.kind == "allreduce" or int(size) <= 1 or budget == 0:
            out.append(0)
            continue
        if spec.kind == "hypercube":
            exact = int(size).bit_length() - 1
            k = exact if budget is None else min(budget, exact)
        else:  # ring
            k = 2 if budget is None else budget
        out.append(k)
        if budget is not None:
            budget -= k
    return out


# ----------------------------------------------------------------------------
# Mesh substrate (inside shard_map)
# ----------------------------------------------------------------------------

def sync_tree_mesh(tree, spec: SyncSpec, axis_names: Sequence[str],
                   axis_sizes: Sequence[int]):
    """Synchronize a pytree across one or more mesh axes.

    For multiple axes (e.g. ("pod", "data")) gossip rounds run per-axis in
    sequence — a hypercube over the product graph, which is itself a
    hypercube, so exactness composes.
    """
    if spec.kind == "allreduce":
        return jax.tree.map(
            lambda x: jax.lax.pmean(x, tuple(axis_names)), tree)

    for name, size, k in zip(axis_names, axis_sizes,
                             rounds_per_axis(spec, axis_sizes)):
        if k == 0:
            continue
        schedule = (comm_mod.GossipSchedule.hypercube(int(size))
                    if spec.kind == "hypercube"
                    else comm_mod.GossipSchedule.ring(int(size), k))
        for r in range(k):
            tree = comm_mod.mesh_round(
                tree, schedule.data[r % schedule.n_rounds], name)
    return tree


def is_exact(spec: SyncSpec, axis_sizes: Sequence[int]) -> bool:
    """Whether the spec reaches exact consensus on the given axes."""
    if spec.kind == "allreduce":
        return True
    if spec.kind == "hypercube":
        need = sum(int(s).bit_length() - 1 for s in axis_sizes if s > 1)
        return spec.rounds is None or spec.rounds >= need
    return False


def collective_bytes_per_sync(spec: SyncSpec, payload_bytes: int,
                              axis_sizes: Sequence[int]) -> int:
    """Napkin model of ICI bytes each device sends for one synchronization.

    ring all-reduce: 2 * B * (n-1)/n; each gossip round: B (one ppermute).
    Used by the roofline report to credit gossip's collective savings.
    """
    n = int(np.prod(axis_sizes))
    if spec.kind == "allreduce":
        return int(2 * payload_bytes * (n - 1) / n)
    return payload_bytes * sum(rounds_per_axis(spec, axis_sizes))


# ----------------------------------------------------------------------------
# Simulation substrate (stacked node axis; tests + CPU experiments)
# ----------------------------------------------------------------------------

def sync_tree_sim(tree, spec: SyncSpec, n_nodes: int,
                  comm: comm_mod.Communicator | None = None):
    """Synchronize a pytree whose every leaf has leading axis [n_nodes, ...].

    Semantics match sync_tree_mesh with a single axis of size n_nodes.
    Rounds are applied through a simulation `Communicator` (pure-jnp dense
    by default; pass comm=PallasSimComm(...) to route [n, K, V] leaves
    through the gossip_mix kernel). Through the default communicator a
    non-finite entry of one node's leaf reaches that entry of every
    node's leaf in the first round (:func:`gossip.partner_mix`).
    """
    if spec.kind == "allreduce":
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x.mean(0, keepdims=True), x.shape),
            tree)

    comm = comm or comm_mod.DenseSimComm()
    (k,) = rounds_per_axis(spec, (n_nodes,))
    schedule = (comm_mod.GossipSchedule.hypercube(n_nodes)
                if spec.kind == "hypercube"
                else comm_mod.GossipSchedule.ring(n_nodes, max(k, 1)))
    for r in range(k):
        p = jnp.asarray(schedule.data[r % schedule.n_rounds])
        tree = jax.tree.map(lambda x: comm.mix_matching(x, p), tree)
    return tree


# ----------------------------------------------------------------------------
# Local-steps (DiLoCo-style) wrapper: H local optimizer steps, then one
# parameter synchronization — the paper's sync/async trade-off for LMs.
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LocalStepsConfig:
    sync: str = "gossip-hypercube"   # parse_sync spec
    local_steps: int = 1             # H: optimizer steps between syncs
    sync_params: bool = True         # average params (vs. gradients)


def make_sync_fn(cfg: LocalStepsConfig, axis_names: Sequence[str],
                 axis_sizes: Sequence[int]):
    """Return sync(tree) usable inside shard_map over `axis_names`."""
    spec = parse_sync(cfg.sync)

    def sync(tree):
        return sync_tree_mesh(tree, spec, axis_names, axis_sizes)

    return sync
