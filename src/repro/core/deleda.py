"""DELEDA — Decentralized LDA (paper Algorithm 1 + asynchronous variant).

n agents sit on an undirected graph; each holds a private shard of documents
and a local sufficient-statistics iterate s_i (shape [K, V]). Per iteration:

  1. a gossip event mixes statistics: either ONE edge (i, j) ~ Uniform(E)
     activates (the paper's Algorithm 1) or a whole random maximal MATCHING
     fires at once (the synchronous multi-edge round — one round mixes ~n/2
     pairs, so paper-scale n=50 doesn't need n x more scan steps);
  2. *synchronous*: EVERY node performs a local G-OEM update (eq. 2) on a
     minibatch of its own documents;
     *asynchronous*: only the awake nodes update (the activated pair for an
     edge event; every matched node for a matching round).

The asynchronous variant keeps per-node iteration counters (each node's
step size rho_{t_i} advances only when that node updates) and optionally the
degree correction of Remark 1 / [4]: under uniform edge activation node i
wakes with probability deg(i)/|E|, so its updates are reweighted by
mean_degree/deg(i) to keep the network optimizing the *uniform* objective on
irregular graphs.

Gossip mixing goes through the unified :mod:`repro.core.comm` layer
(``DeledaConfig.comm_backend``): the pure-jnp oracle or the gossip_mix
Pallas kernel, interchangeable and test-asserted equivalent. The local
G-OEM E-steps go through the twin :mod:`repro.core.estep` layer
(``DeledaConfig.estep_backend``): all awake nodes' minibatches are fused
into ONE [A*B, L] sweep call per iteration (one Pallas grid instead of A
degenerate B-doc grids) and the per-node [K, V] statistics are scattered
back. Per-node PRNG streams are derived by ``fold_in(key, node_id)``, which
makes an edge schedule and its one-pair-per-round matching view produce
bit-identical trajectories (tests/test_comm.py) and keeps the fused batch
bit-identical to per-node E-step calls (tests/test_estep.py).

**Lifecycle layer** — training is carried as a first-class
:class:`TrainState` pytree and runs as resumable *segments* of ONE
compiled scan:

* :func:`init_state` builds the state (per-node statistics — dense or
  vocab-sharded — step counters, the base PRNG key, ``stats_version``, a
  membership mask, and the streaming-corpus cursor);
* :func:`train_steps` advances a state through one jitted scan segment
  and returns the new state plus that segment's trace. Per-step PRNG
  keys derive as ``fold_in(state.key, absolute_step)`` — a pure function
  of the step INDEX, not of the segmentation — so splitting a run into
  segments (for checkpointing or mid-run corpus swaps) is bitwise
  invisible. All segments share one compiled executable (same shapes;
  cache-size asserted in tests/test_scenario.py);
* :func:`run_deleda` is the host driver: it loops ``train_steps`` over a
  gcd-derived segment grid, swaps the streamed corpus between segments
  (``stream=``, data/lda_synthetic.CorpusStream), saves the carried
  state every ``save_every`` steps (``checkpoint_dir=``) and resumes a
  killed run from disk (``restore_from=``) with a BITWISE-identical
  trajectory — statistics, consensus history, in-loop eval LP and the
  threaded PRNG stream (tests/test_lifecycle.py).

Dynamic-network scenarios (core/scenario.py) ride the same scan: a
time-varying :class:`~repro.core.scenario.GraphSequence` just changes the
pre-drawn schedule *data* (same shapes — zero recompiles, asserted in
tests/test_scenario.py), message drops arrive as the comm layer's existing
no-op encodings (self-partner rows / the ``(i, i)`` edge sentinel), node
churn threads through the optional ``alive [T, n]`` input, and PERMANENT
membership (cold joins / departures, Scenario.joins/leaves) through the
``member [T, n]`` input: a node that is down or not (yet) a member neither
mixes nor updates and its step counter stays frozen, and the consensus
trace is computed over members only. A cold join needs no new collective
kind — the joiner's first gossip round IS the handoff (it inherits the
mixed statistic from its sponsor pair), so the analysis layer's
privacy/collective audits hold unchanged across all comm backends.
``degrees`` may be per-step ``[T, n]`` so the Remark-1 correction tracks a
rewiring topology.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import provenance as prov_mod
from repro.checkpoint import checkpoint as ckpt_mod
from repro.core import comm as comm_mod
from repro.core import estep as estep_mod
from repro.core import evaluation as eval_mod
from repro.core import gossip
from repro.core.graph import Graph
from repro.core.lda import LDAConfig, init_stats
from repro.core.oem import forgetting_rho, make_decay_schedule, \
    make_rho_schedule


@dataclasses.dataclass(frozen=True)
class DeledaConfig:
    """Run configuration for Algorithm 1 (and its async variant)."""

    lda: LDAConfig
    mode: str = "async"              # "sync" | "async"
    batch_size: int = 20             # docs per local update, per node
    rho_kind: str = "power"          # step-size schedule (oem.make_rho_schedule)
    rho_kappa: float = 0.6
    rho_t0: float = 10.0
    degree_correction: bool = True   # Remark 1 ([4]) reweighting, async only
    use_pallas: bool = False         # DEPRECATED alias for estep_backend
    comm_backend: str = "dense"      # gossip mixing: "dense" | "pallas"
    estep_backend: str = "dense"     # local E-steps: "dense" | "pallas"
    vocab_shards: int = 1            # Scale layer: split V into S blocks
    corpus_layout: str = "dense"     # Sparse corpus layer: "dense" runs
                                     # the per-position oracle sweeps,
                                     # "unique" the count-weighted CSR
                                     # sweeps over (word_id, count) pairs
    max_unique: int = 0              # U of the unique view (0 = L, always
                                     # sufficient); docs with more distinct
                                     # words than U drop the overflow
    eval_every: int = 0              # Evaluation layer: in-loop held-out
                                     # LP every this many steps (0 = off;
                                     # needs an EvalSpec, must be a
                                     # multiple of record_every)
    eval_backend: str = "fused"      # left-to-right estimator backend:
                                     # "fused" (multi-doc grid, the fast
                                     # path), "serial" (reference), or
                                     # "pallas" (kernels/lda_l2r); all
                                     # bit-compatible per document
    decay: tuple[float, float] | None = None
                                     # Lifecycle layer: Robbins–Monro
                                     # forgetting (tau0, kappa) — the
                                     # carried statistic is additionally
                                     # discounted by d_t = (tau0+t)^-kappa
                                     # each local update so streamed
                                     # documents supersede stale ones
                                     # (oem.forgetting_rho); None = the
                                     # paper's plain eq. (2), bit-exact
    mesh: Mesh | None = dataclasses.field(default=None, repr=False)
                                     # comm_backend="mesh": the device mesh
                                     # whose first axis holds the nodes in
                                     # contiguous blocks of n/d, and whose
                                     # second axis, if any, splits V into
                                     # blocks; train_steps then runs under
                                     # shard_map and gossips with ppermute
                                     # (left out of the repr, so checkpoint
                                     # digests do not change)

    def __post_init__(self):
        if self.mode not in ("sync", "async"):
            raise ValueError(f"mode must be sync|async, got {self.mode!r}")
        if self.eval_every < 0:
            raise ValueError(f"eval_every must be >= 0, "
                             f"got {self.eval_every}")
        if self.eval_backend not in eval_mod.EVAL_BACKENDS:
            raise ValueError(
                f"eval_backend must be one of {eval_mod.EVAL_BACKENDS}, "
                f"got {self.eval_backend!r}")
        if self.vocab_shards < 1:
            raise ValueError(f"vocab_shards must be >= 1, "
                             f"got {self.vocab_shards}")
        if self.lda.vocab_size % self.vocab_shards:
            raise ValueError(
                f"vocab_shards={self.vocab_shards} must divide "
                f"vocab_size={self.lda.vocab_size}")
        # the deprecation shim itself — the one sanctioned reader
        if self.use_pallas:   # lint: allow(use-pallas-alias)
            warnings.warn(
                "DeledaConfig.use_pallas is deprecated; use "
                "estep_backend='pallas' instead", DeprecationWarning,
                stacklevel=3)
            if self.estep_backend == "dense":
                object.__setattr__(self, "estep_backend", "pallas")
        if self.comm_backend == "mesh":
            if self.mesh is None or len(self.mesh.axis_names) not in (1, 2):
                raise ValueError("comm_backend='mesh' needs a 1-D node mesh "
                                 "or a 2-D node x vocab mesh "
                                 "(DeledaConfig.mesh)")
            if self.vocab_shards > 1:
                raise ValueError("the mesh backend carries [n, K, V] "
                                 "statistics: split V over a second mesh "
                                 "axis, not vocab_shards")
            if len(self.mesh.axis_names) == 2:
                blocks = self.mesh.shape[self.mesh.axis_names[1]]
                if self.lda.vocab_size % blocks:
                    raise ValueError(
                        f"the mesh's vocab axis of {blocks} devices must "
                        f"divide vocab_size={self.lda.vocab_size}")
                if self.eval_every:
                    raise ValueError("in-loop evaluation does not run on a "
                                     "node x vocab mesh")
        elif self.mesh is not None:
            raise ValueError("DeledaConfig.mesh needs comm_backend='mesh'")
        elif self.comm_backend not in comm_mod.SIM_BACKENDS:
            raise ValueError(
                f"comm_backend must be one of {comm_mod.SIM_BACKENDS} "
                f"or 'mesh', got {self.comm_backend!r}")
        if self.estep_backend not in estep_mod.ESTEP_BACKENDS:
            raise ValueError(
                f"estep_backend must be one of {estep_mod.ESTEP_BACKENDS}, "
                f"got {self.estep_backend!r}")
        if self.corpus_layout not in ("dense", "unique"):
            raise ValueError(f"corpus_layout must be dense|unique, "
                             f"got {self.corpus_layout!r}")
        if self.max_unique < 0:
            raise ValueError(f"max_unique must be >= 0 (0 = use L), "
                             f"got {self.max_unique}")
        if self.max_unique and self.corpus_layout != "unique":
            raise ValueError("max_unique only applies to "
                             "corpus_layout='unique'")
        if self.decay is not None:
            if len(self.decay) != 2:
                raise ValueError(f"decay must be (tau0, kappa), "
                                 f"got {self.decay!r}")
            object.__setattr__(self, "decay",
                               (float(self.decay[0]), float(self.decay[1])))
            make_decay_schedule(*self.decay)   # validates the ranges


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TrainState:
    """The carried lifecycle state of one decentralized training run.

    Everything a kill/restore needs travels HERE — restoring this pytree
    and re-entering :func:`train_steps` reproduces the uninterrupted
    trajectory bit-for-bit (tests/test_lifecycle.py):

    stats          [n, K, V] (or vocab-sharded [n, K, S, V/S]) per-node
                   sufficient statistics, in the carried layout;
    steps          [n] int32 per-node LOCAL update counters (the async
                   variant's rho_{t_i} clocks);
    key            the base run PRNG key (constant across segments;
                   per-step keys derive as fold_in(key, absolute_step));
    t              scalar int32 — the ABSOLUTE step cursor (how many
                   gossip rounds this state has consumed);
    stats_version  scalar int32 — monotonic, bumped once per round; the
                   serving layer's staleness token (core/serving.py);
    member         [n] bool — permanent membership at step t (False
                   before a cold join / after a departure);
    cursor         scalar int32 — the streaming-corpus segment index the
                   last consumed minibatches came from.
    """

    stats: jax.Array
    steps: jax.Array
    key: jax.Array
    t: jax.Array
    stats_version: jax.Array
    member: jax.Array
    cursor: jax.Array

    @property
    def n_nodes(self) -> int:
        return self.stats.shape[0]

    def dense_stats(self) -> jax.Array:
        """The statistics in the dense [n, K, V] external layout."""
        if self.stats.ndim == 4:
            n, k, s, vs = self.stats.shape
            return self.stats.reshape(n, k, s * vs)
        return self.stats


class SegmentTrace(NamedTuple):
    """What one ``train_steps`` segment records (per-segment shapes)."""

    history: jax.Array        # [R, n, K, V] recorded stats snapshots
    consensus: jax.Array      # [R] member-masked ||S - mean||_F
    eval_lp: jax.Array | None = None   # [E, probe_nodes] in-loop eval


class DeledaTrace(NamedTuple):
    stats: jax.Array          # [n, K, V] final per-node sufficient statistics
    steps: jax.Array          # [n] int32 per-node local-update counters
    history: jax.Array        # [R, n, K, V] recorded stats snapshots
    consensus: jax.Array      # [R] ||S - mean||_F at each record point
    eval_lp: jax.Array | None = None   # [E, probe_nodes] in-loop held-out
                                       # LP (config.eval_every > 0 only)
    state: "TrainState | None" = None  # the final carried TrainState
                                       # (stats in carried layout) — feed
                                       # it to save_state / train_steps


def _resolve_schedule_kind(schedule: jax.Array, n: int, kind: str) -> str:
    """'auto': [T, 2] is an edge list, [T, n] a matching partner matrix.

    For n == 2 both shapes coincide; 'auto' reads it as edges there (pass
    schedule_kind='matching' explicitly for 2-node matching schedules).
    """
    if kind in ("edge", "matching"):
        return kind
    if kind != "auto":
        raise ValueError(f"schedule_kind must be auto|edge|matching, "
                         f"got {kind!r}")
    if schedule.ndim != 2:
        raise ValueError(f"schedule must be [T, 2] or [T, n], "
                         f"got shape {schedule.shape}")
    if schedule.shape[1] == 2:
        return "edge"
    if schedule.shape[1] == n:
        return "matching"
    raise ValueError(f"schedule shape {schedule.shape} matches neither "
                     f"[T, 2] edges nor [T, {n}] matchings")


def _mesh_axes(config: DeledaConfig, n: int) -> tuple[str, str | None]:
    """The mesh's node axis, whose devices must split n into equal
    blocks, and its vocab axis (None on a 1-D mesh)."""
    axis, *vocab = config.mesh.axis_names
    n_dev = config.mesh.shape[axis]
    if n % n_dev:
        raise ValueError(f"n={n} nodes do not split over the mesh's "
                         f"{n_dev} devices")
    return axis, (vocab[0] if vocab else None)


def _stats_spec(axis: str, vocab_axis: str | None) -> P:
    """How a mesh shards the [n, K, V] statistic: rows by node blocks,
    and on a 2-D mesh columns by V blocks."""
    return P(axis) if vocab_axis is None else P(axis, None, vocab_axis)


def init_state(config: DeledaConfig, key: jax.Array, n: int) -> TrainState:
    """Build the step-0 :class:`TrainState` for an ``n``-node network.

    Consumes ``key`` exactly like the pre-lifecycle monolith (one
    ``split`` into the init and run streams, then per-node init draws),
    so existing seeds keep their init statistics bit-identical; the run
    half is STORED as ``TrainState.key`` and per-step keys derive from
    it by absolute step index.

    With ``config.mesh`` each device draws its own contiguous block of
    node rows (the same numbers), and ``stats``, ``steps`` and ``member``
    come out sharded by node, ``stats`` by V blocks too on a 2-D mesh (a
    device draws its rows whole and keeps its block); ``key`` and the
    scalars are replicated.
    """
    k_init, k_run = jax.random.split(key)
    draw = jax.vmap(lambda k: init_stats(config.lda, k))
    if config.mesh is None:
        stats0 = draw(jax.random.split(k_init, n))      # [n, K, V]
    else:
        axis, vocab_axis = _mesh_axes(config, n)
        node = NamedSharding(config.mesh, P(axis))
        rep = NamedSharding(config.mesh, P())
        if vocab_axis is None:
            block = draw
        else:
            width = config.lda.vocab_size // config.mesh.shape[vocab_axis]

            def block(keys):
                lo = jax.lax.axis_index(vocab_axis) * width
                return jax.lax.dynamic_slice_in_dim(draw(keys), lo, width,
                                                    axis=-1)
        stats0 = jax.shard_map(block, mesh=config.mesh, in_specs=P(axis),
                               out_specs=_stats_spec(axis, vocab_axis))(
            jax.random.split(k_init, n))
        return TrainState(
            stats=stats0,
            steps=jax.device_put(jnp.zeros((n,), jnp.int32), node),
            key=jax.device_put(k_run, rep),
            t=jax.device_put(jnp.zeros((), jnp.int32), rep),
            stats_version=jax.device_put(jnp.zeros((), jnp.int32), rep),
            member=jax.device_put(jnp.ones((n,), bool), node),
            cursor=jax.device_put(jnp.zeros((), jnp.int32), rep))
    if config.vocab_shards > 1:
        # the sharded carry: [n, K, S, V/S] — a pure layout reshape (V is
        # contiguous), so the dense and sharded trajectories are the same
        # floats and every consumer below is shard-oblivious
        stats0 = stats0.reshape(n, config.lda.n_topics, config.vocab_shards,
                                config.lda.vocab_size // config.vocab_shards)
    return TrainState(
        stats=stats0,
        steps=jnp.zeros((n,), jnp.int32),
        key=k_run,
        t=jnp.zeros((), jnp.int32),
        stats_version=jnp.zeros((), jnp.int32),
        member=jnp.ones((n,), bool),
        cursor=jnp.zeros((), jnp.int32))


@partial(jax.jit, static_argnames=("config", "record_every", "kind"))
def train_steps(config: DeledaConfig, state: TrainState, words: jax.Array,
                mask: jax.Array, schedule: jax.Array, corr: jax.Array,
                live: jax.Array, member_rec: jax.Array | None = None,
                record_every: int = 10, kind: str = "matching",
                eval_spec: eval_mod.EvalSpec | None = None
                ) -> tuple[TrainState, SegmentTrace]:
    """Advance ``state`` through one compiled scan segment of T rounds.

    The resumability contract: every per-step input is indexed by the
    ABSOLUTE step (``state.t + offset``) — the per-step PRNG key is
    ``fold_in(state.key, absolute_step)`` and ``corr``/``live``/
    ``schedule`` are the caller's host-side slices of the full-horizon
    arrays — so running [0, T) in one segment or as any partition into
    aligned segments is bitwise identical. One executable serves every
    segment of the same shape (this is the fn ``CompileCounter`` pins).

    words/mask [n, D, L] (dense layout; converted in-jit when
    ``config.corpus_layout == "unique"``); schedule [T, 2] edges or
    [T, n] matchings; corr [T, n] float32 Remark-1 weights; live [T, n]
    bool — aliveness AND membership (a False node neither mixes nor
    updates, its counter frozen); member_rec [T/record_every, n] bool
    membership at each record point (None = everyone: the consensus
    trace is then the original unmasked computation, bit-for-bit).

    ``config.mesh`` (``comm_backend="mesh"``, matching schedules) runs the
    round under ``shard_map`` over the mesh's node axis: ``state`` as
    :func:`init_state` shards it, words/mask sharded by node, the rest
    replicated. Each device takes its contiguous n/d rows; keys and
    minibatches follow the GLOBAL node id, so a seed follows the
    one-device trajectory. The mix is one ppermute pass per pair of
    devices (:func:`comm.mix_matching_sharded`), the record reduces
    across devices, ``history`` comes out sharded like the statistic, and
    nothing gathers the [n, K, V] statistic onto one device. In-loop
    eval: each device evaluates its first min(probe_nodes, n/d) rows, and
    the probe nodes' columns are kept, ``[E, probe_nodes]`` as on one
    device.
    A 2-D mesh also splits V into blocks over its second axis: the
    E-step assembles its beta columns with ``psum``s over that axis
    (:func:`estep.beta_w_from_stats`), each device scatters its block's
    words, and the mix runs unchanged over the node axis.
    """
    t_seg = schedule.shape[0]
    if t_seg % record_every != 0:
        raise ValueError(f"segment length {t_seg} must be divisible by "
                         f"record_every={record_every}")
    n, d, l = words.shape
    mesh = config.mesh
    if mesh is None:
        comm = comm_mod.get_communicator(config.comm_backend)
        vocab_axis = None
    else:
        axis, vocab_axis = _mesh_axes(config, n)
        if kind != "matching":
            raise ValueError("the mesh backend runs matching schedules only")
    unique = config.corpus_layout == "unique"
    if unique:
        estep = estep_mod.get_sparse_estep(config.estep_backend)
        # one sort+segment pass over the whole corpus, inside the jit;
        # from here on `words` holds unique ids and `mask` the counts
        # (every consumer below only indexes rows or passes them through)
        words, mask = estep_mod.dense_to_unique(
            words, mask, config.max_unique or l)
    else:
        estep = estep_mod.get_estep(config.estep_backend)
    rho_fn = make_rho_schedule(config.rho_kind, kappa=config.rho_kappa,
                               t0=config.rho_t0)
    decay_fn = (make_decay_schedule(*config.decay)
                if config.decay is not None else None)
    n_topics, vocab = config.lda.n_topics, config.lda.vocab_size
    shards = config.vocab_shards
    all_ids = jnp.arange(n, dtype=jnp.int32)

    def bcast(rows, ndim):
        # [n]-shaped masks/steps against the (possibly vocab-sharded) stats
        return rows.reshape((-1,) + (1,) * (ndim - 1))

    def sample_batch(k, node_words, node_mask):
        idx = jax.random.randint(k, (config.batch_size,), 0, d)
        return node_words[idx], node_mask[idx]

    def update_rows(stats_rows, steps_rows, ids, k_sel, k_gibbs,
                    words_rows, mask_rows, corr_rows):
        """Fused G-OEM updates (eq. 2) for a set of awake node rows.

        Per-node streams come from fold_in(key, GLOBAL node id), so the
        same node sees the same stream regardless of which/how many nodes
        are updated alongside it — the property that makes edge schedules
        and their 1-pair matching views bit-identical, and that keeps this
        fused [A*B, L] batch bit-identical to per-node E-step calls.
        """
        with jax.named_scope("deleda.estep"):
            bw, bm = jax.vmap(
                lambda i, w_, m_: sample_batch(jax.random.fold_in(k_sel, i),
                                               w_, m_))(
                ids, words_rows, mask_rows)               # [A, B, L]
            keys = jax.vmap(lambda i: jax.random.fold_in(k_gibbs, i))(ids)
            # blocked-stats E-step: beta columns are gathered straight
            # from the (possibly vocab-sharded) statistic — no dense
            # [A, K, V] eta_star temporary; bitwise-equal to the
            # materialized path. In the unique layout bw/bm hold
            # (word_id, count) rows instead of (token, mask) rows and the
            # sweeps are count-weighted.
            # On a 2-D mesh the rows hold this device's V block.
            if unique:
                stats_hat = estep_mod.estep_batch_from_stats_unique(
                    estep, config.lda, keys, bw, bm, stats_rows,
                    axis_name=vocab_axis)
            else:
                stats_hat = estep_mod.estep_batch_from_stats(
                    estep, config.lda, keys, bw, bm, stats_rows,
                    axis_name=vocab_axis)                        # [A,K,V]
            stats_hat = stats_hat.reshape(stats_rows.shape)
        with jax.named_scope("deleda.blend"):
            t = steps_rows + 1
            rho = (rho_fn(t) * corr_rows).astype(stats_rows.dtype)
            rho = jnp.clip(rho, 0.0, 1.0)
            if decay_fn is not None:
                # Robbins–Monro forgetting (lifecycle layer): discount the
                # carried statistic by d_t before blending — streamed
                # minibatches supersede stale ones (oem.forgetting_rho)
                decay = jnp.clip(decay_fn(t), 0.0, 1.0).astype(
                    stats_rows.dtype)
                rho = forgetting_rho(rho, decay)
            rho = bcast(rho, stats_rows.ndim)
            return (1.0 - rho) * stats_rows + rho * stats_hat, t

    n_rec = t_seg // record_every
    t_idx = state.t + jnp.arange(t_seg, dtype=jnp.int32)      # absolute
    blocks = jax.tree_util.tree_map(
        lambda x: x.reshape((n_rec, record_every) + x.shape[1:]),
        (schedule, t_idx, live.astype(bool), corr))
    mem_rec = (None if member_rec is None
               else member_rec.astype(bool))                  # [n_rec, n]
    xs = (blocks, mem_rec)
    if config.eval_every:
        if config.eval_every % record_every != 0:
            raise ValueError(
                f"eval_every={config.eval_every} must be a multiple of "
                f"record_every={record_every}")
        if t_seg % config.eval_every != 0:
            raise ValueError(f"segment length {t_seg} must be divisible "
                             f"by eval_every={config.eval_every}")
        if eval_spec is None:
            raise ValueError("config.eval_every > 0 needs an eval_spec "
                             "(repro.core.evaluation.EvalSpec)")
        # Evaluation layer: nest the record blocks inside eval blocks so
        # the LP trajectory is recorded on-device by the SAME scan. The
        # probe nodes' (possibly vocab-sharded) statistic rows feed the
        # blocked beta gather directly.
        spec = eval_spec
        probe = min(spec.probe_nodes, n)
        blocks_per_eval = config.eval_every // record_every
        n_eval = t_seg // config.eval_every
        if spec.layout == "unique":
            # one conversion outside the scan; the in-loop evaluator then
            # runs the count-weighted left-to-right over U unique slots
            ew, em = estep_mod.dense_to_unique(spec.words, spec.mask)
        else:
            ew, em = spec.words, spec.mask

    def run_segment(stats, steps, key, words, mask, xs):
        """The segment's scans over one device's node rows: all n, or
        under ``shard_map`` the device's contiguous block, whose rows
        keep their GLOBAL node ids (keys, minibatches) while the
        schedule, liveness and weights come in whole."""
        if mesh is None:
            node_ids, mix = all_ids, comm.mix_matching
            record = gossip.consensus_distance

            def local(rows):
                return rows
        else:
            n_local = stats.shape[0]
            lo = jax.lax.axis_index(axis) * n_local
            node_ids = lo + jnp.arange(n_local, dtype=jnp.int32)
            mix = partial(comm_mod.mix_matching_sharded, axis_name=axis,
                          n_dev=mesh.shape[axis])
            record = partial(gossip.consensus_distance, axis_name=axis,
                             vocab_axis=vocab_axis)

            def local(rows):
                return jax.lax.dynamic_slice_in_dim(rows, lo, n_local)

        def iteration(carry, inp):
            stats, steps = carry
            event, t_abs, al, corr_row = inp                  # al/corr [n]
            # the per-step stream is a pure function of the ABSOLUTE step
            # index — segmentation-invariant, hence kill/restore-invariant
            k = jax.random.fold_in(key, t_abs)
            k_sel, k_gibbs = jax.random.split(k)

            if kind == "edge":
                i, j = event[0], event[1]
                with jax.named_scope("deleda.mix"):
                    # an event is live unless it is the (i, i) drop
                    # sentinel or an endpoint is down this step (churn) /
                    # not a member (lifecycle)
                    ev_live = (i != j) & al[i] & al[j]
                    # -- gossip averaging step (Algorithm 1, line 4); a
                    # dead event mixes (i, i), which every backend applies
                    # as the identity
                    j_eff = jnp.where(ev_live, j, i)
                    stats = comm.mix_edge(stats, i, j_eff)
                if config.mode == "sync":
                    # -- every live node updates locally (Alg. 1, l. 5-7)
                    new_stats, new_steps = update_rows(
                        stats, steps, node_ids, k_sel, k_gibbs, words,
                        mask, corr_row)
                    with jax.named_scope("deleda.blend"):
                        stats = jnp.where(bcast(al, stats.ndim), new_stats,
                                          stats)
                        steps = jnp.where(al, new_steps, steps)
                else:
                    # -- only the two awake nodes update (async variant)
                    active = jnp.stack([i, j])                # [2]
                    up_stats, up_steps = update_rows(
                        stats[active], steps[active], active, k_sel,
                        k_gibbs, words[active], mask[active],
                        corr_row[active])
                    with jax.named_scope("deleda.blend"):
                        upd = jnp.stack([ev_live, ev_live])
                        up_stats = jnp.where(bcast(upd, up_stats.ndim),
                                             up_stats, stats[active])
                        up_steps = jnp.where(upd, up_steps, steps[active])
                        stats = stats.at[active].set(up_stats)
                        steps = steps.at[active].set(up_steps)
            else:
                partners = event                              # [n]
                with jax.named_scope("deleda.mix"):
                    # liveness guard: a pair with a down or non-member
                    # endpoint mixes as self-self (symmetric in (i, p[i]),
                    # so the row stays an involution)
                    partners = jnp.where(al & al[partners], partners,
                                         all_ids)
                    stats = mix(stats, partners)
                partners, al, corr_row = (local(partners), local(al),
                                          local(corr_row))
                new_stats, new_steps = update_rows(stats, steps, node_ids,
                                                   k_sel, k_gibbs, words,
                                                   mask, corr_row)
                with jax.named_scope("deleda.blend"):
                    if config.mode == "sync":
                        upd = al                              # [n]
                    else:
                        # matched live nodes are the awake ones this round
                        upd = (partners != node_ids) & al
                    stats = jnp.where(bcast(upd, stats.ndim), new_stats,
                                      stats)
                    steps = jnp.where(upd, new_steps, steps)

            return (stats, steps), None

        def record_block(carry, inp):
            xs, mem = inp
            carry, _ = jax.lax.scan(iteration, carry, xs)
            stats, _steps = carry
            with jax.named_scope("deleda.record"):
                return carry, (stats, record(stats, mem))

        if not config.eval_every:
            return (*jax.lax.scan(record_block, (stats, steps), xs), None)

        def eval_block(carry, inp):
            carry, (hist, cons) = jax.lax.scan(record_block, carry, inp)
            stats, _steps = carry
            # the first `probe` rows; on a mesh, as many of the device's
            # own rows, of which the caller keeps the probe nodes' rows
            lp = jax.vmap(lambda st: eval_mod.heldout_lp_from_stats(
                spec.key, ew, em, st, config.lda.tau,
                config.lda.alpha, spec.n_particles,
                spec.layout, config.eval_backend))(
                    stats[:min(probe, stats.shape[0])])
            return carry, (hist, cons, lp)

        xs = jax.tree_util.tree_map(
            lambda x: x.reshape((n_eval, blocks_per_eval) + x.shape[1:]),
            xs)
        carry, (history, consensus, eval_lp) = jax.lax.scan(
            eval_block, (stats, steps), xs)
        return (carry, (history.reshape((n_rec,) + history.shape[2:]),
                        consensus.reshape(n_rec)), eval_lp)

    if mesh is None:
        (stats, steps), (history, consensus), eval_lp = run_segment(
            state.stats, state.steps, state.key, words, mask, xs)
    else:
        # one program over the node axis: each device carries its block
        # of the statistic, and only the mix's ppermutes, the record's
        # all-reduces and a 2-D mesh's vocab-axis psums cross devices
        node = P(axis)
        block = _stats_spec(axis, vocab_axis)
        out_specs = ((block, node), (P(None, *block), P()))
        if config.eval_every:
            segment, out_specs = run_segment, out_specs + (P(None, axis),)
        else:
            def segment(*a):
                return run_segment(*a)[:2]
        # the evaluator's scans start from replicated zeros beside the
        # device's own rows, which the varying-axes check refuses
        (stats, steps), (history, consensus), *lp = jax.shard_map(
            segment, mesh=mesh,
            in_specs=(block, node, P(), node, node, P()),
            out_specs=out_specs, check_vma=not config.eval_every)(
                state.stats, state.steps, state.key, words, mask, xs)
        # device d's first rows are columns d*r.. of lp, so the first
        # `probe` columns are the probe nodes whether r = probe or n/d
        eval_lp = lp[0][:, :probe] if lp else None
    if shards > 1:
        # externally the trace is always dense [.., K, V]; the shard axis
        # was contiguous layout only, so this reshape is free
        history = history.reshape(n_rec, n, n_topics, vocab)
    member = state.member if mem_rec is None else mem_rec[-1]
    if mesh is not None and mem_rec is not None:
        member = jax.lax.with_sharding_constraint(
            member, NamedSharding(mesh, P(axis)))
    new_state = TrainState(
        stats=stats, steps=steps, key=state.key,
        t=state.t + t_seg,
        stats_version=state.stats_version + t_seg,
        member=member, cursor=state.cursor)
    return new_state, SegmentTrace(history=history, consensus=consensus,
                                   eval_lp=eval_lp)


def run_deleda(config: DeledaConfig, key: jax.Array,
               words: jax.Array | None, mask: jax.Array | None,
               schedule: jax.Array, degrees: jax.Array,
               n_steps: int, record_every: int = 10,
               schedule_kind: str = "auto",
               alive: jax.Array | None = None,
               eval_spec: eval_mod.EvalSpec | None = None,
               member: jax.Array | None = None,
               stream=None, save_every: int = 0,
               checkpoint_dir: str | None = None,
               restore_from: str | None = None) -> DeledaTrace:
    """Run DELEDA for `n_steps` gossip iterations.

    words: [n, D, L] int32 private documents per node; mask: [n, D, L] bool;
    schedule: [n_steps, 2] int32 pre-drawn edge activations
    (gossip.draw_edge_schedule) OR [n_steps, n] int32 matching partner
    vectors (gossip.draw_matching_schedule / comm.GossipSchedule.partners);
    degrees: [n] int32 node degrees, or [n_steps, n] per-step degrees for a
    time-varying topology (both feed the async degree correction);
    alive: optional [n_steps, n] bool churn mask (core/scenario.py) — a
    node that is down at step t neither mixes nor updates at t and its step
    counter stays frozen. Dropped gossip events need no extra input: they
    are encoded in the schedule itself (self-partner rows / ``(i, i)`` edge
    sentinels) and skip the mix and — async — the wake-up.

    ``member`` [n_steps, n] bool (lifecycle layer) is PERMANENT membership
    (``CompiledScenario.run_inputs`` builds it from ``Scenario.joins`` /
    ``leaves``): a non-member behaves like a churned node — frozen, no
    mixing — and is additionally excluded from the consensus trace; its
    first member round is its cold-join handoff, an ordinary gossip mix
    with its sponsor. None (the default) keeps the original computation
    bit-for-bit.

    ``stream`` (data/lda_synthetic.make_corpus_stream) swaps the training
    minibatch source every ``stream.refresh_every`` rounds BETWEEN scan
    segments — words/mask may then be None (segment 0 is the stream's
    base corpus, bit-identical to the frozen-corpus run until the first
    refresh). ``save_every > 0`` + ``checkpoint_dir`` saves the carried
    :class:`TrainState` at every save point (and the final step when it
    is one); ``restore_from`` resumes a killed run from its latest
    committed checkpoint — the resumed trajectory is BITWISE identical
    to the uninterrupted one (same full-horizon schedule/degrees/alive/
    member must be passed; the stored PRNG key supersedes ``key``).

    ``config.vocab_shards = S`` (the Scale layer) carries the statistics
    vocab-sharded as [n, K, S, V/S] through the SAME single-jit scan: the
    comm layer mixes each V-shard independently (gossip is row-linear) and
    the E-step gathers only the minibatch's beta columns from the sharded
    statistic (``estep.estep_batch_from_stats``) instead of materializing
    the dense [n, K, V] topic matrix each iteration. The trajectory
    matches the dense run to a few ulps (only the blocked denominator
    reduce may re-associate across shards; mixing, gathers, scatters and
    blends are elementwise or identical-order) and the returned trace is
    always densely shaped.

    ``config.corpus_layout = "unique"`` (the Sparse corpus layer) converts
    the dense [n, D, L] documents ONCE per segment, inside the jit, to
    per-document (word_id, count) pairs padded to U = ``config.max_unique``
    slots (0 = L, always sufficient) and runs every local E-step as
    count-weighted sweeps over the U unique slots instead of per-position
    sweeps over the L tokens — O(U) categorical draws per sweep. On
    Zipf-shaped corpora with many within-document duplicates this is the
    dominant cost win (benchmarks/sparse_bench.py); the blocked move
    (all c copies of a word redrawn together) is a different, valid
    sampler than c per-copy moves, statistically indistinguishable at the
    trajectory level and bit-identical when every count is 1
    (tests/test_sparse.py). Dense stays the default and the oracle.

    ``config.eval_every = E`` (the Evaluation layer) rides the same scan:
    at every E-th step the held-out LP of the first
    ``eval_spec.probe_nodes`` nodes is computed ON-DEVICE straight from
    the (possibly vocab-sharded) carried statistic — the blocked
    ``beta_w_from_stats`` gather, no dense [K, V] beta temporary — and
    recorded in ``trace.eval_lp`` [n_steps/E, probe_nodes]. The training
    trajectory is unchanged (the evaluator has its own ``eval_spec.key``
    stream), asserted against the pinned goldens.

    ``config.mesh`` (``comm_backend="mesh"``) runs every segment with the
    nodes sharded over the mesh (:func:`train_steps`): a seed follows the
    one-device trajectory, checkpoints hold the sharded state, and
    ``restore_from`` places it as :func:`init_state` does.
    """
    if n_steps % record_every != 0:
        raise ValueError("n_steps must be divisible by record_every")
    if config.eval_every:
        if eval_spec is None:
            raise ValueError("config.eval_every > 0 needs an eval_spec "
                             "(repro.core.evaluation.EvalSpec)")
        if config.eval_every % record_every != 0:
            raise ValueError(
                f"eval_every={config.eval_every} must be a multiple of "
                f"record_every={record_every}")
        if n_steps % config.eval_every != 0:
            raise ValueError(f"n_steps={n_steps} must be divisible by "
                             f"eval_every={config.eval_every}")
    if save_every:
        if checkpoint_dir is None:
            raise ValueError("save_every > 0 needs a checkpoint_dir")
        if save_every % record_every != 0:
            raise ValueError(f"save_every={save_every} must be a multiple "
                             f"of record_every={record_every}")
    if stream is not None:
        if stream.refresh_every % record_every != 0:
            raise ValueError(
                f"stream.refresh_every={stream.refresh_every} must be a "
                f"multiple of record_every={record_every}")
        n = stream.n_nodes
    elif words is not None:
        n = words.shape[0]
    else:
        raise ValueError("pass words/mask or a corpus stream")
    kind = _resolve_schedule_kind(schedule, n, schedule_kind)

    # ---- host-side per-step inputs over the FULL horizon (sliced per
    # segment below, so every segment sees its absolute-step rows)
    deg_f = jnp.asarray(degrees).astype(jnp.float32)
    if deg_f.ndim == 1:
        deg_t = jnp.broadcast_to(deg_f, (n_steps, n))   # static topology
    elif deg_f.shape == (n_steps, n):
        deg_t = deg_f                                   # per-step degrees
    else:
        raise ValueError(f"degrees must be [n={n}] or [{n_steps}, {n}], "
                         f"got shape {deg_f.shape}")
    # Remark 1 reweighting models SINGLE-EDGE activation, where node i wakes
    # with probability deg(i)/|E|. Under random maximal matching rounds wake
    # rates are near-uniform in the degree, so the correction would skew the
    # objective instead of fixing it — it only applies to edge schedules.
    if (config.degree_correction and config.mode == "async"
            and kind == "edge"):
        corr_t = (deg_t.mean(axis=1, keepdims=True)
                  / jnp.maximum(deg_t, 1.0))            # [T, n]
    else:
        corr_t = jnp.ones((n_steps, n), jnp.float32)

    if alive is None:
        alive_t = jnp.ones((n_steps, n), bool)
    else:
        if alive.shape != (n_steps, n):
            raise ValueError(f"alive must be [{n_steps}, {n}], "
                             f"got shape {alive.shape}")
        alive_t = jnp.asarray(alive).astype(bool)
    if member is None:
        member_t = None
        live_t = alive_t
        member_rec = None
    else:
        if member.shape != (n_steps, n):
            raise ValueError(f"member must be [{n_steps}, {n}], "
                             f"got shape {member.shape}")
        member_t = jnp.asarray(member).astype(bool)
        live_t = alive_t & member_t
        member_rec = member_t[record_every - 1::record_every]  # [R, n]

    # ---- initial state: fresh, or the latest committed checkpoint
    if restore_from is not None:
        state = restore_state(restore_from, init_state(config, key, n),
                              config=config)
        t0 = int(state.t)
        if t0 >= n_steps:
            raise ValueError(f"checkpoint at step {t0} has nothing left "
                             f"to run (n_steps={n_steps})")
        if t0 % record_every != 0:
            raise ValueError(
                f"checkpoint step {t0} is not a multiple of "
                f"record_every={record_every}")
    else:
        state = init_state(config, key, n)
        t0 = 0

    # ---- the segment grid: the coarsest equal split on which every
    # lifecycle action (save, corpus refresh, the restore point) falls on
    # a boundary. One shape -> one compiled executable for the whole run
    # (resuming mid-run may pick a finer grid than the original — harmless,
    # since the per-step streams are absolute-indexed).
    seg = n_steps
    if save_every:
        seg = math.gcd(seg, save_every)
    if stream is not None:
        seg = math.gcd(seg, stream.refresh_every)
    if t0:
        seg = math.gcd(seg, t0)
    if seg % record_every != 0:
        raise ValueError(
            f"the segment grid gcd(n_steps, save_every, refresh_every, "
            f"restore step) = {seg} must be a multiple of "
            f"record_every={record_every}")
    if config.eval_every and seg % config.eval_every != 0:
        raise ValueError(
            f"the segment grid gcd(n_steps, save_every, refresh_every, "
            f"restore step) = {seg} must be a multiple of "
            f"eval_every={config.eval_every} "
            f"(in-loop eval points must fall inside segments)")

    parts = []
    cur_words, cur_mask = words, mask
    cur_sidx = None
    for t_start in range(t0, n_steps, seg):
        if stream is not None:
            s_idx = t_start // stream.refresh_every
            if s_idx != cur_sidx:
                cur_words, cur_mask = stream.segment(s_idx)
                cur_sidx = s_idx
            state = dataclasses.replace(
                state, cursor=jnp.asarray(s_idx, jnp.int32))
        sl = slice(t_start, t_start + seg)
        rec_sl = slice(t_start // record_every,
                       (t_start + seg) // record_every)
        state, part = train_steps(
            config, state, cur_words, cur_mask, schedule[sl], corr_t[sl],
            live_t[sl],
            None if member_rec is None else member_rec[rec_sl],
            record_every=record_every, kind=kind, eval_spec=eval_spec)
        parts.append(part)
        t_end = t_start + seg
        if save_every and t_end % save_every == 0:
            save_state(checkpoint_dir, state, config=config)

    if len(parts) == 1:
        history, consensus, eval_lp = parts[0]
    else:
        history = jnp.concatenate([p.history for p in parts], axis=0)
        consensus = jnp.concatenate([p.consensus for p in parts], axis=0)
        eval_lp = (jnp.concatenate([p.eval_lp for p in parts], axis=0)
                   if parts[0].eval_lp is not None else None)
    return DeledaTrace(stats=state.dense_stats(), steps=state.steps,
                       history=history, consensus=consensus,
                       eval_lp=eval_lp, state=state)


# ----------------------------------------------------------------------------
# TrainState <-> disk (the checkpoint layer wiring)
# ----------------------------------------------------------------------------

def _is_typed_key(key: jax.Array) -> bool:
    try:
        return jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
    except TypeError:
        return False


def save_state(directory: str, state: TrainState,
               config: DeledaConfig | None = None) -> str:
    """Save a :class:`TrainState` as ``<dir>/step_<t>/state.npz``.

    Typed PRNG keys are serialized via ``jax.random.key_data`` (npz has
    no extended dtypes); the sidecar records the flavor plus the config
    digest so a restore under a different configuration warns. Returns
    the committed npz path.
    """
    typed = _is_typed_key(state.key)
    flat = dataclasses.replace(
        state,
        key=jax.random.key_data(state.key) if typed else state.key)
    meta = {"typed_key": bool(typed), "kind": "deleda_train_state"}
    if config is not None:
        meta["config_digest"] = prov_mod.config_digest(config)
    return ckpt_mod.save_checkpoint(directory, flat, int(state.t),
                                    meta=meta)


def restore_state(directory: str, like: TrainState,
                  config: DeledaConfig | None = None,
                  step: int | None = None) -> TrainState:
    """Restore a :class:`TrainState` saved by :func:`save_state`.

    ``like`` supplies the structure and layout (build it with
    :func:`init_state` under the SAME config — a shape mismatch, e.g. a
    different ``vocab_shards``, fails with the offending key and both
    shapes); its key flavor (typed vs legacy uint32) decides how the
    stored key bits are rewrapped — both flavors derive bit-identical
    streams, so either resumes the exact trajectory. ``config`` enables
    the sidecar digest check (restore warns when it differs). Each array
    is placed as ``like``'s is, so a mesh run's state comes back sharded
    as :func:`init_state` shards it.
    """
    typed = _is_typed_key(like.key)
    flat_like = dataclasses.replace(
        like, key=jax.random.key_data(like.key) if typed else like.key)
    digest = (prov_mod.config_digest(config) if config is not None
              else None)
    flat = ckpt_mod.restore_checkpoint(directory, flat_like, step=step,
                                       expect_config_digest=digest)
    if typed:
        flat = dataclasses.replace(
            flat, key=jax.random.wrap_key_data(jnp.asarray(flat.key)))
    return jax.tree_util.tree_map(
        lambda a, placed: jax.device_put(a, placed.sharding), flat, like)


def make_run_inputs(graph: Graph, n_steps: int, seed: int = 0,
                    kind: str = "edge") -> tuple[jax.Array, jax.Array]:
    """Convenience: (schedule, degrees [n]) device arrays for run_deleda.

    kind="edge" draws [T, 2] single-edge activations (Algorithm 1);
    kind="matching" draws [T, n] random maximal matching rounds.
    """
    rng = np.random.default_rng(seed)
    if kind == "edge":
        sched = comm_mod.GossipSchedule.draw_edges(graph, n_steps, rng)
    elif kind == "matching":
        sched = comm_mod.GossipSchedule.draw_matchings(graph, n_steps, rng)
    else:
        raise ValueError(f"kind must be edge|matching, got {kind!r}")
    return (jnp.asarray(sched.data),
            jnp.asarray(graph.degrees.astype(np.int32)))


# ----------------------------------------------------------------------------
# Theory diagnostic: measured consensus vs. the eq. (3) envelope
# ----------------------------------------------------------------------------

def consensus_report(trace: DeledaTrace, graph: Graph,
                     config: DeledaConfig, n_steps: int,
                     record_every: int) -> dict:
    """Compare the measured consensus distance with the lambda2 envelope."""
    lam2 = graph.lambda2()
    rho_fn = make_rho_schedule(config.rho_kind, kappa=config.rho_kappa,
                               t0=config.rho_t0)
    rhos = np.asarray(jax.vmap(rho_fn)(jnp.arange(1, n_steps + 1)))
    # ||G|| bound: stats rows are per-document normalized counts; a crude
    # but valid bound is the max recorded iterate magnitude over ALL
    # snapshots — taking only history[0] makes the envelope spuriously
    # tight whenever the early iterates are small and the statistics
    # still grow, falsely reporting envelope violations.
    hist = np.asarray(trace.history, np.float64)            # [R, n, K, V]
    g_norm = float(np.linalg.norm(
        hist.reshape(hist.shape[0], hist.shape[1], -1),
        axis=-1).max() + 1.0)
    env = gossip.consensus_envelope(lam2, rhos, g_norm)[record_every - 1::record_every]
    measured = np.asarray(trace.consensus)
    return {
        "lambda2": lam2,
        "spectral_gap": 1.0 - lam2,
        "measured": measured,
        "envelope": env,
        "within_envelope_frac": float((measured <= env + 1e-6).mean()),
    }
