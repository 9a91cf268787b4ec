"""DELEDA on a device mesh: the paper's algorithm as an SPMD program.

The simulation substrate (core/deleda.py) stacks the n agents on an array
axis of ONE device. This launcher instead maps agents onto the MESH: each
device owns one shard of nodes (documents never leave their device — the
privacy constraint becomes a physical placement), local G-OEM updates run
data-parallel, and the gossip averaging step goes through the unified
``repro.core.comm.MeshComm`` backend: each matching round is routed as
intra-device row mixes plus one-hop bidirectional ``ppermute`` exchanges of
the local statistics block. Per round a device moves O(K x V) bytes — NOT
the O(n x K x V) of the all_gather-then-select this launcher used to do.

Note the schedule adaptation (recorded in DESIGN.md): single-edge
asynchronous gossip has no SPMD analogue — lockstep devices would idle.
The mesh variant uses random MATCHING rounds (every node pairs at most
once per round), which is the standard synchronous gossip generalization;
with nodes_per_device shards it degrades gracefully to intra-device
matchings plus cross-device ppermute passes.

  PYTHONPATH=src python -m repro.launch.gossip_sim --nodes 8 --steps 50
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.lda_paper import CONFIG as PAPER
from repro.core import comm as comm_mod
from repro.core import evaluation
from repro.core import gossip
from repro.core import deleda as deleda_mod
from repro.core.comm import GossipSchedule, MeshComm
from repro.core.graph import complete_graph, watts_strogatz_graph
from repro.core.lda import LDAConfig, beta_distance, eta_star, init_stats
from repro.core.oem import make_rho_schedule
from repro.core import estep as estep_mod
from repro.data.lda_synthetic import CorpusSpec, make_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def build_update_step(lda: LDAConfig, batch_size: int, mesh,
                      vocab_axis: str | None = None,
                      estep_backend: str = "dense",
                      corpus_layout: str = "dense"):
    """The mesh local-update step as a standalone jitted SPMD program.

    Returns the jitted shard_map over ``update_fn(stats, steps, key,
    words, mask, alive)`` that :func:`run_mesh_deleda` drives once per
    gossip round — exported at module level so the invariant auditor
    (`repro.analysis.trace_audit`) can lower it on its own and assert
    the collective inventory: NO collectives at all on a 1-D mesh, and
    on a 2-D node x vocab grid only the vocab-axis psums of the blocked
    beta assembly (never a node-axis collective, never a doc-shaped
    operand).

    ``stats`` [n, K, V(/vocab_devices)] sharded over "data" (and
    ``vocab_axis`` when set); ``words``/``mask`` [n, D, L] node-sharded
    ("dense" layout) or the `estep.unique_view` (ids, counts) pair
    ("unique"); ``steps``/``alive`` [n].
    """
    rho_fn = make_rho_schedule("power")
    unique = corpus_layout == "unique"
    if corpus_layout not in ("dense", "unique"):
        raise ValueError(f"corpus_layout must be dense|unique, "
                         f"got {corpus_layout!r}")
    estep = (estep_mod.get_sparse_estep(estep_backend) if unique
             else estep_mod.get_estep(estep_backend))
    node = P("data")
    stats_spec = P("data", None, vocab_axis) if vocab_axis else node

    def update_fn(stats, steps, key, w, m, al):
        # stats [n_local, K, V_local]; pure local G-OEM — gossip already
        # happened via MeshComm outside this jit, and the only collective
        # here is the O(B*L*K) beta-column psum over the vocab axis of a
        # 2-D grid. All of the device's nodes run as ONE fused
        # [n_local*B, L] E-step call; al [n_local] masks down nodes.
        with jax.named_scope("deleda.estep"):
            n_local = stats.shape[0]
            dev = jax.lax.axis_index("data")
            key = jax.random.fold_in(key, dev)   # per-device stream (varying
                                                 # over nodes, NOT over vocab
                                                 # shards of the same nodes)
            ks = jax.vmap(jax.random.split)(jax.random.split(key, n_local))
            k_sel, k_gibbs = ks[:, 0], ks[:, 1]  # [n_local] each

            def select(k, node_words, node_mask):
                idx = jax.random.randint(k, (batch_size,), 0,
                                         node_words.shape[0])
                return node_words[idx], node_mask[idx]

            bw, bm = jax.vmap(select)(k_sel, w, m)          # [n_local, B, L]
            maskf = bm.astype(stats.dtype)
            if vocab_axis:
                # -- blocked beta assembly across the vocab axis: each shard
                # contributes (stats[:, w] + tau) for ITS words, one psum of
                # the [n_local, B, L, K] partials builds the full likelihood
                # rows — the dense [K, V] topic matrix never exists anywhere
                v_local = stats.shape[-1]
                v0 = jax.lax.axis_index(vocab_axis) * v_local
                denom = jax.lax.psum((stats + lda.tau).sum(-1),
                                     vocab_axis)            # [n_local, K]
                lw = bw - v0                                # local word ids
                in_shard = (lw >= 0) & (lw < v_local)
                lw = jnp.clip(lw, 0, v_local - 1)
                cols = jax.vmap(
                    lambda st, ww: jnp.moveaxis(st[:, ww], 0, -1))(stats, lw)
                part = jnp.where(in_shard[..., None], cols + lda.tau, 0.0)
                beta_w = jax.lax.psum(part, vocab_axis) / denom[:, None, None]
                scatter_w, v_scatter = lw, v_local
                per_pos_mask = in_shard
            else:
                beta_w = jax.vmap(
                    lambda st, ww: estep_mod.beta_w_from_stats(
                        st, ww, lda.tau))(stats, bw)
                scatter_w, v_scatter = bw, lda.vocab_size
                per_pos_mask = None
            if unique:
                # count-weighted sweeps over the U unique slots; the rows come
                # back with their token mass folded in, so the shared scatter
                # below needs no count reweighting (maskf IS the counts here)
                per_pos = estep_mod.fused_sweeps_sparse(estep, lda, k_gibbs,
                                                        beta_w, maskf)
            else:
                per_pos = estep_mod.fused_sweeps(estep, lda, k_gibbs, beta_w,
                                                 maskf)     # [n_local,B,L,K]
            if per_pos_mask is not None:
                # each vocab shard scatters only ITS words' contributions
                per_pos = jnp.where(per_pos_mask[..., None], per_pos, 0.0)
            stats_hat = estep_mod.stats_per_node(scatter_w, per_pos,
                                                 v_scatter, maskf)
        with jax.named_scope("deleda.blend"):
            rho = rho_fn(steps + 1).astype(stats.dtype)[:, None, None]
            new_stats = (1 - rho) * stats + rho * stats_hat
            return (jnp.where(al[:, None, None], new_stats, stats),
                    jnp.where(al, steps + 1, steps))

    shmap = jax.shard_map(
        update_fn, mesh=mesh,
        in_specs=(stats_spec, node, P(), node, node, node),
        out_specs=(stats_spec, node))
    return jax.jit(shmap, donate_argnums=(0,))


def run_mesh_deleda(lda: LDAConfig, words, mask, graph, n_steps: int,
                    batch_size: int, seed: int = 0, mesh=None,
                    schedule: GossipSchedule | None = None,
                    estep_backend: str = "dense",
                    scenario=None, alive: np.ndarray | None = None,
                    mesh_shape: tuple[int, int] | None = None,
                    eval_every: int = 0,
                    eval_spec: evaluation.EvalSpec | None = None,
                    corpus_layout: str = "dense",
                    eval_backend: str = "fused",
                    member: np.ndarray | None = None,
                    save_every: int = 0,
                    checkpoint_dir: str | None = None,
                    restore_from: str | None = None):
    """words/mask [n, D, L] node-sharded over the mesh "data" axis.

    Returns (stats [n, K, V], consensus trace, wall seconds) — plus, when
    ``eval_every > 0``, a fourth element: the in-loop held-out LP
    trajectory [n_steps/eval_every, probe_nodes] evaluated every
    ``eval_every`` steps from the first ``eval_spec.probe_nodes`` nodes'
    statistics via the Evaluation layer's blocked-stats path (no dense
    [K, V] beta temporary, chunk-invariant fold_in(key, doc_id) streams). The gossip
    path is pure MeshComm ppermute routing; the local-update step contains
    no node-axis collectives at all — each device runs ONE fused E-step
    over all of its local nodes' minibatches
    (`repro.core.estep.fused_sweeps`).

    ``mesh_shape=(node_devices, vocab_devices)`` builds a 2-D node x vocab
    execution grid (the Scale layer): statistics live sharded
    [n, K, V/vocab_devices] per device, gossip ppermutes each vocab
    shard's own block over the node axis (per-link payload drops by the
    vocab-axis size), and the E-step assembles the minibatch's beta
    columns with one O(B*L*K) psum over the vocab axis — the O(K*V) topic
    matrix is never materialized nor gathered. Documents are replicated
    over the vocab axis only (never across the node axis: the privacy
    placement is unchanged).

    ``corpus_layout="unique"`` (the Sparse corpus layer) converts the
    node shards host-side ONCE to the per-document (word_id, count) view
    trimmed to the realized U (`estep.unique_view`) and runs each
    device's fused E-step as count-weighted sweeps over U slots instead
    of per-position sweeps over L tokens (`estep.fused_sweeps_sparse`).
    The vocab-axis beta assembly and the per-shard scatter are layout-
    oblivious: counts serve as the scatter mask (a document is non-empty
    iff it has a positive count) and the per-unique rows already carry
    their full token mass.

    Dynamic-network regimes: pass a `repro.core.scenario.Scenario` (its
    compiled schedule + churn mask replace `schedule`/`alive`; `graph` may
    then be None) or an explicit `alive [T, n]` mask. Dropped pairs are
    self-partner rows, so `_route_matching` emits NO ppermute pass for them
    — a masked exchange costs zero wire bytes, not a wasted hop. Down
    (churned) nodes skip their local update and their step counter stays
    frozen, matching `run_deleda`'s semantics.
    """
    if mesh_shape is not None:
        if mesh is not None:
            raise ValueError("pass mesh OR mesh_shape, not both")
        if lda.vocab_size % mesh_shape[1]:
            raise ValueError(f"vocab axis {mesh_shape[1]} must divide "
                             f"vocab_size={lda.vocab_size}")
        mesh = comm_mod.make_grid_mesh(*mesh_shape)
    mesh = mesh or make_host_mesh()
    vocab_axis = "vocab" if mesh_shape is not None else None
    n = words.shape[0]
    comm = MeshComm(mesh=mesh, axis_name="data", vocab_axis=vocab_axis)
    assert n % comm.n_devices == 0, (n, comm.n_devices)
    if scenario is not None:
        if scenario.topology.n_nodes != n:
            raise ValueError(
                f"scenario topology has {scenario.topology.n_nodes} nodes "
                f"but the corpus shards {n}")
        compiled = scenario.compile(np.random.default_rng(seed))
        schedule, alive = compiled.schedule, compiled.alive
        if member is None:
            member = compiled.member
        if n_steps > schedule.n_rounds:
            raise ValueError(f"scenario horizon {schedule.n_rounds} < "
                             f"n_steps {n_steps}")
    if schedule is None:
        rng = np.random.default_rng(seed)
        schedule = GossipSchedule.draw_matchings(graph, n_steps, rng)
    partners = schedule.partners()[:n_steps]             # [T, n]
    if len(partners) < n_steps:
        raise ValueError(f"schedule has {len(partners)} rounds < "
                         f"n_steps {n_steps}")
    if alive is None:
        alive = np.ones((n_steps, n), bool)
    else:
        alive = np.asarray(alive, bool)[:n_steps]
        if alive.shape != (n_steps, n):
            raise ValueError(f"alive must cover [{n_steps}, {n}], "
                             f"got shape {alive.shape}")
    # permanent membership (lifecycle layer): a non-member behaves like a
    # churned node — no mixing, no update, frozen counter — and is
    # additionally excluded from the consensus trace. The compiled
    # scenario already encodes membership cancels in the schedule; the
    # host guard below just keeps explicit `member` inputs consistent.
    if member is None:
        live = alive
    else:
        member = np.asarray(member, bool)[:n_steps]
        if member.shape != (n_steps, n):
            raise ValueError(f"member must cover [{n_steps}, {n}], "
                             f"got shape {member.shape}")
        live = alive & member
    ids = np.arange(n, dtype=np.int32)
    # churn guard (host-side, symmetric): a pair with a down or
    # non-member endpoint becomes self-partners -> MeshComm routes no
    # ppermute for it
    rows = np.arange(n_steps)[:, None]
    pair_up = live & live[rows, partners]
    partners = np.where(pair_up, partners, ids)
    if corpus_layout == "unique":
        # host-side conversion, trimmed to the realized max unique count;
        # from here `words` holds unique ids and `mask` the int32 counts
        words, mask = estep_mod.unique_view(words, mask)

    node = P("data")
    stats_spec = P("data", None, vocab_axis) if vocab_axis else node
    sharding = NamedSharding(mesh, node)
    words = jax.device_put(words, sharding)
    mask = jax.device_put(mask, sharding)

    stats0 = jax.vmap(lambda k: init_stats(lda, k))(
        jax.random.split(jax.random.key(seed), n))
    stats0 = jax.device_put(stats0, NamedSharding(mesh, stats_spec))

    jitted = build_update_step(lda, batch_size, mesh, vocab_axis=vocab_axis,
                               estep_backend=estep_backend,
                               corpus_layout=corpus_layout)

    eval_fn = None
    if eval_every:
        if eval_spec is None:
            raise ValueError("eval_every > 0 needs an eval_spec "
                             "(repro.core.evaluation.EvalSpec)")
        if n_steps % eval_every != 0:
            raise ValueError(
                f"n_steps={n_steps} must be divisible by "
                f"eval_every={eval_every} (the LP trajectory is "
                f"[n_steps/eval_every, probe_nodes])")
        probe = min(eval_spec.probe_nodes, n)
        if eval_spec.layout == "unique":
            ew, em = estep_mod.unique_view(eval_spec.words,
                                           eval_spec.mask)
        else:
            ew, em = eval_spec.words, eval_spec.mask
        eval_fn = jax.jit(jax.vmap(
            lambda st: evaluation.heldout_lp_from_stats(
                eval_spec.key, ew, em, st,
                lda.tau, lda.alpha, eval_spec.n_particles,
                eval_spec.layout, eval_backend)))

    if save_every and checkpoint_dir is None:
        raise ValueError("save_every > 0 needs a checkpoint_dir")

    def carry_state(stats, steps, t_next):
        # the mesh carry as a sim-layer TrainState: per-step keys are
        # already absolute-indexed (jax.random.key(seed*100003 + t)), so
        # (stats, steps, t) is everything a bitwise resume needs; the
        # stored key just preserves the seed stream's flavor
        mrow = (jnp.ones((n,), bool) if member is None
                else jnp.asarray(member[min(t_next, n_steps) - 1]))
        return deleda_mod.TrainState(
            stats=jnp.asarray(stats), steps=jnp.asarray(steps),
            key=jax.random.key(seed),
            t=jnp.asarray(t_next, jnp.int32),
            stats_version=jnp.asarray(t_next, jnp.int32),
            member=mrow, cursor=jnp.zeros((), jnp.int32))

    stats = stats0
    steps = jnp.zeros((n,), jnp.int32)
    t_start = 0
    if restore_from is not None:
        restored = deleda_mod.restore_state(restore_from,
                                            carry_state(stats0, steps, 0))
        stats = jax.device_put(restored.stats,
                               NamedSharding(mesh, stats_spec))
        steps = jnp.asarray(restored.steps)
        t_start = int(restored.t)
        if t_start >= n_steps:
            raise ValueError(f"checkpoint at step {t_start} has nothing "
                             f"left to run (n_steps={n_steps})")

    alive_dev = jnp.asarray(live)
    member_dev = None if member is None else jnp.asarray(member)
    consensus = []
    eval_lp = []
    t0 = time.time()
    for t in range(t_start, n_steps):
        # ---- gossip: one matching round, MeshComm ppermute routing
        stats = comm.mix_matching(stats, partners[t])
        # ---- local G-OEM updates (every live node, synchronous variant)
        stats, steps = jitted(stats, steps,
                              jax.random.key(seed * 100003 + t),
                              words, mask,
                              jax.device_put(alive_dev[t], sharding))
        if t % 10 == 0 or t == n_steps - 1:
            mrow = None if member_dev is None else member_dev[t]
            consensus.append(float(gossip.consensus_distance(stats, mrow)))
        if eval_fn is not None and (t + 1) % eval_every == 0:
            eval_lp.append(np.asarray(eval_fn(stats[:probe])))
        if save_every and (t + 1) % save_every == 0:
            deleda_mod.save_state(checkpoint_dir,
                                  carry_state(stats, steps, t + 1))
    # async dispatch: without the barrier the wall clock reads queueing
    # time for the tail steps, not compute time
    jax.block_until_ready(stats)
    if eval_fn is not None:
        return stats, consensus, time.time() - t0, np.asarray(eval_lp)
    return stats, consensus, time.time() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--graph", default="complete",
                    choices=["complete", "ws"])
    ap.add_argument("--batch", type=int, default=5)
    ap.add_argument("--docs-per-node", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--estep-backend", default="dense",
                    choices=list(estep_mod.ESTEP_BACKENDS))
    ap.add_argument("--corpus-layout", default="dense",
                    choices=["dense", "unique"],
                    help="dense per-position sweeps or the unique-token "
                         "(CSR) count-weighted sweeps")
    ap.add_argument("--drop", type=float, default=0.0,
                    help="per-event gossip message drop probability")
    ap.add_argument("--churn", type=float, default=0.0,
                    help="stationary fraction of nodes down at any round")
    ap.add_argument("--mesh-shape", default=None, metavar="NODES,VOCAB",
                    help="2-D node x vocab device grid, e.g. 4,2 "
                         "(needs NODES*VOCAB devices)")
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint the carried state every N rounds "
                         "(0 = off; needs --checkpoint-dir)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for step_<t>/state.npz checkpoints")
    ap.add_argument("--restore", default=None,
                    help="resume from the latest committed checkpoint in "
                         "this directory (bitwise-identical trajectory)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    mesh_shape = None
    if args.mesh_shape:
        try:
            mesh_shape = tuple(int(x) for x in args.mesh_shape.split(","))
        except ValueError:
            ap.error(f"--mesh-shape expects NODES,VOCAB integers, "
                     f"got {args.mesh_shape!r}")
        if len(mesh_shape) != 2:
            ap.error(f"--mesh-shape expects exactly NODES,VOCAB, "
                     f"got {args.mesh_shape!r}")

    lda = LDAConfig(n_topics=PAPER.lda.n_topics,
                    vocab_size=PAPER.lda.vocab_size,
                    alpha=PAPER.lda.alpha, doc_len_max=32,
                    n_gibbs=10, n_gibbs_burnin=5)
    corpus = make_corpus(lda, jax.random.key(args.seed),
                         CorpusSpec(n_nodes=args.nodes,
                                    docs_per_node=args.docs_per_node,
                                    n_test=20))
    graph = (complete_graph(args.nodes) if args.graph == "complete"
             else watts_strogatz_graph(args.nodes, 4, 0.3, args.seed))
    print(f"n={args.nodes} graph={graph.name} lambda2={graph.lambda2():.4f}")

    scenario = None
    if args.drop > 0 or args.churn > 0:
        from repro.core.scenario import GraphSequence, Scenario
        scenario = Scenario(
            topology=GraphSequence.static(graph, args.steps),
            drop_prob=args.drop, churn=args.churn,
            name=f"drop{args.drop}-churn{args.churn}")
        print(f"scenario: drop={args.drop} churn={args.churn}")

    stats, consensus, sec = run_mesh_deleda(
        lda, corpus.words, corpus.mask, graph, args.steps, args.batch,
        args.seed, estep_backend=args.estep_backend, scenario=scenario,
        mesh_shape=mesh_shape, corpus_layout=args.corpus_layout,
        save_every=args.save_every, checkpoint_dir=args.checkpoint_dir,
        restore_from=args.restore)
    d = float(beta_distance(eta_star(stats[0]), corpus.beta_star))
    print(f"{args.steps} steps in {sec:.1f}s | consensus {consensus} "
          f"| D(beta, beta*) node0 = {d:.4f}")


if __name__ == "__main__":
    main()
