"""DELEDA on a device mesh: the paper's algorithm as an SPMD program.

The simulation (core/deleda.py) stacks the n agents on an array axis of
one device. This launcher runs the same ``run_deleda`` with
``DeledaConfig.mesh``: each device owns a contiguous block of nodes
(documents never leave their device, so the privacy constraint becomes a
physical placement), local G-OEM updates run data-parallel, and each
gossip round pairs devices with ``ppermute`` (``comm.mix_matching_sharded``).
``--mesh-shape NODES,VOCAB`` adds a second mesh axis that splits V into
blocks.

Note the schedule adaptation (recorded in DESIGN.md): single-edge
asynchronous gossip has no SPMD analogue — lockstep devices would idle.
The mesh variant uses random MATCHING rounds (every node pairs at most
once per round), the standard synchronous gossip generalization.

  PYTHONPATH=src python -m repro.launch.gossip_sim --nodes 8 --steps 50
"""

from __future__ import annotations

import argparse
import math
import time

import jax
import numpy as np
from jax.sharding import AxisType

from repro.configs.lda_paper import CONFIG as PAPER
from repro.core import deleda as deleda_mod
from repro.core import estep as estep_mod
from repro.core.comm import GossipSchedule
from repro.core.graph import complete_graph, watts_strogatz_graph
from repro.core.lda import LDAConfig, beta_distance, eta_star
from repro.data.lda_synthetic import CorpusSpec, make_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


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
    if args.mesh_shape:
        try:
            shape = tuple(int(x) for x in args.mesh_shape.split(","))
        except ValueError:
            ap.error(f"--mesh-shape expects NODES,VOCAB integers, "
                     f"got {args.mesh_shape!r}")
        if len(shape) != 2:
            ap.error(f"--mesh-shape expects exactly NODES,VOCAB, "
                     f"got {args.mesh_shape!r}")
        mesh = jax.make_mesh(shape, ("data", "vocab"),
                             axis_types=(AxisType.Auto,) * 2)
    else:
        mesh = make_host_mesh()

    lda = LDAConfig(n_topics=PAPER.lda.n_topics,
                    vocab_size=PAPER.lda.vocab_size,
                    alpha=PAPER.lda.alpha, doc_len_max=32,
                    n_gibbs=10, n_gibbs_burnin=5)
    config = deleda_mod.DeledaConfig(
        lda=lda, mode="sync", batch_size=args.batch, comm_backend="mesh",
        mesh=mesh, estep_backend=args.estep_backend,
        corpus_layout=args.corpus_layout)
    corpus = make_corpus(lda, jax.random.key(args.seed),
                         CorpusSpec(n_nodes=args.nodes,
                                    docs_per_node=args.docs_per_node,
                                    n_test=20))
    graph = (complete_graph(args.nodes) if args.graph == "complete"
             else watts_strogatz_graph(args.nodes, 4, 0.3, args.seed))
    print(f"n={args.nodes} graph={graph.name} lambda2={graph.lambda2():.4f}")

    rng = np.random.default_rng(args.seed)
    alive = member = None
    if args.drop > 0 or args.churn > 0:
        from repro.core.scenario import GraphSequence, Scenario
        scenario = Scenario(
            topology=GraphSequence.static(graph, args.steps),
            drop_prob=args.drop, churn=args.churn,
            name=f"drop{args.drop}-churn{args.churn}")
        print(f"scenario: drop={args.drop} churn={args.churn}")
        sched, degrees, alive, member = scenario.compile(rng).run_inputs()
    else:
        sched, degrees = (GossipSchedule.draw_matchings(graph, args.steps,
                                                        rng).data,
                          graph.degrees)

    # the consensus is recorded every 10 rounds where the run allows
    record_every = math.gcd(10, args.steps, args.save_every)
    t0 = time.perf_counter()
    trace = deleda_mod.run_deleda(
        config, jax.random.key(args.seed), corpus.words, corpus.mask,
        sched, degrees, args.steps, record_every=record_every,
        schedule_kind="matching", alive=alive, member=member,
        save_every=args.save_every, checkpoint_dir=args.checkpoint_dir,
        restore_from=args.restore)
    jax.block_until_ready(trace.stats)
    sec = time.perf_counter() - t0
    d = float(beta_distance(eta_star(trace.stats[0]), corpus.beta_star))
    consensus = [round(float(c), 4) for c in trace.consensus]
    print(f"{args.steps} steps in {sec:.1f}s | consensus {consensus} "
          f"| D(beta, beta*) node0 = {d:.4f}")


if __name__ == "__main__":
    main()
