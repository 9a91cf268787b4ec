"""Topic-inference serving launcher: a node answering live queries.

The online half of the paper's story: after (or while) the gossip
training runs, each node holds a sufficient statistic and must answer
topic queries *locally* — per-document topic mixtures and held-out
left-to-right log-likelihoods — at interactive rates. This launcher
stands up one node: it trains a quick G-OEM statistic (or restores one
from a checkpoint), wraps it in the staleness-aware
:class:`core.serving.ServingState` cache, and drives a seeded open-loop
Poisson request stream through the continuous-batching
:class:`core.serving.TopicServer`. ``--gossip-every`` publishes a fresh
statistic every N slabs mid-serve, exercising the cache-invalidation
protocol (results report which ``stats_version`` answered them).

  PYTHONPATH=src python -m repro.launch.serve_topics --requests 200
  PYTHONPATH=src python -m repro.launch.serve_topics \
      --restore /tmp/lda_ckpt --rate 500 --mixture-frac 0.5
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.core import serving
from repro.core.lda import LDAConfig, LDAState, init_state
from repro.core.oem import run_oem
from repro.data.lda_synthetic import CorpusSpec, make_corpus
from repro.launch.compile_cache import enable_compile_cache


def _percentile(xs: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


def _get_stats(config: LDAConfig, args, corpus) -> LDAState:
    key = jax.random.key(args.seed)
    if args.restore_train:
        # serve one node of a DELEDA training run: restore the carried
        # TrainState (lifecycle layer) and lift node i's statistic row
        # into the single-node serving state — the post-training story of
        # the paper, "each node answers queries from its own statistic"
        from repro.core import deleda
        dcfg = deleda.DeledaConfig(
            lda=config, vocab_shards=args.restore_vocab_shards)
        # no config= here: the serving side only knows the model shape,
        # not the training hyperparameters, so a digest check would
        # always warn spuriously
        like = deleda.init_state(dcfg, key, args.restore_nodes)
        tstate = deleda.restore_state(args.restore_train, like)
        i = args.restore_node
        if not 0 <= i < tstate.n_nodes:
            raise SystemExit(f"--restore-node {i} out of range for the "
                             f"{tstate.n_nodes}-node checkpoint")
        if not bool(tstate.member[i]):
            print(f"note: node {i} is not a member at step "
                  f"{int(tstate.t)} — serving its frozen statistic")
        state = LDAState(stats=tstate.dense_stats()[i],
                         step=jnp.asarray(tstate.steps[i]),
                         stats_version=jnp.asarray(tstate.stats_version))
        print(f"restored train state: node {i}/{tstate.n_nodes} at "
              f"round {int(tstate.t)} (local steps "
              f"{int(tstate.steps[i])}, stats_version "
              f"{int(tstate.stats_version)})")
        return state
    if args.restore:
        like = init_state(config, key)
        state = restore_checkpoint(args.restore, like)
        print(f"restored checkpoint: step={int(state.step)} "
              f"stats_version={int(state.stats_version)}")
        return state
    trace = run_oem(config, jax.random.fold_in(key, 1), corpus.flat_words,
                    corpus.flat_mask, n_steps=args.train_steps,
                    batch_size=args.train_batch,
                    record_every=args.train_steps)
    state = trace.state
    print(f"trained G-OEM statistic: {args.train_steps} steps "
          f"(stats_version={int(state.stats_version)})")
    if args.save:
        path = save_checkpoint(args.save, state, int(state.step))
        print("checkpoint:", path)
    return state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--topics", type=int, default=5)
    ap.add_argument("--vocab", type=int, default=1000)
    ap.add_argument("--doc-len", type=int, default=32)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--train-steps", type=int, default=20)
    ap.add_argument("--train-batch", type=int, default=16)
    ap.add_argument("--save", default=None,
                    help="checkpoint dir to save the trained statistic")
    ap.add_argument("--restore", default=None,
                    help="checkpoint dir to restore instead of training")
    ap.add_argument("--restore-train", default=None, metavar="DIR",
                    help="restore a DELEDA TrainState checkpoint "
                         "(run_deleda's save_every, as gossip_sim "
                         "--save-every writes it) and serve "
                         "one node's statistic")
    ap.add_argument("--restore-node", type=int, default=0,
                    help="which node's statistic to serve (--restore-train)")
    ap.add_argument("--restore-nodes", type=int, default=50,
                    help="node count the train checkpoint was written with")
    ap.add_argument("--restore-vocab-shards", type=int, default=1,
                    help="vocab_shards the train checkpoint was written "
                         "with (the carried stats layout)")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate (requests/sec)")
    ap.add_argument("--mixture-frac", type=float, default=0.25,
                    help="fraction of requests asking for topic mixtures")
    ap.add_argument("--particles", type=int, default=10)
    ap.add_argument("--buckets", type=int, default=3)
    ap.add_argument("--slab-docs", type=int, default=None)
    ap.add_argument("--backend", default="fused")
    ap.add_argument("--gossip-every", type=int, default=0,
                    help="publish a fresh statistic every N slabs (0 = off)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    config = LDAConfig(n_topics=args.topics, vocab_size=args.vocab,
                       alpha=args.alpha, doc_len_max=args.doc_len,
                       n_gibbs=30, n_gibbs_burnin=15)
    corpus = make_corpus(config, jax.random.fold_in(jax.random.key(args.seed),
                                                    7),
                         CorpusSpec(n_nodes=10, docs_per_node=20,
                                    n_test=max(args.requests, 100)))
    state = _get_stats(config, args, corpus)

    sstate = serving.ServingState(state.stats, tau=config.tau,
                                  version=int(state.stats_version))
    server = serving.TopicServer(
        sstate, alpha=config.alpha, key=jax.random.key(args.seed + 1),
        doc_len_max=config.doc_len_max, n_particles=args.particles,
        n_buckets=args.buckets, slab_docs=args.slab_docs,
        backend=args.backend)
    print(f"server: buckets={server.buckets} "
          f"slab_docs={server.slab_docs} backend={args.backend}")

    # request stream: held-out documents (trimmed to true length), seeded
    # Poisson arrival times, a seeded coin for the query kind
    rng = np.random.default_rng(args.seed)
    test_words = np.asarray(corpus.test_words)
    test_lens = np.asarray(corpus.test_mask).sum(-1).astype(int)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    kinds = np.where(rng.random(args.requests) < args.mixture_frac,
                     "mixture", "ll")

    results: list[serving.ServeResult] = []
    t0 = time.perf_counter()
    submitted = 0
    while len(results) < args.requests:
        # open-loop pacing clock: intentionally host wall time, arrivals
        # must not wait on device work
        now = time.perf_counter() - t0   # lint: allow(timer-no-barrier)
        while submitted < args.requests and arrivals[submitted] <= now:
            i = submitted % test_words.shape[0]
            server.submit(test_words[i, :max(test_lens[i], 1)],
                          kind=str(kinds[submitted]), doc_id=i)
            submitted += 1
        if server.pending_count():
            batch = server.step()
            results.extend(batch)
            if args.gossip_every and server.n_slabs % args.gossip_every == 0:
                # a gossip round lands mid-serve: perturb the statistic the
                # way a neighbor averaging would, publish, version bumps —
                # the next slab lazily re-derives the cache
                mixed = 0.5 * (sstate.stats + jnp.roll(sstate.stats, 1, 0))
                sstate.publish(mixed)
        elif submitted < args.requests:
            # idle until the next arrival — host wall by construction
            # lint: allow(timer-no-barrier)
            time.sleep(max(0.0, arrivals[submitted] - (time.perf_counter()
                                                       - t0)))
    # every result was materialized by server.step() (numpy values), so
    # the serve wall is already closed when the queue drains
    wall = time.perf_counter() - t0   # lint: allow(timer-no-barrier)

    lat = [r.latency_s for r in results]
    lls = [r.value for r in results if r.kind == "ll"]
    versions = sorted({r.stats_version for r in results})
    print(f"served {len(results)} requests in {wall:.2f}s "
          f"({len(results) / wall:.1f} req/s offered {args.rate:.0f}/s)")
    print(f"latency p50 {1e3 * _percentile(lat, 50):.1f}ms "
          f"p99 {1e3 * _percentile(lat, 99):.1f}ms | "
          f"slabs {server.n_slabs} occupancy {server.mean_occupancy:.2f}")
    print(f"stats_versions answered: {versions} "
          f"(cache derivations: {sstate.n_derivations})")
    if lls:
        print(f"mean held-out LL {np.mean(lls):.3f} over {len(lls)} docs")
    mix = next((r for r in results if r.kind == "mixture"), None)
    if mix is not None:
        top = np.argsort(mix.value)[::-1][:3]
        print(f"sample mixture doc={mix.doc_id}: top topics {top.tolist()} "
              f"weights {np.asarray(mix.value)[top].round(3).tolist()}")


if __name__ == "__main__":
    main()
