"""Bring-up smoke run of DELEDA on a TPU: train, resume, evaluate, serve.

Drives the system's main path once through its own entry points at the
largest size the repo runs (the ``big`` regime of
``benchmarks/scale_bench.py``): n=1024 nodes, V=50,000 words, K=4 topics,
2 documents per node per step, L=16 tokens, 4 Gibbs sweeps (2 burn-in),
on a Watts-Strogatz graph (k=4, p=0.3) with matching rounds. The corpus
is generated from ``--seed``.

One chip (the default):

* estep     one fused E-step call, Pallas kernel against the jnp sweeps;
* train     ``run_deleda`` with in-loop held-out eval, once with the
            kernels (``lda_gibbs``, ``gossip_mix``, ``lda_l2r``) and once
            with the jnp paths; their held-out LP must fall in a band;
* lifecycle checkpoint mid-run, restore, and match the uninterrupted run
            bit for bit;
* eval      ``evaluate_heldout`` on node 0's statistic, kernel vs jnp;
* serve     a ``TopicServer`` answers mixed ``ll``/``mixture`` requests;
            its ``ll`` answers match ``evaluate_heldout``;
* mesh      ``run_mesh_deleda`` on the one-device mesh.

``--four-chips`` runs only the multi-chip path: ``run_mesh_deleda`` on a
4-device 1-D mesh and on the (2, 2) node x vocab grid, each compared with
the same schedule on one device.

Every kernel phase checks that the compiled program holds the kernel as a
``tpu_custom_call`` (not the interpreter). Any failed check exits non-zero
before the last line, which is one JSON object naming the device.

    python chip_smoke.py [--seed 0] [--four-chips]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import comm as comm_mod  # noqa: E402
from repro.core import deleda  # noqa: E402
from repro.core import estep as estep_mod  # noqa: E402
from repro.core import evaluation as eval_mod  # noqa: E402
from repro.core.graph import watts_strogatz_graph  # noqa: E402
from repro.core.lda import (LDAConfig, beta_distance, eta_star,  # noqa: E402
                            init_stats)
from repro.core.serving import ServingState, TopicServer  # noqa: E402
from repro.data.lda_synthetic import CorpusSpec, make_corpus  # noqa: E402
from repro.launch import gossip_sim  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402

# agreement limits between the kernel and jnp paths; the draws are the
# same ops on the same uniforms, so they agree up to reduction order and
# division rounding, and a rare draw that flips on a one-ulp tie
ESTEP_Z_AGREE = 0.999       # fraction of identical topic assignments
ESTEP_STATS_ATOL = 1e-3     # one flipped token moves an entry by <= 1/B
LL_RTOL = 1e-5              # per-document held-out log-likelihoods
LP_BAND = 0.01              # relative held-out LP gap of two whole runs
# mesh vs one-device simulator: different PRNG streams, so a statistical
# band; across three seeds each of the simulator, the 4x1 mesh and the 2x2
# grid at n=1,024, V=5,000 (CPU runs), D varied by at most 0.47% and
# consensus by a factor 1.022
MESH_BETA_BAND = 0.02       # relative D(mean beta, beta*) gap
MESH_CONSENSUS_RATIO = 1.15  # consensus distances within this factor


@dataclasses.dataclass(frozen=True)
class Size:
    n_nodes: int = 1024
    vocab: int = 50_000
    topics: int = 4
    batch: int = 2
    doc_len: int = 16
    sweeps: int = 4
    burnin: int = 2
    docs_per_node: int = 8
    n_test: int = 64
    particles: int = 4
    probe_nodes: int = 2
    steps: int = 8
    record_every: int = 4
    mesh_steps: int = 4
    requests: int = 48


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str):
    print(msg, flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def check_kernels(compiled_text: str, names, phase: str):
    """Each named kernel must sit on a ``tpu_custom_call`` line."""
    calls = [ln for ln in compiled_text.splitlines()
             if "tpu_custom_call" in ln]
    for name in names:
        check(any(name in ln for ln in calls),
              f"{phase}: kernel {name} is not a tpu_custom_call "
              f"(interpret mode?)")
    log(f"  {phase}: tpu_custom_call for {', '.join(names)} "
        f"({len(calls)} custom calls)")


@dataclasses.dataclass
class Ctx:
    size: Size
    seed: int
    lda: LDAConfig
    corpus: object
    graph: object
    sched: jax.Array
    degs: jax.Array
    spec: eval_mod.EvalSpec


def setup(size: Size, seed: int) -> Ctx:
    lda = LDAConfig(n_topics=size.topics, vocab_size=size.vocab, alpha=0.5,
                    doc_len_max=size.doc_len, n_gibbs=size.sweeps,
                    n_gibbs_burnin=size.burnin)
    corpus, t = timed(lambda: make_corpus(
        lda, jax.random.key(seed),
        CorpusSpec(n_nodes=size.n_nodes, docs_per_node=size.docs_per_node,
                   n_test=size.n_test)))
    graph = watts_strogatz_graph(size.n_nodes, 4, 0.3, seed)
    sched, degs = deleda.make_run_inputs(graph, size.steps, seed=seed,
                                         kind="matching")
    spec = eval_mod.EvalSpec(words=corpus.test_words, mask=corpus.test_mask,
                             key=jax.random.key(seed + 1),
                             n_particles=size.particles,
                             probe_nodes=size.probe_nodes)
    log(f"setup: n={size.n_nodes} V={size.vocab} K={size.topics} "
        f"L={size.doc_len} B={size.batch} sweeps={size.sweeps} "
        f"stats {size.n_nodes * size.topics * size.vocab * 4 / 1e9:.2f} GB"
        f" | corpus {t:.2f} s")
    return Ctx(size, seed, lda, corpus, graph, sched, degs, spec)


def deleda_config(ctx: Ctx, kernels: bool) -> deleda.DeledaConfig:
    return deleda.DeledaConfig(
        lda=ctx.lda, mode="sync", batch_size=ctx.size.batch,
        estep_backend="pallas" if kernels else "dense",
        comm_backend="pallas" if kernels else "dense",
        eval_backend="pallas" if kernels else "fused",
        eval_every=ctx.size.record_every)


def run_deleda(ctx: Ctx, cfg, n_steps=None, **kw):
    n_steps = n_steps or ctx.size.steps
    return deleda.run_deleda(
        cfg, jax.random.key(ctx.seed), ctx.corpus.words, ctx.corpus.mask,
        ctx.sched[:n_steps], ctx.degs, n_steps,
        record_every=ctx.size.record_every, eval_spec=ctx.spec, **kw)


def phase_estep(ctx: Ctx):
    """One E-step call on identical inputs, kernel against jnp."""
    s = ctx.size
    words = ctx.corpus.words[:, :s.batch].reshape(-1, s.doc_len)
    mask = ctx.corpus.mask[:, :s.batch].reshape(-1, s.doc_len)
    beta = eta_star(init_stats(ctx.lda, jax.random.key(ctx.seed + 2)),
                    ctx.lda.tau)
    key = jax.random.key(ctx.seed + 3)
    out = {}
    for name in ("pallas", "dense"):
        fn = jax.jit(lambda k, w, m, b, name=name: estep_mod.get_estep(name)(
            ctx.lda, k, w, m, b))
        _, t_first = timed(lambda: fn(key, words, mask, beta))
        out[name], t_steady = timed(lambda: fn(key, words, mask, beta))
        log(f"estep[{name}]: {words.shape[0]} docs, first call "
            f"{t_first:.3f} s, steady {t_steady * 1e3:.3f} ms")
        if name == "pallas":
            check_kernels(fn.lower(key, words, mask, beta).compile()
                          .as_text(), ["lda_gibbs"], "estep")
    k, j = out["pallas"], out["dense"]
    z_agree = float(jnp.mean(k.z == j.z))
    d_stats = float(jnp.abs(k.stats - j.stats).max())
    d_theta = float(jnp.abs(k.theta - j.theta).max())
    log(f"estep: kernel vs jnp: z agree {z_agree:.6f}, max |d stats| "
        f"{d_stats:.3e}, max |d theta| {d_theta:.3e} (limits "
        f"{ESTEP_Z_AGREE}, {ESTEP_STATS_ATOL})")
    check(z_agree >= ESTEP_Z_AGREE, f"estep: z agreement {z_agree}")
    check(d_stats <= ESTEP_STATS_ATOL, f"estep: stats differ by {d_stats}")


def train_text(ctx: Ctx, cfg) -> str:
    """Compiled text of the run's training segment (the same call
    ``run_deleda`` makes for a fresh run)."""
    s = ctx.size
    state = deleda.init_state(cfg, jax.random.key(ctx.seed), s.n_nodes)
    return deleda.train_steps.lower(
        cfg, state, ctx.corpus.words, ctx.corpus.mask, ctx.sched,
        jnp.ones((s.steps, s.n_nodes), jnp.float32),
        jnp.ones((s.steps, s.n_nodes), bool), None,
        record_every=s.record_every, kind="matching",
        eval_spec=ctx.spec).compile().as_text()


def phase_train(ctx: Ctx):
    s = ctx.size
    lps = {}
    stats0 = None
    for kernels in (True, False):
        name = "kernels" if kernels else "jnp"
        cfg = deleda_config(ctx, kernels)
        _, t_first = timed(lambda: run_deleda(ctx, cfg).stats)
        trace, t_steady = timed(lambda: run_deleda(ctx, cfg))
        lp = np.asarray(trace.eval_lp)
        check(np.isfinite(lp).all(), f"train[{name}]: non-finite LP {lp}")
        check(np.isfinite(np.asarray(trace.consensus)).all(),
              f"train[{name}]: non-finite consensus")
        lps[name] = float(lp[-1].mean())
        log(f"train[{name}]: {s.steps} steps, first call {t_first:.2f} s "
            f"(compile incl.), steady {t_steady / s.steps * 1e3:.2f} "
            f"ms/step | held-out LP {lp.mean(axis=1).round(4).tolist()} "
            f"| consensus {float(trace.consensus[-1]):.4f}")
        if kernels:
            stats0 = trace.stats[0]
            check_kernels(train_text(ctx, cfg),
                          ["lda_gibbs", "gossip_mix", "lda_l2r"],
                          "train")
        del trace
    gap = abs(lps["kernels"] - lps["jnp"]) / abs(lps["jnp"])
    log(f"train: held-out LP kernels {lps['kernels']:.5f} vs jnp "
        f"{lps['jnp']:.5f}, relative gap {gap:.2e} (band {LP_BAND})")
    check(gap <= LP_BAND, f"train: LP gap {gap} outside {LP_BAND}")
    return stats0


def phase_lifecycle(ctx: Ctx):
    s = ctx.size
    cfg = deleda_config(ctx, kernels=True)
    half = s.steps // 2
    with tempfile.TemporaryDirectory() as d_full, \
            tempfile.TemporaryDirectory() as d_cut:
        (full, t_full) = timed(lambda: run_deleda(
            ctx, cfg, save_every=half, checkpoint_dir=d_full))
        run_deleda(ctx, cfg, n_steps=half, save_every=half,
                   checkpoint_dir=d_cut)           # "killed" at step half
        resumed, t_res = timed(lambda: run_deleda(
            ctx, cfg, save_every=half, checkpoint_dir=d_cut,
            restore_from=d_cut))
        same = bool(np.array_equal(np.asarray(full.stats),
                                   np.asarray(resumed.stats)))
        same_steps = bool(np.array_equal(np.asarray(full.steps),
                                         np.asarray(resumed.steps)))
    log(f"lifecycle: save every {half} steps; uninterrupted {t_full:.2f} s,"
        f" resumed from step {half} {t_res:.2f} s; statistic bitwise "
        f"equal: {same}, step counters equal: {same_steps}")
    check(same and same_steps, "lifecycle: resumed run differs")


def phase_eval(ctx: Ctx, stats0):
    s = ctx.size
    c = ctx.corpus
    key = ctx.spec.key
    lls = {}
    for backend in ("pallas", "fused"):
        def ev(backend=backend):
            return eval_mod.evaluate_heldout(
                key, c.test_words, c.test_mask, stats=stats0,
                tau=ctx.lda.tau, alpha=ctx.lda.alpha,
                n_particles=s.particles, backend=backend)
        _, t_first = timed(ev)
        lls[backend], t_steady = timed(ev)
        log(f"eval[{backend}]: {s.n_test} docs x {s.particles} particles,"
            f" first call {t_first:.3f} s, steady {t_steady * 1e3:.3f} ms")
    ids = jnp.arange(s.n_test, dtype=jnp.int32)
    check_kernels(eval_mod.ll_slab_from_stats.lower(
        key, ids, c.test_words, c.test_mask, stats0, ctx.lda.tau,
        ctx.lda.alpha, s.particles, "dense", "pallas").compile().as_text(),
        ["lda_l2r"], "eval")
    k, f = np.asarray(lls["pallas"]), np.asarray(lls["fused"])
    check(np.isfinite(k).all(), "eval: non-finite LL")
    rel = float(np.max(np.abs(k - f) / np.maximum(np.abs(f), 1e-30)))
    log(f"eval: kernel vs jnp max relative |d ll| {rel:.3e} "
        f"(limit {LL_RTOL})")
    check(rel <= LL_RTOL, f"eval: LL differ by {rel}")


def phase_serve(ctx: Ctx, stats0):
    s = ctx.size
    c = ctx.corpus
    key = jax.random.key(ctx.seed + 4)
    words = np.asarray(c.test_words)
    lens = np.asarray(c.test_mask).sum(-1).astype(int)
    ids = [i for i in range(s.n_test) if lens[i] > 0][:s.requests]
    check(len(ids) >= 2, "serve: too few non-empty held-out documents")
    server = TopicServer(ServingState(stats0, tau=ctx.lda.tau),
                         alpha=ctx.lda.alpha, key=key,
                         doc_len_max=s.doc_len, n_particles=s.particles,
                         n_buckets=1, backend="pallas")

    def serve_all():
        for n, i in enumerate(ids):
            server.submit(words[i, :lens[i]],
                          kind="ll" if n % 2 == 0 else "mixture", doc_id=i)
        return server.drain()

    _, t_first = timed(serve_all)
    results, t_steady = timed(serve_all)
    lat = np.asarray([r.latency_s for r in results])
    log(f"serve: {len(ids)} mixed requests, first drain {t_first:.3f} s "
        f"(compile incl.), steady {t_steady * 1e3:.3f} ms "
        f"({len(ids) / t_steady:.1f} req/s, mean latency "
        f"{lat.mean() * 1e3:.3f} ms, {server.n_slabs} slabs so far)")
    lb = server.buckets[0]
    check_kernels(eval_mod.ll_slab_from_beta.lower(
        key, jnp.zeros((server.slab_docs[lb],), jnp.int32),
        jnp.zeros((server.slab_docs[lb], lb), jnp.int32),
        jnp.zeros((server.slab_docs[lb], lb), bool),
        server.state.beta(), ctx.lda.alpha, s.particles, "dense",
        "pallas").compile().as_text(), ["lda_l2r"], "serve")
    want = np.asarray(eval_mod.evaluate_heldout(
        key, c.test_words, c.test_mask, stats=stats0, tau=ctx.lda.tau,
        alpha=ctx.lda.alpha, n_particles=s.particles, backend="pallas"))
    ll = {r.doc_id: r.value for r in results if r.kind == "ll"}
    mix = [np.asarray(r.value) for r in results if r.kind == "mixture"]
    got = np.asarray([ll[i] for i in sorted(ll)])
    rel = float(np.max(np.abs(got - want[sorted(ll)])
                       / np.abs(want[sorted(ll)])))
    sums = np.asarray([m.sum() for m in mix])
    log(f"serve: ll vs evaluate_heldout max relative diff {rel:.3e} "
        f"(limit {LL_RTOL}); {len(mix)} mixtures, max |sum - 1| "
        f"{float(np.abs(sums - 1).max()):.2e}")
    check(rel <= LL_RTOL, f"serve: ll answers differ by {rel}")
    check(all(m.shape == (s.topics,) and np.isfinite(m).all() for m in mix)
          and np.allclose(sums, 1.0, atol=1e-5), "serve: bad mixtures")


def update_text(ctx: Ctx, mesh, vocab_axis: str | None = None) -> str:
    """Compiled text of the mesh launcher's local-update step."""
    s = ctx.size
    node = NamedSharding(mesh, P("data"))
    fn = gossip_sim.build_update_step(ctx.lda, s.batch, mesh,
                                      vocab_axis=vocab_axis,
                                      estep_backend="pallas")
    stats = jax.ShapeDtypeStruct(
        (s.n_nodes, s.topics, s.vocab), jnp.float32,
        sharding=NamedSharding(mesh, P("data", None, vocab_axis)))
    return fn.lower(
        stats, jax.ShapeDtypeStruct((s.n_nodes,), jnp.int32, sharding=node),
        jax.random.key(0),
        jax.device_put(ctx.corpus.words, node),
        jax.device_put(ctx.corpus.mask, node),
        jax.device_put(jnp.ones((s.n_nodes,), bool), node)
    ).compile().as_text()


def mesh_run(ctx: Ctx, **kw):
    return gossip_sim.run_mesh_deleda(
        ctx.lda, ctx.corpus.words, ctx.corpus.mask, ctx.graph,
        ctx.size.mesh_steps, ctx.size.batch, seed=ctx.seed,
        estep_backend="pallas", **kw)


def sim_reference(ctx: Ctx) -> tuple[float, float]:
    """D(mean beta, beta*) and final consensus of ``run_deleda`` on one
    device over the mesh runs' schedule length."""
    s = ctx.size
    cfg = deleda.DeledaConfig(lda=ctx.lda, mode="sync", batch_size=s.batch,
                              estep_backend="pallas")
    sim, t_sim = timed(lambda: deleda.run_deleda(
        cfg, jax.random.key(ctx.seed), ctx.corpus.words, ctx.corpus.mask,
        ctx.sched[:s.mesh_steps], ctx.degs, s.mesh_steps,
        record_every=s.mesh_steps))
    d_sim = float(beta_distance(eta_star(sim.stats.mean(0)),
                                ctx.corpus.beta_star))
    c_sim = float(sim.consensus[-1])
    log(f"sim[1 device]: {s.mesh_steps} steps {t_sim:.2f} s | "
        f"D(mean beta, beta*) {d_sim:.5f} | consensus {c_sim:.4f}")
    return d_sim, c_sim


def check_against_sim(ctx: Ctx, stats, cons, ref, label: str):
    """The mesh run lands in the simulator's band (different streams)."""
    d_sim, c_sim = ref
    d_mesh = float(beta_distance(eta_star(jnp.asarray(stats).mean(0)),
                                 ctx.corpus.beta_star))
    c_mesh = float(cons[-1])
    gap = abs(d_mesh - d_sim) / d_sim
    ratio = max(c_mesh, c_sim) / max(min(c_mesh, c_sim), 1e-30)
    log(f"{label}: D(mean beta, beta*) {d_mesh:.5f} (gap to sim {gap:.2e}, "
        f"band {MESH_BETA_BAND}) | consensus {c_mesh:.4f} (ratio to sim "
        f"{ratio:.4f}, limit {MESH_CONSENSUS_RATIO})")
    check(np.isfinite(np.asarray(cons)).all(), f"{label}: non-finite "
          "consensus")
    check(gap <= MESH_BETA_BAND, f"{label}: beta-distance gap {gap}")
    check(ratio <= MESH_CONSENSUS_RATIO, f"{label}: consensus ratio {ratio}")


def phase_mesh_one(ctx: Ctx):
    s = ctx.size
    mesh = make_host_mesh()
    (stats, cons, wall), t_first = timed(lambda: mesh_run(ctx, mesh=mesh))
    (stats, cons, wall), t_steady = timed(lambda: mesh_run(ctx, mesh=mesh))
    check(stats.shape == (s.n_nodes, s.topics, s.vocab),
          f"mesh: stats shape {stats.shape}")
    log(f"mesh[1 device]: {s.mesh_steps} steps, first call {t_first:.2f} s"
        f", second call {t_steady:.2f} s (loop {wall / s.mesh_steps * 1e3:.2f}"
        f" ms/step)")
    check_against_sim(ctx, stats, cons, sim_reference(ctx), "mesh[1 device]")
    check_kernels(update_text(ctx, mesh), ["lda_gibbs"], "mesh")


def check_placement(stats, mesh, phase: str):
    """Every device holds exactly its own block of nodes (and vocab)."""
    n, k, v = stats.shape
    shape = dict(mesh.shape)
    rows = n // shape["data"]
    cols = v // shape.get("vocab", 1)
    pos = {d: idx for idx, d in np.ndenumerate(mesh.devices)}
    for sh in stats.addressable_shards:
        i = pos[sh.device]
        r0 = i[0] * rows
        c0 = i[1] * cols if len(i) > 1 else 0
        got = (range(*sh.index[0].indices(n)), range(*sh.index[2].indices(v)))
        check(sh.data.shape == (rows, k, cols)
              and got == (range(r0, r0 + rows), range(c0, c0 + cols)),
              f"{phase}: device {sh.device} holds {sh.index}, "
              f"shape {sh.data.shape}")
    log(f"  {phase}: each of {len(stats.addressable_shards)} devices holds "
        f"its own [{rows}, {k}, {cols}] block")


def phase_four_chips(ctx: Ctx):
    s = ctx.size
    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, found "
          f"{len(jax.devices())}")
    partners = np.asarray(ctx.sched[0])
    stats0 = jax.vmap(lambda k: init_stats(ctx.lda, k))(
        jax.random.split(jax.random.key(ctx.seed + 5), s.n_nodes))
    want_mix = comm_mod.DenseSimComm().mix_matching(stats0, partners)
    ref = sim_reference(ctx)

    for label, kw in (("4x1 mesh", {"mesh": make_host_mesh()}),
                      ("2x2 grid", {"mesh_shape": (2, 2)})):
        mesh = kw.get("mesh") or comm_mod.make_grid_mesh(2, 2)
        vocab_axis = "vocab" if "mesh_shape" in kw else None
        mc = comm_mod.MeshComm(mesh=mesh, vocab_axis=vocab_axis)
        placed = jax.device_put(stats0, NamedSharding(
            mesh, P("data", None, vocab_axis)))
        got_mix, t_mix = timed(lambda: mc.mix_matching(placed, partners))
        d_mix = float(jnp.abs(jnp.asarray(got_mix) - want_mix).max())
        log(f"{label}: MeshComm.mix_matching vs DenseSimComm max |d| "
            f"{d_mix:.3e} ({t_mix * 1e3:.2f} ms)")
        check(d_mix <= 1e-6, f"{label}: mixing differs by {d_mix}")
        del got_mix, placed

        (stats, cons, wall), t_first = timed(lambda: mesh_run(ctx, **kw))
        check_placement(stats, mesh, label)
        check_kernels(update_text(ctx, mesh, vocab_axis), ["lda_gibbs"],
                      label)
        log(f"{label}: {s.mesh_steps} steps, call {t_first:.2f} s (loop "
            f"{wall / s.mesh_steps * 1e3:.2f} ms/step)")
        check_against_sim(ctx, stats, cons, ref, label)
        del stats


def run(size: Size, seed: int, four_chips: bool):
    ctx = setup(size, seed)
    if four_chips:
        phase_four_chips(ctx)
        return
    phase_estep(ctx)
    stats0 = phase_train(ctx)
    phase_lifecycle(ctx)
    phase_eval(ctx, stats0)
    phase_serve(ctx, stats0)
    phase_mesh_one(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh and node x vocab grid "
                         "path, against the same schedule on one device")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} | compile cache {cache}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this run needs the chip",
              file=sys.stderr)
        return 1
    try:
        run(Size(), args.seed, args.four_chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
