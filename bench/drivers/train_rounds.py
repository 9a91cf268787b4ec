"""Training traffic: ``deleda.train_steps`` segments from ``init_state``.

Set-up makes the corpus and the gossip schedule from the seed, builds the
state with ``deleda.init_state`` and drives the first ``check_steps``
segments through the window's own call; those steps compile it, and the
reference follows them after the window. The window then runs segments
back to back, each dispatched as its predecessor completes, until
``--seconds`` have passed; it ends when the last segment started in it
completes. ``train_tokens_per_s`` is the real (unmasked) tokens of every
node's minibatches in those rounds over the window.

With ``--trace 1`` the window is traced instead (``trace_segments``
segments), and afterwards, outside it, the E-step entry and the gossip mix
are each called alone on the carried statistic under a second trace.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, gen, reference, trace
from repro.core import comm as comm_mod
from repro.core import deleda
from repro.core import estep as estep_mod
from repro.core.lda import LDAConfig

# Limits of the comparison with the reference; the readings each was set
# from are in PERF.md, section 2.
LIMITS = {"marginal_gap": 1e-4, "change1_gap": 5e-2, "change3_gap": 2e-2}


def _lda(cfg: dict) -> LDAConfig:
    return LDAConfig(n_topics=cfg["n_topics"], vocab_size=cfg["vocab_size"],
                     alpha=cfg["alpha"], tau=cfg["tau"],
                     n_gibbs=cfg["n_gibbs"],
                     n_gibbs_burnin=cfg["n_gibbs_burnin"],
                     doc_len_max=cfg["doc_len_max"])


def make_inputs(run: common.Run):
    """Corpus, schedule and keys: a pure function of the seed."""
    cfg = run.config
    base = gen.key_from_seed(run.seed)
    words, mask = gen.corpus(
        jax.random.fold_in(base, 0), n_nodes=cfg["n_nodes"],
        docs_per_node=cfg["docs_per_node"], doc_len=cfg["doc_len_max"],
        vocab=cfg["vocab_size"], n_topics=cfg["n_topics"],
        alpha=cfg["alpha"], mean_len=cfg["mean_doc_len"],
        sigma=cfg["doc_len_sigma"], zipf=cfg["zipf_exponent"],
        concentration=cfg["topic_concentration"])
    rng = np.random.default_rng([run.seed, 2])
    g = cfg["graph"]
    edges = gen.watts_strogatz_edges(cfg["n_nodes"], g["k"], g["p"], rng)
    partners = gen.matchings(edges, cfg["n_nodes"],
                             run.traffic["schedule_rounds"], rng)
    return words, mask, partners, jax.random.fold_in(base, 1)


@jax.jit
def _change_norms(a, b):
    n = a.shape[0]
    return jnp.linalg.norm((a - b).reshape(n, -1), axis=1)


@partial(jax.jit, static_argnums=0)
def _change_from_init(dcfg, stats, run_key):
    """Per-node ||s - s_0||, with s_0 made again from its key: the
    initial statistic is not kept alive beside a segment's temporaries."""
    s0 = deleda.init_state(dcfg, run_key, stats.shape[0]).stats
    return _change_norms(stats, s0)


@jax.jit
def _marginals(stats):
    """Per-node word marginals sum_k s[i, k, v], [n, V], in float32."""
    return stats.astype(jnp.float32).sum(axis=1)


@jax.jit
def _window_tokens(run_key, lengths, t_abs, batch_ids):
    """Real tokens in every node's minibatches of rounds ``t_abs``.

    The minibatch of node i in round t is ``randint(fold_in(k_sel, i),
    (B,), 0, D)`` with ``k_sel`` the first half of ``fold_in(key, t)``:
    the system's stream contract, which the reference comparison holds
    it to.
    """
    n, d = lengths.shape

    def one_round(t):
        k_sel, _ = jax.random.split(jax.random.fold_in(run_key, t))
        idx = jax.vmap(lambda i: jax.random.randint(
            jax.random.fold_in(k_sel, i), batch_ids.shape, 0, d))(
                jnp.arange(n, dtype=jnp.int32))
        return jnp.take_along_axis(lengths, idx, axis=1).sum()

    return jax.lax.map(one_round, t_abs).sum()


def first_steps(trainer, run_key, n_check) -> dict:
    """Drive the first ``n_check`` segments; keep what the reference checks."""
    prog = {}
    for i in range(n_check):
        trainer.segment()
        if i == 0:
            prog["change1"] = np.asarray(_change_from_init(
                trainer.cfg, trainer.state.stats, run_key))
    prog["change_last"] = np.asarray(_change_from_init(
        trainer.cfg, trainer.state.stats, run_key))
    prog["marginals"] = np.asarray(_marginals(trainer.state.stats))
    return prog


def reference_steps(run, words, mask, partners, run_key, n_rounds, seg,
                    dtype) -> dict:
    """The reference over the same rounds, with the same readings."""
    cfg = run.config
    stats, r_key = reference.init_stats(run_key, cfg["n_nodes"],
                                        cfg["n_topics"], cfg["vocab_size"],
                                        dtype)
    s0 = stats
    steps = jnp.zeros((cfg["n_nodes"],), jnp.int32)
    hp = dict(batch=cfg["batch_size"], tau=cfg["tau"], alpha=cfg["alpha"],
              n_sweeps=cfg["n_gibbs"], burnin=cfg["n_gibbs_burnin"],
              rho_t0=cfg["rho_t0"], rho_kappa=cfg["rho_kappa"])
    out = {}
    for t in range(n_rounds):
        stats, steps = reference.round_(stats, steps, r_key, jnp.int32(t),
                                        jnp.asarray(partners[t]), words,
                                        mask, **hp)
        if t + 1 == seg:
            out["change1"] = np.asarray(_change_norms(
                stats.astype(jnp.float32), s0.astype(jnp.float32)))
    out["change_last"] = np.asarray(_change_norms(
        stats.astype(jnp.float32), s0.astype(jnp.float32)))
    out["marginals"] = np.asarray(_marginals(stats))
    return out


def median_node_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """The median node's |program norm - reference norm| / reference norm."""
    return float(np.median(np.abs(prog - ref) / ref))


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers (PERF.md, section 2)."""
    dm = np.linalg.norm(prog["marginals"] - ref["marginals"], axis=1)
    return {
        "marginal_gap": float(np.max(
            dm / np.linalg.norm(ref["marginals"], axis=1))),
        "change1_gap": median_node_gap(prog["change1"], ref["change1"]),
        "change3_gap": median_node_gap(prog["change_last"],
                                       ref["change_last"]),
    }


def compare(prog: dict, ref: dict) -> list[common.Check]:
    return [common.Check(k, v, LIMITS[k])
            for k, v in readings(prog, ref).items()]


class Trainer:
    """The system under test, driven as its users call it."""

    def __init__(self, run: common.Run, words, mask, partners, run_key):
        cfg = run.config
        self.cfg = deleda.DeledaConfig(
            lda=_lda(cfg), mode=cfg["mode"], batch_size=cfg["batch_size"],
            rho_kappa=cfg["rho_kappa"], rho_t0=cfg["rho_t0"])
        self.seg = run.traffic["segment_rounds"]
        n = cfg["n_nodes"]
        self.words, self.mask = words, mask
        self.n_sched = len(partners) // self.seg
        self.sched = [jnp.asarray(partners[i * self.seg:(i + 1) * self.seg])
                      for i in range(self.n_sched)]
        self.corr = jnp.ones((self.seg, n), jnp.float32)
        self.live = jnp.ones((self.seg, n), bool)
        self.state = deleda.init_state(self.cfg, run_key, n)
        self.n_segments = 0

    def segment(self):
        """Dispatch one segment."""
        with common.span("segment dispatch"):
            self.state, _trace = deleda.train_steps(
                self.cfg, self.state, self.words, self.mask,
                self.sched[self.n_segments % self.n_sched], self.corr,
                self.live, record_every=self.seg)
        self.n_segments += 1


def run(run: common.Run) -> common.Result:
    cfg, tr_cfg = run.config, run.traffic
    words, mask, partners, run_key = make_inputs(run)
    trainer = Trainer(run, words, mask, partners, run_key)
    seg, n_check = trainer.seg, tr_cfg["check_steps"]

    # -- the first steps: compile, warm, and record what the reference checks
    prog = first_steps(trainer, run_key, n_check)
    lengths = mask.sum(-1).astype(jnp.int32)
    batch_ids = jnp.zeros((cfg["batch_size"],), jnp.int32)
    t_first = trainer.n_segments * seg
    _window_tokens(run_key, lengths, jnp.arange(t_first, t_first + seg),
                   batch_ids).block_until_ready()
    jax.block_until_ready(trainer.state.stats)
    setup_s = common.now() - run.t_process

    # -- the window
    layer = None
    if run.trace:
        n_seg = tr_cfg["trace_segments"]
        with common.profiled("window") as tdir:
            t0 = common.now()
            for _ in range(n_seg):
                trainer.segment()
                jax.block_until_ready(trainer.state.stats)
            window_s = common.now() - t0
        window = trace.summarize(tdir)
    else:
        n_seg = 0
        t0 = common.now()
        while common.now() - t0 < run.seconds:
            trainer.segment()
            jax.block_until_ready(trainer.state.stats)
            n_seg += 1
        window_s = common.now() - t0
    rounds = n_seg * seg
    tokens = int(_window_tokens(run_key, lengths,
                                jnp.arange(t_first, t_first + rounds),
                                batch_ids))
    mem = common.memory_peak_bytes(run.devices)

    if run.trace:
        probes = _probe(run, trainer, partners, run_key)
        layer = common.Layer(
            window=window, probes=probes, config=cfg, peaks=run.peaks,
            chips=run.chips,
            counters={"rounds": rounds, "tokens": tokens,
                      "record_every": seg})

    # -- the comparison, after the window, with the program's state freed
    del trainer
    ref = reference_steps(run, words, mask, partners, run_key, n_check * seg,
                          seg, jnp.float32)
    checks = compare(prog, ref)
    return common.Result(
        correct=all(c.ok for c in checks), attempted=rounds, failed=0,
        e2e={"setup_s": setup_s, "train_tokens_per_s": tokens / window_s},
        checks=checks, memory_peak_bytes=mem, layer=layer)


def _probe(run, trainer, partners, run_key):
    """The E-step entry and the gossip mix, each called alone, traced."""
    cfg = run.config
    lda = trainer.cfg.lda
    est = estep_mod.get_estep(trainer.cfg.estep_backend)
    comm = comm_mod.get_communicator(trainer.cfg.comm_backend)

    @jax.jit
    def bench_probe_estep(keys, w, m, stats):
        return estep_mod.estep_batch_from_stats(est, lda, keys, w, m, stats)

    @jax.jit
    def bench_probe_mix(stats, p):
        return comm.mix_matching(stats, p)

    n, b = cfg["n_nodes"], cfg["batch_size"]
    k_sel, k_gibbs = jax.random.split(jax.random.fold_in(run_key, 0))
    keys = jax.vmap(lambda i: jax.random.fold_in(k_gibbs, i))(
        jnp.arange(n, dtype=jnp.int32))
    idx = jax.vmap(lambda i: jax.random.randint(
        jax.random.fold_in(k_sel, i), (b,), 0, cfg["docs_per_node"]))(
            jnp.arange(n, dtype=jnp.int32))
    w = jnp.take_along_axis(trainer.words, idx[..., None], axis=1)
    m = jnp.take_along_axis(trainer.mask, idx[..., None], axis=1)
    p = jnp.asarray(partners[0])
    stats = trainer.state.stats
    jax.block_until_ready(bench_probe_estep(keys, w, m, stats))
    jax.block_until_ready(bench_probe_mix(stats, p))
    calls = run.traffic["probe_calls"]
    with common.profiled("probes") as tdir:
        for _ in range(calls):
            with common.span("probe"):
                jax.block_until_ready(bench_probe_estep(keys, w, m, stats))
        for _ in range(calls):
            with common.span("probe"):
                jax.block_until_ready(bench_probe_mix(stats, p))
    summary = trace.summarize(tdir)
    for prog in ("bench_probe_estep", "bench_probe_mix"):
        print(f"{prog} top ops over {calls} calls:",
              summary.top_ops(8, within=prog), flush=True)
    return summary
