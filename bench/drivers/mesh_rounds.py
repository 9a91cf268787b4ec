"""Training traffic over a node mesh: node-sharded ``deleda.train_steps``.

As ``train_rounds``: set-up makes the corpus and the gossip schedule from
the seed, builds the state with ``deleda.init_state`` and drives the
first ``check_steps`` segments of ``segment_rounds`` rounds through the
window's own call; the window runs segments back to back, each
dispatched as its predecessor completes, for ``--seconds``; afterwards
the reference follows the first segments. Here the configuration's nodes
lie in contiguous blocks on a 1-D mesh of the run's devices
(``comm_backend="mesh"``): each chip runs its block's E-step and blend,
and the gossip between blocks crosses chips as ``ppermute``. The
reference is ``bench/reference_blocked.py``, one block of nodes a chip,
and every reading is taken on the sharded state. With ``--trace 1`` the
window is traced; nothing is probed outside it.

The counter ``cross_pairs`` is the matched pairs of the window's rounds
whose two nodes lie on different chips, counted from the schedule after
the liveness guard: what the gossip has to move between chips.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import common, reference_blocked, trace
from bench.drivers import train_rounds as tr
from repro.core import deleda

AXIS = "nodes"


def mesh_config(config: dict, devices) -> deleda.DeledaConfig:
    """The cell's ``DeledaConfig``, its nodes sharded over ``devices``."""
    return deleda.DeledaConfig(
        lda=tr._lda(config), mode=config["mode"],
        batch_size=config["batch_size"], rho_kappa=config["rho_kappa"],
        rho_t0=config["rho_t0"], comm_backend="mesh",
        mesh=Mesh(np.asarray(devices), (AXIS,)))


def cross_pairs(partners: np.ndarray, live: np.ndarray, chips: int) -> int:
    """Matched pairs in the rounds of ``partners`` [T, n] whose nodes lie on
    different chips, after the liveness guard ``live`` [T, n]."""
    n = partners.shape[1]
    ids = np.arange(n)
    rows = np.arange(len(partners))[:, None]
    p = np.where(live & live[rows, partners], partners, ids)
    block = n // chips
    return int(((ids < p) & (ids // block != p // block)).sum())


class Trainer(tr.Trainer):
    """The system under test on the mesh, driven as its users call it."""

    def __init__(self, dcfg, run: common.Run, words, mask, partners,
                 run_key):
        n = run.config["n_nodes"]
        self.cfg = dcfg
        self.seg = run.traffic["segment_rounds"]
        node = NamedSharding(dcfg.mesh, P(AXIS))
        rep = NamedSharding(dcfg.mesh, P())
        self.words = jax.device_put(words, node)
        self.mask = jax.device_put(mask, node)
        self.n_sched = len(partners) // self.seg
        self.sched = [jax.device_put(partners[i * self.seg:(i + 1) * self.seg],
                                     rep) for i in range(self.n_sched)]
        self.corr = jax.device_put(jnp.ones((self.seg, n), jnp.float32), rep)
        self.live = jax.device_put(jnp.ones((self.seg, n), bool), rep)
        self.state = deleda.init_state(dcfg, run_key, n)
        self.n_segments = 0


def run(run: common.Run) -> common.Result:
    # first, so that a program without the mesh backend stops here
    dcfg = mesh_config(run.config, run.devices)
    cfg, tr_cfg = run.config, run.traffic
    words, mask, partners, run_key = tr.make_inputs(run)
    trainer = Trainer(dcfg, run, words, mask, partners, run_key)
    seg, n_check = trainer.seg, tr_cfg["check_steps"]

    # -- the first steps: compile, warm, and record what the reference checks
    prog = tr.first_steps(trainer, run_key, n_check)
    lengths = mask.sum(-1).astype(jnp.int32)
    batch_ids = jnp.zeros((cfg["batch_size"],), jnp.int32)
    t_first = trainer.n_segments * seg
    tr._window_tokens(run_key, lengths, jnp.arange(t_first, t_first + seg),
                      batch_ids).block_until_ready()
    jax.block_until_ready(trainer.state.stats)
    setup_s = common.now() - run.t_process

    # -- the window
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
    tokens = int(tr._window_tokens(run_key, lengths,
                                   jnp.arange(t_first, t_first + rounds),
                                   batch_ids))
    mem = common.memory_peak_bytes(run.devices)

    layer = None
    if run.trace:
        t_abs = np.arange(t_first, t_first + rounds) % len(partners)
        layer = common.Layer(
            window=window, probes=None, config=cfg, peaks=run.peaks,
            chips=run.chips,
            counters={"rounds": rounds, "tokens": tokens,
                      "record_every": seg,
                      "cross_pairs": cross_pairs(
                          partners[t_abs], np.ones((rounds, cfg["n_nodes"]),
                                                   bool), run.chips)})

    # -- the comparison, after the window, with the program's state freed
    del trainer
    ref = reference_blocked.reference_steps(
        run, words, mask, partners, run_key, n_check * seg, seg,
        jnp.float32)
    checks = tr.compare(prog, ref)
    return common.Result(
        correct=all(c.ok for c in checks), attempted=rounds, failed=0,
        e2e={"setup_s": setup_s, "train_tokens_per_s": tokens / window_s},
        checks=checks, memory_peak_bytes=mem, layer=layer)
