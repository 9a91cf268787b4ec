"""The plain reference of synchronous DELEDA rounds, a block of nodes a device.

The equations are ``bench/reference.py``'s: its ``node_update`` runs
unchanged, the initial rows are drawn as its ``init_stats`` draws them,
and a round is its ``round_``: gossip over the round's matching, then
every node's E-step and blend with the keys ``fold_in(fold_in(run_key,
t), node)``. Only the placement differs. The n nodes lie in equal
contiguous blocks, block b on device b, so a network whose statistic no
one device holds (128 PubMed-shaped nodes: 7.2 GB) is followed block by
block; the mix gathers each block's partner rows from the blocks that
hold them with plain ``jnp`` indexing and ``jax.device_put``. Like
``bench/reference.py`` it imports nothing of the program.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


def split_rows(x, devices) -> list:
    """``x`` [n, ...] as contiguous row blocks, block b on ``devices[b]``."""
    m = x.shape[0] // len(devices)
    return [jax.device_put(x[b * m:(b + 1) * m], dev)
            for b, dev in enumerate(devices)]


def init_blocks(key: jax.Array, n: int, k: int, v: int, devices,
                dtype=jnp.float32):
    """(stats0 blocks [n/d, K, V], run key): ``reference.init_stats``'s
    normalized Exponential(1) rows, each block drawn on its device."""
    k_init, k_run = jax.random.split(key)

    def one(kk):
        g = jax.random.exponential(kk, (k, v))
        return g / g.sum(axis=1, keepdims=True)

    return ([jax.vmap(one)(keys).astype(dtype)
             for keys in split_rows(jax.random.split(k_init, n), devices)],
            k_run)


@jax.jit
def _gather(rows, idx):
    return rows[idx]


@partial(jax.jit, donate_argnums=0)
def _put(buf, dst, rows):
    return buf.at[dst].set(rows)


@jax.jit
def _average(rows, other):
    return 0.5 * (rows + other)


def _padded(idx: np.ndarray, m: int) -> jax.Array:
    """``idx`` padded to the next power of two, at least 4 and at most m,
    by repeating its last entry: a round's gathers take a few shapes."""
    size = min(m, max(4, 1 << (len(idx) - 1).bit_length()))
    return jnp.asarray(np.concatenate([idx, np.full(size - len(idx),
                                                    idx[-1])]))


def mix(blocks: list, partners: np.ndarray) -> list:
    """s_i <- (s_i + s_p(i)) / 2 over a matching ``partners`` [n] (host
    ints): each block gathers its partners' rows, its own and those the
    other blocks hold, moved to its device."""
    m = blocks[0].shape[0]
    out = []
    for b, rows in enumerate(blocks):
        dev = next(iter(rows.devices()))
        p = np.asarray(partners[b * m:(b + 1) * m])
        owner = p // m
        other = _gather(rows, jnp.asarray(np.where(owner == b, p % m, 0)))
        for c in np.unique(owner[owner != b]):
            r = np.nonzero(owner == c)[0]
            piece = jax.device_put(_gather(blocks[c], _padded(p[r] % m, m)),
                                   dev)
            other = _put(other, _padded(r, m), piece)
        out.append(_average(rows, other))
    return out


@partial(jax.jit, static_argnames=("batch", "tau", "alpha", "n_sweeps",
                                   "burnin", "rho_t0", "rho_kappa"))
def _update(stats, steps, run_key, t_abs, ids, words, mask, **hp):
    """``reference.round_``'s E-step and blend for one block's nodes."""
    k_sel, k_gibbs = jax.random.split(jax.random.fold_in(run_key, t_abs))
    upd = partial(reference.node_update, **hp)
    new = jax.vmap(
        lambda s, i, w, m, t: upd(s, jax.random.fold_in(k_sel, i),
                                  jax.random.fold_in(k_gibbs, i), w, m, t))(
        stats, ids, words, mask, steps + 1)
    return new, steps + 1


def round_(blocks, steps, run_key, t_abs: int, partners, words, mask,
           map_fn=map, **hp):
    """One synchronous matching round over every block: (blocks, steps),
    each a list of blocks; ``words``/``mask`` are row blocks too.
    ``map_fn`` dispatches the blocks' updates (a thread pool's ``map``
    compiles them for their devices at once)."""
    blocks = mix(blocks, partners)
    m = blocks[0].shape[0]

    def update(b):
        return _update(blocks[b], steps[b], run_key, jnp.int32(t_abs),
                       jnp.arange(b * m, (b + 1) * m, dtype=jnp.int32),
                       words[b], mask[b], **hp)

    out = list(map_fn(update, range(len(blocks))))
    return [o[0] for o in out], [o[1] for o in out]


@jax.jit
def _change_norms(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.linalg.norm((a - b).reshape(a.shape[0], -1), axis=1)


@jax.jit
def _marginals(stats):
    return stats.astype(jnp.float32).sum(axis=1)


def reference_steps(run, words, mask, partners, run_key, n_rounds: int,
                    seg: int, dtype) -> dict:
    """The readings ``train_rounds.readings`` compares (per-node change
    norms after the first segment and after ``n_rounds``, word
    marginals), computed block by block, one block on each of the run's
    devices."""
    cfg = run.config
    devices = run.devices
    stats, r_key = init_blocks(run_key, cfg["n_nodes"], cfg["n_topics"],
                               cfg["vocab_size"], devices, dtype)
    s0 = stats
    steps = split_rows(jnp.zeros((cfg["n_nodes"],), jnp.int32), devices)
    words, mask = split_rows(words, devices), split_rows(mask, devices)
    hp = dict(batch=cfg["batch_size"], tau=cfg["tau"], alpha=cfg["alpha"],
              n_sweeps=cfg["n_gibbs"], burnin=cfg["n_gibbs_burnin"],
              rho_t0=cfg["rho_t0"], rho_kappa=cfg["rho_kappa"])

    def changes():
        return np.concatenate([np.asarray(_change_norms(s, z))
                               for s, z in zip(stats, s0)])

    out = {}
    # one program a device: the first round compiles them side by side
    with ThreadPoolExecutor(len(devices)) as pool:
        for t in range(n_rounds):
            stats, steps = round_(stats, steps, r_key, t, partners[t], words,
                                  mask, map_fn=pool.map, **hp)
            if t + 1 == seg:
                out["change1"] = changes()
    out["change_last"] = changes()
    out["marginals"] = np.concatenate([np.asarray(_marginals(s))
                                       for s in stats])
    return out
