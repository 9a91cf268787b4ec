"""Plain reference of synchronous DELEDA rounds (arXiv:1610.01417, Alg. 1).

Written from the paper and the published G-OEM update, in straightforward
``jax.numpy``, independent of the program: it imports nothing of it and
takes nothing it made. The only thing shared is the seed, from which the
reference derives the same random stream the system's documented
contract uses (initial statistics from Exponential(1) rows; per round
``fold_in(run_key, t)``; per node ``fold_in(., node)``; the minibatch and
the Gibbs uniforms from those keys).

One round, for every node i at once:

  1. gossip: s_i <- (s_i + s_{p(i)}) / 2 over the round's matching p;
  2. E-step: draw B documents of node i's shard, gather
     beta[:, w] = (s_i[:, w] + tau) / sum_v (s_i[:, v] + tau), run
     collapsed Gibbs sweeps (inverse-CDF draws from (n_dk + alpha) beta,
     running sums from the left) and average the Rao-Blackwellized
     conditionals of the kept sweeps into a per-document-mean [K, V]
     statistic s_hat;
  3. G-OEM blend: s_i <- (1 - rho_t) s_i + rho_t s_hat,
     rho_t = (t0 + t)^-kappa.

``dtype`` sets the precision of the whole computation. float32 is the
configuration's own; bfloat16 is the control that the comparison has to
fail.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def init_stats(key: jax.Array, n: int, k: int, v: int, dtype=jnp.float32):
    """(stats0 [n, K, V], run key): normalized Exponential(1) rows per node."""
    k_init, k_run = jax.random.split(key)

    def one(kk):
        g = jax.random.exponential(kk, (k, v))
        return g / g.sum(axis=1, keepdims=True)

    stats = jax.vmap(one)(jax.random.split(k_init, n))
    return stats.astype(dtype), k_run


def _draw(probs, u):
    """Inverse-CDF draw: the number of running sums below u * total."""
    k = probs.shape[-1]
    c = probs[..., 0]
    cums = [c]
    for j in range(1, k):
        c = c + probs[..., j]
        cums.append(c)
    thresh = u * cums[-1]
    z = jnp.zeros(thresh.shape, jnp.int32)
    for cj in cums:
        z = z + (cj < thresh).astype(jnp.int32)
    return z


def gibbs(beta_w, maskf, u, z0, alpha, n_sweeps, burnin):
    """Rao-Blackwellized collapsed Gibbs sweeps over a batch of documents.

    beta_w [B, L, K], maskf [B, L], u [S, B, L], z0 [B, L]. Returns the
    mean over kept sweeps of each position's conditional, [B, L, K].
    """
    b, l, k = beta_w.shape
    dt = beta_w.dtype
    topics = jnp.arange(k, dtype=jnp.int32)
    ndk = ((z0[..., None] == topics).astype(dt) * maskf[..., None]).sum(1)
    alpha = jnp.asarray(alpha, dt)

    def position(i, carry, s):
        z, ndk, acc = carry
        m = maskf[:, i]
        ndk = ndk - m[:, None] * (z[:, i, None] == topics).astype(dt)
        probs = (ndk + alpha) * beta_w[:, i]
        new = jnp.where(m > 0, _draw(probs, u[s, :, i]), z[:, i])
        ndk = ndk + m[:, None] * (new[:, None] == topics).astype(dt)
        post = probs / jnp.maximum(probs.sum(-1, keepdims=True),
                                   jnp.asarray(1e-30, dt))
        keep = (s >= burnin).astype(dt)
        acc = acc.at[:, i].add(keep * m[:, None] * post)
        return z.at[:, i].set(new), ndk, acc

    def sweep(carry, s):
        return jax.lax.fori_loop(0, l, lambda i, c: position(i, c, s),
                                 carry), None

    (_, _, acc), _ = jax.lax.scan(
        sweep, (z0, ndk, jnp.zeros_like(beta_w)), jnp.arange(n_sweeps))
    return acc / jnp.asarray(n_sweeps - burnin, dt) * maskf[..., None]


def node_update(stats, key_sel, key_gibbs, words, mask, t, *, batch, tau,
                alpha, n_sweeps, burnin, rho_t0, rho_kappa):
    """One node's E-step and blend; ``stats`` [K, V] is the mixed statistic."""
    k, v = stats.shape
    dt = stats.dtype
    d, l = words.shape
    idx = jax.random.randint(key_sel, (batch,), 0, d)
    bw, bm = words[idx], mask[idx]
    k_z0, k_u = jax.random.split(key_gibbs)
    u = jax.random.uniform(k_u, (n_sweeps, batch, l), jnp.float32).astype(dt)
    z0 = jax.random.randint(k_z0, (batch, l), 0, k, jnp.int32)
    tau = jnp.asarray(tau, dt)
    denom = (stats + tau).sum(-1)
    beta_w = (jnp.moveaxis(stats[:, bw], 0, -1) + tau) / denom
    maskf = bm.astype(dt)
    per_pos = gibbs(beta_w, maskf, u, z0, alpha, n_sweeps, burnin)
    n_docs = jnp.maximum((maskf.sum(-1) > 0).sum(), 1).astype(dt)
    s_hat = jnp.zeros((k, v), dt).at[:, bw.reshape(-1)].add(
        per_pos.reshape(-1, k).T) / n_docs
    rho = ((rho_t0 + t.astype(jnp.float32)) ** (-rho_kappa)).astype(dt)
    return (1 - rho) * stats + rho * s_hat


@partial(jax.jit, static_argnames=("batch", "tau", "alpha", "n_sweeps",
                                   "burnin", "rho_t0", "rho_kappa"))
def round_(stats, steps, run_key, t_abs, partners, words, mask, **hp):
    """One synchronous matching round over all nodes: (stats, steps)."""
    k = jax.random.fold_in(run_key, t_abs)
    k_sel, k_gibbs = jax.random.split(k)
    stats = 0.5 * (stats + stats[partners])
    ids = jnp.arange(stats.shape[0], dtype=jnp.int32)
    upd = partial(node_update, **hp)
    new = jax.vmap(
        lambda s, i, w, m, t: upd(s, jax.random.fold_in(k_sel, i),
                                  jax.random.fold_in(k_gibbs, i), w, m, t))(
        stats, ids, words, mask, steps + 1)
    return new, steps + 1

