"""The work a DELEDA round requires, counted from shapes alone.

These counts are the yardstick of the roofline and utilization metrics.
They come from the algorithm (arXiv:1610.01417 Alg. 1 with G-OEM E-steps),
never from the compiled program, so they read the same whatever
implementation a later change brings.
"""

from __future__ import annotations

F32 = 4


def gibbs_flops_per_token(n_topics: int, n_sweeps: int, burnin: int) -> int:
    """Operations per real token of one E-step's collapsed Gibbs sweeps.

    Per sweep: remove the token's count (1), form the conditional
    (n_dk + alpha) * beta[:, w] (2K), its running sum (K - 1) and the
    inverse-CDF draw against u * total (1 + K compares), add the count
    back (1). Per kept sweep also the Rao-Blackwell posterior: normalize
    (K - 1 adds, K divides) and accumulate it (K adds).
    """
    k = n_topics
    per_sweep = 1 + 2 * k + (k - 1) + (1 + k) + 1
    per_kept = (k - 1) + k + k
    return n_sweeps * per_sweep + (n_sweeps - burnin) * per_kept


def statistic_flops(n_nodes: int, n_topics: int, vocab: int) -> int:
    """Operations on the [n, K, V] statistic per round.

    Gossip average (add, halve: 2), the M-step row sums (1), the G-OEM
    blend (1 - rho) s + rho s_hat (3): 6 per element.
    """
    return 6 * n_nodes * n_topics * vocab


def round_flops(tokens: float, n_nodes: int, n_topics: int, vocab: int,
                n_sweeps: int, burnin: int) -> float:
    """Operations of one round: sweeps, scatter of each token's K-vector
    into s_hat (K adds per token), and the statistic passes."""
    return (tokens * (gibbs_flops_per_token(n_topics, n_sweeps, burnin)
                      + n_topics)
            + statistic_flops(n_nodes, n_topics, vocab))


def round_bytes(n_nodes: int, n_topics: int, vocab: int,
                record_every: int) -> float:
    """HBM bytes one round requires.

    Each node's float32 statistic is read once and written once for mix
    and blend (2 n K V 4). Every ``record_every`` rounds the history
    record is written and the consensus read: 2 n K V 4 more, spread
    over those rounds.
    """
    stat = n_nodes * n_topics * vocab * F32
    return 2 * stat + 2 * stat / record_every


def mix_bytes(n_nodes: int, n_topics: int, vocab: int) -> int:
    """HBM bytes of one gossip mix: read every statistic, write the result."""
    return 2 * n_nodes * n_topics * vocab * F32


def least_time(flops: float, bytes_: float, chips: int, peaks: dict
               ) -> tuple[float, str]:
    """(seconds, bound): the larger of the compute and the memory bound."""
    t_flops = flops / (chips * peaks["flops_per_s"])
    t_bytes = bytes_ / (chips * peaks["hbm_bytes_per_s"])
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
