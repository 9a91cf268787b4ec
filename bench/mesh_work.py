"""What a node-sharded DELEDA round requires of the interconnect.

``bench/work.py`` counts a round's operations and HBM bytes; a round
whose nodes lie on several chips also has to move statistics between
them. That need comes from the algorithm and the placement alone: each
matched pair whose two nodes lie on different chips exchanges their
[K, V] rows, one each way. What the program ships (whole blocks, say)
does not enter, so a program that ships more reads a lower share, never
one above 100%.
"""

from __future__ import annotations

from bench import work


def ici_bytes(cross_pairs: float, n_topics: int, vocab: int) -> float:
    """Interconnect bytes of one round with ``cross_pairs`` cross-chip
    matched pairs: two float32 [K, V] rows a pair."""
    return 2 * cross_pairs * n_topics * vocab * work.F32


def ici_seconds(cross_pairs: float, n_topics: int, vocab: int, chips: int,
                peaks: dict) -> float:
    """Those bytes over the chips' summed interconnect bandwidth."""
    return ici_bytes(cross_pairs, n_topics, vocab) / (
        chips * peaks["ici_bytes_per_s"])


def mix_least_time(n_nodes: int, n_topics: int, vocab: int,
                   cross_pairs: float, chips: int, peaks: dict
                   ) -> tuple[float, str]:
    """(seconds, bound) of one gossip mix: the larger of its HBM bound
    (``work.mix_bytes`` over the chips' HBM bandwidth) and its ICI bound."""
    t_hbm, _ = work.least_time(0.0, work.mix_bytes(n_nodes, n_topics, vocab),
                               chips, peaks)
    t_ici = ici_seconds(cross_pairs, n_topics, vocab, chips, peaks)
    return (t_ici, "ici") if t_ici > t_hbm else (t_hbm, "bytes")


def round_least_time(tokens: float, n_nodes: int, n_topics: int, vocab: int,
                     n_sweeps: int, burnin: int, record_every: int,
                     cross_pairs: float, chips: int, peaks: dict
                     ) -> tuple[float, str]:
    """(seconds, bound) of one round: ``work.least_time`` of its operations
    and HBM bytes over the chips' peaks, or its ICI bound where larger."""
    least = work.least_time(
        work.round_flops(tokens, n_nodes, n_topics, vocab, n_sweeps, burnin),
        work.round_bytes(n_nodes, n_topics, vocab, record_every),
        chips, peaks)
    t_ici = ici_seconds(cross_pairs, n_topics, vocab, chips, peaks)
    return (t_ici, "ici") if t_ici > least[0] else least
