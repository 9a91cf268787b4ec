"""Synthetic LDA corpora (paper §4 setup) — IID or topically-skewed shards.

The paper attaches 20 documents to each of n=50 nodes, generated by the LDA
generative process with V=100, K=5, document lengths ~ Poisson(10), and holds
out test documents for the left-to-right perplexity. This module generates
exactly that, plus the centralized view (all documents flattened) used by the
G-OEM baseline.

The `topic_skew` knob (core/scenario.py's non-IID ingredient) breaks the
IID-shard assumption the way real sensor networks do: each node i draws
topic weights w_i ~ Dirichlet(topic_skew · 1_K) and its documents use the
asymmetric prior theta ~ Dirichlet(K·alpha·w_i) — same total concentration
mass as the symmetric case, but each node's corpus is topically biased
(small skew = nearly single-topic nodes). The held-out test set stays IID
symmetric, so perplexity still measures the *global* model. This is the
regime where gossip actually matters: no node's local data identifies all
topics, so a non-communicating node cannot fit the corpus-level model.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.estep import unique_view
from repro.core.lda import LDAConfig, sample_document, sample_topic_matrix

# warn when more than this fraction of drawn document lengths had to be
# clipped into [2, doc_len_max] — past it the realized length distribution
# no longer resembles the requested one (the silent-clip bug this exposes)
LENGTH_TRUNCATION_WARN_FRAC = 0.05


class SyntheticCorpus(NamedTuple):
    """A node-sharded synthetic corpus with its generating parameters."""

    words: jax.Array        # [n_nodes, docs_per_node, L] int32
    mask: jax.Array         # [n_nodes, docs_per_node, L] bool
    test_words: jax.Array   # [n_test, L] int32 held-out documents
    test_mask: jax.Array    # [n_test, L] bool
    beta_star: jax.Array    # [K, V] generating topic matrix
    alpha_star: float       # generating Dirichlet parameter
    node_topic_weights: Optional[jax.Array] = None  # [n, K] if skewed
    length_truncation_frac: Optional[float] = None  # drawn lengths clipped
                                                    # into [2, doc_len_max]

    @property
    def flat_words(self) -> jax.Array:
        """Centralized view [n*docs, L] for the G-OEM baseline."""
        return self.words.reshape(-1, self.words.shape[-1])

    @property
    def flat_mask(self) -> jax.Array:
        return self.mask.reshape(-1, self.mask.shape[-1])

    def unique_view(self) -> tuple[jax.Array, jax.Array]:
        """CSR view of the training shards: ([n, D, U], [n, D, U]).

        Per-document (word_id, count) pairs padded to U = the realized
        maximum unique-token count, built by the jit-able sort+segment
        pass (``repro.core.estep.dense_to_unique``). A derived view of
        the SAME corpus — generation is untouched, so existing seeds
        reproduce bit-identical (words, mask) arrays.
        """
        return unique_view(self.words, self.mask)

    def test_unique_view(self) -> tuple[jax.Array, jax.Array]:
        """CSR view of the held-out documents: ([n_test, U], counts)."""
        return unique_view(self.test_words, self.test_mask)


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    """Paper §4 defaults (+ the non-IID shard knob)."""

    n_nodes: int = 50
    docs_per_node: int = 20
    n_test: int = 100
    doc_len_poisson: float = 10.0
    topic_concentration: float = 0.1   # Dirichlet conc. of beta* rows
    topic_skew: float | None = None    # per-node topic bias; None = IID
    # Zipf-realistic knobs (benchmarks/sparse_bench.py): a power-law
    # word-frequency envelope folded into beta*, and lognormal document
    # lengths replacing the Poisson draw. Both default off — existing
    # seeds reproduce bit-identical corpora.
    zipf_exponent: float | None = None        # p(v) ~ (v+1)^-a envelope
    doc_len_lognormal: tuple[float, float] | None = None   # (mu, sigma)
    # Lifecycle layer: a STREAMING corpus re-draws every node's training
    # shard each `refresh_every` gossip rounds (documents keep arriving;
    # run_deleda swaps the minibatch source between scan segments).
    # beta*, the per-node topic skew, and the held-out test set stay
    # FIXED — perplexity keeps measuring the same global model. 0 = the
    # frozen corpus (existing seeds bit-identical).
    refresh_every: int = 0

    def __post_init__(self):
        if self.refresh_every < 0:
            raise ValueError(f"refresh_every must be >= 0 (0 = frozen), "
                             f"got {self.refresh_every}")
        if self.topic_skew is not None and self.topic_skew <= 0.0:
            raise ValueError(
                f"topic_skew must be positive (small = more skewed), "
                f"got {self.topic_skew}")
        if self.zipf_exponent is not None and self.zipf_exponent <= 0.0:
            raise ValueError(f"zipf_exponent must be positive, "
                             f"got {self.zipf_exponent}")
        if self.doc_len_lognormal is not None:
            if (len(self.doc_len_lognormal) != 2
                    or self.doc_len_lognormal[1] <= 0.0):
                raise ValueError(
                    f"doc_len_lognormal must be (mu, sigma > 0), "
                    f"got {self.doc_len_lognormal}")


def _poisson_lengths(key: jax.Array, lam: float, shape, max_len: int):
    lengths = jax.random.poisson(key, lam, shape)
    # at least 2 tokens so the left-to-right estimator has a w_{>1}
    return jnp.clip(lengths, 2, max_len).astype(jnp.int32)


def _raw_lengths(key: jax.Array, spec: CorpusSpec, shape) -> jax.Array:
    """Un-clipped length draws: Poisson by default, lognormal if asked.

    The Poisson branch consumes ``key`` exactly like the pre-knob code,
    so existing seeds keep their lengths bit-identical.
    """
    if spec.doc_len_lognormal is not None:
        mu, sigma = spec.doc_len_lognormal
        z = jax.random.normal(key, shape)
        return jnp.round(jnp.exp(mu + sigma * z)).astype(jnp.int32)
    return jax.random.poisson(key, spec.doc_len_poisson, shape)


def _clip_lengths(raw: jax.Array, max_len: int
                  ) -> tuple[jax.Array, jax.Array]:
    """Clip to [2, max_len]; also return the out-of-range draw mask.

    `_poisson_lengths` used to do this silently — at short doc_len_max
    or heavy-tailed length specs the realized distribution is visibly
    biased with no trace. The mask feeds the corpus-level
    ``length_truncation_frac`` diagnostic.
    """
    truncated = (raw < 2) | (raw > max_len)
    return jnp.clip(raw, 2, max_len).astype(jnp.int32), truncated


def _zipf_envelope(beta_star: jax.Array, exponent: float) -> jax.Array:
    """Fold a power-law word-frequency envelope into the topic matrix.

    Multiplies every topic's column v by (v + 1)^-exponent and
    renormalizes rows, so the corpus-level word marginal inherits the
    Zipf head/tail real vocabularies have (a few words soak up most
    tokens -> many within-document duplicates, the regime the sparse
    corpus layer targets). Deterministic given beta*: no PRNG stream is
    consumed, existing seeds are unaffected when the knob is off.
    """
    v = beta_star.shape[-1]
    env = (jnp.arange(v, dtype=beta_star.dtype) + 1.0) ** (-exponent)
    out = beta_star * env
    return out / out.sum(axis=-1, keepdims=True)


def node_alpha_vectors(config: LDAConfig, key: jax.Array, n_nodes: int,
                       topic_skew: float
                       ) -> tuple[jax.Array, jax.Array]:
    """Per-node Dirichlet-skewed topic priors.

    Returns (weights [n, K] node topic weights ~ Dirichlet(skew·1),
    alpha_vecs [n, K] per-node document priors K·alpha·weights). The row
    sums of alpha_vecs all equal K·alpha — the symmetric case's total
    concentration — so only the *allocation* of topics to nodes changes.
    """
    k = config.n_topics
    weights = jax.random.dirichlet(
        key, jnp.full((k,), topic_skew), shape=(n_nodes,))    # [n, K]
    weights = jnp.maximum(weights, 1e-6)
    weights = weights / weights.sum(-1, keepdims=True)
    return weights, k * config.alpha * weights


def _train_alpha_vecs(config: LDAConfig, spec: CorpusSpec,
                      k_skew: jax.Array):
    """(node_weights or None, per-DOCUMENT alpha vectors [n*D, K])."""
    n_train = spec.n_nodes * spec.docs_per_node
    if spec.topic_skew is None:
        return None, jnp.broadcast_to(
            jnp.full((config.n_topics,), config.alpha),
            (n_train, config.n_topics))
    node_weights, node_alphas = node_alpha_vectors(
        config, k_skew, spec.n_nodes, spec.topic_skew)
    return node_weights, jnp.repeat(node_alphas, spec.docs_per_node, axis=0)


# each document's draw materializes [L, V] word logits; chunks of
# documents keep that block under this many bytes (V=50,000, L=16 would
# otherwise need 26 GB for 8,192 documents at once)
_DRAW_CHUNK_BYTES = 1 << 28


def _draw_documents(config: LDAConfig, draw, *per_doc):
    """``vmap(draw)(*per_doc)`` in chunks of bounded memory: the same
    per-document values, whatever the chunk size."""
    per_doc_bytes = config.doc_len_max * config.vocab_size * 4
    batch = max(1, _DRAW_CHUNK_BYTES // per_doc_bytes)
    return jax.lax.map(lambda xs: draw(*xs), per_doc, batch_size=batch)


def _draw_training_shards(config: LDAConfig, spec: CorpusSpec,
                          beta_star: jax.Array, alpha_vecs: jax.Array,
                          k_len: jax.Array, k_doc: jax.Array):
    """One draw of every node's [D, L] training shard (words, mask, trunc).

    Factored out of :func:`make_corpus` so a streaming corpus
    (:class:`CorpusStream`) can re-draw the shards mid-run with fresh
    keys while beta*, the per-node skew and the test set stay fixed.
    """
    n_train = spec.n_nodes * spec.docs_per_node
    lengths, trunc = _clip_lengths(
        _raw_lengths(k_len, spec, (n_train,)), config.doc_len_max)
    words, mask = _draw_documents(
        config,
        lambda k, ln, av: sample_document(config, k, beta_star, ln,
                                          alpha_vec=av),
        jax.random.split(k_doc, n_train), lengths, alpha_vecs)
    words = words.reshape(spec.n_nodes, spec.docs_per_node, -1)
    mask = mask.reshape(spec.n_nodes, spec.docs_per_node, -1)
    return words, mask, trunc


def make_corpus(config: LDAConfig, key: jax.Array,
                spec: CorpusSpec = CorpusSpec()) -> SyntheticCorpus:
    """Generate the node-sharded corpus + held-out test set of paper §4."""
    # same 5-way split as the IID-only version so existing seeds reproduce
    # bit-identical corpora; the skew stream is folded in separately
    k_beta, k_len, k_doc, k_tlen, k_tdoc = jax.random.split(key, 5)
    k_skew = jax.random.fold_in(key, 0x5157)
    beta_star = sample_topic_matrix(config, k_beta,
                                    spec.topic_concentration)
    if spec.zipf_exponent is not None:
        beta_star = _zipf_envelope(beta_star, spec.zipf_exponent)

    node_weights, alpha_vecs = _train_alpha_vecs(config, spec, k_skew)
    words, mask, trunc = _draw_training_shards(
        config, spec, beta_star, alpha_vecs, k_len, k_doc)

    # held-out documents stay IID symmetric: perplexity measures the
    # GLOBAL model, whichever way the training shards were biased
    t_lengths, t_trunc = _clip_lengths(
        _raw_lengths(k_tlen, spec, (spec.n_test,)), config.doc_len_max)
    t_words, t_mask = _draw_documents(
        config, lambda k, ln: sample_document(config, k, beta_star, ln),
        jax.random.split(k_tdoc, spec.n_test), t_lengths)

    trunc_frac = float(jnp.concatenate([trunc, t_trunc])
                       .astype(jnp.float32).mean())
    if trunc_frac > LENGTH_TRUNCATION_WARN_FRAC:
        warnings.warn(
            f"{trunc_frac:.1%} of drawn document lengths fell outside "
            f"[2, doc_len_max={config.doc_len_max}] and were clipped — "
            f"the realized length distribution is biased; raise "
            f"doc_len_max or shrink the length spec", stacklevel=2)

    return SyntheticCorpus(words=words, mask=mask, test_words=t_words,
                           test_mask=t_mask, beta_star=beta_star,
                           alpha_star=config.alpha,
                           node_topic_weights=node_weights,
                           length_truncation_frac=trunc_frac)


# distinct fold_in lane for the streaming re-draw keys, disjoint from the
# base corpus' 5-way split and the 0x5157 skew lane
_STREAM_SALT = 0x5EA5


@dataclasses.dataclass(frozen=True)
class CorpusStream:
    """A streaming view of a synthetic corpus (lifecycle layer).

    Segment 0 is the base corpus, bit-identical to ``make_corpus`` with
    the same (config, key, spec). Each later segment re-draws every
    node's [D, L] training shard with fresh document-level keys while
    beta*, the per-node topic skew, and the held-out test set stay
    fixed — the "documents keep arriving" regime. ``run_deleda``
    consumes this between scan segments (``CorpusSpec.refresh_every``),
    so the compiled step function never sees the swap: shard shapes are
    identical across segments and only the array contents change.
    """

    config: LDAConfig
    key: jax.Array
    spec: CorpusSpec
    base: SyntheticCorpus
    alpha_vecs: jax.Array     # [n*D, K] per-document priors (fixed skew)

    @property
    def refresh_every(self) -> int:
        return self.spec.refresh_every

    @property
    def n_nodes(self) -> int:
        return self.spec.n_nodes

    def segment(self, s: int) -> tuple[jax.Array, jax.Array]:
        """(words, mask) of streaming segment ``s`` (0 = base corpus)."""
        if s < 0:
            raise ValueError(f"segment index must be >= 0, got {s}")
        if s == 0:
            return self.base.words, self.base.mask
        k_seg = jax.random.fold_in(
            jax.random.fold_in(self.key, _STREAM_SALT), s)
        k_len, k_doc = jax.random.split(k_seg)
        words, mask, _ = _draw_training_shards(
            self.config, self.spec, self.base.beta_star,
            self.alpha_vecs, k_len, k_doc)
        return words, mask


def make_corpus_stream(config: LDAConfig, key: jax.Array,
                       spec: CorpusSpec) -> CorpusStream:
    """Build the streaming corpus view for ``spec.refresh_every > 0``.

    Deterministic in (config, key, spec): segment s is a pure function
    of the stream, so a killed-and-restored run re-materializes the
    exact shard a fresh run would have used at the same step — the
    property the bitwise checkpoint/restore golden relies on.
    """
    if spec.refresh_every <= 0:
        raise ValueError("make_corpus_stream needs spec.refresh_every > 0 "
                         f"(got {spec.refresh_every}); use make_corpus for "
                         "a frozen corpus")
    base = make_corpus(config, key, spec)
    _, alpha_vecs = _train_alpha_vecs(
        config, spec, jax.random.fold_in(key, 0x5157))
    return CorpusStream(config=config, key=key, spec=spec, base=base,
                        alpha_vecs=alpha_vecs)


def paper_corpus(key: jax.Array, doc_len_max: int = 32) -> tuple[LDAConfig,
                                                                 SyntheticCorpus]:
    """The exact paper configuration: n=50, 20 docs/node, V=100, K=5."""
    config = LDAConfig(n_topics=5, vocab_size=100, alpha=0.5,
                       doc_len_max=doc_len_max)
    return config, make_corpus(config, key, CorpusSpec())
