"""Declarative invariant audits over lowered entry points.

The paper's guarantee is structural — raw documents never leave a node,
only sufficient statistics move — and the Scale/Eval/Serving layers add
two more structural claims: no dense topic-matrix temporary on the
sharded/blocked paths, and one compiled trace per entry point. This
module turns all three into machine-checked invariants:

- :class:`InvariantSpec` — per-entry-point allow-lists over the compiled
  module's collectives (kind allow-list, the privacy boundary on
  doc-shaped buffers, replica-group placement for grid collectives) and
  a peak-temp budget from XLA's ``memory_analysis()``.
- :func:`audit_hlo_text` / :func:`audit_compiled` — run one spec against
  one compiled module and report violations + the collective inventory.
- :data:`ENTRY_POINTS` / :func:`collect_inventories` — the registry of
  audited repo entry points (the `run_deleda` scan and its node-sharded
  forms on a 1-D mesh and a node x vocab grid, the fused eval chunk, the
  serving slabs) and the golden-pinning helpers
  (`tests/golden_collectives.json`).
- :class:`CompileCounter` — the reusable recompile guard generalizing
  the scattered ``_cache_size() == 1`` asserts.

The audits parse post-partitioning HLO *text* (`repro.analysis.hlo`):
that is where XLA's actual placement decisions live, so the check is on
what will execute, not on what the tracer intended.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Callable

from repro.analysis.hlo import CollectiveOp, parse_collective_ops

GOSSIP_ALLOWED = frozenset({"collective-permute"})


@dataclasses.dataclass(frozen=True)
class InvariantSpec:
    """What one entry point's compiled module is allowed to do.

    ``allowed_collectives`` — collective kinds that may appear at all.
    ``max_counts`` — optional per-kind instruction-count ceilings.
    ``doc_len`` — the privacy boundary: no collective result may carry an
    integer buffer whose trailing dimension equals the document length
    (token buffers are int32 ``[..., L]``; statistics are float
    ``[..., K]``/``[..., V]``). ``forbidden_dims`` adds exact shapes.
    ``replica_groups`` — when set, every collective of a kind in
    ``grouped_kinds`` must use exactly this device grouping (e.g. the
    2-D grid's vocab-axis rows — a node-axis reduce groups differently
    and is caught here even though the kind is allowed).
    ``max_temp_bytes`` — XLA peak-temp budget; pinned below the size a
    dense topic-matrix temporary would need, so "no dense beta" fails
    loudly instead of silently regressing.
    """
    name: str
    allowed_collectives: frozenset[str] = frozenset()
    max_counts: tuple[tuple[str, int], ...] = ()
    doc_len: int | None = None
    forbidden_dims: tuple[tuple[int, ...], ...] = ()
    replica_groups: tuple[tuple[int, ...], ...] | None = None
    grouped_kinds: frozenset[str] = frozenset()
    max_temp_bytes: int | None = None


@dataclasses.dataclass(frozen=True)
class Violation:
    spec: str
    rule: str
    message: str

    def __str__(self):
        return f"[{self.spec}] {self.rule}: {self.message}"


@dataclasses.dataclass
class AuditReport:
    spec: InvariantSpec
    ops: list[CollectiveOp]
    violations: list[Violation]
    temp_bytes: int | None = None

    @property
    def ok(self) -> bool:
        return not self.violations

    @property
    def inventory(self) -> dict[str, int]:
        inv: dict[str, int] = {}
        for op in self.ops:
            inv[op.kind] = inv.get(op.kind, 0) + 1
        return inv

    def summary(self) -> str:
        inv = ", ".join(f"{k}={v}" for k, v in sorted(self.inventory.items()))
        head = (f"{self.spec.name}: collectives {{{inv or 'none'}}}"
                + (f", temp={self.temp_bytes}B"
                   if self.temp_bytes is not None else ""))
        if self.ok:
            return head + " — OK"
        return head + "\n" + "\n".join(f"  FAIL {v}" for v in self.violations)


def _doc_shaped(op: CollectiveOp, spec: InvariantSpec) -> list[str]:
    bad = []
    for s in op.shapes:
        if s.dims in spec.forbidden_dims:
            bad.append(f"forbidden shape {s.dtype}{list(s.dims)}")
        elif (spec.doc_len is not None and s.is_integer and len(s.dims) >= 1
              and s.dims[-1] == spec.doc_len):
            bad.append(f"doc-shaped token buffer {s.dtype}{list(s.dims)} "
                       f"(trailing dim == L={spec.doc_len})")
    return bad


def audit_hlo_text(hlo_text: str, spec: InvariantSpec,
                   temp_bytes: int | None = None) -> AuditReport:
    """Audit one compiled module's text against one spec."""
    ops = parse_collective_ops(hlo_text)
    violations: list[Violation] = []
    counts: dict[str, int] = {}
    for op in ops:
        counts[op.kind] = counts.get(op.kind, 0) + 1
        if op.kind not in spec.allowed_collectives:
            violations.append(Violation(
                spec.name, "collective-allowlist",
                f"{op.kind} not in allow-list "
                f"{sorted(spec.allowed_collectives)}: {op.line}"))
        for msg in _doc_shaped(op, spec):
            violations.append(Violation(
                spec.name, "privacy-doc-buffer",
                f"{op.kind} moves a {msg}: {op.line}"))
        if spec.replica_groups is not None and op.kind in spec.grouped_kinds:
            want = {frozenset(g) for g in spec.replica_groups}
            got = (None if op.replica_groups is None
                   else {frozenset(g) for g in op.replica_groups})
            if got != want:
                violations.append(Violation(
                    spec.name, "replica-groups",
                    f"{op.kind} groups {op.replica_groups} != expected "
                    f"{spec.replica_groups}: {op.line}"))
    for kind, cap in spec.max_counts:
        if counts.get(kind, 0) > cap:
            violations.append(Violation(
                spec.name, "collective-count",
                f"{counts[kind]} {kind} ops > budget {cap}"))
    if spec.max_temp_bytes is not None and temp_bytes is not None:
        if temp_bytes > spec.max_temp_bytes:
            violations.append(Violation(
                spec.name, "temp-budget",
                f"peak temp {temp_bytes}B > budget "
                f"{spec.max_temp_bytes}B (dense-beta regression?)"))
    return AuditReport(spec, ops, violations, temp_bytes)


def _temp_bytes(compiled) -> int | None:
    try:
        mem = compiled.memory_analysis()
        return None if mem is None else int(mem.temp_size_in_bytes)
    except Exception:       # backend without memory_analysis support
        return None


def audit_compiled(compiled, spec: InvariantSpec) -> AuditReport:
    """Audit a ``jax.stages.Compiled`` (or anything with ``as_text()``)."""
    return audit_hlo_text(compiled.as_text(), spec, _temp_bytes(compiled))


# ---------------------------------------------------------------------------
# Compile counter — the single-trace invariant
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts new traces of jitted callables across a ``with`` block.

    Generalizes the scattered ``train_steps._cache_size()`` delta asserts:

        with CompileCounter(deleda.train_steps) as cc:
            ... drive N segments ...
        assert cc.total == 1, cc.counts

    Any jitted function (``jax.jit`` output or a jitted method cached on
    an object) works — anything exposing ``_cache_size()``.
    """

    def __init__(self, *fns):
        if not fns:
            raise ValueError("CompileCounter needs at least one jitted fn")
        self.fns = fns
        self.counts: dict[str, int] = {}

    @staticmethod
    def _name(fn) -> str:
        return getattr(fn, "__name__", None) or repr(fn)

    def __enter__(self):
        self._before = [f._cache_size() for f in self.fns]
        return self

    def __exit__(self, *exc):
        self.counts = {self._name(f): f._cache_size() - b
                       for f, b in zip(self.fns, self._before)}
        return False

    @property
    def total(self) -> int:
        return sum(self.counts.values())


# ---------------------------------------------------------------------------
# Entry-point registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One audited entry point: a builder returning a Compiled + its spec.

    ``min_devices`` gates the multi-device (mesh) entries: tier-1 runs
    the single-device rows; the slow tier / audit CLI runs everything
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    spec: InvariantSpec
    build: Callable[[], object]
    min_devices: int = 1


_L = 8          # audit doc length; shared so privacy specs can name it
_BIG_V = 50_000  # vocab size for the no-dense-beta budget rows
_BIG_K = 8


def _tiny_lda():
    from repro.core.lda import LDAConfig
    return LDAConfig(n_topics=3, vocab_size=32, alpha=0.5, doc_len_max=_L,
                     n_gibbs=4, n_gibbs_burnin=2)


def _build_deleda(vocab_shards: int = 1):
    import jax
    import jax.numpy as jnp
    from repro.core import deleda
    from repro.core.graph import complete_graph

    def build():
        n, d, t = 4, 6, 4
        cfg = deleda.DeledaConfig(lda=_tiny_lda(), mode="async",
                                  batch_size=3, vocab_shards=vocab_shards)
        edges, degs = deleda.make_run_inputs(complete_graph(n), t, seed=0)
        words = jnp.zeros((n, d, _L), jnp.int32)
        mask = jnp.ones((n, d, _L), bool)
        # the lifecycle layer's compiled unit: run_deleda is now a host
        # driver looping THIS jitted segment fn, so the scan invariants
        # are audited where the executable actually lives
        state = deleda.init_state(cfg, jax.random.key(0), n)
        corr = jnp.ones((t, n), jnp.float32)
        live = jnp.ones((t, n), bool)
        return deleda.train_steps.lower(
            cfg, state, words, mask, edges, corr, live,
            record_every=2, kind="edge").compile()
    return build


def _build_eval_chunk():
    import jax
    import jax.numpy as jnp
    from repro.core import evaluation

    def build():
        c, el = 8, 64
        words = jnp.zeros((c, el), jnp.int32)
        mask = jnp.ones((c, el), bool)
        stats = jnp.zeros((_BIG_K, _BIG_V), jnp.float32)
        return evaluation.ll_slab_from_stats.lower(
            jax.random.key(0), jnp.arange(c), words, mask, stats,
            jnp.float32(0.01), jnp.float32(0.5), n_particles=2,
            backend="fused").compile()
    return build


def _build_serve_slab(kind: str):
    import jax
    import jax.numpy as jnp
    from repro.core import serving

    def build():
        c, el = 8, 64
        words = jnp.zeros((c, el), jnp.int32)
        mask = jnp.ones((c, el), bool)
        stats = jnp.zeros((_BIG_K, _BIG_V), jnp.float32)
        key, ids = jax.random.key(0), jnp.arange(c)
        tau, alpha = jnp.float32(0.01), jnp.float32(0.5)
        if kind == "mixture":
            denom = (stats + tau).sum(-1)
            return serving._mixture_slab_from_stats.lower(
                key, ids, words, mask, stats, denom, tau, alpha,
                n_sweeps=4, burnin=2).compile()
        from repro.core import evaluation
        return evaluation.ll_slab_from_stats.lower(
            key, ids, words, mask, stats, tau, alpha, n_particles=2,
            backend="fused",
            denom=(stats + tau).sum(-1)).compile()
    return build


def _build_deleda_mesh(shape: tuple[int, ...]):
    """The node-sharded ``train_steps`` segment on a mesh of the first
    devices, ``shape`` (nodes,) or (nodes, vocab): two nodes a node-axis
    device, two record blocks."""
    def build():
        import math

        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core import deleda
        devices = np.asarray(jax.devices()[:math.prod(shape)])
        mesh = Mesh(devices.reshape(shape), ("data", "vocab")[:len(shape)])
        cfg = deleda.DeledaConfig(lda=_tiny_lda(), mode="sync",
                                  batch_size=3, comm_backend="mesh",
                                  mesh=mesh)
        n, d, t = 2 * shape[0], 6, 4
        node = NamedSharding(mesh, P("data"))
        words = jax.device_put(jnp.zeros((n, d, _L), jnp.int32), node)
        mask = jax.device_put(jnp.ones((n, d, _L), bool), node)
        state = deleda.init_state(cfg, jax.random.key(0), n)
        sched = jnp.tile(jnp.arange(n, dtype=jnp.int32) ^ 1, (t, 1))
        return deleda.train_steps.lower(
            cfg, state, words, mask, sched, jnp.ones((t, n), jnp.float32),
            jnp.ones((t, n), bool), record_every=2).compile()
    return build


ENTRY_POINTS: dict[str, EntryPoint] = {
    # single-device rows (tier-1): the simulation scan, the fused eval
    # chunk, the serving slabs — all must compile to ZERO collectives,
    # and the blocked/big-V paths must stay under the dense-beta budget.
    "deleda_scan": EntryPoint(
        InvariantSpec("deleda_scan", doc_len=_L), _build_deleda(1)),
    "deleda_scan_sharded": EntryPoint(
        InvariantSpec("deleda_scan_sharded", doc_len=_L),
        _build_deleda(4)),
    # eval_chunk derives the row normalizer on the fly, which owns ONE
    # [K, V] add-temporary (1.65 MB at the audit point); the budget
    # allows that but not a second dense [K, V] (materialized eta_star
    # would land at ~3.3 MB). The serving slabs receive the cached
    # denominator and must stay pure column gathers: their measured
    # temps are ~40 KB, and the 1 MB budget sits far below ONE dense
    # [K, V] = 1.6 MB.
    "eval_chunk": EntryPoint(
        InvariantSpec("eval_chunk", doc_len=64,
                      max_temp_bytes=int(2.5 * (1 << 20))),
        _build_eval_chunk()),
    "serve_slab_ll": EntryPoint(
        InvariantSpec("serve_slab_ll", doc_len=64,
                      max_temp_bytes=1 << 20), _build_serve_slab("ll")),
    "serve_slab_mixture": EntryPoint(
        InvariantSpec("serve_slab_mixture", doc_len=64,
                      max_temp_bytes=1 << 20),
        _build_serve_slab("mixture")),
    # node-sharded training on four devices: the round body's only
    # collectives are the d - 1 = 3 ppermute passes of the mix, and the
    # record's two all-reduces (the node sum and the squared norm); no
    # all-gather of the statistic, no doc-shaped operand
    "deleda_scan_mesh_1d": EntryPoint(
        InvariantSpec("deleda_scan_mesh_1d",
                      allowed_collectives=GOSSIP_ALLOWED | {"all-reduce"},
                      max_counts=(("collective-permute", 3),
                                  ("all-reduce", 2)),
                      doc_len=_L),
        _build_deleda_mesh((4,)), min_devices=4),
    # the node x vocab grid, 2 x 2: the node axis's one ppermute pass;
    # one all-reduce over each vocab row for the blocked beta assembly
    # (its two psums, the column partials and the denominator, combined
    # into one); the record's node sum over the node axis and its squared
    # norm over both axes. No all-gather, no doc-shaped operand
    "deleda_scan_grid_2d": EntryPoint(
        InvariantSpec("deleda_scan_grid_2d",
                      allowed_collectives=GOSSIP_ALLOWED | {"all-reduce"},
                      max_counts=(("collective-permute", 1),
                                  ("all-reduce", 3)),
                      doc_len=_L),
        _build_deleda_mesh((2, 2)), min_devices=4),
}


def available_entry_points() -> dict[str, EntryPoint]:
    """The registry rows runnable on this process's device count."""
    import jax
    n = len(jax.devices())
    return {name: ep for name, ep in ENTRY_POINTS.items()
            if ep.min_devices <= n}


def run_audits(names=None) -> dict[str, AuditReport]:
    """Lower + compile + audit the requested (default: runnable) rows."""
    eps = available_entry_points()
    if names is not None:
        missing = sorted(set(names) - set(ENTRY_POINTS))
        if missing:
            raise KeyError(f"unknown entry points: {missing}")
        eps = {n: ENTRY_POINTS[n] for n in names if n in eps}
    return {name: audit_compiled(ep.build(), ep.spec)
            for name, ep in eps.items()}


# ---------------------------------------------------------------------------
# Golden pinning
# ---------------------------------------------------------------------------

def collect_inventories(reports: dict[str, AuditReport]) -> dict:
    """The golden payload: per entry point, per-kind collective counts."""
    return {name: {"collectives": dict(sorted(r.inventory.items()))}
            for name, r in sorted(reports.items())}


def check_against_golden(reports: dict[str, AuditReport],
                         golden: dict) -> list[str]:
    """Mismatches between audited inventories and the pinned golden.

    Compares per-kind instruction COUNTS (bytes vary with audit shapes
    and XLA version; a new collective kind or instruction on a hot path
    is the regression the golden exists to catch). Only entry points
    present in both are compared, so a tier-1 run (no mesh rows) checks
    against the same golden the full audit regenerates.
    """
    problems = []
    for name, report in sorted(reports.items()):
        if name not in golden:
            problems.append(f"{name}: no golden entry (regen the golden: "
                            f"python -m repro.analysis.audit --regen)")
            continue
        want = golden[name]["collectives"]
        got = report.inventory
        if got != want:
            problems.append(f"{name}: collective inventory {got} != "
                            f"pinned {want}")
    return problems


def load_golden(path) -> dict:
    with open(path) as f:
        return json.load(f)


def save_golden(path, reports: dict[str, AuditReport],
                merge: dict | None = None) -> dict:
    """Write inventories to ``path``, merging over an existing golden so
    a single-device regen does not drop the mesh rows."""
    payload = dict(merge or {})
    payload.update(collect_inventories(reports))
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return payload
