"""Invariant-audit CLI: ``python -m repro.analysis.audit``.

Lowers + compiles every registry entry point runnable on this process's
device count (`trace_audit.ENTRY_POINTS`), audits each against its
:class:`~repro.analysis.trace_audit.InvariantSpec`, checks the
collective inventories against the pinned golden
(``tests/golden_collectives.json``), and runs the PRNG-stream lint over
the traced entry points. Exits nonzero on any violation or golden
mismatch.

The mesh rows need 8 devices: this CLI forces the 8-way host-device CPU
platform BEFORE jax initializes (the same subprocess idiom the slow-tier
mesh tests use), so one invocation covers everything:

    PYTHONPATH=src python -m repro.analysis.audit
    PYTHONPATH=src python -m repro.analysis.audit --regen   # repin golden
    PYTHONPATH=src python -m repro.analysis.audit --only deleda_scan_grid_2d
"""

from __future__ import annotations

import os

# must land before jax initializes a backend — keep above other imports;
# a caller-provided XLA_FLAGS (e.g. a different device count) wins
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse     # noqa: E402
import pathlib      # noqa: E402
import sys          # noqa: E402

GOLDEN = pathlib.Path(__file__).resolve().parents[3] / "tests" \
    / "golden_collectives.json"


def _prng_checks() -> list[str]:
    """PRNG-stream lint over the traced single-device entry points."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.analysis import prng_lint
    from repro.core import deleda, evaluation, serving
    from repro.core.graph import complete_graph
    from repro.analysis.trace_audit import _tiny_lda

    problems = []
    c, el = 4, 8
    key, ids = jax.random.key(0), jnp.arange(c)
    words = jnp.zeros((c, el), jnp.int32)
    mask = jnp.ones((c, el), bool)
    stats = jnp.zeros((3, 32), jnp.float32)
    tau, alpha = jnp.float32(0.01), jnp.float32(0.5)

    # chunk-invariant paths: zero batch-splits allowed
    for name, fn, args in [
        ("eval_chunk", functools.partial(evaluation.ll_slab_from_stats,
                                         n_particles=2, backend="fused"),
         (key, ids, words, mask, stats, tau, alpha)),
        ("serve_slab_mixture",
         functools.partial(serving._mixture_slab_from_stats, n_sweeps=4,
                           burnin=2),
         (key, ids, words, mask, stats, (stats + tau).sum(-1), tau, alpha)),
    ]:
        for f in prng_lint.check_fn(fn, *args, allow_batch_splits=0):
            problems.append(f"prng[{name}]: {f}")

    # the training driver: its single batch split (per-node init stats)
    # IS the semantics — batch identity is node identity there; reuse
    # still forbidden. The lifecycle refactor removed the old per-step
    # key batch split (step keys now derive by fold_in(key, absolute
    # step), which the lint likes), so exactly ONE split site remains.
    lda = _tiny_lda()
    cfg = deleda.DeledaConfig(lda=lda, mode="async", batch_size=3)
    edges, degs = deleda.make_run_inputs(complete_graph(4), 4, seed=0)
    dwords = jnp.zeros((4, 6, lda.doc_len_max), jnp.int32)
    dmask = jnp.ones((4, 6, lda.doc_len_max), bool)
    fn = functools.partial(deleda.run_deleda, cfg, n_steps=4,
                           record_every=2)
    for f in prng_lint.check_fn(fn, key, dwords, dmask, edges, degs,
                                allow_batch_splits=1):
        problems.append(f"prng[deleda_scan]: {f}")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.audit",
        description="lower + audit the repo's core entry points")
    ap.add_argument("--regen", action="store_true",
                    help="rewrite tests/golden_collectives.json from this "
                         "run (merges over existing rows)")
    ap.add_argument("--only", nargs="*", default=None,
                    help="audit only these entry points")
    ap.add_argument("--golden", default=str(GOLDEN))
    args = ap.parse_args(argv)

    from repro.analysis import trace_audit as ta

    reports = ta.run_audits(args.only)
    failed = False
    for name, report in reports.items():
        print(report.summary())
        failed |= not report.ok

    golden_path = pathlib.Path(args.golden)
    if args.regen:
        merge = ta.load_golden(golden_path) if golden_path.exists() else {}
        ta.save_golden(golden_path, reports, merge=merge)
        print(f"golden written: {golden_path} ({len(reports)} entries)")
    elif golden_path.exists():
        for problem in ta.check_against_golden(
                reports, ta.load_golden(golden_path)):
            print(f"GOLDEN MISMATCH {problem}")
            failed = True
    else:
        print(f"warning: no golden at {golden_path} (run --regen)",
              file=sys.stderr)

    for problem in _prng_checks():
        print(f"FAIL {problem}")
        failed = True

    skipped = sorted(set(ta.ENTRY_POINTS) - set(reports))
    if skipped:
        print(f"skipped (need more devices or --only): {skipped}",
              file=sys.stderr)
    print("audit:", "FAIL" if failed else "OK", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
