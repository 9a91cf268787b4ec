"""Analysis-layer tests: HLO parser, invariant audits, PRNG lint, source
lint — including the auditor's own negative tests (a planted all_gather
of documents must FAIL the privacy audit; the anti-pattern fixture must
produce exactly the expected findings)."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import prng_lint, source_lint
from repro.analysis import trace_audit as ta
from repro.analysis.hlo import parse_collective_ops, parse_collectives

HERE = pathlib.Path(__file__).parent
GOLDEN = HERE / "golden_collectives.json"
FIXTURE = HERE / "fixtures" / "lint_antipatterns.py"


# ---------------------------------------------------------------------------
# HLO parser
# ---------------------------------------------------------------------------

CANNED = textwrap.dedent("""\
    %ag = s32[64,8]{1,0} all-gather(s32[8,8]{1,0} %docs), dimensions={0}, replica_groups={{0,1,2,3,4,5,6,7}}
    %cp = f32[4,64]{1,0} collective-permute(f32[4,64]{1,0} %stats), source_target_pairs={{0,1},{1,0}}
    %ar-start = f32[2,3]{1,0} all-reduce-start(f32[2,3]{1,0} %x), replica_groups=[4,2]<=[8]
    %ar-done = f32[2,3]{1,0} all-reduce-done(f32[2,3]{1,0} %ar-start)
    %tup = (f32[8]{0}, f32[4]{0}) all-reduce(%a, %b), replica_groups={}
""")


def test_parse_collective_ops_kinds_shapes_groups():
    ops = parse_collective_ops(CANNED)
    kinds = [op.kind for op in ops]
    assert kinds == ["all-gather", "collective-permute", "all-reduce",
                     "all-reduce"]
    ag = ops[0]
    assert ag.shapes[0].dtype == "s32"
    assert ag.shapes[0].dims == (64, 8)
    assert ag.shapes[0].is_integer
    assert ag.replica_groups == ((0, 1, 2, 3, 4, 5, 6, 7),)
    # iota form: [4,2]<=[8] -> four consecutive pairs
    assert ops[2].replica_groups == ((0, 1), (2, 3), (4, 5), (6, 7))
    # tuple results parse every member shape
    assert [s.dims for s in ops[3].shapes] == [(8,), (4,)]


def test_parse_collectives_aggregate_counts_and_bytes():
    agg = parse_collectives(CANNED)
    assert agg["all-gather"]["count"] == 1
    assert agg["all-gather"]["bytes"] == 64 * 8 * 4
    # the async -done line must not double count
    assert agg["all-reduce"]["count"] == 2


def test_roofline_reexports_shared_parser():
    from repro.roofline import hlo as roofline_hlo
    assert roofline_hlo.parse_collectives is parse_collectives


# ---------------------------------------------------------------------------
# Trace audit on canned text (the privacy boundary, no devices needed)
# ---------------------------------------------------------------------------

GOSSIP_SPEC = ta.InvariantSpec(
    "gossip", allowed_collectives=ta.GOSSIP_ALLOWED, doc_len=8)


def test_planted_all_gather_of_docs_fails_privacy_audit():
    leaked = ("%ag = s32[64,8]{1,0} all-gather(s32[8,8]{1,0} %docs), "
              "dimensions={0}, replica_groups={{0,1,2,3,4,5,6,7}}")
    report = ta.audit_hlo_text(leaked, GOSSIP_SPEC)
    rules = {v.rule for v in report.violations}
    assert "collective-allowlist" in rules   # all-gather not allowed at all
    assert "privacy-doc-buffer" in rules     # ...and it moves doc tokens
    assert not report.ok


def test_float_stats_permute_passes_privacy_audit():
    ok_line = ("%cp = f32[4,64]{1,0} collective-permute(f32[4,64]{1,0} "
               "%stats), source_target_pairs={{0,1},{1,0}}")
    report = ta.audit_hlo_text(ok_line, GOSSIP_SPEC)
    assert report.ok, report.summary()
    assert report.inventory == {"collective-permute": 1}


def test_forbidden_exact_dims_and_count_budget():
    spec = ta.InvariantSpec(
        "x", allowed_collectives=frozenset({"all-reduce"}),
        max_counts=(("all-reduce", 1),),
        forbidden_dims=((2, 3),))
    two = ("%a = f32[2,3]{1,0} all-reduce(%x), replica_groups={}\n"
           "%b = f32[4]{0} all-reduce(%y), replica_groups={}")
    rules = {v.rule for v in ta.audit_hlo_text(two, spec).violations}
    assert rules == {"privacy-doc-buffer", "collective-count"}


def test_replica_group_placement_checked():
    spec = ta.InvariantSpec(
        "grid", allowed_collectives=frozenset({"all-reduce"}),
        replica_groups=((0, 1), (2, 3)),
        grouped_kinds=frozenset({"all-reduce"}))
    good = "%a = f32[4]{0} all-reduce(%x), replica_groups={{0,1},{2,3}}"
    bad = "%a = f32[4]{0} all-reduce(%x), replica_groups={{0,2},{1,3}}"
    assert ta.audit_hlo_text(good, spec).ok
    report = ta.audit_hlo_text(bad, spec)
    assert [v.rule for v in report.violations] == ["replica-groups"]


def test_temp_budget_violation():
    spec = ta.InvariantSpec("m", max_temp_bytes=100)
    report = ta.audit_hlo_text("", spec, temp_bytes=101)
    assert [v.rule for v in report.violations] == ["temp-budget"]
    assert ta.audit_hlo_text("", spec, temp_bytes=100).ok


# ---------------------------------------------------------------------------
# Entry-point audits vs the pinned golden (single-device rows in tier-1)
# ---------------------------------------------------------------------------

def test_single_device_entry_points_pass_and_match_golden():
    reports = ta.run_audits()
    assert set(reports) >= {"deleda_scan", "deleda_scan_sharded",
                            "eval_chunk", "serve_slab_ll",
                            "serve_slab_mixture"}
    for name, report in reports.items():
        assert report.ok, report.summary()
    problems = ta.check_against_golden(reports, ta.load_golden(GOLDEN))
    assert not problems, problems


def test_golden_covers_mesh_rows_too():
    golden = ta.load_golden(GOLDEN)
    assert set(golden) == set(ta.ENTRY_POINTS)
    assert golden["deleda_scan_mesh_1d"]["collectives"] == {
        "collective-permute": 3, "all-reduce": 2}
    assert golden["deleda_scan_grid_2d"]["collectives"] == {
        "collective-permute": 1, "all-reduce": 3}


# ---------------------------------------------------------------------------
# CompileCounter
# ---------------------------------------------------------------------------

def test_compile_counter_counts_new_traces():
    @jax.jit
    def f(x):
        return x * 2

    with ta.CompileCounter(f) as cc:
        f(jnp.zeros((2,)))
        f(jnp.ones((2,)))        # same shape: cached
    assert cc.total == 1, cc.counts

    with ta.CompileCounter(f) as cc:
        f(jnp.zeros((3,)))       # new shape: new trace
        f(jnp.zeros((2,)))       # still cached from before
    assert cc.total == 1, cc.counts


def test_compile_counter_requires_fns():
    with pytest.raises(ValueError):
        ta.CompileCounter()


# ---------------------------------------------------------------------------
# PRNG lint
# ---------------------------------------------------------------------------

def test_prng_lint_flags_key_reuse():
    def leaky(key):
        a = jax.random.uniform(key, (3,))
        b = jax.random.normal(key, (3,))
        return a + b

    findings = prng_lint.lint_fn(leaky, jax.random.key(0))
    assert [f.kind for f in findings] == ["key-reuse"]


def test_prng_lint_flags_batch_split():
    def per_doc_by_split(key, docs):
        ks = jax.random.split(key, docs.shape[0])
        return jax.vmap(lambda k: jax.random.uniform(k, (4,)))(ks)

    findings = prng_lint.lint_fn(per_doc_by_split, jax.random.key(0),
                                 jnp.zeros((16, 4)))
    assert [f.kind for f in findings] == ["batch-split"]


def test_prng_lint_clean_fold_in_idiom():
    def per_doc_by_fold_in(key, ids):
        ks = jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)
        return jax.vmap(lambda k: jax.random.uniform(k, (4,)))(ks)

    assert prng_lint.lint_fn(per_doc_by_fold_in, jax.random.key(0),
                             jnp.arange(16)) == []


def test_prng_lint_recurses_into_scan():
    def scanned(key, xs):
        def body(k, x):
            k1, k2 = jax.random.split(k)
            return k1, jax.random.uniform(k2) + x
        _, ys = jax.lax.scan(body, key, xs)
        return ys

    assert prng_lint.lint_fn(scanned, jax.random.key(0),
                             jnp.zeros((4,))) == []

    def scanned_reuse(key, xs):
        def body(k, x):
            u = jax.random.uniform(k)
            k2 = jax.random.fold_in(k, 0)     # k consumed twice
            return k2, u + x
        _, ys = jax.lax.scan(body, key, xs)
        return ys

    kinds = [f.kind for f in prng_lint.lint_fn(
        scanned_reuse, jax.random.key(0), jnp.zeros((4,)))]
    assert "key-reuse" in kinds


def test_prng_check_fn_allowance():
    def two_splits(key, n):
        ks = jax.random.split(key, 4)
        k2 = jax.random.split(ks[0], 8)
        return jax.random.uniform(k2[0], (2,)) * n

    args = (jax.random.key(0), jnp.float32(1.0))
    assert len(prng_lint.check_fn(two_splits, *args)) == 2
    assert prng_lint.check_fn(two_splits, *args,
                              allow_batch_splits=2) == []


def test_eval_and_serving_slabs_are_chunk_invariant_streams():
    """The serving/eval entry points must not batch-split (PR-5 class)."""
    import functools

    from repro.core import evaluation, serving

    c, el = 4, 8
    key, ids = jax.random.key(0), jnp.arange(c)
    words = jnp.zeros((c, el), jnp.int32)
    mask = jnp.ones((c, el), bool)
    stats = jnp.zeros((3, 32), jnp.float32)
    tau, alpha = jnp.float32(0.01), jnp.float32(0.5)
    assert prng_lint.check_fn(
        functools.partial(evaluation.ll_slab_from_stats, n_particles=2,
                          backend="fused"),
        key, ids, words, mask, stats, tau, alpha) == []
    assert prng_lint.check_fn(
        functools.partial(serving._mixture_slab_from_stats, n_sweeps=4,
                          burnin=2),
        key, ids, words, mask, stats, (stats + tau).sum(-1), tau,
        alpha) == []


# ---------------------------------------------------------------------------
# Source lint
# ---------------------------------------------------------------------------

def test_fixture_produces_exactly_the_expected_findings():
    findings = source_lint.lint_file(FIXTURE)
    got = [(f.line, f.rule) for f in findings]
    assert got == [(9, "optional-import"),
                   (15, "timer-no-barrier"),
                   (21, "jit-per-call"),
                   (26, "jit-per-call"),
                   (30, "use-pallas-alias")], got


def test_barrier_closes_timer_interval():
    clean = textwrap.dedent("""\
        import time, jax
        def timed(fn, x):
            t0 = time.perf_counter()
            y = jax.block_until_ready(fn(x))
            return y, time.perf_counter() - t0
    """)
    assert source_lint.lint_text(clean) == []


def test_unbarriered_interval_flagged_and_pragma_suppresses():
    dirty = textwrap.dedent("""\
        import time
        def timed(fn, x):
            t0 = time.perf_counter()
            y = fn(x)
            return y, time.perf_counter() - t0
    """)
    findings = source_lint.lint_text(dirty)
    assert [f.rule for f in findings] == ["timer-no-barrier"]
    suppressed = dirty.replace(
        "return y, time.perf_counter() - t0",
        "return y, time.perf_counter() - t0  # lint: allow(timer-no-barrier)")
    assert source_lint.lint_text(suppressed) == []


def test_guarded_and_lazy_optional_imports_allowed():
    ok = textwrap.dedent("""\
        try:
            import hypothesis
        except ImportError:
            hypothesis = None
        def lazy():
            import scipy
            return scipy
    """)
    assert source_lint.lint_text(ok) == []
    assert [f.rule for f in source_lint.lint_text("import scipy\n")] \
        == ["optional-import"]


def test_hoisted_jit_not_flagged():
    ok = textwrap.dedent("""\
        import jax
        def bench(fn, xs):
            jitted = jax.jit(lambda x: fn(x))
            return [jitted(x) for x in xs]
    """)
    assert source_lint.lint_text(ok) == []


def test_repo_tree_is_lint_clean():
    findings = source_lint.lint_paths()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lint_cli_exit_codes():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(HERE.parent / "src")
    clean = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint"], env=env,
        cwd=HERE.parent, capture_output=True, text=True, timeout=120)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    dirty = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", str(FIXTURE)],
        env=env, cwd=HERE.parent, capture_output=True, text=True,
        timeout=120)
    assert dirty.returncode == 1, dirty.stdout + dirty.stderr
    assert "use-pallas-alias" in dirty.stdout


# ---------------------------------------------------------------------------
# Mesh rows + the planted-leak negative test (8 host devices, subprocess)
# ---------------------------------------------------------------------------

LEAK_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.analysis import trace_audit as ta
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import PartitionSpec as P

    mesh = make_host_mesh()
    node = P("data")

    def leaky(docs):
        # the anti-pattern the auditor exists to catch: raw documents
        # gathered across nodes
        return jax.lax.all_gather(docs, "data", tiled=True)

    fn = jax.jit(jax.shard_map(leaky, mesh=mesh, in_specs=node,
                               out_specs=node))
    docs = jnp.zeros((8, 8), jnp.int32)             # [B, L] tokens
    report = ta.audit_compiled(
        fn.lower(docs).compile(),
        ta.InvariantSpec("leaky_mesh",
                         allowed_collectives=ta.GOSSIP_ALLOWED,
                         doc_len=8))
    assert not report.ok, "planted all_gather of docs must fail"
    rules = {v.rule for v in report.violations}
    assert "collective-allowlist" in rules, rules
    assert "privacy-doc-buffer" in rules, rules
    print("LEAK_AUDIT_OK")
""")


@pytest.mark.slow
def test_planted_all_gather_fails_on_real_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(HERE.parent / "src")
    r = subprocess.run([sys.executable, "-c", LEAK_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "LEAK_AUDIT_OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.slow
def test_full_audit_cli_passes_on_8_devices():
    """The CI entry point: every registry row (mesh included) + golden +
    PRNG checks, in one subprocess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(HERE.parent / "src")
    env.pop("XLA_FLAGS", None)     # the CLI sets the 8-device platform
    r = subprocess.run([sys.executable, "-m", "repro.analysis.audit"],
                       env=env, cwd=HERE.parent, capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(GOLDEN.read_text())
    assert set(out) == set(ta.ENTRY_POINTS)
