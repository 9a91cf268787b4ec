"""Readings that set the limits of a training cell's comparison.

    python bench/control.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--fault-seeds 1 2 3]

At the cell's own size, in one process, for each seed: the program's
first steps against the float32 reference (the sound readings, whose
largest is a limit's lower end); for each control seed the reference in
bfloat16 put in the program's place (the control, whose smallest reading
is a limit's upper end); for each fault seed the program with a fault
planted underneath (half of each minibatch left out, the gossip exchange
left out, the E-step computed in bfloat16 over float32 statistics). Prints one JSON line per reading. Not reachable from
``bench/run.py``: the benchmark's own runs do not run the control.
With ``JAX_PLATFORMS=cpu`` and ``--tiny`` it runs at a test's size.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

TINY = {"n_nodes": 4, "docs_per_node": 16, "doc_len_max": 24,
        "mean_doc_len": 10, "vocab_size": 300, "n_topics": 7,
        "batch_size": 3, "n_gibbs": 6, "n_gibbs_burnin": 3}


def setup(workload: str, tiny: bool):
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import run as run_mod
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, centry, _e2e, _layer = run_mod.cell_entries(manifest, workload)
    config = json.loads((ROOT / centry["file"]).read_text())
    if tiny:
        config.update(TINY)
        config["graph"] = dict(config["graph"], k=2)
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return config, traffic


@contextlib.contextmanager
def fault(name: str | None):
    """Plant a fault in the program underneath the timed path."""
    import jax

    from repro.core import comm as comm_mod
    from repro.core import deleda
    from repro.core import estep as estep_mod
    saved = []

    def patch(obj, attr, fn):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, fn)

    if name == "half_batch":
        orig = estep_mod.estep_batch_from_stats

        def half(backend, config, keys, words, mask, stats, **kw):
            h = words.shape[1] // 2
            return orig(backend, config, keys, words[:, :h], mask[:, :h],
                        stats, **kw)
        patch(estep_mod, "estep_batch_from_stats", half)
    elif name == "estep_bf16":
        orig = estep_mod.estep_batch_from_stats

        def low(backend, config, keys, words, mask, stats, **kw):
            return orig(backend, config, keys, words, mask,
                        stats.astype(jax.numpy.bfloat16),
                        **kw).astype(stats.dtype)
        patch(estep_mod, "estep_batch_from_stats", low)
    elif name == "no_exchange":
        patch(comm_mod.DenseSimComm, "mix_matching",
              lambda self, stats, partners: stats)
    elif name == "state_unchanged":
        orig_steps = deleda.train_steps

        def unchanged(config, state, *a, **kw):
            new, tr = orig_steps(config, state, *a, **kw)
            return state, tr
        patch(deleda, "train_steps", unchanged)
    elif name is not None:
        raise ValueError(f"unknown fault {name!r}")
    jax.clear_caches()
    try:
        yield
    finally:
        for obj, attr, val in reversed(saved):
            setattr(obj, attr, val)
        jax.clear_caches()


def readings(workload: str, seed: int, *, tiny: bool = False,
             control: bool = False, fault_name: str | None = None,
             config=None, traffic=None) -> list[dict]:
    """The compared numbers of one seed: program (or faulty program) vs
    the float32 reference, and with ``control`` the bfloat16 reference
    in the program's place."""
    import jax
    import jax.numpy as jnp

    from bench import common
    from bench.drivers import train_rounds as tr
    if config is None:
        config, traffic = setup(workload, tiny)
    run = common.Run(workload=workload, seed=seed, seconds=0.0, trace=False,
                     config=config, traffic=traffic,
                     devices=jax.devices()[:1], peaks={}, t_process=0.0)
    words, mask, partners, run_key = tr.make_inputs(run)
    seg, n_check = traffic["segment_rounds"], traffic["check_steps"]
    with fault(fault_name):
        trainer = tr.Trainer(run, words, mask, partners, run_key)
        prog = tr.first_steps(trainer, run_key, n_check)
        del trainer
    ref = tr.reference_steps(run, words, mask, partners, run_key,
                             n_check * seg, seg, jnp.float32)
    side = "program" if fault_name is None else f"fault:{fault_name}"
    out = [dict(seed=seed, side=side, **tr.readings(prog, ref))]
    if control:
        low = tr.reference_steps(run, words, mask, partners, run_key,
                                 n_check * seg, seg, jnp.bfloat16)
        out.append(dict(seed=seed, side="control:bfloat16",
                        **tr.readings(low, ref)))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=["half_batch",
                                                    "no_exchange",
                                                    "estep_bf16"])
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    config, traffic = setup(args.workload, args.tiny)
    from bench import run as run_mod
    run_mod.enable_compile_cache()
    for s in args.seeds:
        for row in readings(args.workload, s, control=s in args.control_seeds,
                            config=config, traffic=traffic):
            print(json.dumps(row), flush=True)
    for f in args.faults:
        for s in args.fault_seeds:
            for row in readings(args.workload, s, fault_name=f,
                                config=config, traffic=traffic):
                print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
