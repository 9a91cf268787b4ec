"""DELEDA chip benchmark: one cell, one run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's root:
the cell's configuration file (``configs``), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``driver`` names the module under
``bench/drivers/`` that plays it) and, with ``--trace 1``, one reader per
per-layer metric (``bench/metrics/<metric>.py``). Adding a cell or a metric
adds files; no file here changes.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each compared number with its limit).
The same checks are the last lines of standard error. A run that finds no
TPU, fewer chips than the cell asks for, or a device kind without published
peaks exits non-zero before it measures anything.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class Refused(Exception):
    """The run cannot measure here (no chip, wrong chip, no manifest)."""


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_entries(manifest: dict, workload: str):
    """(cell, config entry, end-to-end entries, per-layer entries)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return cell, config, e2e, layer


def devices_for(chips: int):
    """The cell's devices, or Refused where this machine cannot measure."""
    import jax

    from bench import peaks as peaks_mod
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX finds no accelerator: {e}") from e
    if devs[0].platform != "tpu":
        raise Refused(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise Refused(f"cell needs {chips} chips, JAX finds {len(devs)}")
    try:
        peaks = peaks_mod.peaks_for(devs[0].device_kind)
    except KeyError as e:
        raise Refused(str(e)) from e
    return devs[:chips], peaks


def enable_compile_cache():
    """JAX's persistent cache at a fixed path in the checkout (or the
    directory ``JAX_COMPILATION_CACHE_DIR`` names), for every program."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest_path = ROOT / "BENCHMARK.json"
    if not manifest_path.is_file():
        raise Refused("no BENCHMARK.json at the checkout's root")
    manifest = json.loads(manifest_path.read_text())
    cell, config_entry, e2e, layer = cell_entries(manifest, args.workload)
    config = json.loads((ROOT / config_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))        # the system under test
    # libtpu would log under /tmp; a run writes only inside its checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enable_compile_cache()
    devs, peaks = devices_for(int(cell["chips"]))
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")

    from bench import common
    run = common.Run(workload=args.workload, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     config=config, traffic=traffic, devices=devs,
                     peaks=peaks, t_process=T_PROCESS)
    res = driver.run(run)

    if args.trace:
        metrics = {}
        for m in layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(res.layer)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        missing = [m["name"] for m in e2e if m["name"] not in res.e2e]
        if missing:
            raise Refused(f"driver {traffic['driver']} gave no {missing}")
        metrics = {m["name"]: {"value": res.e2e[m["name"]], "unit": m["unit"]}
                   for m in e2e}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": res.memory_peak_bytes}
    line = {"correct": res.correct, "attempted": res.attempted,
            "failed": res.failed, "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = res.layer.window.busy_s
        device["window_s"] = res.layer.window.window_s
        line["breakdown"] = res.layer.window.breakdown()
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in res.checks}
    for c in res.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        code = 3
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
