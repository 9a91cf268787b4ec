"""CPU rehearsal of a cell at a tiny size, past the harness's look for a chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <name> [--seed N] [--seconds S]

Runs the cell's driver end to end (set-up, window, reference comparison)
on the CPU with the sizes in ``control.TINY``, Pallas kernels in interpret
mode, and prints the result. It checks paths, arguments and control flow;
its times are the CPU's and are never reported as device numbers. Not
reachable from ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def rehearse(workload: str, seed: int, seconds: float = 1.0):
    """The cell driver's Result for a tiny CPU run of ``workload``."""
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench import common, control, peaks
    from bench import run as run_mod
    config, traffic = control.setup(workload, tiny=True)
    driver = run_mod.load_module(
        ROOT / "bench" / "drivers" / f"{traffic['driver']}.py",
        f"bench_driver_{traffic['driver']}")
    run = common.Run(workload=workload, seed=seed, seconds=seconds,
                     trace=False, config=config, traffic=traffic,
                     devices=jax.devices()[:1],
                     peaks=peaks.PEAKS["TPU v5 lite"],
                     t_process=common.now())
    return driver.run(run)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    res = rehearse(args.workload, args.seed, args.seconds)
    print(json.dumps({"correct": res.correct, "attempted": res.attempted,
                      "failed": res.failed, "e2e": res.e2e,
                      "checks": {c.name: [c.value, c.limit]
                                 for c in res.checks}}))


if __name__ == "__main__":
    main()
