"""Gossip communication benchmarks through the unified Communicator API.

Two deliverables:

1. **Backend sweep** (the default): run identical matching schedules
   through every `repro.core.comm` mixer — DenseSimComm (jnp oracle),
   PallasSimComm (gossip_mix kernel) and mix_matching_sharded (ppermute
   passes over the host mesh, inside shard_map) — and write
   ``BENCH_gossip.json`` with bytes-moved and wall-clock per backend, so
   future PRs have a perf trajectory to beat.
   (Interpret-mode Pallas wall-times on CPU are NOT TPU predictions; the
   dense oracle is the CPU reference.)

2. **Collective byte model** (`--arch-table`): for each assigned arch's
   gradient payload, the per-device ICI bytes of one synchronization under
   allreduce / gossip-hypercube[k] / ring[k]
   (core.decentralized.collective_bytes_per_sync), optionally verified
   against HLO-parsed bytes on a small host mesh (`--verify-hlo`).

Usage: PYTHONPATH=src python -m benchmarks.gossip_collectives
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks import bench_util
from repro.configs import get_config, list_archs
from repro.core import comm as comm_mod
from repro.core import decentralized as dec
from repro.core.graph import watts_strogatz_graph
from repro.launch.mesh import make_host_mesh

SPECS = ["allreduce", "gossip-hypercube", "gossip-hypercube[2]",
         "gossip-hypercube[1]", "gossip-ring[2]"]

VERIFY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.core import decentralized as dec
    from repro.roofline import parse_collectives

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    x = jnp.zeros((8, 1024), jnp.float32)   # 4 KiB payload per node
    for s in %r:
        spec = dec.parse_sync(s)
        f = jax.jit(jax.shard_map(
            lambda v: dec.sync_tree_mesh(v, spec, ("data",), (8,)),
            mesh=mesh, in_specs=P("data"), out_specs=P("data")))
        hlo = f.lower(x).compile().as_text()
        colls = parse_collectives(hlo)
        by = {k: int(v["bytes"]) for k, v in colls.items()}
        print(f"HLO {s}: {by}")
""" % SPECS)


def mesh_mixer():
    """``mix_matching_sharded`` over the host's devices, and the bytes its
    passes ship a round: every pass moves each device's whole block."""
    mesh = make_host_mesh()
    n_dev = mesh.shape["data"]
    mix = jax.jit(jax.shard_map(
        partial(comm_mod.mix_matching_sharded, axis_name="data",
                n_dev=n_dev),
        mesh=mesh, in_specs=(P("data"), P()), out_specs=P("data")))

    def bytes_per_round(shape, itemsize, partners):
        block = shape[0] // n_dev * int(np.prod(shape[1:])) * itemsize
        return sum(len(p) for p in comm_mod.device_passes(n_dev)) * block
    return mix, bytes_per_round


def bench_backends(n: int, k_topics: int, vocab: int, rounds: int,
                   seed: int, out_path: str) -> dict:
    """Time every gossip mixer on one matching schedule."""
    graph = watts_strogatz_graph(n, 4, 0.3, seed)
    sched = comm_mod.GossipSchedule.draw_matchings(
        graph, rounds, np.random.default_rng(seed))
    stats = jax.random.uniform(jax.random.key(seed), (n, k_topics, vocab))
    itemsize = stats.dtype.itemsize

    results = {
        "shape": {"n": n, "k": k_topics, "v": vocab, "rounds": rounds,
                  "graph": graph.name, "dtype": str(stats.dtype)},
        "jax_backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "backends": {},
    }
    print(f"backend sweep: n={n} K={k_topics} V={vocab} rounds={rounds} "
          f"({len(jax.devices())} {jax.default_backend()} device(s))")
    print(f"{'backend':>8s} {'us/round':>10s} {'MB moved':>10s} "
          f"{'vs dense':>9s}")

    def run_all(mix, s):
        for t in range(sched.n_rounds):
            s = mix(s, jnp.asarray(sched.data[t]))
        return s

    mixers = {name: (c.mix_matching, c.bytes_per_round)
              for name, c in ((b, comm_mod.get_communicator(b))
                              for b in comm_mod.SIM_BACKENDS)}
    mixers["mesh"] = mesh_mixer()
    ref_out = np.asarray(run_all(mixers["dense"][0], stats))
    ref_us = None
    for name, (mix, bytes_per_round) in mixers.items():
        out = run_all(mix, stats)                     # warmup / compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        iters = 3
        for _ in range(iters):
            out = run_all(mix, stats)
        jax.block_until_ready(out)
        us_per_round = ((time.perf_counter() - t0) / iters
                        / sched.n_rounds * 1e6)
        total_bytes = sum(
            bytes_per_round(stats.shape, itemsize, sched.data[t])
            for t in range(sched.n_rounds))
        err = float(np.abs(np.asarray(out) - ref_out).max())
        assert err < 1e-5, (name, err)
        ref_us = ref_us if ref_us is not None else us_per_round
        results["backends"][name] = {
            "us_per_round": us_per_round,
            "bytes_moved": int(total_bytes),
            "max_err_vs_dense": err,
        }
        print(f"{name:>8s} {us_per_round:10.1f} {total_bytes/1e6:10.3f} "
              f"{us_per_round/ref_us:8.2f}x")

    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(bench_util.stamp(results), f, indent=2)
    print(f"wrote {out_path}")
    return results


def arch_table(chips: int):
    print(f"per-device bytes for ONE gradient sync on {chips} chips "
          f"(data-parallel axis)\n")
    hdr = f"{'arch':18s}{'payload GB':>11s}" + "".join(
        f"{s:>22s}" for s in SPECS)
    print(hdr)
    for arch in list_archs():
        cfg = get_config(arch)
        payload = cfg.n_params() * 4       # f32 grads
        row = f"{arch:18s}{payload/1e9:11.2f}"
        for s in SPECS:
            spec = dec.parse_sync(s)
            b = dec.collective_bytes_per_sync(spec, payload, (chips,))
            row += f"{b/1e9:22.2f}"
        print(row)
    print("\nexactness: " + ", ".join(
        f"{s}={dec.is_exact(dec.parse_sync(s), (chips,))}"
        for s in SPECS))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=256)
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--topics", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("-o", "--out", default="BENCH_gossip.json")
    ap.add_argument("--arch-table", action="store_true",
                    help="also print the per-arch collective byte model")
    ap.add_argument("--verify-hlo", action="store_true")
    args = ap.parse_args(argv)

    bench_backends(args.nodes, args.topics, args.vocab, args.rounds,
                   args.seed, args.out)

    if args.arch_table:
        print()
        arch_table(args.chips)

    if args.verify_hlo:
        # the child wants 8 virtual CPU devices, and it must never reach
        # for the accelerator this process already holds
        env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", VERIFY], env=env,
                           capture_output=True, text=True, timeout=600)
        print("\n" + r.stdout + r.stderr[-500:])


if __name__ == "__main__":
    main()
