import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Gossip-vs-allreduce gradient sync at production mesh scale, measured
from compiled HLO (not just the analytic model).

For a real architecture's parameter pytree, lower + compile ONE
synchronization step over the 16-way "data" axis of the production mesh
under each strategy, and parse the per-device collective bytes out of the
partitioned HLO. This closes the loop on the paper's technique at LM
scale: the napkin model in core/decentralized.collective_bytes_per_sync
is validated against what XLA actually emits.

  PYTHONPATH=src python -m repro.launch.gossip_dryrun --arch xlstm_125m
"""

import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.core import decentralized as dec
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import abstract_params
from repro.roofline import parse_collectives

SPECS = ["allreduce", "gossip-hypercube", "gossip-hypercube[2]",
         "gossip-hypercube[1]", "gossip-ring[2]", "gossip-ring[1]"]


def measure(arch: str, out_path: str | None = None) -> dict:
    cfg = get_config(arch)
    mesh = make_production_mesh()                  # 16 x 16
    n_data = dict(mesh.shape)["data"]

    # gradient pytree: one full param set per data shard (gossip-DP
    # semantics: node-stacked leading axis sharded over "data")
    abs_p = abstract_params(cfg)
    abs_grads = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n_data,) + tuple(x.shape),
                                       jnp.float32), abs_p)
    payload = sum(int(jnp.prod(jnp.asarray(x.shape[1:]))) * 4
                  for x in jax.tree.leaves(abs_grads))

    node = P("data")
    results = {"arch": arch, "payload_bytes": payload, "specs": {}}
    print(f"{arch}: payload {payload/1e9:.2f} GB per node, data axis "
          f"{n_data}")
    print(f"{'spec':>22s} {'model GB':>10s} {'HLO GB':>10s} "
          f"{'HLO/model':>10s} {'exact':>6s}")
    for spec_str in SPECS:
        spec = dec.parse_sync(spec_str)

        def sync(tree):
            return dec.sync_tree_mesh(tree, spec, ("data",), (n_data,))

        shmap = jax.shard_map(sync, mesh=mesh, in_specs=node,
                              out_specs=node)
        # one-shot lower per spec: each iteration compiles a DIFFERENT
        # program for inspection, nothing is re-traced on a hot path
        compiled = jax.jit(shmap).lower(abs_grads).compile()   # lint: allow(jit-per-call)
        colls = parse_collectives(compiled.as_text())
        hlo_bytes = sum(v["bytes"] for v in colls.values())
        model_bytes = dec.collective_bytes_per_sync(spec, payload,
                                                    (n_data,))
        results["specs"][spec_str] = {
            "hlo_bytes": int(hlo_bytes),
            "model_bytes": int(model_bytes),
            "collectives": {k: (int(v["count"]), int(v["bytes"]))
                            for k, v in colls.items()},
            "exact": dec.is_exact(spec, (n_data,)),
        }
        ratio = hlo_bytes / max(model_bytes, 1)
        print(f"{spec_str:>22s} {model_bytes/1e9:10.3f} "
              f"{hlo_bytes/1e9:10.3f} {ratio:10.2f} "
              f"{str(dec.is_exact(spec, (n_data,))):>6s}")

    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {out_path}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm_125m", choices=list_archs())
    ap.add_argument("-o", "--out", default=None)
    args = ap.parse_args(argv)
    measure(args.arch, args.out
            or f"results/gossip_sync_{args.arch}.json")


if __name__ == "__main__":
    main()
