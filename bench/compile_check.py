"""Compile a training cell's programs for a described TPU v5e, on the host.

    JAX_PLATFORMS=cpu python bench/compile_check.py <config-name> [...]

No chip is needed: the TPU compiler is installed here and compiles for a
chip that is described, not attached. For each configuration this lowers
the window's program (``deleda.train_steps`` at the cell's segment) and the
reference's round at the cell's real shapes, and prints the compiler's
``memory_analysis`` and the host's compile seconds. What the compiler
refuses here costs no chip time. Not reachable from ``bench/run.py``.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(names):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import reference
    from bench.drivers import train_rounds
    from repro.core import deleda

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    traffic = json.loads((ROOT / "bench/traffic/train_rounds.json").read_text())
    seg = traffic["segment_rounds"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    for name in names:
        cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
        n, d, l = cfg["n_nodes"], cfg["docs_per_node"], cfg["doc_len_max"]
        k, v = cfg["n_topics"], cfg["vocab_size"]
        dcfg = deleda.DeledaConfig(
            lda=train_rounds._lda(cfg), mode=cfg["mode"],
            batch_size=cfg["batch_size"], rho_kappa=cfg["rho_kappa"],
            rho_t0=cfg["rho_t0"])
        key = jax.eval_shape(lambda: jax.random.key(0))
        state = jax.eval_shape(lambda kk: deleda.init_state(dcfg, kk, n), key)
        state = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), state)
        words, mask = sds((n, d, l), jnp.int32), sds((n, d, l), jnp.bool_)
        sched = sds((seg, n), jnp.int32)
        corr, live = sds((seg, n), jnp.float32), sds((seg, n), jnp.bool_)
        hp = dict(batch=cfg["batch_size"], tau=cfg["tau"],
                  alpha=cfg["alpha"], n_sweeps=cfg["n_gibbs"],
                  burnin=cfg["n_gibbs_burnin"], rho_t0=cfg["rho_t0"],
                  rho_kappa=cfg["rho_kappa"])
        programs = {
            "train_steps": lambda: deleda.train_steps.lower(
                dcfg, state, words, mask, sched, corr, live,
                record_every=seg),
            "reference.round_": lambda: reference.round_.lower(
                sds((n, k, v), jnp.float32), sds((n,), jnp.int32),
                jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), key),
                sds((), jnp.int32), sds((n,), jnp.int32), words, mask, **hp),
        }
        for prog, lower in programs.items():
            t0 = time.perf_counter()
            compiled = lower().compile()
            secs = time.perf_counter() - t0
            ma = compiled.memory_analysis()
            row = {"config": name, "program": prog,
                   "compile_s": round(secs, 1),
                   "argument_bytes": ma.argument_size_in_bytes,
                   "output_bytes": ma.output_size_in_bytes,
                   "temp_bytes": ma.temp_size_in_bytes,
                   "alias_bytes": ma.alias_size_in_bytes}
            row["total_gb"] = round((row["argument_bytes"] + row["output_bytes"]
                                     + row["temp_bytes"]
                                     - row["alias_bytes"]) / 1e9, 2)
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or ["nytimes-k100", "pubmed-k100"])
