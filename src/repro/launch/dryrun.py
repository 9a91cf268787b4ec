import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.

For each combination this driver:
  1. builds the production mesh (16x16 single-pod or 2x16x16 multi-pod),
  2. assembles the jitted step via launch.steps.build (abstract inputs,
     shape-aware shardings),
  3. .lower().compile() — any sharding mismatch / unsupported collective
     is a bug in the system and fails loudly,
  4. prints memory_analysis() and cost_analysis(),
  5. parses collective bytes out of the compiled HLO and writes the
     roofline JSON consumed by benchmarks/roofline_table.py.

Usage:
  python -m repro.launch.dryrun --arch gemma2_2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  python -m repro.launch.dryrun --all --skip-existing -o results/dryrun
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.roofline import analyze, parse_collectives


def applicable_shapes(cfg) -> list[str]:
    out = []
    for name, shape in INPUT_SHAPES.items():
        if shape.kind == "decode" and not cfg.decode_shapes:
            continue
        if name == "long_500k" and not cfg.supports_long_context:
            continue
        out.append(name)
    return out


def _mem_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: str | None = None, verbose: bool = True,
            unroll: bool = False, cfg_override=None,
            constrain_acts: bool = True, tag: str = "",
            rules=None) -> dict:
    import dataclasses as _dc
    cfg = cfg_override or get_config(arch)
    if unroll:
        # cost_analysis counts a While body ONCE: unroll the layer loop so
        # the roofline's FLOP/byte terms reflect the real per-step work.
        cfg = _dc.replace(cfg, scan_layers=False)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = ("2x16x16" if multi_pod else "16x16") + \
        ("-unroll" if unroll else "") + tag
    chips = mesh.devices.size

    t0 = time.time()
    with jax.default_device(jax.devices("cpu")[0]):
        step = steps_mod.build(cfg, shape, mesh, rules=rules,
                               constrain_acts=constrain_acts)
        lowered = step.lower()
        # lower()/compile() are host-blocking: no device work in flight
        t_lower = time.time() - t0    # lint: allow(timer-no-barrier)
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower   # lint: allow(timer-no-barrier)

    mem = _mem_dict(compiled)
    try:
        cost = compiled.cost_analysis() or {}
    except Exception:
        cost = {}
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    coll_bytes = sum(v["bytes"] for v in colls.values())

    report = analyze(cfg, shape, mesh_name, chips, flops, bytes_accessed,
                     coll_bytes, colls, mem)
    result = report.as_dict()
    result.update(lower_sec=t_lower, compile_sec=t_compile,
                  status="ok")

    if verbose:
        print(f"== {arch} x {shape_name} x {mesh_name} "
              f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
        print(f"   memory_analysis: {mem}")
        print(f"   cost_analysis: flops={flops:.3e} "
              f"bytes={bytes_accessed:.3e}")
        colls_fmt = {k: (int(v["count"]), int(v["bytes"]))
                     for k, v in colls.items()}
        print(f"   collectives: {colls_fmt}")
        print(f"   roofline: compute={report.compute_sec:.4f}s "
              f"memory={report.memory_sec:.4f}s "
              f"collective={report.collective_sec:.4f}s "
              f"dominant={report.dominant} "
              f"useful_ratio={report.useful_flops_ratio:.3f}")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_name}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=2, default=str)
    return result


def _depth_pair(cfg) -> tuple[int, int, int]:
    """Two reduced layer counts (L1, L2) whose unrolled compiles identify
    the per-layer cost, plus the structural period. Layer stacks are
    homogeneous per family, so FLOPs/bytes/collective-bytes are affine in
    depth: F(L) = F0 + L*body. cost_analysis counts While bodies once, so
    honest full-depth numbers come from unrolling L1, L2 << L_full and
    extrapolating — minutes instead of hours of compile."""
    if cfg.family == "moe":
        base = cfg.first_dense_layers
        return base + 2, base + 4, 1
    if cfg.family == "hybrid":
        p = cfg.attn_every
        return p, 2 * p, p
    if cfg.family == "ssm":
        p = cfg.slstm_every or 1
        return p, 2 * p, p
    return 2, 4, 1


def run_extrapolated(arch: str, shape_name: str, multi_pod: bool,
                     out_dir: str | None = None,
                     constrain_acts: bool = True, tag: str = "",
                     overrides: dict | None = None, rules=None) -> dict:
    """Honest roofline numbers via two reduced-depth UNROLLED compiles."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    l1, l2, _p = _depth_pair(cfg)
    assert l2 <= cfg.n_layers, (arch, l2)

    def reduced(n):
        upd = dict(n_layers=n, scan_layers=False)
        if cfg.family == "encdec":
            upd["n_encoder_layers"] = n
        return _dc.replace(cfg, **upd)

    r1 = run_one(arch, shape_name, multi_pod, verbose=False,
                 cfg_override=reduced(l1), constrain_acts=constrain_acts,
                 rules=rules)
    r2 = run_one(arch, shape_name, multi_pod, verbose=False,
                 cfg_override=reduced(l2), constrain_acts=constrain_acts,
                 rules=rules)

    mesh_name = ("2x16x16" if multi_pod else "16x16") + "-xtrap" + tag
    shape = INPUT_SHAPES[shape_name]
    chips = r1["chips"]
    l_full = cfg.n_layers
    # enc-dec scales encoder and decoder together (full has 1:1 ratio)

    def affine(key):
        slope = (r2[key] - r1[key]) / (l2 - l1)
        return max(r1[key] + slope * (l_full - l1), 0.0)

    flops = affine("hlo_flops_per_device")
    bytes_ = affine("hlo_bytes_per_device")
    coll = affine("collective_bytes_per_device")
    report = analyze(cfg, shape, mesh_name, chips, flops, bytes_, coll,
                     {"extrapolated_from": [l1, l2]},
                     memory_analysis={
                         k: int(max(
                             r1["memory_analysis"].get(k, 0)
                             + (r2["memory_analysis"].get(k, 0)
                                - r1["memory_analysis"].get(k, 0))
                             / (l2 - l1) * (l_full - l1), 0))
                         for k in r1.get("memory_analysis", {})})
    result = report.as_dict()
    result.update(status="ok", method=f"depth-extrapolated[{l1},{l2}]",
                  lower_sec=r1["lower_sec"] + r2["lower_sec"],
                  compile_sec=r1["compile_sec"] + r2["compile_sec"])
    print(f"== {arch} x {shape_name} x {mesh_name} "
          f"(depths {l1},{l2} -> {l_full}) "
          f"compute={report.compute_sec:.4f}s "
          f"memory={report.memory_sec:.4f}s "
          f"collective={report.collective_sec:.4f}s "
          f"dominant={report.dominant} "
          f"ratio={report.useful_flops_ratio:.3f}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_name}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=2, default=str)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 mesh (default 16x16)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("-o", "--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer loops for honest cost_analysis "
                         "(roofline numbers)")
    ap.add_argument("--extrapolate", action="store_true",
                    help="honest roofline via two reduced-depth unrolled "
                         "compiles + affine extrapolation in depth")
    ap.add_argument("--constrain-acts", dest="constrain_acts",
                    action="store_true", default=True,
                    help="activation-sharding constraints (default ON)")
    ap.add_argument("--no-constrain-acts", dest="constrain_acts",
                    action="store_false")
    ap.add_argument("--moe-impl", default=None,
                    choices=["ragged", "capacity"])
    ap.add_argument("--xlstm-chunk", type=int, default=None)
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--dp-only", action="store_true",
                    help="replicate the model axis (pure DP rules)")
    ap.add_argument("--remat-policy", default=None,
                    choices=["full", "dots", "none"])
    ap.add_argument("--tag", default="",
                    help="suffix for result filenames (perf experiments)")
    args = ap.parse_args(argv)

    combos = []
    archs = list_archs() if args.all or not args.arch else [args.arch]
    for arch in archs:
        cfg = get_config(arch)
        shapes = (applicable_shapes(cfg) if args.all or not args.shape
                  else [args.shape])
        for s in shapes:
            meshes = [args.multi_pod] if not args.both_meshes \
                else [False, True]
            for mp in meshes:
                combos.append((arch, s, mp))

    failures = []
    for arch, s, mp in combos:
        suffix = ("-xtrap" if args.extrapolate else (
            "-unroll" if args.unroll else "")) + args.tag
        mesh_name = ("2x16x16" if mp else "16x16") + suffix
        fname = os.path.join(args.out, f"{arch}__{s}__{mesh_name}.json")
        if args.skip_existing and os.path.exists(fname):
            print(f"-- skip {arch} x {s} x {mesh_name} (exists)")
            continue
        try:
            overrides = {}
            if args.moe_impl and get_config(arch).family == "moe":
                overrides["moe_impl"] = args.moe_impl
            if args.xlstm_chunk is not None \
                    and get_config(arch).family == "ssm":
                overrides["xlstm_chunk"] = args.xlstm_chunk
            if args.attn_chunk is not None:
                overrides["attn_chunk_q"] = args.attn_chunk
            if args.remat_policy is not None:
                overrides["remat_policy"] = args.remat_policy
            rules = None
            if args.dp_only:
                from repro.sharding import DP_ONLY_RULES
                rules = DP_ONLY_RULES
            if args.extrapolate:
                run_extrapolated(arch, s, mp, out_dir=args.out,
                                 constrain_acts=args.constrain_acts,
                                 tag=args.tag, overrides=overrides,
                                 rules=rules)
            else:
                run_one(arch, s, mp, out_dir=args.out, unroll=args.unroll,
                        constrain_acts=args.constrain_acts, tag=args.tag,
                        rules=rules)
        except Exception as e:
            failures.append((arch, s, mesh_name, repr(e)))
            print(f"!! FAIL {arch} x {s} x {mesh_name}: {e}")
            traceback.print_exc()

    print(f"\n{len(combos) - len(failures)}/{len(combos)} combinations "
          f"lowered+compiled")
    if failures:
        for f in failures:
            print("FAILED:", *f)
        sys.exit(1)


if __name__ == "__main__":
    main()
