import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh(es) with 512 placeholder host devices, print
memory_analysis / cost_analysis, and extract roofline terms.

MUST be run as its own process (device count locks at first jax init):
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
        --out artifacts/dryrun
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import sharding as sh
from repro.analysis import hlo as hlo_an
from repro.analysis import roofline as rl
from repro.configs import RunConfig, cells, get_config, get_shape
from repro.launch import steps as st
from repro.launch.mesh import make_production_mesh
from repro.sharding_ctx import use_mesh


def run_cell(arch, shape_name, *, multi_pod=False, run_overrides=None,
             moe_overrides=None, keep_hlo=False):
    """Lower+compile one cell; returns a result dict (JSON-serializable)."""
    cfg = get_config(arch)
    if moe_overrides and cfg.moe is not None:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, **moe_overrides))
    shape = get_shape(shape_name)
    if run_overrides and "grad_accum" in run_overrides:
        import dataclasses
        shape = dataclasses.replace(
            shape, grad_accum=run_overrides.pop("grad_accum"))
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    run = RunConfig(model=cfg, shape=shape)
    if run_overrides:
        run = run.replace(**run_overrides)
    t0 = time.time()

    with use_mesh(mesh):
        pstruct = st.params_struct(cfg, jnp.bfloat16)
        psh = sh.param_shardings(pstruct, mesh)
        if shape.kind == "train":
            ostruct = st.opt_struct(cfg, pstruct)
            osh = sh.opt_shardings(ostruct, mesh)
            batch = st.input_specs(cfg, shape)
            bsh = sh.batch_shardings(batch, mesh)
            fn = st.make_train_step(cfg, run)
            jitted = jax.jit(fn, in_shardings=(psh, osh, bsh),
                             out_shardings=(psh, osh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(pstruct, ostruct, batch)
        elif shape.kind == "prefill":
            batch = st.input_specs(cfg, shape)
            bsh = sh.batch_shardings(batch, mesh)
            fn = st.make_prefill_step(cfg, run)
            jitted = jax.jit(fn, in_shardings=(psh, bsh))
            lowered = jitted.lower(pstruct, batch)
        else:  # decode
            specs = st.input_specs(cfg, shape)
            csh = sh.cache_shardings(specs["caches"], mesh)
            tsh = sh.batch_shardings(
                {"t": specs["token"]}, mesh)["t"]
            fn = st.make_decode_step(cfg, run)
            jitted = jax.jit(fn, in_shardings=(psh, csh, tsh,
                                               sh.replicated(mesh)),
                             out_shardings=(None, csh),
                             donate_argnums=(1,))
            lowered = jitted.lower(pstruct, specs["caches"],
                                   specs["token"], specs["pos"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    parsed = hlo_an.analyze(hlo_text)
    roof = rl.compute_roofline(cfg, shape, n_chips,
                               parsed["dot_flops"],
                               parsed["collective_bytes"])
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "xla_cost": {"flops": cost.get("flops"),
                     "bytes_accessed": cost.get("bytes accessed")},
        "hlo_parsed": parsed,
        "roofline": roof.to_dict(),
        "state_bytes_per_dev": rl.state_bytes(cfg, shape, n_chips),
        "status": "ok",
    }
    if keep_hlo:
        result["hlo_text"] = hlo_text
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=("no", "yes", "both"),
                    default="no")
    ap.add_argument("--out", default=None, help="artifact dir for JSON")
    ap.add_argument("--remat-policy", default=None)
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--moe-quant", default=None, choices=("none", "int8"))
    ap.add_argument("--moe-local-cf", type=float, default=None)
    ap.add_argument("--grad-accum", type=int, default=None)
    args = ap.parse_args()

    overrides = {}
    if args.remat_policy:
        overrides["remat_policy"] = args.remat_policy
    if args.q_chunk:
        overrides["attention_q_chunk"] = args.q_chunk
    if args.grad_accum:
        overrides["grad_accum"] = args.grad_accum
    moe_overrides = {}
    if args.moe_quant:
        moe_overrides["dispatch_quant"] = args.moe_quant
    if args.moe_local_cf:
        moe_overrides["local_capacity_factor"] = args.moe_local_cf

    todo = []
    if args.all:
        todo = [(a, s, skip) for a, s, skip in cells()]
    else:
        cfgc = get_config(args.arch)
        skip = (args.shape == "long_500k" and not cfgc.is_subquadratic)
        todo = [(args.arch, args.shape, skip)]

    pods = {"no": [False], "yes": [True], "both": [False, True]}[
        args.multi_pod]

    results, failures = [], 0
    for arch, shape_name, skip in todo:
        for mp in pods:
            tag = f"{arch}/{shape_name}/{'2x16x16' if mp else '16x16'}"
            if skip:
                results.append({"arch": arch, "shape": shape_name,
                                "mesh": "2x16x16" if mp else "16x16",
                                "status": "skipped",
                                "reason": "full attention; no sub-quadratic "
                                          "path (DESIGN.md)"})
                print(f"[SKIP] {tag}")
                continue
            try:
                r = run_cell(arch, shape_name, multi_pod=mp,
                             run_overrides=overrides or None,
                             moe_overrides=moe_overrides or None)
                results.append(r)
                rf = r["roofline"]
                print(f"[OK]   {tag}  compile={r['compile_s']:.0f}s "
                      f"dotF/dev={rf['hlo_flops_device']:.3e} "
                      f"coll/dev={r['hlo_parsed']['collective_bytes']:.3e}B "
                      f"bound={rf['bottleneck']} "
                      f"terms(c/m/x)=({rf['compute_s']:.4f}/"
                      f"{rf['memory_s']:.4f}/{rf['collective_s']:.4f})s")
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                failures += 1
                results.append({"arch": arch, "shape": shape_name,
                                "mesh": "2x16x16" if mp else "16x16",
                                "status": "error", "error": repr(e)})
                print(f"[FAIL] {tag}: {e}")
                traceback.print_exc(limit=4)
            sys.stdout.flush()

    if args.out:
        import pathlib
        out = pathlib.Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        suffix = (args.arch or "all") + "_" + (args.shape or "all")
        path = out / f"dryrun_{suffix}_{args.multi_pod}.json"
        path.write_text(json.dumps(results, indent=1))
        print(f"wrote {path}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
