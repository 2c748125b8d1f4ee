import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo on
512 placeholder CPU devices; record memory_analysis / cost_analysis /
collective schedule for the roofline report.

Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
          --mesh both --out experiments/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro import obs
from repro.analysis.costmodel import analyze as cost_analyze
from repro.analysis.roofline import analyze
from repro.configs import get_config, list_configs
from repro.exec import Planner, ResidencySpec, kernelize_plan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, production_mesh_spec
from repro.launch.steps import SHAPES, build_jitted, shape_applicable
from repro.obs.audit import memory_metrics, plan_audit
from repro.obs.cli import add_obs_args, configure_from_args


def run_one(arch: str, shape_name: str, multi_pod: bool, fsdp: bool,
            out_dir: str, verbose: bool = True, overrides: dict = None,
            tag_suffix: str = "", kernel: str = "lax",
            residency: str = "", plan_cache: str = "") -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "fsdp": fsdp, "overrides": overrides or {},
           "status": "skipped"}

    def _solve():
        # the resolved row-centric execution plan is part of the record
        # so a dry-run artefact fully determines how the step would
        # execute — the plan is solved against THIS mesh (per-device
        # batch), and its single-device projection rides along so the
        # artefact replays on any host
        plan = Planner.for_model(
            cfg, shape.batch, shape.seq,
            mesh=production_mesh_spec(multi_pod=multi_pod),
            residency=ResidencySpec.parse(residency))
        if kernel:
            # the chosen KernelSpec (or its lax fallback + reason) is
            # part of the artefact: a dry-run record fully pins kernel
            # policy too
            plan = kernelize_plan(plan, kernel)
        return plan

    if plan_cache:
        from repro.exec.costmodel import hardware_fingerprint
        from repro.exec.plancache import cached_plan
        plan, hit, key = cached_plan(plan_cache, dict(
            mode="dryrun", arch=arch, shape=shape_name, mesh=mesh_name,
            kernel=kernel, residency=residency,
            overrides=overrides or {},
            fingerprint=hardware_fingerprint()), _solve)
        rec["plan_cache_hit"] = hit
    else:
        plan = _solve()
    rec["exec_plan"] = plan.to_dict()
    rec["exec_plan_per_device"] = plan.per_device().to_dict()
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec["reason"] = why
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            tag = f"{arch}_{shape_name}_{mesh_name}"
            with open(os.path.join(out_dir, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=2)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    try:
        jit, args = build_jitted(cfg, shape, mesh, fsdp=fsdp)
        with mesh:
            lowered = jit.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            # measured-vs-estimated peak bytes, next to the plan it
            # audits (recorded in every artefact; also emitted to the
            # trace when an obs session is active)
            rec["plan_audit"] = plan_audit(
                plan, memory_metrics(mem), "dryrun",
                extra={"arch": arch, "shape": shape_name,
                       "mesh_name": mesh_name})
            if verbose:
                cost = compiled.cost_analysis()
                print(f"[{arch} x {shape_name} x {mesh_name}] "
                      f"memory_analysis: {mem}")
                print(f"[{arch} x {shape_name} x {mesh_name}] "
                      f"cost_analysis: flops={cost.get('flops', 0):.3e} "
                      f"bytes={cost.get('bytes accessed', 0):.3e}")
            hlo = compiled.as_text()
            roof = analyze(compiled, hlo, cfg, shape, mesh_name, n_chips)
            rec.update({f"hlo_{k}" if not k.startswith(("arch", "shape",
                                                        "mesh", "n_chips"))
                        else k: v for k, v in roof.as_dict().items()})
            model = cost_analyze(cfg, shape,
                                 dict(zip(mesh.axis_names,
                                          mesh.devices.shape)))
            rec["analytic"] = model.as_dict()
            rec["bottleneck"] = model.bottleneck
            rec["status"] = "ok"
            rec["t_lower_s"] = round(t_lower, 2)
            rec["t_compile_s"] = round(t_compile, 2)
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{mesh_name}" \
            + ("_fsdp" if fsdp else "") + tag_suffix
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=2, default=str)
    return rec


def _parse_overrides(pairs):
    out = {}
    for kv in pairs or []:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        out[k] = v
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--set", nargs="*", default=[],
                    help="config overrides, e.g. remat=block_rows "
                         "param_dtype=bfloat16 capacity_factor=1.0")
    ap.add_argument("--tag", default="", help="output filename suffix")
    ap.add_argument("--kernel", default="lax", choices=["lax", "pallas"],
                    help="kernel backend recorded on the exec plan "
                         "(pallas swaps in the kernel-backed engine when "
                         "the tiling is feasible)")
    ap.add_argument("--residency", default="",
                    choices=["", "device", "host", "recompute"],
                    help="boundary-cache residency policy recorded on "
                         "the exec plan (artefacts replay it verbatim)")
    from repro.exec.plancache import add_plan_cache_arg
    add_plan_cache_arg(ap)
    add_obs_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    overrides = _parse_overrides(args.set)
    configure_from_args(args, tool="dryrun", arch=args.arch,
                        shape=args.shape)

    archs = list_configs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_err = n_skip = 0
    for arch in archs:
        for sh in shapes:
            for mp in meshes:
                t0 = time.time()
                rec = run_one(arch, sh, mp, args.fsdp, args.out,
                              overrides=overrides, tag_suffix=args.tag,
                              kernel=args.kernel,
                              residency=args.residency,
                              plan_cache=args.plan_cache)
                dt = time.time() - t0
                print(f"{rec['status']:8s} {arch:24s} {sh:12s} "
                      f"{rec['mesh']:8s} {dt:7.1f}s "
                      f"{rec.get('bottleneck', rec.get('reason', rec.get('error', '')))[:80]}")
                n_ok += rec["status"] == "ok"
                n_err += rec["status"] == "error"
                n_skip += rec["status"] == "skipped"
    print(f"done: {n_ok} ok, {n_skip} skipped (documented), {n_err} errors")
    obs.shutdown()
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
