"""Smoke run of the row-centric trainer on TPU, at full VGG-16.

    python chip_smoke.py             # one chip: phases a-c below
    python chip_smoke.py --chips 4   # four chips: the sharded pair only

Everything runs in this one process (a chip belongs to one process at a
time) through ``repro.launch.train``'s own parser, ``setup_cnn`` and
jitted SGD step, at ``configs/vgg16.CONFIG``: 224x224 images, batch 32,
10 classes, float32, weights from seed 0.  Each phase AOT-compiles the
step, runs three steps (losses must be finite) and prints its plan,
compile seconds, median step time (after ``block_until_ready``), the
compiled step's temporary bytes and the device's peak bytes so far.
Those numbers orient; they are not a benchmark.

One chip:
  a. row-centric vs column: the config's plan (``twophase_h``, N=8) and
     ``--strategy base`` from the same seed and data; the step-0 losses
     must agree (the paper's "no loss of accuracy");
  b. the compiled kernel path: ``--strategy overlap --rows 4 --kernel
     pallas`` must run ``overlap_pallas`` with no ``kernel_fallback``,
     compiled (not interpreted), with a ``tpu_custom_call`` in the step;
     its loss must match a's column loss;
  c. host residency: ``--strategy twophase_h --rows 8 --residency host``
     must offload to ``pinned_host``; its step-0 loss and gradient norm
     and its step-1 loss, which follow the gradients through the
     offloaded caches, must match a's row plan.

Four chips (``--chips 4``): ``pipeline_rows`` N=4 over ``data=2,model=2``
against ``base`` over ``data=4`` at the same global batch; both must span
all four chips and their step-0 losses must agree.

The steps run at the default matmul precision, as training does.  The
checks compare each phase's step-0 loss computed again by the trainer's
``loss_fn`` under ``jax.default_matmul_precision("highest")``, so every
matmul and conv, the Pallas kernel's included, runs in full float32 and
the engines differ only in summation order.  (The whole SGD step at
HIGHEST compiles several times slower.)  Any failed check or error exits
non-zero; the last line of a passing run is one JSON object naming the
device.  With no TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: Tolerances on the relative difference |x - y| / |y| of two losses.
#: Row-centric and column execution compute the same float32 sums in a
#: different order (rows slice every conv and reduction), so they may
#: differ by reassociation only: f32 rounding of 6e-8 per add, over sums
#: of up to 4608 products per conv output and 16 layers, stays well below
#: 1e-5; 1e-4 leaves a margin without admitting a wrong row boundary,
#: which moves the loss by far more.
ROW_VS_COLUMN_RTOL = 1e-4
#: The Pallas conv's in-kernel dot follows the ambient precision too, so
#: the kernel path differs from the column one by summation order only,
#: as above.
KERNEL_VS_COLUMN_RTOL = 1e-4
#: Host residency only moves the same boundary caches to pinned host
#: memory and back, so its arithmetic is the row plan's own, compared at
#: the same default precision.  A memory-space annotation may still change
#: fusion and conv tiling, i.e. summation order, and the default precision
#: rounds conv inputs to bfloat16, so a reassociated activation can round
#: to a neighbouring bf16 value; 1e-4 bounds that on the step-0 loss and
#: gradient norm (a v5e measured 1.3e-7 and 8.1e-6).
HOST_VS_DEVICE_RTOL = 1e-4
#: One SGD step later those roundings have moved the weights: on a v5e the
#: step-1 losses of the row and column plans, which differ by summation
#: order only, are 1.6e-4 apart.  1e-3 admits that, while a stale or
#: corrupted cache, which changes the gradient by O(1), moves the step-1
#: loss by percents (the step itself moves it by 18%).
HOST_VS_DEVICE_STEP1_RTOL = 1e-3
#: The four-chip pair is compared at the default precision, where each
#: conv rounds its inputs to bfloat16: activations that differ by
#: reassociation (row order, cross-device sums of the batch mean and of
#: model-sharded channels) can round to neighbouring bf16 values (2^-8
#: apart) in a few elements.  1e-3 bounds that; a wrong stage boundary
#: or shard moves the loss by far more.
SHARDED_RTOL = 1e-3

STEPS = 3
#: SGD step size for the smoke.  The trainer's CNN default (0.05) diverges
#: on full VGG-16, which has no batch norm: on a v5e the loss went 3.62 ->
#: 208 -> 9.6e7 over three steps, and in that regime the last losses of
#: two engines amplify rounding differences without bound.  A smaller
#: step keeps the three steps comparable.
LR = "1e-3"


def _rel(x: float, y: float) -> float:
    return abs(x - y) / max(abs(y), 1e-30)


def _check(name: str, got: float, want: float, rtol: float) -> None:
    rel = _rel(got, want)
    print(f"check {name}: {got!r} vs {want!r} rel {rel:.3e} "
          f"(rtol {rtol:g})", flush=True)
    assert rel <= rtol, f"{name}: relative difference {rel:.3e} > {rtol:g}"


def run_phase(name: str, flags, highest: bool = True):
    """Build the trainer from ``repro.launch.train``'s own CLI flags,
    compile its SGD step and run ``STEPS`` steps; with ``highest``, first
    compute the step-0 loss again at HIGHEST precision for the checks."""
    import jax

    from repro.launch import train

    args = train.build_parser().parse_args(
        ["--arch", "vgg16", "--preset", "full", "--seed", "0",
         "--lr", LR, *flags])
    print(f"== phase {name}: {' '.join(flags) or '(config plan)'}",
          flush=True)
    run = train.setup_cnn(args)
    params, opt = run.params, run.opt
    images, labels = run.batch_at(0)
    exact, exact_compile_s, loss0_highest = None, None, None
    if highest:
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            exact = jax.jit(run.loss_fn).lower(params, images,
                                               labels).compile()
        exact_compile_s = time.perf_counter() - t0
        loss0_highest = float(exact(params, images, labels))
    t0 = time.perf_counter()
    compiled = run.step_fn.lower(params, opt, images, labels).compile()
    compile_s = time.perf_counter() - t0
    losses, grad_norms, step_s = [], [], []
    loss = None
    for step in range(STEPS):
        images, labels = run.batch_at(step)
        t0 = time.perf_counter()
        params, opt, loss, m = compiled(params, opt, images, labels)
        jax.block_until_ready((params, opt, loss, m))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
        grad_norms.append(float(m["grad_norm"]))
    assert all(math.isfinite(x) for x in losses + grad_norms), losses
    stats = jax.devices()[0].memory_stats() or {}
    mem = compiled.memory_analysis()
    rec = {
        "phase": name, "plan": run.plan.describe(),
        "batch": run.batch, "compile_s": compile_s,
        "step_s_median": statistics.median(step_s), "step_s": step_s,
        "losses": losses, "grad_norms": grad_norms,
        "loss0_highest": loss0_highest,
        "highest_loss_compile_s": exact_compile_s,
        "step_temp_bytes": mem.temp_size_in_bytes,
        "step_arg_bytes": mem.argument_size_in_bytes,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "loss_devices": len(loss.sharding.device_set),
    }
    print(json.dumps(rec), flush=True)
    return rec, run.plan, exact, compiled


def one_chip() -> None:
    from repro.exec import rowprog
    from repro.kernels import resolve_interpret

    # a. row-centric (the config's own plan) vs column
    row, _, _, _ = run_phase("a-row", [])
    col, _, _, _ = run_phase("a-column", ["--strategy", "base"])
    _check("a step-0 loss row vs column", row["loss0_highest"],
           col["loss0_highest"], ROW_VS_COLUMN_RTOL)

    # b. the compiled Pallas kernel path
    ker, plan, exact, compiled = run_phase(
        "b-pallas", ["--strategy", "overlap", "--rows", "4",
                     "--kernel", "pallas"])
    assert plan.engine == "overlap_pallas", plan.describe()
    assert "kernel_fallback" not in plan.extras, plan.extras
    assert resolve_interpret(plan.kernel.interpret) is False, plan.kernel
    n_custom = [c.as_text().count("tpu_custom_call")
                for c in (compiled, exact)]
    print(f"b: {n_custom[0]} tpu_custom_call(s) in the compiled step, "
          f"{n_custom[1]} in the HIGHEST loss, "
          f"{plan.get('kernel_layers')} conv layers on the kernel",
          flush=True)
    assert min(n_custom) > 0, "no Pallas kernel in a compiled program"
    _check("b step-0 loss pallas vs column", ker["loss0_highest"],
           col["loss0_highest"], KERNEL_VS_COLUMN_RTOL)

    # c. host residency of the boundary caches
    # residency changes only what the backward reads, so the forward loss
    # is a's by construction: compare the step-0 gradient norm and the
    # step-1 loss too, which follow the gradients through the offloaded
    # caches
    host, plan, _, compiled = run_phase(
        "c-host", ["--strategy", "twophase_h", "--rows", "8",
                   "--residency", "host"], highest=False)
    assert plan.residency is not None and plan.residency.default == "host"
    assert rowprog.host_memory_kind() == "pinned_host", \
        rowprog.host_memory_kind()
    # S(5) is the TPU's pinned-host memory space in compiled HLO layouts
    n_host = compiled.as_text().count("S(5)")
    print(f"c: {n_host} buffer(s) in host memory space S(5) in the "
          f"compiled step", flush=True)
    assert n_host > 0, "the step places nothing in pinned_host"
    for what, key, step, rtol in (
            ("loss", "losses", 0, HOST_VS_DEVICE_RTOL),
            ("grad norm", "grad_norms", 0, HOST_VS_DEVICE_RTOL),
            ("loss", "losses", 1, HOST_VS_DEVICE_STEP1_RTOL)):
        _check(f"c step-{step} {what} host vs device residency",
               host[key][step], row[key][step], rtol)


def four_chips() -> None:
    from repro.exec import MeshSpec
    from repro.launch.mesh import build_mesh

    mesh = build_mesh(MeshSpec.parse("data=2,model=2"))
    assert len(set(mesh.devices.flat)) == 4, mesh
    pipe, _, _, _ = run_phase(
        "pipeline-data2-model2",
        ["--strategy", "pipeline_rows", "--rows", "4",
         "--mesh", "data=2,model=2"], highest=False)
    dp, _, _, _ = run_phase(
        "column-data4", ["--strategy", "base", "--mesh", "data=4"],
        highest=False)
    for rec in (pipe, dp):
        assert rec["loss_devices"] == 4, rec
    _check("step-0 loss pipeline data=2,model=2 vs column data=4",
           pipe["losses"][0], dp["losses"][0], SHARDED_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: phases a-c on one chip; 4: the sharded pair "
                         "on a 2x2 host")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {dev.device_kind} x{len(devices)}", flush=True)
    if args.chips == 1:
        one_chip()
    else:
        four_chips()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
