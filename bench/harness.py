"""One run of one cell: set-up, the timed window, the traced window, and
the comparison with the plain reference.

Everything a cell needs is found by name: ``workloads/<cell>.json``
names its configuration, batch, plan flags, learning rate, chips and the
limits of its comparison; ``configs/<config>.json`` holds the model's
sizes and the trainer flags that run it, with the plain reference beside
it in ``configs/<config>.py``; each per-layer metric of
``BENCHMARK.json`` is read by ``metrics/<metric>.py``.

The cell drives the program's own entry: ``repro.launch.train``'s
parser and ``setup_cnn``, and the AOT-compiled ``CNNRun.step_fn``.  Set-up
makes three distinct batches on the device from the seed and runs the
first three steps through that compiled step on them (they are the
warm-up and the steps the reference follows).  The window then steps the
same object through the ring of batches for the given seconds, as the
trainer's own loop does: each step is dispatched while the one before it
runs, and the host waits only for that one, so at most two are in
flight and the device never waits on the host's launch of the next.  After it, the device's memory is read, the program's
state is freed, and the reference follows the first three steps.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import hashlib
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# the program under test, imported from the checkout's own source tree
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
#: batches in the ring the window steps through; the first three steps
#: (the ones the reference follows) each see a different batch
RING = 3
#: rows per block of the reference's scan over the batch
REF_BLOCK = 32
#: the traced window lasts at least this long, and at least 3 steps
TRACE_SECONDS = 1.0


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    """``(workload, config, plain model)`` of a cell, by its name."""
    workload = load_json(BENCH / "workloads" / f"{name}.json")
    config = load_json(BENCH / "configs" / f"{workload['config']}.json")
    model = load_module(BENCH / "configs" / f"{config['model']}.py",
                        f"bench_model_{config['model']}")
    return workload, config, model


def seed31(seed: int) -> int:
    """A non-negative int32 seed drawn from any integer ``seed``: JAX's
    PRNGKey keeps only 32 bits of a larger one."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


def ref_block(batch: int) -> int:
    return max(d for d in range(1, min(batch, REF_BLOCK) + 1)
               if batch % d == 0)


def build_program(config: dict, workload: dict, seed: int):
    """The program's own trainer for this cell: ``repro.launch.train``'s
    parser and ``setup_cnn``."""
    from repro.launch import train

    argv = [*config["trainer"], "--batch", str(workload["batch"]),
            "--seed", str(seed), "--lr", repr(workload["lr"]),
            *workload["plan"]]
    return train.setup_cnn(train.build_parser().parse_args(argv))


def device_bytes(devices, prefix="") -> int:
    """Bytes on the fullest chip, by the TPU runtime's allocator: live
    buffers plus what loaded executables reserve for their temporaries,
    which ``bytes_in_use`` alone does not see.  ``prefix="peak_"`` gives
    the peaks of both since the process started."""
    out = []
    for d in devices:
        s = d.memory_stats() or {}
        out.append(s.get(f"{prefix}bytes_in_use", 0)
                   + s.get(f"{prefix}bytes_reserved", 0))
    return max(out)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader reads."""
    batch: int
    chips: int
    peak: dict
    model_flops_per_image: int
    images_per_s: float
    compiled_bytes: int
    plan: object
    compiled: object
    trace: object

    @functools.cached_property
    def hlo_text(self) -> str:
        return self.compiled.as_text()

    @functools.cached_property
    def conv_ops(self) -> dict:
        from bench import hlo
        return hlo.conv_ops(self.hlo_text)


def _window(step, state, ring, k, *, seconds=math.inf, n_steps=None,
            annotate=None):
    """Step ``state`` through the ring from batch ``k`` as the trainer's
    loop does: dispatch a step, then wait for the one before it, so at
    most two are in flight.  Stops before the first dispatch after
    ``seconds`` or after ``n_steps`` steps, and waits for the last.
    ``annotate(i)`` gives the span around step i's dispatch and the wait
    for step i - 1, ``annotate("dispatch")`` and ``annotate("block")``
    those around each part.  Returns ``(state, losses, done, wall
    seconds)``, where ``done[i]`` is when the host saw step i end, in
    seconds from the start."""
    import jax

    span = annotate or (lambda *_: contextlib.nullcontext())
    params, opt = state
    losses, done, prev = [], [], None
    start = time.perf_counter()
    while (len(losses) != n_steps
           and time.perf_counter() - start < seconds):
        with span(len(losses)):
            with span("dispatch"):
                params, opt, loss, _ = step(
                    params, opt, *ring[(k + len(losses)) % len(ring)])
            if prev is not None:
                with span("block"):
                    prev.block_until_ready()
                done.append(time.perf_counter() - start)
        losses.append(loss)
        prev = loss
    jax.block_until_ready((params, opt, prev))
    done.append(time.perf_counter() - start)
    return (params, opt), losses, done, done[-1]


def _traced_window(step, state, ring, k, n_steps):
    """Run ``n_steps`` steps under the profiler and reduce the trace.
    A first step is launched before the ``step`` spans, so that the traced
    window opens on a busy device, as every step of the timed window
    after its first does."""
    import jax

    from bench import trace

    def annotate(i):
        if isinstance(i, str):
            return jax.profiler.TraceAnnotation(i)
        if i == 0:
            return contextlib.nullcontext()
        return jax.profiler.StepTraceAnnotation(trace.STEP, step_num=i)

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
        jax.profiler.start_trace(tmp)
        try:
            state, *_ = _window(step, state, ring, k, n_steps=n_steps + 1,
                                annotate=annotate)
        finally:
            jax.profiler.stop_trace()
        (path,) = Path(tmp).glob("plugins/profile/*/*.xplane.pb")
        return trace.load(path), state


def _breakdown(tr, conv_ops) -> dict:
    ops = sorted(tr.op_seconds().items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[f"{n} (conv)" if n in conv_ops else n, s]
                       for n, s in ops],
        "idle_gaps": [[label, s] for label, s in tr.idle_gaps()[:10]],
    }


def run_cell(workload: dict, config: dict, model, seed: int,
             seconds: float, trace: bool, *, t0: float, benchmark: dict,
             require_chip: bool = True, break_step=None) -> dict:
    """One run; returns the result object the last line prints.

    ``require_chip=False`` skips the look for a TPU (tests on the CPU);
    ``break_step(compiled) -> step`` plants a fault under the timed path."""
    import jax

    from bench import check, data, flops
    from bench.reference import Reference, model_readings
    from repro.obs.audit import memory_metrics

    devices = jax.devices()
    chips = workload["chips"]
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    peaks = load_json(BENCH / "peaks.json")
    kind = devices[0].device_kind
    if require_chip and kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    s = seed31(seed)
    batch = workload["batch"]
    shape = (batch, config["image"], config["image"], config["channels"],
             config["n_classes"])

    # ---- set-up: the program, its inputs, its first three steps
    run = build_program(config, workload, s)
    ring = data.make_ring(s, RING, *shape)
    compiled = run.step_fn.lower(run.params, run.opt, *ring[0]).compile()
    step = compiled if break_step is None else break_step(compiled)
    got, params, opt = model_readings(step, run.params, run.opt, ring)
    plan = run.plan
    del run
    setup_s = time.perf_counter() - t0

    # ---- the timed window
    (params, opt), losses, done, window_s = _window(
        step, (params, opt), ring, RING, seconds=seconds)
    n_steps = len(losses)
    step_hbm = device_bytes(devices[:chips])
    images_per_s = n_steps * batch / window_s
    losses = [float(x) for x in losses]
    nonfinite = sum(not math.isfinite(x) for x in losses)
    print("window_losses " + json.dumps(losses), flush=True)
    print("step_done_s " + json.dumps(done), flush=True)
    mem = memory_metrics(compiled.memory_analysis())
    print("compiled_step_bytes " + json.dumps(mem), flush=True)

    tr = None
    if trace:
        n = max(3, math.ceil(TRACE_SECONDS * n_steps / window_s))
        tr, (params, opt) = _traced_window(step, (params, opt), ring,
                                           RING + n_steps, n)
    memory_peak = device_bytes(devices[:chips], "peak_")

    ctx = Context(batch, chips, peaks.get(kind), flops.train_flops_per_image(
                      model.conv_layers(config), model.linear(config)),
                  images_per_s, mem["peak_bytes"], plan, compiled, tr)
    e2e = {"images_per_s": images_per_s, "step_hbm_gib": step_hbm / 2**30,
           "setup_s": setup_s}
    name = workload["name"]
    metrics = {}
    if trace:
        for m in benchmark["per_layer"]:
            if name in m.get("workloads", [name]):
                reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                     f"bench_metric_{m['name']}")
                value = reader.read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = _breakdown(tr, ctx.conv_ops)
    else:
        for m in benchmark["end_to_end"]:
            if name in m.get("workloads", [name]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # ---- free the program, then the reference follows the three steps
    del ctx, compiled, step, params, opt, ring, losses
    gc.collect()
    jax.clear_caches()
    t = time.perf_counter()
    ring = data.make_ring(s, RING, *shape)
    ref = Reference(model, config, workload["lr"], block=ref_block(batch))
    want = ref.readings(s, ring)
    numbers = check.gaps(got, want)
    print("reference " + json.dumps({
        "seconds": time.perf_counter() - t, "losses": want["losses"],
        "program_losses": got["losses"], "gaps": numbers}), flush=True)
    checks = check.judge(numbers, workload["limits"])
    correct = check.passed(checks) and nonfinite == 0

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": n_steps,
              "failed": nonfinite, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = breakdown
    result["checks"] = {**checks, "nonfinite_window_losses": {
        "value": nonfinite, "limit": 0}}
    return result
