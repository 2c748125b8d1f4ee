"""What decides ``correct``, at a size the CPU holds.

Each cell runs here on its configuration's reduced preset (64x64 images,
channels x1/8, batch 4) with its own plan flags and limits.  The sound
program passes; the control, the reference computed in bfloat16 put in
the program's place, fails; and a run of the harness with a fault
planted under its timed path, a step that returns its state unchanged or
one that takes the mean over half of the batch, comes out not correct.
"""

import time

import jax.numpy as jnp
import pytest

from bench import check, data, harness
from bench.reference import Reference

BENCHMARK = harness.load_json(harness.ROOT / "BENCHMARK.json")
CELLS = [c["name"] for c in BENCHMARK["workloads"]]
BATCH = 4


def tiny(name):
    workload, config, model = harness.load_cell(name)
    config = harness.load_json(
        harness.BENCH / "tests" / "data" / f"{config['name']}_tiny.json")
    return dict(workload, batch=BATCH), config, model


def unchanged(step):
    def broken(params, opt, images, labels):
        return (params, opt, *step(params, opt, images, labels)[2:])
    return broken


def half_batch(step):
    def broken(params, opt, images, labels):
        n = images.shape[0] // 2
        return step(params, opt, jnp.concatenate([images[:n]] * 2),
                    jnp.concatenate([labels[:n]] * 2))
    return broken


@pytest.mark.parametrize("fault", [None, unchanged, half_batch],
                         ids=["sound", "unchanged", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_run_is_correct_only_when_sound(cell, fault):
    workload, config, model = tiny(cell)
    result = harness.run_cell(workload, config, model, 2**33 + 7, 0.2,
                              False, t0=time.perf_counter(),
                              benchmark=BENCHMARK, require_chip=False,
                              break_step=fault)
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(cell):
    workload, config, model = tiny(cell)
    s = harness.seed31(2**31 + 3)
    ring = data.make_ring(s, harness.RING, BATCH, config["image"],
                          config["image"], config["channels"],
                          config["n_classes"])
    kw = dict(block=BATCH)
    want = Reference(model, config, workload["lr"], **kw).readings(s, ring)
    control = Reference(model, config, workload["lr"], dtype=jnp.bfloat16,
                        precision=None, **kw).readings(s, ring)
    checks = check.judge(check.gaps(control, want), workload["limits"])
    assert not check.passed(checks), checks
