"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --cells vgg16.rows vgg16.column \
        --seeds 12 --faults 3 --out chiprun_out/calibrate_vgg16.json

The cells must share a configuration and a batch.  In one process, for
each of ``--seeds`` seeds: the program's readings of its first three
steps in each cell (the compiled step of each cell built once, its
parameters drawn anew from each seed), and the reference's.  For the
first ``--faults`` seeds also the control (the reference computed in
bfloat16, put in the program's place) and the half-batch fault (the
reference with the mean taken over the first half of each batch).  Each
reading is compared with the reference's by ``check.gaps``; the output
holds every number of every seed, and the summary the largest sound
reading (the lower one) and the smallest reading of the control and of
the fault (candidates for the upper one).  A step that returns its state
unchanged reads ``delta_gap`` 1 by construction and is not run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cells", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import check, data, harness
    from bench.reference import Reference, model_readings
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    cells = [harness.load_cell(c) for c in args.cells]
    _, config, model = cells[0]
    batch = cells[0][0]["batch"]
    lr = cells[0][0]["lr"]
    assert all(w["config"] == config["name"] and w["batch"] == batch
               and w["lr"] == lr for w, _, _ in cells), args.cells
    seeds = [args.first_seed + i for i in range(args.seeds)]
    s31 = [harness.seed31(s) for s in seeds]
    shape = (batch, config["image"], config["image"], config["channels"],
             config["n_classes"])
    out = {"cells": args.cells, "seeds": seeds, "program": {}, "timing": {}}

    for workload, _, _ in cells:
        t = time.perf_counter()
        compiled = None
        readings = []
        for s in s31:
            run = harness.build_program(config, workload, s)
            ring = data.make_ring(s, harness.RING, *shape)
            if compiled is None:
                compiled = run.step_fn.lower(run.params, run.opt,
                                             *ring[0]).compile()
            got, _, _ = model_readings(compiled, run.params, run.opt, ring)
            readings.append(got)
            del run, ring
        out["program"][workload["name"]] = readings
        out["timing"][workload["name"]] = time.perf_counter() - t
        del compiled
        jax.clear_caches()
        print(f"{workload['name']}: {len(readings)} seeds", flush=True)

    block = harness.ref_block(batch)
    refs = {
        "reference": Reference(model, config, lr, block=block),
        "control_bf16": Reference(model, config, lr, block=block,
                                  dtype=jnp.bfloat16, precision=None),
        "fault_half_batch": Reference(model, config, lr, block=block,
                                      rows=batch // 2),
    }
    want, others = [], {"control_bf16": [], "fault_half_batch": []}
    t = time.perf_counter()
    for i, s in enumerate(s31):
        ring = data.make_ring(s, harness.RING, *shape)
        want.append(refs["reference"].readings(s, ring))
        if i < args.faults:
            for name in others:
                others[name].append(refs[name].readings(s, ring))
        del ring
    out["timing"]["references"] = time.perf_counter() - t

    numbers = {name: [check.gaps(g, w) for g, w in zip(rs, want)]
               for name, rs in out["program"].items()}
    for name, rs in others.items():
        numbers[name] = [check.gaps(g, w) for g, w in zip(rs, want)]
    out["numbers"] = numbers
    out["reference"] = want
    out["others"] = others
    summary = {}
    for name, rows in numbers.items():
        pick = min if name in others else max
        summary[name] = {k: pick(r[k] for r in rows) for k in check.NAMES}
    out["summary"] = summary
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(json.dumps({"summary": summary, "timing": out["timing"],
                      "numbers": numbers}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
