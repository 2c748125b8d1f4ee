"""Where a cell's device time goes, by program scope.

    python3 bench/scope_breakdown.py --workload vgg16.rows --seed 7

On the chip the cell asks for: sets the cell up as ``bench/run.py`` does
(the program's own trainer and compiled step, the ring of batches made
from the seed), runs five steps, traces at least three more, and prints
one JSON line: the device milliseconds per step under each scope path
(``bench/scopes.py``: ``trunk/seg/bp_row/vjp``, ``none`` for ops under no
program scope), the ten longest ops under none, the share of the busy
time whose op carries a program scope, and the three per-layer shares of
the row engines.  ``--dump DIR`` also writes the compiled HLO and the
traced device ops there (``<cell>.hlo.txt.gz``, ``<cell>.trace.json.gz``),
to read offline.  It makes no comparison with the reference and is not a
benchmark run.
"""

import argparse
import gzip
import json
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--dump", default="")
    args = ap.parse_args(argv)

    from bench import data, harness, scopes

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    workload, config, _ = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("scope_breakdown: needs a TPU", file=sys.stderr)
        return 3
    s = harness.seed31(args.seed)
    run = harness.build_program(config, workload, s)
    ring = data.make_ring(s, harness.RING, workload["batch"], config["image"],
                          config["image"], config["channels"],
                          config["n_classes"])
    compiled = run.step_fn.lower(run.params, run.opt, *ring[0]).compile()
    state, *_ = harness._window(compiled, (run.params, run.opt), ring, 0,
                                n_steps=5)
    del run
    tr, _ = harness._traced_window(compiled, state, ring, 5, args.steps)

    text = compiled.as_text()
    if args.dump:
        out = Path(args.dump)
        out.mkdir(parents=True, exist_ok=True)
        with gzip.open(out / f"{args.workload}.hlo.txt.gz", "wt") as f:
            f.write(text)
        with gzip.open(out / f"{args.workload}.trace.json.gz", "wt") as f:
            json.dump({"ops": tr.ops, "host": tr.host,
                       "window": tr.window}, f)
    steps = len([1 for _, _, n in tr.host if n == "step"])
    busy = tr.busy_s() * workload["chips"]
    by_kind = scopes.seconds_by_kind(text, tr)
    scoped = scopes.op_scopes(text)
    unscoped = sorted(((sec, name) for name, sec in tr.op_seconds().items()
                       if not scoped.get(name)), reverse=True)[:10]
    ctx = types.SimpleNamespace(trace=tr, hlo_text=text,
                                chips=workload["chips"])
    print(json.dumps({
        "workload": args.workload, "steps": steps,
        "busy_ms_per_step": 1e3 * busy / steps,
        "window_ms_per_step": 1e3 * tr.window_s / steps,
        "scoped_share": 100.0 * (1.0 - by_kind.get("none", 0.0) / busy),
        "shares": {name: scopes.share(ctx, inner) for name, inner in (
            ("bp_replay_share", ("replay",)),
            ("grad_scatter_share", ("grad_scatter",)),
            ("sd_cache_share", ("sd_import", "sd_export")))},
        "ms_per_step": {k: 1e3 * v / steps for k, v in sorted(
            by_kind.items(), key=lambda kv: -kv[1])},
        "unscoped_ms_per_step": [[name, 1e3 * sec / steps]
                                 for sec, name in unscoped],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
