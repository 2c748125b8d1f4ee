"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload vgg16.rows --seed 7 --seconds 10 --trace 0

From the root of a checkout, on a machine that holds the chips the cell
asks for.  ``--trace 0`` prints the cell's end-to-end metrics;
``--trace 1`` runs the same window, then a short window under the
profiler, and prints the per-layer metrics, the device's busy time and a
breakdown.  Every run then compares the program's first three steps with
the plain reference and prints each compared number beside its limit, as
the last lines of standard error and under ``checks`` in the result.
With no TPU, or fewer chips than the cell asks for, it exits 3 and prints
no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _plain(x):
    """JSON numbers stay numbers; a non-finite one becomes a string."""
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    from bench import harness

    benchmark = harness.load_json(harness.ROOT / "BENCHMARK.json")
    workload, config, model = harness.load_cell(args.workload)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        result = harness.run_cell(workload, config, model, args.seed,
                                  args.seconds, bool(args.trace), t0=T0,
                                  benchmark=benchmark)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    result = _plain(result)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
