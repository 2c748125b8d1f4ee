"""Record the scoped row-program trace that ``test_scopes.py`` reads.

    python3 bench/tests/record_scoped.py [OUT_DIR]

On one TPU: a four-conv trunk in two hybrid segments of the 2PS row
program (N=2 each, batch 8, 32x32x16 inputs) with a mean-pool linear
head, stepped by SGD inside the program's own scopes (``trunk``,
``head_loss``, ``sgd_update``); the second segment's input takes a
gradient, so its rows scatter one.  Writes
the compiled step's optimized HLO to ``tpu_rows2.hlo.txt.gz`` and a profiler
trace of three steps, each under a ``step`` span, to ``tpu_rows2.xplane.pb.gz``,
in ``OUT_DIR`` (default ``bench/tests/data``).
"""

import gzip
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
sys.path.insert(0, str(ROOT / "src"))


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.core.hybrid import SegmentSpec, make_hybrid_apply
    from repro.models.cnn.layers import Conv, ReLU, init_trunk

    out = Path(argv[0]) if argv else DATA
    out.mkdir(parents=True, exist_ok=True)
    if jax.default_backend() != "tpu":
        print("record_scoped: needs a TPU", file=sys.stderr)
        return 3
    mods = [Conv(16), ReLU(), Conv(16), ReLU(), Conv(16), ReLU(), Conv(16)]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    trunk, (_, _, c) = init_trunk(mods, k1, (32, 32, 16))
    params = {"trunk": trunk,
              "head": jax.random.normal(k2, (c, 10), jnp.float32) / c}
    apply = make_hybrid_apply(mods, 32, [SegmentSpec(0, 4, 2, "twophase"),
                                         SegmentSpec(4, 7, 2, "twophase")])
    x = jax.random.normal(k3, (8, 32, 32, 16), jnp.float32)
    y = jnp.arange(8) % 10

    def loss_fn(p):
        with obs.scope("trunk"):
            feats = apply(p["trunk"], x)
        with obs.scope("head_loss"):
            logp = jax.nn.log_softmax(jnp.mean(feats, (1, 2)) @ p["head"])
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(loss_fn)(p)
        with obs.scope("sgd_update"):
            p = jax.tree.map(lambda a, b: a - 1e-3 * b, p, g)
        return p, loss

    compiled = step.lower(params).compile()
    with gzip.open(out / "tpu_rows2.hlo.txt.gz", "wt") as f:
        f.write(compiled.as_text())
    params, loss = compiled(params)
    loss.block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.enable_hlo_proto = False  # the HLO is written beside it
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=options)
        for i in range(3):
            with jax.profiler.StepTraceAnnotation("step", step_num=i):
                params, loss = compiled(params)
                loss.block_until_ready()
        jax.profiler.stop_trace()
        (path,) = Path(tmp).glob("plugins/profile/*/*.xplane.pb")
        with open(path, "rb") as src, \
                gzip.open(out / "tpu_rows2.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
