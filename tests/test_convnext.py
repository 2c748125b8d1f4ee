"""ConvNeXt-T through the row engines, at the reduced preset (256x256,
widths x1/8, the published depths) on the CPU.

Grouped convolutions and the ConvNeXt modules compute exactly the rows a
2PS or OverL plan asks of them, every CNN engine agrees with ``base``, and
the program's first step agrees with the plain reference that decides the
benchmark's ``correct`` (``bench/configs/convnext.py``).

Tolerances.  Rows are the same arithmetic as the whole tensor, but
XLA:CPU splits a convolution's reduction across threads by its shape, so
a row-sliced conv may differ from the whole one in the last bits (ROADMAP
C8): rows and engines are held to 1e-5 of the largest value, gradients
summed over rows to 1e-4.  The program against the reference adds the
reference's own summation order.  Each test also computes its quantity in
bfloat16 and asserts that it would fail the same tolerance, so none is
loose enough to pass a lower precision.
"""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro import obs
from repro.core import twophase as tp
from repro.core.overlap import make_column_apply, plan_overlap
from repro.core.rowplan import feature_bytes
from repro.exec import Planner, build_apply
from repro.launch import train
from repro.models.cnn.convnext import convnext_modules, init_convnext
from repro.models.cnn.layers import (
    Chain, Conv, ConvNeXtBlock, LayerNorm2d, init_trunk,
)

ROOT = Path(__file__).resolve().parents[1]
#: rows against the whole tensor: thread-split reductions on XLA:CPU
ROW_TOL = 1e-5
#: gradients: the same, summed over rows and leaves
GRAD_TOL = 1e-4
#: gamma's initial value in these tests: at the published 1e-6 every
#: block's branch adds ~1e-6 of its input, under the tolerances above, so
#: a fault inside a block would not show
LAYER_SCALE = 1.0


def _gap(a, b) -> float:
    """Largest difference over the largest magnitude of ``b``, over the
    leaves of two trees."""
    out = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        scale = float(jnp.abs(y).max())
        if scale:
            out = max(out, float(jnp.abs(x - y).max()) / scale)
    return out


def _bf16(tree):
    return jax.tree.map(lambda t: t.astype(jnp.bfloat16), tree)


def _rows(y, iv):
    return lax.slice_in_dim(y, iv[0], iv[1], axis=1)


def test_grouped_conv_rows_equal_apply():
    conv = Conv(16, k=7, s=1, p=3, groups=16)
    params = conv.init(jax.random.PRNGKey(0), (40, 40, 16))
    assert params["w"].shape == (7, 7, 1, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 40, 16))
    whole = conv.apply(params, x)
    for out_iv in [(0, 5), (5, 17), (17, 40), (0, 40)]:
        in_iv = conv.in_interval(out_iv, 40)
        got = conv.apply_row(params, _rows(x, in_iv), in_iv, 40, out_iv)
        assert _gap(got, _rows(whole, out_iv)) <= ROW_TOL, out_iv
    low = conv.apply(_bf16(params), _bf16(x)).astype(jnp.float32)
    assert _gap(low, whole) > ROW_TOL


def _small_trunk():
    """Stem, two blocks, a downsample and a block: every ConvNeXt module
    kind, at 128x128 input."""
    return [Chain("stem", (Conv(16, k=4, s=4, p=0), LayerNorm2d())),
            ConvNeXtBlock(16, LAYER_SCALE), ConvNeXtBlock(16, LAYER_SCALE),
            Chain("downsample", (LayerNorm2d(), Conv(32, k=2, s=2, p=0))),
            ConvNeXtBlock(32, LAYER_SCALE)]


def _levels(mods, params, x):
    acts = [x]
    for m, p in zip(mods, params):
        acts.append(m.apply(p, acts[-1]))
    return acts


@pytest.mark.parametrize("engine", ["twophase", "overlap"])
def test_modules_compute_the_rows_a_plan_asks_for(engine):
    mods = _small_trunk()
    h0 = 128
    params, _ = init_trunk(mods, jax.random.PRNGKey(2), (h0, h0, 3))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, h0, h0, 3))
    acts = _levels(mods, params, x)
    asks = []  # (level, in_iv, out_iv)
    if engine == "twophase":
        n = tp.max_valid_rows(mods, h0)
        assert n >= 2
        plan = tp.module_boundaries(mods, h0, n)
        for l in range(1, plan.n_levels + 1):
            for r in range(n):
                out_iv = plan.row_iv(l, r)
                hi = mods[l - 1].in_interval(out_iv, plan.heights[l - 1])[1]
                asks.append((l, (plan.need_lo[l - 1][r], hi), out_iv))
    else:
        plan = plan_overlap(mods, h0, 4)
        for chain in plan.chains:
            asks += [(l, chain[l - 1], chain[l])
                     for l in range(1, len(mods) + 1)]
    hs = tp.module_boundaries(mods, h0, 1).heights
    for l, in_iv, out_iv in asks:
        m, p = mods[l - 1], params[l - 1]
        got = m.apply_row(p, _rows(acts[l - 1], in_iv), in_iv, hs[l - 1],
                          out_iv)
        assert _gap(got, _rows(acts[l], out_iv)) <= ROW_TOL, (l, in_iv)
    low = _levels(mods, _bf16(params), _bf16(x))[-1].astype(jnp.float32)
    assert _gap(low, acts[-1]) > ROW_TOL


@pytest.fixture(scope="module")
def reduced():
    """The reduced preset's trunk, params and a batch of two images."""
    from repro.configs.convnext_t import reduced as preset
    cfg = preset()
    shape = (cfg.image, cfg.image, cfg.channels)
    mods, params = init_convnext(jax.random.PRNGKey(4), shape,
                                 cfg.width_mult, n_classes=cfg.n_classes,
                                 layer_scale=LAYER_SCALE)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, *shape))

    def loss_and_grads(apply):
        def loss(p, x):
            return jnp.mean(apply(p, x) ** 2)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
            params["trunk"], x)

    base = loss_and_grads(make_column_apply(mods))
    return mods, shape, loss_and_grads, base


@pytest.mark.parametrize("engine,n_rows", [
    ("twophase", 2), ("twophase_h", 8), ("overlap", 4), ("overlap_h", 8),
    ("ckp", 1)])
def test_engines_agree_with_base(reduced, engine, n_rows):
    mods, shape, loss_and_grads, (loss0, grads0) = reduced
    if engine == "twophase":
        # the whole trunk admits one 2PS row at 256; its first stage two
        mods = mods[:4]
        n_rows = min(n_rows, tp.max_valid_rows(mods, shape[0]))
        assert n_rows == 2
    plan = Planner(mods, shape, 2).plan(engine, n_rows)
    if engine.endswith("_h"):
        assert max(n for _, _, n in plan.segments) > 1, plan.segments
    loss, grads = loss_and_grads(build_apply(mods, plan))
    if engine == "twophase":
        loss0, grads0 = loss_and_grads(make_column_apply(mods))
    assert abs(float(loss) - float(loss0)) <= ROW_TOL * abs(float(loss0))
    assert _gap(grads, grads0) <= GRAD_TOL


def test_planner_prices_the_hidden_expansion():
    mods = convnext_modules(0.125)
    shapes = [(256, 256, 3)]
    for m in mods:
        shapes.append(m.out_shape(shapes[-1]))
    got = feature_bytes(mods, (256, 256, 3), 2)
    h, w, c = shapes[2]  # the first block's output
    assert isinstance(mods[1], ConvNeXtBlock)
    assert got[1] == 2 * h * w * 4 * c * 4
    h, w, c = shapes[1]  # the stem's, priced at its own channels
    assert got[0] == 2 * h * w * c * 4


def test_segments_are_recorded_as_traced(reduced):
    mods, shape, _, _ = reduced
    plan = Planner(mods, shape, 2).plan("twophase_h", 8)
    apply = build_apply(mods, plan)
    params, _ = init_trunk(mods, jax.random.PRNGKey(0), shape)
    with obs.capture() as s:
        jax.eval_shape(apply, params, jax.ShapeDtypeStruct((2, *shape),
                                                           jnp.float32))
        events = [r for r in s.tracer.records if r.get("name") == "segment"]
        total = s.metrics.counters["twophase.sd_rows"].value
    assert [(e["attrs"]["start"], e["attrs"]["end"], e["attrs"]["n_rows"])
            for e in events] == [tuple(seg) for seg in plan.segments]
    want = 0
    hs = tp.module_boundaries(mods, shape[0], 1).heights
    for e, (a, b, n) in zip(events, plan.segments):
        sizes = tp.module_boundaries(mods[a:b], hs[a], n).cache_sizes()
        assert e["attrs"]["sd_rows"] == sizes
        want += sum(map(sum, sizes))
    assert total == want > 0


def test_unknown_arch_raises():
    args = train.build_parser().parse_args(["--arch", "alexnet"])
    with pytest.raises(ValueError, match="unknown CNN arch"):
        train.setup_cnn(args)


def _gammas(params):
    return [p["gamma"] for p in params["trunk"]
            if isinstance(p, dict) and "gamma" in p]


@pytest.mark.parametrize("flag,want", [([], 1e-6),
                                       (["--layer-scale-init", "0.5"], 0.5)])
def test_layer_scale_starts_at_the_published_value(flag, want):
    run = train.setup_cnn(train.build_parser().parse_args(
        ["--arch", "convnext_t", "--preset", "reduced", "--batch", "2",
         *flag]))
    gammas = _gammas(run.params)
    assert len(gammas) == sum((3, 3, 9, 3))
    assert all(float(jnp.min(g)) == float(jnp.max(g)) == jnp.float32(want)
               for g in gammas)


def test_layer_scale_flag_needs_a_layer_scale():
    args = train.build_parser().parse_args(
        ["--arch", "vgg16", "--layer-scale-init", "1"])
    with pytest.raises(ValueError, match="has no layer scale"):
        train.setup_cnn(args)


def _reference():
    path = ROOT / "bench" / "configs" / "convnext.py"
    spec = importlib.util.spec_from_file_location("convnext_ref", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    cfg = json.loads((ROOT / "bench" / "tests" / "data"
                      / "convnext_t_tiny.json").read_text())
    return ref, cfg


def test_first_step_agrees_with_the_plain_reference():
    ref, cfg = _reference()
    seed = 11
    run = train.setup_cnn(train.build_parser().parse_args(
        [*cfg["trainer"], "--strategy", "twophase_h", "--rows", "8",
         "--batch", "2", "--seed", str(seed)]))
    want = ref.init(cfg, jax.random.PRNGKey(seed))
    assert (jax.tree_util.tree_structure(want)
            == jax.tree_util.tree_structure(run.params))
    assert _gap(want, run.params) == 0.0  # drawn alike from the seed
    images, labels = run.batch_at(0)

    def ref_loss(p, x, precision=lax.Precision.HIGHEST):
        logp = jax.nn.log_softmax(ref.forward(cfg, p, x, precision))
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(run.loss_fn))(
            run.params, images, labels)
    loss0, grads0 = jax.jit(jax.value_and_grad(ref_loss))(want, images)
    assert abs(float(loss) - float(loss0)) <= ROW_TOL * abs(float(loss0))
    assert _gap(grads, grads0) <= GRAD_TOL
    low, glow = jax.jit(jax.value_and_grad(
        lambda p, x: ref_loss(p, x, None)))(_bf16(want), _bf16(images))
    assert (abs(float(low) - float(loss0)) > ROW_TOL * abs(float(loss0))
            or _gap(glow, grads0) > GRAD_TOL)
