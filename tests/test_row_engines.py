"""Exactness of the row-centric engines (the paper's central claim:
row-centric training is lossless)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import obs
from repro.core.overlap import (
    make_column_apply, make_overlap_apply, make_splitcnn_apply, plan_overlap,
)
from repro.exec import ExecutionPlan, build_apply
from repro.core.twophase import make_twophase_apply, max_valid_rows
from repro.models.cnn.layers import init_trunk
from repro.models.cnn.resnet import resnet50_modules
from repro.models.cnn.vgg import vgg16_modules

H = 96
KEY = jax.random.PRNGKey(0)
X = jax.random.normal(jax.random.PRNGKey(1), (2, H, H, 3))


def _setup(kind):
    if kind == "vgg":
        mods = vgg16_modules(width_mult=0.125, n_stages=3)
    else:
        mods = resnet50_modules(width_mult=0.125, stage_blocks=[1, 1, 1, 1])
    params, _ = init_trunk(mods, KEY, (H, H, 3))
    return mods, params


def _grads(apply_fn, params, x):
    def loss(p, x):
        return jnp.sum(apply_fn(p, x) ** 2)
    return jax.grad(loss, argnums=(0, 1))(params, x)


def _max_rel(a, b):
    out = 0.0
    for l1, l2 in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        denom = float(jnp.abs(l1).max())
        if denom > 0:
            out = max(out, float(jnp.abs(l1 - l2).max()) / denom)
    return out


@pytest.mark.parametrize("kind", ["vgg", "resnet"])
@pytest.mark.parametrize("n_rows", [2, 3])
def test_overlap_forward_exact(kind, n_rows):
    mods, params = _setup(kind)
    ref = make_column_apply(mods)(params, X)
    got = make_overlap_apply(mods, H, n_rows)(params, X)
    assert got.shape == ref.shape
    assert float(jnp.abs(got - ref).max()) == 0.0  # bit-exact


@pytest.mark.parametrize("kind", ["vgg", "resnet"])
def test_overlap_grads_exact(kind):
    mods, params = _setup(kind)
    gref = _grads(make_column_apply(mods), params, X)
    # N_FP != N_BP (paper Sec. III-C)
    gov = _grads(make_overlap_apply(mods, H, 2, n_rows_bp=3), params, X)
    assert _max_rel(gref, gov) < 1e-5


@pytest.mark.parametrize("kind", ["vgg", "resnet"])
def test_twophase_exact(kind):
    mods, params = _setup(kind)
    n = max_valid_rows(mods, H)
    assert n >= 2, "plan should admit at least 2 rows"
    ref = make_column_apply(mods)(params, X)
    tp = make_twophase_apply(mods, H, n)
    got = tp(params, X)
    assert float(jnp.abs(got - ref).max()) == 0.0
    gref = _grads(make_column_apply(mods), params, X)
    gtp = _grads(tp, params, X)
    assert _max_rel(gref, gtp) < 1e-5


@pytest.mark.parametrize("kind", ["vgg", "resnet"])
@pytest.mark.parametrize("strategy", ["twophase", "twophase_h"])
def test_row_gradient_barrier_changes_no_float(kind, strategy, monkeypatch):
    """The barrier in front of each row's gradient scatter only orders the
    compiled step: the jitted gradients equal, bit for bit, those of the
    same step with the barrier taken out."""
    mods, params = _setup(kind)
    n = 2 if strategy == "twophase" else 3
    plan = ExecutionPlan.explicit(strategy, n_rows=n, in_shape=(H, H, 3))

    def grads():
        apply = build_apply(mods, plan)
        return jax.jit(lambda p, x: _grads(apply, p, x))(params, X)

    with obs.capture() as s:
        guarded = grads()
    assert s.metrics.counters["rowprog.bp_rows"].value >= n
    monkeypatch.setattr(lax, "optimization_barrier", lambda x: x)
    unguarded = grads()
    for a, b in zip(jax.tree.leaves(guarded), jax.tree.leaves(unguarded),
                    strict=True):
        np.testing.assert_array_equal(a, b)


def test_twophase_invalid_n_raises():
    mods, params = _setup("vgg")
    n = max_valid_rows(mods, H)
    with pytest.raises(ValueError):
        make_twophase_apply(mods, H, n + 1)


@pytest.mark.parametrize("strategy", ["ckp", "overlap_h", "twophase_h"])
def test_hybrid_exact(strategy):
    mods, params = _setup("vgg")
    ref = make_column_apply(mods)(params, X)
    fn = build_apply(mods, ExecutionPlan.explicit(strategy, n_rows=3,
                                                  in_shape=(H, H, 3)))
    got = fn(params, X)
    assert float(jnp.abs(got - ref).max()) == 0.0
    gref = _grads(make_column_apply(mods), params, X)
    ghy = _grads(fn, params, X)
    assert _max_rel(gref, ghy) < 1e-5


def test_splitcnn_is_broken():
    """Fig. 11's ablation: naive splitting (no seam handling) changes the
    output — feature loss + padding redundancy."""
    mods, params = _setup("vgg")
    ref = make_column_apply(mods)(params, X)
    got = make_splitcnn_apply(mods, H, 3)(params, X)
    # shape law of Sec. III-B: concatenated height differs or values differ
    if got.shape == ref.shape:
        assert float(jnp.abs(got - ref).max()) > 1e-3
    else:
        assert got.shape[1] != ref.shape[1]


def test_overlap_plan_halo_positive():
    mods, _ = _setup("vgg")
    plan = plan_overlap(mods, H, 3)
    halos = plan.overlap_rows_level0()
    assert all(h > 0 for h in halos)  # receptive fields straddle seams


def test_jit_compatible():
    mods, params = _setup("vgg")
    fn = jax.jit(make_overlap_apply(mods, H, 2))
    ref = make_column_apply(mods)(params, X)
    assert float(jnp.abs(fn(params, X) - ref).max()) == 0.0
