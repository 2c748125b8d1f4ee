"""Kernel-parity test tier for the Pallas-backed engines.

Every pallas engine must (a) match its lax reference engine for loss AND
grads in interpret mode on CPU, (b) be selectable purely via
``ExecutionPlan`` / ``Planner`` — with automatic lax fallback when the
tiling is infeasible — and (c) compose with PR 3 sharded plans without any
engine-code changes.  The kernel case tables come from tests/conftest.py
(shared with the kernel-level oracle tests in tests/test_kernels.py).

Sharded-composition tests need 8 virtual devices (the same convention as
tests/test_sharded_plans.py):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest -q tests/test_pallas_engines.py

They skip under the plain tier-1 run; everything else runs everywhere.
The property tests are importorskip-guarded on hypothesis (the PR 1
convention).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core.overlap import make_column_apply
from repro.exec import (
    ExecutionPlan, KernelSpec, MeshSpec, PlanRequest, Planner, build_apply,
    kernelize_plan, list_engines,
)
from repro.kernels.conv2d_rows import good_tiling, halo_ok, vmem_bytes
from repro.models.cnn.layers import Conv
from repro.models.cnn.vgg import init_vgg16

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # property tests need hypothesis (PR 1 convention)
    HAS_HYPOTHESIS = False

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

H, BATCH = 32, 2
SHAPE = (H, H, 3)
KEY = jax.random.PRNGKey(0)
MODS, PARAMS = init_vgg16(KEY, SHAPE, width_mult=0.125, n_classes=4,
                          n_stages=2)
X = jax.random.normal(jax.random.PRNGKey(1), (BATCH, H, H, 3))
#: interpret pinned True so the tier is TPU-host-proof (CPU CI is the
#: platform default anyway; see repro.kernels.resolve_interpret)
PALLAS = KernelSpec(backend="pallas", interpret=True)


def _grads(apply_fn, *args):
    def loss(*a):
        return jnp.sum(apply_fn(*a) ** 2)
    return jax.value_and_grad(loss, argnums=tuple(range(len(args))))(*args)


def _max_rel(a, b):
    out = 0.0
    for l1, l2 in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        denom = float(jnp.abs(l1).max())
        if denom > 0:
            out = max(out, float(jnp.abs(l1 - l2).max()) / denom)
    return out


def _swa_attend(window):
    """The lax attend callable seq_swa_overlap chunks over ((B,S,H,D))."""
    def attend(qc, kc, vc, q_offset, k_offset):
        d = qc.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc) / jnp.sqrt(d)
        qp = q_offset + jnp.arange(qc.shape[1])
        kp = k_offset + jnp.arange(kc.shape[1])
        ok = (kp[None, :] <= qp[:, None]) & (kp[None, :] >= 0)
        if window > 0:
            ok &= kp[None, :] > qp[:, None] - window
        s = jnp.where(ok[None, None], s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vc)
    return attend


# ---------------------------------------------------------------------------
# registry: pallas engines are first-class entries under the same kinds
# ---------------------------------------------------------------------------


def test_registry_has_pallas_engines():
    assert "overlap_pallas" in list_engines("cnn")
    seq = list_engines("seq")
    assert "seq_swa_pallas" in seq and "seq_ssd_pallas" in seq


# ---------------------------------------------------------------------------
# loss+grad parity vs the lax reference engines, across the row grid
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_h", [2, 3, 4, 8])
def test_overlap_pallas_trunk_parity(block_h):
    """VGG trunk: pallas conv rows vs the lax OverL engine at every conv
    row-block granularity — loss and grads."""
    spec = KernelSpec(backend="pallas", block_h=block_h, interpret=True)
    pal = build_apply(MODS, ExecutionPlan.explicit(
        "overlap_pallas", 1, in_shape=SHAPE, kernel=spec))
    ref = build_apply(MODS, ExecutionPlan.explicit(
        "overlap", 2, in_shape=SHAPE))
    assert jnp.allclose(pal(PARAMS["trunk"], X), ref(PARAMS["trunk"], X),
                        atol=1e-4)
    l_ref, g_ref = _grads(ref, PARAMS["trunk"], X)
    l_pal, g_pal = _grads(pal, PARAMS["trunk"], X)
    assert abs(float(l_pal) - float(l_ref)) / abs(float(l_ref)) < 1e-5
    assert _max_rel(g_ref, g_pal) < 1e-4


def test_overlap_pallas_layer_fallback():
    """block_h=1 rejects every 3x3 stride-1 conv (halo 2 > 1), so the
    engine runs the whole trunk through the lax path — still exact."""
    spec = KernelSpec(backend="pallas", block_h=1, interpret=True)
    pal = build_apply(MODS, ExecutionPlan.explicit(
        "overlap_pallas", 1, in_shape=SHAPE, kernel=spec))
    ref = make_column_apply(MODS)
    assert float(jnp.abs(pal(PARAMS["trunk"], X)
                         - ref(PARAMS["trunk"], X)).max()) == 0.0


def test_single_conv_engine_parity(conv_case):
    """Engine-level consumption of the shared conv table: a one-layer
    trunk through overlap_pallas vs the base engine, loss and grads."""
    Hc, Wc, Cin, Cout, k, s, p, bh = conv_case
    m = Conv(Cout, k=k, s=s, p=p, bias=True)
    params = (m.init(KEY, (Hc, Wc, Cin)),)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, Hc, Wc, Cin))
    spec = KernelSpec(backend="pallas", block_h=bh, interpret=True)
    pal = build_apply([m], ExecutionPlan.explicit(
        "overlap_pallas", 1, in_shape=(Hc, Wc, Cin), kernel=spec))
    base = build_apply([m], ExecutionPlan.explicit(
        "base", 1, in_shape=(Hc, Wc, Cin)))
    assert jnp.allclose(pal(params, x), base(params, x), atol=1e-4)
    l_ref, g_ref = _grads(base, params, x)
    l_pal, g_pal = _grads(pal, params, x)
    assert abs(float(l_pal) - float(l_ref)) / abs(float(l_ref)) < 1e-5
    assert _max_rel(g_ref, g_pal) < 1e-4


def test_seq_swa_pallas_engine_parity(swa_case):
    """Engine-level consumption of the shared swa table: seq_swa_pallas
    vs the lax seq_swa_overlap engine, loss and grads wrt q."""
    S, D, window, bq, bk = swa_case
    if window == 0:
        pytest.skip("the swa engines require a positive window extra")
    B, Hh = 2, 2
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hh, D))
    k = jax.random.normal(ks[1], (B, S, Hh, D))
    v = jax.random.normal(ks[2], (B, S, Hh, D))
    spec = KernelSpec(backend="pallas", bq=bq, bk=bk, interpret=True)
    pal = build_apply(None, ExecutionPlan.explicit(
        "seq_swa_pallas", 4, window=window, seq=S, kernel=spec))
    ref = build_apply(_swa_attend(window), ExecutionPlan.explicit(
        "seq_swa_overlap", 4, window=window))
    assert jnp.allclose(pal(q, k, v), ref(q, k, v), atol=2e-4)
    l_ref, (g_ref,) = _grads(lambda qq: ref(qq, k, v), q)
    l_pal, (g_pal,) = _grads(lambda qq: pal(qq, k, v), q)
    assert abs(float(l_pal) - float(l_ref)) / abs(float(l_ref)) < 1e-5
    assert _max_rel(g_ref, g_pal) < 1e-4


def test_seq_ssd_pallas_engine_parity(ssd_case):
    """Engine-level consumption of the shared ssd table: the pallas
    backend vs the engine's own lax reference path (the fallback the
    planner flips to), loss and grads wrt x."""
    Bt, S, Hh, P, N, chunk = ssd_case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bt, S, Hh, P)) * 0.5
    B = jax.random.normal(ks[1], (Bt, S, N)) * 0.5
    C = jax.random.normal(ks[2], (Bt, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (Bt, S, Hh)))
    a = jnp.exp(-dt * jnp.exp(jax.random.normal(ks[4], (Bt, S, Hh)) * 0.1))
    pal = build_apply(None, ExecutionPlan.explicit(
        "seq_ssd_pallas", S // chunk, seq=S,
        kernel=KernelSpec(backend="pallas", chunk=chunk, interpret=True)))
    ref = build_apply(None, ExecutionPlan.explicit(
        "seq_ssd_pallas", S // chunk, seq=S,
        kernel=KernelSpec(backend="lax")))
    assert jnp.allclose(pal(x, B, C, a, dt), ref(x, B, C, a, dt),
                        atol=1e-3)
    l_ref, (g_ref,) = _grads(lambda xx: ref(xx, B, C, a, dt), x)
    l_pal, (g_pal,) = _grads(lambda xx: pal(xx, B, C, a, dt), x)
    assert abs(float(l_pal) - float(l_ref)) / abs(float(l_ref)) < 1e-4
    assert _max_rel(g_ref, g_pal) < 1e-3


# ---------------------------------------------------------------------------
# plan/Planner selection + automatic lax fallback
# ---------------------------------------------------------------------------


def test_plan_request_kernel_selects_pallas_engine():
    planner = Planner(MODS, SHAPE, BATCH)
    plan = planner.resolve(PlanRequest(engine="overlap", n_rows=2,
                                       kernel="pallas"))
    assert plan.engine == "overlap_pallas"
    assert plan.kernel is not None and plan.kernel.backend == "pallas"
    assert plan.get("kernel_vmem_bytes", 0) > 0  # priced per row block
    # the selected plan executes and stays exact
    fn = build_apply(MODS, plan)
    ref = make_column_apply(MODS)(PARAMS["trunk"], X)
    assert jnp.allclose(fn(PARAMS["trunk"], X), ref, atol=1e-4)


def test_kernelize_base_maps_to_pallas():
    planner = Planner(MODS, SHAPE, BATCH)
    plan = planner.kernelize(planner.plan("base"), PALLAS)
    assert plan.engine == "overlap_pallas"


def test_kernelize_lax_backend_just_attaches():
    planner = Planner(MODS, SHAPE, BATCH)
    plan = planner.kernelize(planner.plan("overlap", 2), "lax")
    assert plan.engine == "overlap"
    assert plan.kernel == KernelSpec(backend="lax")


def test_kernelize_fallback_on_halo_infeasible():
    planner = Planner(MODS, SHAPE, BATCH)
    spec = KernelSpec(backend="pallas", block_h=1, interpret=True)
    plan = planner.kernelize(planner.plan("overlap", 2), spec)
    assert plan.engine == "overlap"            # lax engine kept
    assert plan.kernel.backend == "lax"        # spec downgraded
    assert "halo" in plan.get("kernel_fallback", "")


def test_kernelize_fallback_on_vmem():
    planner = Planner(MODS, SHAPE, BATCH)
    plan = planner.kernelize(planner.plan("overlap", 2), PALLAS,
                             vmem_limit=1024)
    assert plan.kernel.backend == "lax"
    assert "VMEM" in plan.get("kernel_fallback", "")


def test_kernelize_alignment_required_for_compiled_runs():
    """interpret=False means a real lowering: the toy trunk has no
    MXU-aligned conv, so a compiled run must fall back to lax; the same
    spec with interpret=True stays pallas (no MXU on the interpreter)."""
    planner = Planner(MODS, SHAPE, BATCH)
    compiled = planner.kernelize(planner.plan("overlap", 2),
                                 KernelSpec(backend="pallas",
                                            interpret=False))
    assert compiled.kernel.backend == "lax"
    assert "align" in compiled.get("kernel_fallback", "")
    interp = planner.kernelize(planner.plan("overlap", 2), PALLAS)
    assert interp.engine == "overlap_pallas"


def test_kernelize_engine_without_alternate_falls_back():
    planner = Planner(MODS, SHAPE, BATCH)
    plan = planner.kernelize(planner.plan("twophase", 2), PALLAS)
    assert plan.engine == "twophase" and plan.kernel.backend == "lax"
    assert "no pallas alternate" in plan.get("kernel_fallback", "")


def test_kernelize_seq_swa_select_and_fallback():
    plan = Planner.for_budget_seq(128, 64, 2, budget=0, window=32,
                                  engine="seq_swa_overlap")
    ok = kernelize_plan(plan, KernelSpec(backend="pallas", bq=32, bk=16,
                                         interpret=True))
    assert ok.engine == "seq_swa_pallas" and ok.kernel.backend == "pallas"
    bad = kernelize_plan(plan, KernelSpec(backend="pallas", bq=48,
                                          interpret=True))
    assert bad.engine == "seq_swa_overlap" and bad.kernel.backend == "lax"
    assert "tile" in bad.get("kernel_fallback", "")


def test_kernelize_seq_requires_seq_extra():
    """The kernels *assert* tile divisibility at call time, so a plan
    that doesn't know its sequence length must fall back, not crash
    inside jit later."""
    plan = ExecutionPlan.explicit("seq_swa_overlap", 4, window=32)
    out = kernelize_plan(plan, KernelSpec(backend="pallas",
                                          interpret=True))
    assert out.engine == "seq_swa_overlap" and out.kernel.backend == "lax"
    assert "seq" in out.get("kernel_fallback", "")
    ssd = kernelize_plan(ExecutionPlan.explicit("seq_ssd_pallas", 2),
                         KernelSpec(backend="pallas", interpret=True))
    assert ssd.kernel.backend == "lax"


def test_kernelize_seq_swa_vmem_priced_via_head_dim():
    plan = Planner.for_budget_seq(128, 64, 2, budget=0, window=32,
                                  engine="seq_swa_overlap", head_dim=16)
    assert plan.get("head_dim") == 16
    spec = KernelSpec(backend="pallas", bq=32, bk=16, interpret=True)
    ok = kernelize_plan(plan, spec)
    assert ok.engine == "seq_swa_pallas"
    assert ok.get("kernel_vmem_bytes", 0) > 0
    bad = kernelize_plan(plan, spec, vmem_limit=64)
    assert bad.kernel.backend == "lax"
    assert "VMEM" in bad.get("kernel_fallback", "")


def test_for_model_swa_plan_carries_head_dim():
    from repro.configs import get_reduced
    cfg = get_reduced("gemma3_4b")
    plan = Planner.for_model(cfg, 2, 128)
    assert plan.engine == "seq_swa_overlap"
    assert plan.get("head_dim") == cfg.head_dim


def test_kernelize_seq_ssd_divisibility():
    plan = ExecutionPlan.explicit("seq_ssd_pallas", 2, seq=100)
    bad = kernelize_plan(plan, KernelSpec(backend="pallas", chunk=32,
                                          interpret=True))
    assert bad.kernel.backend == "lax"
    assert "divide" in bad.get("kernel_fallback", "")
    ok = kernelize_plan(plan, KernelSpec(backend="pallas", chunk=50,
                                         interpret=True))
    assert ok.engine == "seq_ssd_pallas" and ok.kernel.backend == "pallas"


# ---------------------------------------------------------------------------
# KernelSpec serialization + validation
# ---------------------------------------------------------------------------


def test_kernel_spec_json_roundtrip():
    spec = KernelSpec(backend="pallas", block_h=4, bq=64, bk=32, chunk=16,
                      interpret=True)
    assert KernelSpec.from_dict(spec.to_dict()) == spec
    plan = ExecutionPlan.explicit("overlap_pallas", 2, in_shape=SHAPE,
                                  kernel=spec)
    rt = ExecutionPlan.from_json(plan.to_json())
    assert rt == plan and rt.kernel == spec
    # a kernel-less plan stays kernel-less through JSON
    bare = ExecutionPlan.explicit("overlap", 2, in_shape=SHAPE)
    assert ExecutionPlan.from_json(bare.to_json()).kernel is None


def test_kernel_spec_rides_through_planner_and_per_device():
    mesh = MeshSpec.parse("data=2")
    planner = Planner(MODS, SHAPE, 4, mesh=mesh)
    plan = planner.kernelize(planner.plan("overlap", 2), PALLAS)
    rt = ExecutionPlan.from_json(plan.to_json())
    assert rt == plan and rt.kernel == PALLAS
    assert plan.per_device().kernel == PALLAS  # projection keeps policy


def test_kernel_spec_validates():
    with pytest.raises(ValueError, match="backend"):
        KernelSpec(backend="cuda")
    with pytest.raises(ValueError, match="block_h"):
        KernelSpec(block_h=0)


def test_interpret_env_override(monkeypatch):
    """Interpret mode follows the platform, or a KernelSpec's explicit
    flag: nothing in the environment can force the interpreter onto a
    TPU."""
    from repro import kernels
    from repro.kernels import ops
    assert ops.resolve_interpret is kernels.resolve_interpret
    assert ops.resolve_interpret(KernelSpec(backend="pallas").interpret) \
        is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret(None) is False        # TPU: compiled
    assert ops.resolve_interpret(PALLAS.interpret) is True  # spec wins
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops.resolve_interpret(None) is True         # CPU: interpreter
    assert ops.resolve_interpret(False) is False


# ---------------------------------------------------------------------------
# sharded-plan composition: pallas engines under PR 3 shard wrappers
# ---------------------------------------------------------------------------


@needs_devices
def test_overlap_pallas_shard_parity():
    """A pallas CNN plan with a mesh goes through the SAME kind="cnn"
    shard wrapper as the lax engines — no engine-code changes."""
    x8 = jax.random.normal(jax.random.PRNGKey(3), (8, H, H, 3))
    plan = ExecutionPlan.explicit("overlap_pallas", 1, in_shape=SHAPE,
                                  mesh=MeshSpec.parse("data=8"),
                                  kernel=PALLAS)
    fn = jax.jit(build_apply(MODS, plan))
    ref = make_column_apply(MODS)(PARAMS["trunk"], x8)
    got = fn(PARAMS["trunk"], x8)
    assert jnp.allclose(got, ref, atol=1e-4)
    assert "data" in str(got.sharding.spec)
    l_ref, g_ref = _grads(make_column_apply(MODS), PARAMS["trunk"], x8)
    l_got, g_got = _grads(fn, PARAMS["trunk"], x8)
    assert abs(float(l_got) - float(l_ref)) / abs(float(l_ref)) < 1e-5
    assert _max_rel(g_ref, g_got) < 1e-4


@needs_devices
def test_seq_swa_pallas_shard_parity():
    B, S, Hh, D, window = 8, 128, 2, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, S, Hh, D))
    k = jax.random.normal(ks[1], (B, S, Hh, D))
    v = jax.random.normal(ks[2], (B, S, Hh, D))
    spec = KernelSpec(backend="pallas", bq=32, bk=16, interpret=True)
    sharded = jax.jit(build_apply(None, ExecutionPlan.explicit(
        "seq_swa_pallas", 4, window=window, seq=S,
        mesh=MeshSpec.parse("data=8"), kernel=spec)))
    solo = build_apply(None, ExecutionPlan.explicit(
        "seq_swa_pallas", 4, window=window, seq=S, kernel=spec))
    assert jnp.allclose(sharded(q, k, v), solo(q, k, v), atol=1e-5)


# ---------------------------------------------------------------------------
# property tests: halo precondition + vmem/good_tiling monotonicity
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(k=st.integers(1, 7), s=st.integers(1, 3),
           block_h=st.integers(1, 8), h_out=st.integers(1, 16))
    def test_halo_precondition_property(k, s, block_h, h_out):
        """halo_ok is exactly the clamped-block inequality the kernel
        asserts: (k - s) <= min(block_h, h_out) * s."""
        assert halo_ok(k, s, block_h, h_out) == \
            ((k - s) <= min(block_h, h_out) * s)
        # unclamped form agrees when the output is at least a block tall
        assert halo_ok(k, s, block_h, h_out=max(block_h, h_out)) == \
            halo_ok(k, s, block_h)

    @settings(max_examples=10, deadline=None)
    @given(k=st.integers(1, 5), s=st.integers(1, 2),
           block_h=st.integers(1, 6))
    def test_halo_precondition_admits_kernel(k, s, block_h):
        """Whenever halo_ok admits a geometry, conv2d_rows executes and
        matches the oracle (the precondition is sufficient, not only
        necessary)."""
        from repro.kernels import ref
        from repro.kernels.conv2d_rows import conv2d_rows
        if not halo_ok(k, s, block_h):
            return
        Hc = max(k, block_h * s + k)  # at least one full block + halo
        x = jax.random.normal(KEY, (1, Hc, k + 2, 4))
        w = jax.random.normal(jax.random.PRNGKey(1), (k, k, 4, 4)) * 0.1
        got = conv2d_rows(x, w, stride=s, padding=0, block_h=block_h,
                          interpret=True)
        want = ref.conv2d_ref(x, w, stride=s, padding=0)
        assert jnp.allclose(got, want, atol=1e-4)

    @settings(max_examples=50, deadline=None)
    @given(b1=st.integers(1, 32), b2=st.integers(1, 32),
           s=st.integers(1, 3), w=st.integers(1, 64),
           cin=st.integers(1, 256), cout=st.integers(1, 256),
           k=st.integers(1, 7))
    def test_vmem_bytes_monotone_in_block(b1, b2, s, w, cin, cout, k):
        """A taller row block can never shrink the working set (the
        planner's min-block search relies on this)."""
        lo, hi = sorted((b1, b2))
        assert vmem_bytes(lo, s, w, cin, w, cout, k, k) <= \
            vmem_bytes(hi, s, w, cin, w, cout, k, k)

    @settings(max_examples=50, deadline=None)
    @given(block=st.integers(1, 16), s=st.integers(1, 3),
           w=st.integers(1, 64), c1=st.integers(1, 128),
           c2=st.integers(1, 128), k=st.integers(1, 7))
    def test_vmem_bytes_monotone_in_channels(block, s, w, c1, c2, k):
        lo, hi = sorted((c1, c2))
        assert vmem_bytes(block, s, w, lo, w, lo, k, k) <= \
            vmem_bytes(block, s, w, hi, w, hi, k, k)

    @settings(max_examples=50, deadline=None)
    @given(cin=st.integers(1, 64), cout=st.integers(1, 256),
           mi=st.integers(1, 4), mo=st.integers(1, 4))
    def test_good_tiling_closed_under_scaling(cin, cout, mi, mo):
        """Alignment is preserved by integer channel scaling: widening an
        MXU-aligned layer never un-aligns it."""
        if good_tiling(cin, cout):
            assert good_tiling(cin * mi, cout * mo)
        assert good_tiling(8 * cin, 128 * cout)

else:  # pragma: no cover - matches the PR 1 importorskip convention

    def test_hypothesis_properties():
        pytest.importorskip("hypothesis",
                            reason="property tests need hypothesis")
