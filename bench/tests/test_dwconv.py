"""ConvNeXt's depthwise convolutions in a compiled step: their FLOP
count, and the two per-layer metrics that read them.

``data/tpu_dwconv.hlo.txt`` is the loss and gradient of one 7x7 depthwise
convolution (batch 2, 16x16, 8 channels) under the scope ``dwconv``,
compiled for a described TPU v5e (nothing ran): the forward and the input
gradient are convolutions with ``feature_group_count=8``, the kernel
gradient one with ``batch_group_count=8``."""

import types
from pathlib import Path

import pytest

from bench import harness, hlo, layer_scopes, trace

DATA = Path(__file__).parent / "data"
N, H, C, K = 2, 16, 8, 7
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def text():
    return (DATA / "tpu_dwconv.hlo.txt").read_text()


def test_each_depthwise_form_counts_what_the_conv_needs(text):
    ops = hlo.conv_ops(text)
    forms = sorted(" ".join(g for g in ("feature_group_count=8",
                                        "batch_group_count=8")
                            if g in text.split(f"%{name} = ")[1]
                            .split("\n")[0] + _fused_text(text, name))
                   for name in ops)
    assert forms == ["batch_group_count=8", "feature_group_count=8",
                     "feature_group_count=8"]
    # forward, input gradient and kernel gradient alike: 2 N H W k^2 C
    assert [op.flops for op in ops.values()] == [2 * N * H * H * K * K * C] * 3


def _fused_text(text, name):
    """The text of the computation an entry op fuses, if any."""
    line = text.split(f"%{name} = ")[1].split("\n")[0]
    if "calls=%" not in line:
        return ""
    sub = line.split("calls=%")[1].split(",")[0]
    return text.split(f"%{sub} ")[1].split("\n}")[0]


def _ctx(text, tr):
    return types.SimpleNamespace(hlo_text=text, trace=tr, chips=1,
                                 peak=PEAK, conv_ops=hlo.conv_ops(text))


def test_metrics_at_the_least_time_read_100(text):
    ops = hlo.conv_ops(text)
    # the convolutions, and the values they make as they are moved
    assert set(ops) < layer_scopes.innermost(_ctx(text, None), "dwconv")
    t, runs = 0.0, []
    for name, op in ops.items():
        least = max(op.flops / PEAK["bf16_flops_per_s"],
                    op.bytes / PEAK["hbm_bytes_per_s"])
        runs.append((t, t + least, name))
        t += least
    runs.append((t, 2 * t, "x.1"))  # an op under no scope
    tr = trace.Trace(ops=[runs], host=[(0.0, 2 * t, "step")],
                     window=(0.0, 2 * t))
    ctx = _ctx(text, tr)
    roofline = harness.load_module(
        harness.BENCH / "metrics" / "dwconv_roofline.py", "r")
    share = harness.load_module(
        harness.BENCH / "metrics" / "dwconv_share.py", "s")
    assert roofline.read(ctx) == pytest.approx(100.0)
    assert roofline.read(ctx) <= 100.0 + 1e-9
    assert share.read(ctx) == pytest.approx(50.0)


def test_metrics_read_nothing_without_the_scope(text):
    tr = trace.Trace(ops=[[(0.0, 1.0, "fusion")]], host=[(0.0, 1.0, "step")],
                     window=(0.0, 1.0))
    bare = text.replace("dwconv", "conv")
    ctx = _ctx(bare, tr)
    for name in ("dwconv_roofline", "dwconv_share"):
        reader = harness.load_module(
            harness.BENCH / "metrics" / f"{name}.py", name)
        assert reader.read(ctx) is None
        assert reader.read(_ctx(text, None)) is None


def test_layer_scopes_name_the_block_parts():
    assert layer_scopes.path_scopes(
        "jit(step_fn)/transpose(jvp(trunk))/seg0/bp_row3/vjp/"
        "transpose(jvp(dwconv))/conv_general_dilated") == (
        "trunk", "seg0", "bp_row3", "vjp", "dwconv")
    assert layer_scopes.path_scopes(
        "jit(step_fn)/jvp(trunk)/seg1/fp_row0/pw_mlp/mul")[-1] == "pw_mlp"
    assert layer_scopes.path_scopes(
        "jit(step_fn)/jvp(trunk)/seg0/fp_row0/stem/conv")[-1] == "stem"
