"""Device time by program scope: the op_name parser, and the three
per-layer shares of the row engines on a recorded chip trace."""

import types
from pathlib import Path

import pytest

from bench import harness, scopes, trace

DATA = Path(__file__).parent / "data"
READERS = ("bp_replay_share", "grad_scatter_share", "sd_cache_share")


def test_path_scopes_take_off_transform_wrappers():
    assert scopes.path_scopes(
        "jit(step_fn)/transpose(jvp(trunk))/seg2/bp_row5/vjp/"
        "transpose(jvp(sd_import))/split") == (
        "trunk", "seg2", "bp_row5", "vjp", "sd_import")
    assert scopes.path_scopes(
        "jit(step)/trunk/transpose(trunk)/jvp(bp_row0)/replay/jvp()/cos") \
        == ("trunk", "trunk", "bp_row0", "replay")
    assert scopes.path_scopes("jit(step_fn)/jvp()/conv_general_dilated") \
        == ()
    assert scopes.kind(("trunk", "seg2", "bp_row5", "grad_scatter")) \
        == "trunk/seg/bp_row/grad_scatter"
    assert scopes.kind(()) == "none"


ENTRY = """\
HloModule m

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %a = f32[8]{0} sine(%x), metadata={op_name="jit(s)/jvp(trunk)/seg0/fp_row1/sd_import/concatenate"}
  %b = f32[8]{0} cosine(%a), metadata={op_name="jit(s)/transpose(jvp(trunk))/seg0/bp_row1/replay/jvp()/cos"}
  %c = f32[8]{0} add(%b, %a), metadata={op_name="jit(s)/transpose(jvp(trunk))/seg0/bp_row0/grad_scatter/add"}
  %d = f32[8]{0} negate(%c), metadata={op_name="jit(s)/sgd_update/neg"}
  %f = f32[8]{0} copy(%x)
  ROOT %e = f32[8]{0} copy(%d)
}
"""


def ctx_of(text, tr, chips=1):
    return types.SimpleNamespace(hlo_text=text, trace=tr, chips=chips)


def test_shares_on_a_synthetic_trace():
    # the copy of %d moves a value made under sgd_update, and takes its
    # scopes; the copy of the parameter takes none
    tr = trace.Trace(ops=[[(0.0, 1.0, "a"), (1.0, 3.0, "b"), (3.0, 4.0, "c"),
                           (4.0, 5.0, "d"), (5.0, 6.0, "f"), (6.0, 7.0, "e"),
                           (9.0, 9.5, "b")]],
                     host=[(0.0, 8.0, "step")], window=(0.0, 8.0))
    ctx = ctx_of(ENTRY, tr)
    assert scopes.share(ctx, ("replay",)) == pytest.approx(100 * 2 / 7)
    assert scopes.share(ctx, ("grad_scatter",)) == pytest.approx(100 / 7)
    assert scopes.share(ctx, ("sd_import", "sd_export")) \
        == pytest.approx(100 / 7)
    assert scopes.seconds_by_kind(ENTRY, tr) == pytest.approx({
        "trunk/seg/fp_row/sd_import": 1.0, "trunk/seg/bp_row/replay": 2.0,
        "trunk/seg/bp_row/grad_scatter": 1.0, "sgd_update": 2.0,
        "none": 1.0})


FUSED = """\
HloModule m

%fused_computation (param_0: f32[8]) -> u32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %eq.1 = pred[8]{0} compare(%param_0, %param_0), direction=EQ, metadata={op_name="jit(s)/transpose(jvp(trunk))/bp_row2/replay/jvp()/eq"}
  ROOT %convert.1 = u32[8]{0} convert(%eq.1)
}

ENTRY %main (x: f32[8]) -> u32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %convert_fusion = u32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
}
"""


def test_a_fusion_without_metadata_takes_its_fused_work_scopes():
    # XLA's own root (a convert it added) carries no metadata; the mask
    # the replay computes inside the fusion does
    assert scopes.op_scopes(FUSED)["convert_fusion"] == (
        "trunk", "bp_row2", "replay")


def test_unscoped_program_gives_nothing():
    # a step compiled before the scopes existed: metadata without them
    tr = trace.load(DATA / "tpu_conv3.xplane.pb")
    ctx = ctx_of((DATA / "tpu_conv3.hlo.txt").read_text(), tr)
    for name in READERS:
        reader = harness.load_module(
            harness.BENCH / "metrics" / f"{name}.py", name)
        assert reader.read(ctx) is None
        assert reader.read(ctx_of(ctx.hlo_text, None)) is None


@pytest.fixture(scope="module")
def rows2():
    # three steps of a scoped 2PS step (two hybrid segments, N=2, batch
    # 8, four 3x3 convs at 32x32x16) on one TPU v5e, recorded by
    # record_scoped.py, with the optimized HLO it ran
    import gzip

    from jax.profiler import ProfileData

    with gzip.open(DATA / "tpu_rows2.xplane.pb.gz") as f:
        tr = trace.from_profile(ProfileData.from_serialized_xspace(f.read()))
    with gzip.open(DATA / "tpu_rows2.hlo.txt.gz", "rt") as f:
        return ctx_of(f.read(), tr)


def test_recorded_row_program_is_scoped(rows2):
    scoped = scopes.op_scopes(rows2.hlo_text)
    assert scopes.has_rows(scoped)
    kinds = {scopes.kind(s) for s in scoped.values()}
    assert {"trunk/seg/fp_row", "trunk/seg/bp_row/replay",
            "trunk/seg/bp_row/vjp", "trunk/seg/fp_row/sd_export",
            "head_loss", "sgd_update"} <= kinds
    by_kind = scopes.seconds_by_kind(rows2.hlo_text, rows2.trace)
    busy = rows2.trace.busy_s()
    assert by_kind["none"] / busy == pytest.approx(8.264e-4, rel=1e-3)
    assert by_kind["trunk/seg/bp_row/vjp"] / busy \
        == pytest.approx(0.6342, rel=1e-3)


@pytest.mark.parametrize("name, value", [
    ("bp_replay_share", 7.4505), ("grad_scatter_share", 0.0),
    ("sd_cache_share", 0.13047)])
def test_readers_on_the_recorded_trace(rows2, name, value):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                                 name)
    assert reader.read(rows2) == pytest.approx(value, rel=1e-4, abs=1e-9)
