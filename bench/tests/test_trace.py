"""Reduction of a profiler trace to busy time, op time and idle gaps."""

from pathlib import Path

import pytest

from bench import hlo, trace

DATA = Path(__file__).parent / "data"


def test_union_clip_gaps():
    busy = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (5.0, 9.0)]
    assert trace.union(busy) == [(0.0, 2.0), (3.0, 4.0), (5.0, 9.0)]
    assert trace.gaps(busy, 1.0, 6.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert trace.gaps([], 1.0, 2.0) == [(1.0, 2.0)]
    assert trace.clip(busy, 3.5, 6.0) == [(3.5, 4.0), (5.0, 6.0)]


def test_innermost_names_the_shortest_covering_span():
    spans = [(0.0, 10.0, "step"), (1.0, 4.0, "dispatch"), (4.0, 9.0, "block")]
    assert trace.innermost(spans, 2.0) == "dispatch"
    assert trace.innermost(spans, 9.5) == "step"
    assert trace.innermost(spans, 11.0) == "none"


def test_synthetic_trace():
    tr = trace.Trace(ops=[[(1.0, 2.0, "a"), (2.5, 3.0, "b"), (3.0, 3.5, "a"),
                           (5.0, 6.0, "c")]],
                     host=[(0.5, 4.0, "step"), (2.0, 2.5, "dispatch")],
                     window=(0.5, 4.0))
    assert tr.window_s == 3.5
    assert tr.busy_s() == pytest.approx(2.0)
    assert tr.op_seconds() == pytest.approx({"a": 1.5, "b": 0.5})
    assert tr.idle_gaps() == [("step", 0.5), ("dispatch", 0.5),
                              ("step", 0.5)]


@pytest.fixture(scope="module")
def recorded():
    # three steps of a three-conv SGD step (tests/data/tpu_conv3.hlo.txt)
    # on one TPU v5e, each step annotated "step" with "dispatch" and
    # "block" spans inside, and a 10 ms host sleep between the two
    return trace.load(DATA / "tpu_conv3.xplane.pb")


def test_recorded_trace(recorded):
    assert len(recorded.ops) == 1
    assert 0.03 < recorded.window_s < 0.04
    busy = recorded.busy_s()
    assert 0.8 * recorded.window_s < busy < recorded.window_s
    names = {n for _, _, n in recorded.ops[0]}
    assert "fusion.44" in names and "copy.1" in names
    # no device op may start before the window's host launch it follows
    assert min(s for s, _, _ in recorded.ops[0]) >= recorded.window[0]


def test_recorded_conv_ops_match_the_hlo(recorded):
    conv = hlo.conv_ops((DATA / "tpu_conv3.hlo.txt").read_text())
    seen = {n for _, _, n in recorded.ops[0] if n in conv}
    assert seen == set(conv)
    per_step = [e - s for s, e, n in recorded.ops[0] if n == "fusion.44"]
    assert len(per_step) == 3 and all(t > 0 for t in per_step)
