"""Checkpointing and hybrid row-centric execution (LR-CNN Sec. IV: 2PS-H /
OverL-H; Ckp baseline from Chen et al. [10]).

The trunk is cut into segments at checkpoint locations.  Segment inputs are
the only full feature maps whose liveness spans FP->BP (the checkpoints);
within a segment activations are managed by the chosen engine:

* ``column``  — plain ``jax.checkpoint`` per segment  == the paper's *Ckp*.
* ``overlap`` — OverL within the segment             == *OverL-H*.
* ``twophase``— 2PS within the segment               == *2PS-H*.

Both row engines already recompute their rows inside their custom VJP, so
composing per-segment applies *is* checkpointing: each segment's residuals
are exactly (params, segment input).  Truncating the per-segment depth L is
what shrinks the halo growth o^l / boundary skew and admits a larger N —
the paper's Table I effect.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence, Tuple

import jax

from repro import obs
from repro.core import overlap as _ov
from repro.core import twophase as _tp
from repro.models.cnn.layers import trunk_heights


@dataclasses.dataclass(frozen=True)
class SegmentSpec:
    start: int          # module index range [start, end)
    end: int
    n_rows: int = 1
    strategy: str = "column"  # column | overlap | twophase


def auto_segments(n_modules: int, n_segments: int | None = None) -> List[Tuple[int, int]]:
    """Even segmentation; default count = round(sqrt(L)) (the paper's
    preferred checkpointing frequency)."""
    if n_segments is None:
        n_segments = max(1, round(math.sqrt(n_modules)))
    n_segments = min(n_segments, n_modules)
    base, rem = divmod(n_modules, n_segments)
    cuts, cur = [], 0
    for i in range(n_segments):
        size = base + (1 if i < rem else 0)
        cuts.append((cur, cur + size))
        cur += size
    return cuts


def max_rows_per_segment(modules: Sequence, h0: int,
                         segs: Sequence[Tuple[int, int]],
                         strategy: str, limit: int = 64) -> List[int]:
    """Largest valid N per segment — drives the Table I counters."""
    hs = trunk_heights(modules, h0)
    out = []
    for (a, b) in segs:
        sub = list(modules[a:b])
        h_in = hs[a]
        if strategy == "twophase":
            out.append(_tp.max_valid_rows(sub, h_in, limit))
        else:  # overlap: valid while the final activation has >= N rows
            h_out = hs[b]
            out.append(max(1, min(limit, h_out)))
    return out


def make_hybrid_apply(modules: Sequence, h0: int,
                      segments: Sequence[SegmentSpec], residency=None):
    """Compose per-segment engines into one trunk apply.

    ``residency`` (a :class:`~repro.exec.plan.ResidencySpec`) governs the
    boundary caches of the carry-based (2PS) segments — they are row
    programs, so each segment's SD caches follow the plan's placement
    policy; column and overlap segments carry nothing and ignore it."""
    assert segments[0].start == 0 and segments[-1].end == len(modules)
    hs = trunk_heights(modules, h0)
    seg_fns = []
    for spec in segments:
        sub = list(modules[spec.start:spec.end])
        h_in = hs[spec.start]
        sd_rows = []
        if spec.strategy == "twophase":
            sd_rows = _tp.module_boundaries(sub, h_in,
                                            spec.n_rows).cache_sizes()
        if spec.strategy == "column":
            fn = _ov.make_column_apply(sub)
            if len(segments) > 1 or spec.n_rows > 1:
                fn = jax.checkpoint(fn)
        elif spec.strategy == "overlap":
            fn = _ov.make_overlap_apply(sub, h_in, spec.n_rows)
        elif spec.strategy == "twophase":
            fn = _tp.make_twophase_apply(sub, h_in, spec.n_rows,
                                         residency=residency)
        else:
            raise ValueError(spec.strategy)
        seg_fns.append((spec, fn, sd_rows))

    def apply(params, x):
        for i, (spec, fn, sd_rows) in enumerate(seg_fns):
            # trace-time record of the segment the plan runs; sd_rows[r-1]
            # are the boundary-cache rows row r imports, per level
            obs.event("segment", tick=i, start=spec.start, end=spec.end,
                      n_rows=spec.n_rows, strategy=spec.strategy,
                      sd_rows=sd_rows)
            obs.counter("twophase.sd_rows").inc(sum(map(sum, sd_rows)))
            with obs.scope("seg", tick=i):
                x = fn(params[spec.start:spec.end], x)
        return x

    return apply
