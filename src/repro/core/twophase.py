"""2PS — Two-Phase Sharing row partitioning (LR-CNN Sec. IV-A).

Rows are scheduled sequentially.  Every straddling receptive field is owned
by the *lower* row, which consumes the cached bottom-boundary rows of the
row above ("the common part is exclusively computed within a row and then
preserved in FP and BP phases, for being reused by the next row and
gradient calculation").  No redundant compute; per-row memory is skewed
(row 1 carries the full receptive-field closure — the paper's greedy
partitioning, Eq. 11 vs Eq. 13/14), which the planner accounts for.

Ownership boundaries at every level come from the ``in_end`` recursion
(:func:`module_boundaries`), the module-level generalisation of the paper's
height recursions.  Caches ("SD", sharing data) saved during FP are reused
during BP's per-row recomputation; gradient cotangents for imported cache
rows flow back to the producing row — the reverse scan mirrors the forward
carry, making 2PS gradients exact.

The paper sets ``N = N_BP`` for 2PS (both phases use the same granularity);
we follow that.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core.convmath import Interval, split_even
from repro.models.cnn.layers import trunk_heights


@dataclasses.dataclass(frozen=True)
class TwoPhasePlan:
    h0: int
    heights: Tuple[int, ...]
    bounds: Tuple[Tuple[int, ...], ...]   # bounds[l][r], l = 0..L, r = 0..N
    need_lo: Tuple[Tuple[int, ...], ...]  # need_lo[l][r]: first input row of
                                          # level l-1 needed by row r at module l
                                          # (l = 1..L); index [l-1][r]

    @property
    def n_rows(self) -> int:
        return len(self.bounds[0]) - 1

    @property
    def n_levels(self) -> int:
        return len(self.bounds) - 1

    def row_iv(self, l: int, r: int) -> Interval:
        return (self.bounds[l][r], self.bounds[l][r + 1])

    def cache_head(self, l: int, r: int) -> Interval:
        """Rows of activation level ``l-1`` that row ``r`` imports from row
        r-1's cache (empty for r = 0)."""
        return (self.need_lo[l - 1][r], self.bounds[l - 1][r])

    def cache_sizes(self) -> List[List[int]]:
        """cache[r][l-1] sizes for r >= 1 — the paper's (k-s)·W volume."""
        return [
            [self.bounds[l - 1][r] - self.need_lo[l - 1][r]
             for l in range(1, self.n_levels + 1)]
            for r in range(1, self.n_rows)
        ]

    def shared_rows_total(self) -> int:
        """Total cached boundary rows (SD counter for Fig. 10(b))."""
        return sum(sum(row) for row in self.cache_sizes())


def module_boundaries(modules: Sequence, h0: int, n_rows: int) -> TwoPhasePlan:
    hs = trunk_heights(modules, h0)
    L = len(modules)
    top = split_even(hs[-1], n_rows)
    bounds = [[iv[0] for iv in top] + [hs[-1]]]
    for l in range(L - 1, -1, -1):
        m = modules[l]
        above = bounds[-1]
        cur = [0]
        for r in range(1, n_rows):
            b = above[r]
            e = m.in_interval((max(0, b - 1), b), hs[l])[1] if b > 0 else 0
            cur.append(min(e, hs[l]))
        cur.append(hs[l])
        for r in range(1, n_rows + 1):  # monotonicity for degenerate cases
            cur[r] = max(cur[r], cur[r - 1])
        bounds.append(cur)
    bounds.reverse()

    need_lo: List[List[int]] = []
    for l in range(1, L + 1):
        m = modules[l - 1]
        row = []
        for r in range(n_rows):
            iv = (bounds[l][r], bounds[l][r + 1])
            if iv[0] >= iv[1]:
                row.append(bounds[l - 1][r])
            else:
                row.append(m.in_interval(iv, hs[l - 1])[0])
        need_lo.append(row)
    return TwoPhasePlan(h0, tuple(hs), tuple(map(tuple, bounds)),
                        tuple(map(tuple, need_lo)))


def validate_plan(plan: TwoPhasePlan) -> bool:
    """Cache heads must be produced by the immediately preceding row and
    every row's territory must be non-empty at every level (the paper's
    granularity upper bound)."""
    for l in range(plan.n_levels + 1):
        for r in range(plan.n_rows):
            if plan.bounds[l][r + 1] <= plan.bounds[l][r]:
                return False
    for l in range(1, plan.n_levels + 1):
        for r in range(1, plan.n_rows):
            lo, hi = plan.cache_head(l, r)
            if lo < plan.bounds[l - 1][r - 1]:
                return False
            if hi < lo:
                return False
    return True


def max_valid_rows(modules: Sequence, h0: int, limit: int = 64) -> int:
    best = 1
    for n in range(2, limit + 1):
        try:
            if validate_plan(module_boundaries(modules, h0, n)):
                best = n
            else:
                break
        except ValueError:
            break
    return best


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------


def _run_row(modules, params, plan: TwoPhasePlan, r: int, x_r, caches_in):
    """Run row r through all modules.

    ``x_r`` covers input rows ``m_1.in_interval(row_iv(1, r))``.
    ``caches_in``: list over levels 1..L-1 of imported boundary activations
    (possibly zero-height).  Returns (final rows, caches_out) where
    caches_out exports this row's boundary rows for row r+1.
    """
    hs = plan.heights
    act = x_r  # covers [need_lo[0][r], bounds[0][r+1]) of level 0
    act_lo = plan.need_lo[0][r]
    caches_out = []
    for l in range(1, plan.n_levels + 1):
        m = modules[l - 1]
        out_iv = plan.row_iv(l, r)
        in_iv = (plan.need_lo[l - 1][r], m.in_interval(out_iv, hs[l - 1])[1])
        # assemble the input slice covering in_iv
        if l == 1:
            assert act_lo == in_iv[0]
            x_in = lax.slice_in_dim(act, 0, in_iv[1] - act_lo, axis=1)
        else:
            own_lo = plan.bounds[l - 1][r]
            own = lax.slice_in_dim(act, 0, in_iv[1] - own_lo, axis=1)
            head_n = own_lo - in_iv[0]
            if head_n > 0:
                head = caches_in[l - 2]  # level l-1 import: cache_head(l, r)
                with obs.scope("sd_import"):
                    x_in = jnp.concatenate([head, own], axis=1)
            else:
                x_in = own
        y = m.apply_row(params[l - 1], x_in, in_iv, hs[l - 1], out_iv)
        # export cache for row r+1 from the *input* level l-1 (only rows this
        # row owns; the imported head is re-exported by slicing act where
        # needed — by construction row r+1's head lies within row r's rows).
        if l >= 2 and r + 1 < plan.n_rows:
            nlo = plan.need_lo[l - 1][r + 1]
            nhi = plan.bounds[l - 1][r + 1]
            off = nlo - plan.bounds[l - 1][r]
            assert off >= 0, (l, r, nlo, plan.bounds[l - 1][r])
            with obs.scope("sd_export"):
                caches_out.append(
                    lax.slice_in_dim(act, off, off + (nhi - nlo), axis=1))
        act = y
        act_lo = out_iv[0]
    return act, caches_out


def _x_slice(plan: TwoPhasePlan, r: int, x):
    lo = plan.need_lo[0][r]
    hi_own = plan.bounds[0][r + 1]
    return lax.slice_in_dim(x, lo, hi_own, axis=1)


def twophase_forward(modules: Sequence, params, x, plan: TwoPhasePlan,
                     return_caches: bool = False):
    caches: List = []
    outs = []
    caches_in: List = []
    for r in range(plan.n_rows):
        y, caches_out = _run_row(modules, params, plan, r, _x_slice(plan, r, x),
                                 caches_in)
        outs.append(y)
        caches.append(caches_in)
        caches_in = caches_out
    z = jnp.concatenate(outs, axis=1)
    if return_caches:
        return z, caches
    return z


class TwoPhaseRowProgram:
    """2PS as an explicit row program (:mod:`repro.exec.rowprog`): the
    carry between rows IS the paper's SD boundary cache — one activation
    slab per level ``l`` in ``1..L-1``, named ``"sd_l{l}"`` so a
    :class:`~repro.exec.plan.ResidencySpec` can place each level
    individually (device / host / recompute).  ``row_step`` is the
    original :func:`_run_row` — the carry was always there, it just lived
    inside a scan closure before this seam existed."""

    returns_carry = False

    def __init__(self, modules: Sequence, plan: TwoPhasePlan):
        self.modules = modules
        self.plan = plan
        self.n_rows = plan.n_rows

    def init_carry(self, args):
        return ()  # row 0 imports nothing (it owns the full closure)

    def carry_names(self, r: int):
        if r == 0:
            return ()
        # caches_in[l-2] imports activation level l-1 for module l
        return tuple(f"sd_l{lvl}" for lvl in range(1, self.plan.n_levels))

    def row_args(self, args, r: int):
        params, x = args
        return params, _x_slice(self.plan, r, x)

    def row_step(self, carry, row_args, r: int):
        params, x_r = row_args
        y, caches_out = _run_row(self.modules, params, self.plan, r, x_r,
                                 list(carry))
        return tuple(caches_out), y

    def finish(self, ys):
        return jnp.concatenate(ys, axis=1)

    def out_cotangent(self, g, r: int):
        os_, oe = self.plan.row_iv(self.plan.n_levels, r)
        return lax.slice_in_dim(g, os_, oe, axis=1)


def make_twophase_apply(modules: Sequence, h0: int, n_rows: int,
                        residency=None):
    """Returns ``apply(params, x) -> z_L`` with the 2PS row-centric custom
    VJP, expressed as a row program so ``residency`` (a
    :class:`~repro.exec.plan.ResidencySpec`, or None for device-resident)
    governs where the inter-row boundary caches live."""
    plan = module_boundaries(modules, h0, n_rows)
    if not validate_plan(plan):
        raise ValueError(
            f"2PS plan with N={n_rows} invalid for H0={h0} over {len(modules)} "
            f"modules (granularity bound exceeded; use hybrid checkpointing)")
    from repro.exec.rowprog import make_rowprog_apply
    return make_rowprog_apply(TwoPhaseRowProgram(modules, plan), residency)
