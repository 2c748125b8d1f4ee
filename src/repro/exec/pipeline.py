"""Pipelined row execution: stage-parallel plans over the model axis
(DESIGN.md §6).

LR-CNN's rows are weakly dependent across *every* conv layer, which makes
a row partition exactly the microbatch a GPipe-style schedule streams
through layer stages (Lym et al.'s Mini-batch Serialization exploits the
same inter-layer reuse).  This module turns that observation into the
last unexecuted plan dimension:

* a :class:`~repro.exec.plan.StageSpec` on the plan records how the
  module trunk splits into S contiguous stages;
* :class:`PipelineRowProgram` runs the schedule as a **row program over
  ticks**: tick ``t`` runs stage ``s`` on microbatch (row) ``r = t - s``
  for every live ``(s, r)`` pair, so the whole 2-D (stage x row) grid is
  swept in ``N + S - 1`` ticks.  The boundary activations between stages
  are exactly the program's carries — named ``"stage_b{s}"`` — so the
  shared executor (:mod:`repro.exec.rowprog`), its residency placements
  (device / host / recompute of the GPipe stash) and its row-centric
  custom VJP drive the per-stage FP/BP with no new autodiff machinery;
* rows use OverL interval chains (:mod:`repro.core.overlap`): each
  microbatch owns a disjoint interval of the final rows and carries its
  replicated-halo closure through the stages, so stage outputs compose to
  the exact column-centric result (DESIGN.md §2 applies per stage).

Tensor parallelism stays OUT of this module: the per-kind shard wrapper
(:mod:`repro.exec.engines`) constrains stage-local conv params onto the
mesh's model axis; engines never see the mesh.

``obs`` spans record every ``(stage, row)`` tick plus the measured bubble
fraction of the schedule grid — ``(S-1)/(N+S-1)`` idle slots for the
plain GPipe fill/drain ramp, which is the same term the planner's
roofline charges (``predict_plan_us``).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
from jax import lax

from repro import obs
from repro.core.overlap import plan_overlap
from repro.core.seqrow import _chunk_slice
from repro.exec.plan import ExecutionPlan, StageSpec
from repro.exec.registry import register_engine
from repro.exec.rowprog import RowProgram, make_rowprog_apply


@jax.custom_vjp
def _dep_barrier(x, dep):
    """``x``, scheduled after ``dep``: an ``optimization_barrier`` made
    differentiable (the raw primitive has no VJP rule, and ``row_step`` is
    re-traced under ``jax.vjp`` by the executor's backward pass).  The
    gradient is identity for ``x`` and zero for ``dep`` — the dependency
    is scheduling-only, never a value edge."""
    x, _ = lax.optimization_barrier((x, dep))
    return x


def _dep_barrier_fwd(x, dep):
    aval = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                        dep)
    return _dep_barrier(x, dep), aval


def _dep_barrier_bwd(aval, g):
    import jax.numpy as jnp
    return g, jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), aval)


_dep_barrier.defvjp(_dep_barrier_fwd, _dep_barrier_bwd)


def resolve_stage_spec(n_modules: int, plan: ExecutionPlan) -> StageSpec:
    """The ONE rule turning a plan into a stage partition: an explicit
    ``plan.stage`` wins verbatim (a logged plan replays bit-for-bit);
    otherwise S comes from the ``n_stages`` extra, else the mesh's model
    extent, else 2 — capped at the module count so every stage is
    non-empty."""
    if plan.stage is not None:
        return plan.stage
    n = int(plan.get("n_stages", 0))
    if not n and plan.mesh is not None:
        n = plan.mesh.model
    n = max(1, min(n or 2, n_modules))
    return StageSpec.even(n_modules, n)


class _PipelineBase(RowProgram):
    """Shared tick machinery: the carry entering tick ``t`` is a tuple of
    ``S - 1`` boundary slots — slot ``s`` holds the activation stage ``s``
    exported at tick ``t - 1`` for the microbatch entering stage ``s + 1``
    now, or ``()`` when that slot is outside the fill/drain ramp.  The
    tuple structure is static per tick (the executor unrolls ticks in
    Python), so heterogeneous boundary shapes across the ramp are fine.
    """

    returns_carry = False

    def __init__(self, n_microbatches: int, stage: StageSpec):
        self.n_microbatches = n_microbatches
        self.stage = stage
        #: executor rows == schedule ticks
        self.n_rows = n_microbatches + stage.n_stages - 1

    # -- schedule geometry ---------------------------------------------
    def _live(self, t: int, s: int) -> bool:
        return 0 <= t - s < self.n_microbatches

    def bubble_fraction(self) -> float:
        """Idle fraction of the (stage x tick) schedule grid, measured by
        counting the slots the sweep actually skips (== (S-1)/(N+S-1) for
        the plain fill/drain ramp)."""
        S = self.stage.n_stages
        total = S * self.n_rows
        busy = sum(1 for t in range(self.n_rows) for s in range(S)
                   if self._live(t, s))
        return (total - busy) / total

    # -- row-program protocol ------------------------------------------
    def init_carry(self, args):
        return tuple(() for _ in range(self.stage.n_stages - 1))

    def carry_names(self, t: int):
        # slot s is live entering tick t iff stage s ran microbatch
        # t - 1 - s at the previous tick; each live slot is one array leaf
        return tuple(f"stage_b{s}" for s in range(self.stage.n_stages - 1)
                     if self._live(t - 1, s))

    def _stage_apply(self, params, y, s: int, r: int):
        raise NotImplementedError

    def _row_input(self, row_args, t: int):
        """(params, microbatch-t input) from this tick's row args."""
        raise NotImplementedError

    def row_step(self, carry, row_args, t: int):
        S, N = self.stage.n_stages, self.n_microbatches
        trace = obs.enabled()
        params, xr = self._row_input(row_args, t)
        if jax.tree.leaves(carry) and jax.tree.leaves(xr):
            # serialize ticks: the fresh microbatch's input waits for the
            # previous tick's boundary exports, else XLA may run every
            # stage-0 step concurrently and void the liveness bound (the
            # overlap_forward barrier, tick-wise)
            params, xr = _dep_barrier((params, xr), carry)
        new_carry = [() for _ in range(S - 1)]
        y_out = ()
        for s in range(S):
            r = t - s
            if not 0 <= r < N:
                continue
            with obs.scope("stage_row", tick=t, stage=s, row=r,
                           n_stages=S, n_rows=N):
                obs.counter("pipeline.stage_rows").inc()
                y = xr if s == 0 else carry[s - 1]
                y = self._stage_apply(params, y, s, r)
            if s == S - 1:
                y_out = y
            else:
                new_carry[s] = y
        if trace and t == self.n_rows - 1:
            bf = self.bubble_fraction()
            obs.event("pipeline_bubble", tick=t, n_stages=S,
                      n_microbatches=N, bubble_fraction=bf)
            obs.gauge("pipeline.bubble_fraction").set(bf)
        return tuple(new_carry), y_out

    def finish(self, ys: Sequence):
        # microbatch r's tile drains at tick (S - 1) + r
        return self._concat(ys[self.stage.n_stages - 1:])

    def _concat(self, tiles):
        raise NotImplementedError


class PipelineRowProgram(_PipelineBase):
    """The CNN trunk pipelined: microbatches are OverL rows (replicated
    halo, fully independent), so stage ``s`` maps microbatch ``r``'s
    interval chain from level ``stage.stages[s][0]`` to level
    ``stage.stages[s][1]`` via the same ``apply_row`` sub-chain
    ``overlap._run_row`` uses — exactness per stage is exactness of the
    composition (DESIGN.md §2)."""

    def __init__(self, modules: Sequence, plan: ExecutionPlan,
                 stage: Optional[StageSpec] = None):
        stage = stage or resolve_stage_spec(len(modules), plan)
        if stage.n_modules != len(modules):
            raise ValueError(
                f"StageSpec covers {stage.n_modules} modules but the trunk "
                f"has {len(modules)}")
        super().__init__(max(1, plan.n_rows), stage)
        self.modules = list(modules)
        self.ov = plan_overlap(modules, plan.h0, self.n_microbatches)

    def _row_input(self, row_args, t: int):
        return row_args

    def row_args(self, args, t: int):
        params, x = args
        r = t  # the microbatch entering stage 0 this tick
        if r >= self.n_microbatches:
            return params, ()
        a, b = self.ov.chains[r][0]
        return params, lax.slice_in_dim(x, a, b, axis=1)

    def _stage_apply(self, params, y, s: int, r: int):
        a, b = self.stage.stages[s]
        chain, heights = self.ov.chains[r], self.ov.heights
        for l in range(a, b):
            y = self.modules[l].apply_row(params[l], y, chain[l],
                                          heights[l], chain[l + 1])
        return y

    def _concat(self, tiles):
        import jax.numpy as jnp
        return jnp.concatenate(tiles, axis=1)

    def out_cotangent(self, g, t: int):
        r = t - (self.stage.n_stages - 1)
        if r < 0:
            return ()
        a, b = self.ov.row_ivs[r]
        return lax.slice_in_dim(g, a, b, axis=1)


class SeqPipelineRowProgram(_PipelineBase):
    """The sequence-axis counterpart (DESIGN.md §4): microbatches are
    halo-0 sequence chunks, stages are contiguous splits of a per-chunk
    layer-stack (a list of callables, each mapping one chunk to one
    chunk — a single array; per-token layers, so chunks stay independent
    exactly like :class:`~repro.core.seqrow.ChunkedRowProgram`).  Stage
    fns must not close over differentiable tracers (the executor's custom
    VJP only differentiates explicit apply args — the
    ``StackedCarryScanRowProgram`` caveat)."""

    def __init__(self, fns: Sequence[Callable], n_chunks: int,
                 stage: StageSpec, axis: int = 1):
        if stage.n_modules != len(fns):
            raise ValueError(
                f"StageSpec covers {stage.n_modules} fns but the stack "
                f"has {len(fns)}")
        super().__init__(max(1, n_chunks), stage)
        self.fns = list(fns)
        self.axis = axis

    def _row_input(self, row_args, t: int):
        return None, row_args

    def row_args(self, args, t: int):
        (x,) = args
        if t >= self.n_microbatches:
            return ()
        return _chunk_slice(x, t, self.n_microbatches, self.axis)

    def _stage_apply(self, params, y, s: int, r: int):
        a, b = self.stage.stages[s]
        for l in range(a, b):
            y = self.fns[l](y)
        return y

    def _concat(self, tiles):
        import jax.numpy as jnp
        return jnp.concatenate(tiles, axis=self.axis)

    def out_cotangent(self, g, t: int):
        r = t - (self.stage.n_stages - 1)
        if r < 0:
            return ()
        return _chunk_slice(g, r, self.n_microbatches, self.axis)


# ---------------------------------------------------------------------------
# engine registrations: the same seam as every other engine
# ---------------------------------------------------------------------------


@register_engine("pipeline_rows", kind="cnn",
                 doc="GPipe-style row pipeline: N OverL rows stream "
                     "through S contiguous module stages (plan.stage); "
                     "boundary activations are row-program carries placed "
                     "by plan.residency")
def _build_pipeline_rows(modules, plan: ExecutionPlan):
    prog = PipelineRowProgram(modules, plan)
    return make_rowprog_apply(prog, plan.residency)


@register_engine("pipeline_seq", kind="seq",
                 doc="sequence-axis pipeline: N halo-0 chunks stream "
                     "through S stages of a per-chunk layer stack; the "
                     "LM (params, cfg) form delegates to build_lm_apply")
def _build_pipeline_seq(modules, plan: ExecutionPlan):
    from repro.exec.engines import _seq_modules
    lm = _seq_modules(modules, plan)
    if lm is not None:
        return lm
    fns = list(modules)
    stage = plan.stage or resolve_stage_spec(len(fns), plan)
    prog = SeqPipelineRowProgram(fns, plan.n_rows, stage,
                                 axis=int(plan.get("axis", 1)))
    return make_rowprog_apply(prog, plan.residency)
