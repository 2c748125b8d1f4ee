"""Row programs: the explicit protocol behind every carry-based engine,
and the one executor that drives them all under a residency policy.

LR-CNN's carry-based strategies (2PS rows, hybrid 2PS segments, the
sequence-axis transplants) all share one shape: an initial carry, a
sequential sweep of row steps each of which consumes the previous row's
boundary caches and exports its own, and a merge of the per-row outputs.
Before this module that shape was buried in per-engine scan closures and
hand-written custom VJPs, so there was no seam to hang a *placement*
policy on.  A :class:`RowProgram` names the shape:

* ``init_carry(args)``          — the carry entering row 0 (differentiable
  in ``args``; e.g. the scan's initial recurrent state, or ``()``);
* ``row_args(args, r)``         — row ``r``'s slice of the inputs (linear:
  its transpose IS the gradient scatter);
* ``row_step(carry, row_args, r) -> (carry_out, y_r)`` — one row;
* ``finish(ys)``                — merge per-row outputs;
* ``out_cotangent(g, r)``       — row ``r``'s slice of the output
  cotangent (the transpose of ``finish``);
* ``carry_names(r)``            — names for the boundary caches entering
  row ``r`` (aligned with ``jax.tree.leaves``; a single string names all
  leaves), which is what a :class:`~repro.exec.plan.ResidencySpec`
  targets.

:func:`make_rowprog_apply` turns a program into an ``apply(*args)`` with
the row-centric custom VJP every engine used to hand-write: FP sweeps the
rows; BP re-runs one row at a time (per-row recompute — the Alg. 1 BP
half) consuming the saved boundary caches in reverse.  Residency is
applied *here*, uniformly, so every row-program engine gains it for free:

* ``device``    — carries are saved as-is (today's behaviour);
* ``host``      — carries are offloaded with ``jax.device_put`` after the
  producing row and fetched back during BP ``prefetch_depth`` rows ahead
  of use, so the round-trip overlaps the adjacent row's backward compute
  (the paper's weak inter-row dependency is what makes the copy hideable);
* ``recompute`` — carries are dropped and regenerated during BP by
  re-running the forward chain up to the consuming row, serialized behind
  the gradient carry so only one chain is ever live (Chen et al.'s
  sublinear-memory end of the retain-vs-recompute tradeoff; O(N^2) row
  steps, zero extra residency).

Host offload targets ``pinned_host`` on an accelerator.  On CPU hosts
the transfer is a placement no-op, decided by the platform, but the
program structure — including the double-buffered fetch schedule — is
exercised identically, so one logged plan behaves the same everywhere.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.exec.plan import ResidencySpec


# ---------------------------------------------------------------------------
# memory-kind helpers
# ---------------------------------------------------------------------------


def offload_is_noop() -> bool:
    """True on CPU hosts, where host and device memory are one space: the
    policy is still recorded and the transfer schedule still runs, but
    nothing moves and peak device bytes are unchanged.  Decided by the
    platform, not by the memories a device lists (a CPU device lists
    ``pinned_host`` too, yet cannot place a buffer there eagerly)."""
    return jax.default_backend() == "cpu"


def default_memory_kind() -> str:
    """The backend's accelerator-resident memory kind."""
    return jax.devices()[0].default_memory().kind


def host_memory_kind() -> str:
    """The memory kind host offload targets: ``pinned_host`` on an
    accelerator, the default kind on CPU (see :func:`offload_is_noop`)."""
    return default_memory_kind() if offload_is_noop() else "pinned_host"


def _transfer(x, space):
    if offload_is_noop() or not jax.tree.leaves(x):
        return x
    return jax.tree.map(lambda l: jax.device_put(l, space), x)


def to_host(x):
    """Offload a pytree to pinned host memory (identity on CPU)."""
    return _transfer(x, jax.memory.Space.Host)


def to_device(x):
    """Fetch a pytree back into accelerator memory (identity on CPU)."""
    return _transfer(x, jax.memory.Space.Device)


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------


class RowProgram:
    """Base class spelling out the row-program protocol (engines may also
    duck-type it).  ``n_rows`` is the row count; ``returns_carry`` makes
    ``apply`` return ``(final_carry, merged_output)`` instead of just the
    merged output (scan-shaped programs)."""

    n_rows: int = 1
    returns_carry: bool = False

    # -- structure ------------------------------------------------------
    def init_carry(self, args) -> Any:
        """Carry entering row 0, as a differentiable function of the
        apply args (its transpose routes the final carry cotangent)."""
        return ()

    def carry_names(self, r: int):
        """Names for the boundary-cache leaves entering row ``r``: a
        tuple aligned with ``jax.tree.leaves(carry)``, or one string
        naming all leaves."""
        return ()

    def row_args(self, args, r: int) -> Any:
        """Row ``r``'s view of the apply args.  Must be linear (slices /
        pads / identity): the executor takes its ``jax.vjp`` transpose to
        scatter per-row input gradients back."""
        raise NotImplementedError

    def row_step(self, carry, row_args, r: int) -> Tuple[Any, Any]:
        """Run row ``r``: ``(carry_in, row_args) -> (carry_out, y_r)``."""
        raise NotImplementedError

    def finish(self, ys: Sequence) -> Any:
        """Merge the per-row outputs (typically a concat)."""
        raise NotImplementedError

    def out_cotangent(self, g, r: int) -> Any:
        """Row ``r``'s slice of the merged-output cotangent — the
        transpose of :meth:`finish`."""
        raise NotImplementedError


def _names_for(prog: RowProgram, carry, r: int) -> Tuple[str, ...]:
    names = prog.carry_names(r)
    n_leaves = len(jax.tree.leaves(carry))
    if isinstance(names, str):
        return (names,) * n_leaves
    names = tuple(names)
    if len(names) != n_leaves:
        raise ValueError(
            f"row {r}: carry_names() gave {len(names)} names for "
            f"{n_leaves} carry leaves")
    return names


def _map_leaves(fn, carry, names):
    """tree_map over (carry leaf, its name) preserving structure."""
    leaves, treedef = jax.tree.flatten(carry)
    return jax.tree.unflatten(
        treedef, [fn(l, n) for l, n in zip(leaves, names)])


def _tree_bytes(tree) -> int:
    """Byte size of a pytree from shape/dtype (works on tracers, which
    the executor's obs hooks see — they fire at trace time)."""
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# the shared executor
# ---------------------------------------------------------------------------


def rowprog_forward(prog: RowProgram, args, collect: bool = False):
    """Plain forward sweep.  With ``collect`` also returns the carry
    entering each row (the boundary caches residency governs)."""
    carry = prog.init_carry(args)
    ys, carries_in = [], []
    for r in range(prog.n_rows):
        if collect:
            carries_in.append(carry)
        # the scope sits here, not in row_step, so the BP replay of a row
        # is not tagged as forward work
        with obs.scope("fp_row", tick=r, n_rows=prog.n_rows,
                       carry_bytes=_tree_bytes(carry)):
            obs.counter("rowprog.fp_rows").inc()
            carry, y = prog.row_step(carry, prog.row_args(args, r), r)
        ys.append(y)
    with obs.scope("fp_merge"):
        out = prog.finish(ys)
    out = (carry, out) if prog.returns_carry else out
    if collect:
        return out, carries_in
    return out


def make_rowprog_apply(prog: RowProgram,
                       residency: Optional[ResidencySpec] = None):
    """Build ``apply(*args)`` for a row program under a residency policy.

    The returned function carries the row-centric custom VJP shared by
    every carry-based engine: FP saves only the apply args plus each
    row's incoming boundary caches (placed per ``residency``); BP walks
    the rows in reverse, recomputing one row at a time and chaining the
    carry cotangent backwards — gradients are exact regardless of
    placement, because placement only moves bytes, never values.
    """
    res = residency or ResidencySpec()

    def _placements(carry, r):
        return [res.placement(n) for n in _names_for(prog, carry, r)]

    def _place(carry, r):
        """FP-side placement of the carry entering row ``r``: host leaves
        are offloaded, recompute leaves are dropped to zero-size
        sentinels (structure preserved so the residual pytree is
        static)."""
        names = _names_for(prog, carry, r)

        def place_leaf(leaf, name):
            p = res.placement(name)
            if p == "host":
                return to_host(leaf)
            if p == "recompute":
                return jnp.zeros((0,), leaf.dtype)
            return leaf
        placed = _map_leaves(place_leaf, carry, names)
        if obs.enabled():
            leaves = jax.tree.leaves(carry)
            off = sum(_tree_bytes(l) for l, n in zip(leaves, names)
                      if res.placement(n) == "host")
            drop = sum(_tree_bytes(l) for l, n in zip(leaves, names)
                       if res.placement(n) == "recompute")
            if off:
                obs.event("offload", tick=r, bytes=off)
                obs.counter("rowprog.offload_bytes").inc(off)
            if drop:
                obs.event("drop_recompute", tick=r, bytes=drop)
        return placed

    def _fetch(saved, r, dep):
        """Issue the host->device copies for row ``r``'s host-placed
        leaves (the prefetchable part of a restore); other leaves —
        device-resident or recompute sentinels — pass through.

        The copies are gated behind ``dep`` (the gradient carry at issue
        time) with an optimization barrier: trace order alone would let
        XLA hoist every fetch to the start of BP, re-materializing the
        whole SD volume at once.  The barrier makes row ``r``'s fetch
        depend on the gradient of the row ``prefetch_depth`` above it, so
        at most ``1 + prefetch_depth`` fetches are ever in flight — the
        working set the planner prices."""
        placements = _placements(saved, r)
        if dep is not None and jax.tree.leaves(dep) \
                and "host" in placements:
            saved, _ = lax.optimization_barrier((saved, dep))
        leaves, treedef = jax.tree.flatten(saved)
        return jax.tree.unflatten(
            treedef, [to_device(l) if p == "host" else l
                      for l, p in zip(leaves, placements)])

    def _trace_prefetch(saved_rr, r, rr):
        placements = _placements(saved_rr, rr)
        if "host" not in placements:
            return
        host_bytes = sum(_tree_bytes(l) for l, p in
                         zip(jax.tree.leaves(saved_rr), placements)
                         if p == "host")
        # depth = how many rows ahead of consumption the copy is issued
        # (0 = demand fetch)
        obs.event("prefetch", tick=r, row=rr, depth=r - rr, bytes=host_bytes)
        obs.counter("rowprog.prefetches").inc()
        obs.counter("rowprog.prefetch_bytes").inc(host_bytes)

    def _row_recomputes(saved, r) -> bool:
        return any(p == "recompute" for p in _placements(saved, r))

    def _merge_recomputed(fetched, recomputed, r):
        """Substitute the recompute sentinels with the regenerated
        chain's leaves."""
        placements = _placements(fetched, r)
        f_leaves, treedef = jax.tree.flatten(fetched)
        r_leaves = jax.tree.leaves(recomputed)
        return jax.tree.unflatten(
            treedef, [rec if p == "recompute" else leaf
                      for leaf, p, rec in zip(f_leaves, placements,
                                              r_leaves)])

    def _recompute_chain(args, upto: int, dep):
        """Re-run rows 0..upto-1 to regenerate the carry entering row
        ``upto``.  Serialized behind ``dep`` (the gradient carry of the
        row above) with an optimization barrier so XLA cannot run the N
        chains concurrently and re-materialize every cache at once."""
        with obs.scope("recompute_chain", tick=upto, rows=upto):
            obs.counter("rowprog.recompute_rows").inc(upto)
            if jax.tree.leaves(dep):
                args, _ = lax.optimization_barrier((args, dep))
            carry = prog.init_carry(args)
            for rr in range(upto):
                carry, _ = prog.row_step(carry, prog.row_args(args, rr), rr)
        return carry

    @jax.custom_vjp
    def apply(*args):
        return rowprog_forward(prog, args)

    def fwd(*args):
        out, carries_in = rowprog_forward(prog, args, collect=True)
        with obs.scope("place"):
            saved = tuple(_place(c, r) for r, c in enumerate(carries_in))
        return out, (args, saved)

    def bwd(residuals, g):
        args, saved = residuals
        if prog.returns_carry:
            dcarry, g_out = g
        else:
            dcarry, g_out = None, g
        dargs = jax.tree.map(jnp.zeros_like, args)
        # double-buffered host fetch: rows are fetched up to
        # prefetch_depth ahead of the row that consumes them, so the
        # host->device copy overlaps the rows in between.  ONLY the host
        # copies are prefetched — recompute chains are regenerated at
        # consumption time below, serialized behind the gradient carry,
        # so two chains are never live at once.
        trace = obs.enabled()
        fetched = {}
        for r in range(prog.n_rows - 1, -1, -1):
            recomputes = _row_recomputes(saved[r], r)
            with obs.scope("bp_row", tick=r, n_rows=prog.n_rows,
                           recomputes=recomputes):
                obs.counter("rowprog.bp_rows").inc()
                with obs.scope("fetch"):
                    for rr in range(r, max(-1, r - 1 - res.prefetch_depth),
                                    -1):
                        if rr not in fetched:
                            fetched[rr] = _fetch(saved[rr], rr, dcarry)
                            if trace:
                                _trace_prefetch(saved[rr], r, rr)
                carry_in = fetched.pop(r)
                if recomputes:
                    carry_in = _merge_recomputed(
                        carry_in, _recompute_chain(args, r, dcarry), r)

                def step_r(c, ra, r=r):
                    return prog.row_step(c, ra, r)

                with obs.scope("replay"):
                    # one vjp trace of the slicing yields both the row's
                    # args and the scatter transpose that routes their
                    # gradients back
                    row_args, slice_vjp = jax.vjp(
                        lambda a, r=r: prog.row_args(a, r), args)
                    (carry_out, _y), vjp = jax.vjp(step_r, carry_in,
                                                   row_args)
                if dcarry is None:  # no carry cotangent enters the last row
                    dcarry = jax.tree.map(jnp.zeros_like, carry_out)
                with obs.scope("vjp"):
                    dcin, drow = vjp((dcarry, prog.out_cotangent(g_out, r)))
                # the add is inside the scope: a pad-and-add fusion takes
                # the metadata of its root, the add.  The barrier keeps
                # the transpose's pad out of the ops that make drow:
                # XLA's TPU pipeline would fold a row's pad into its
                # input-gradient convolution, which then runs at the
                # full height of the segment input.
                with obs.scope("grad_scatter"):
                    guarded = jax.tree.map(lax.optimization_barrier, drow)
                    dargs = jax.tree.map(jnp.add, dargs,
                                         slice_vjp(guarded)[0])
                dcarry = dcin
        # close the chain through init_carry (e.g. the scan's carry_init)
        _, init_vjp = jax.vjp(lambda a: prog.init_carry(a), args)
        dargs = jax.tree.map(jnp.add, dargs, init_vjp(dcarry)[0])
        return dargs

    apply.defvjp(fwd, bwd)
    return apply
