"""Device time by program scope, read from the compiled step's metadata.

The program names its work with ``repro.obs.scope`` (``jax.named_scope``
inside jit), and XLA keeps the name on every instruction it emits, fused
ones included, as ``metadata={op_name="jit(step_fn)/transpose(jvp(trunk))/
seg2/bp_row5/grad_scatter/..."}``: a fusion takes its root's.  The entry
computation's instructions are the ops of the device trace (``bench/trace.py``
keys them by the same names), so each device op's time can be given to the
scopes it ran under.  Where XLA made the op itself and gave it no program
scope (a fusion rooted in its own convert or copy, an async copy or slice),
the op takes the scopes of its fused work, else those of the value it
moves (``op_scopes``).

A path's program scopes are its components that name one, with JAX's
transform wrappers (``jvp(...)``, ``transpose(...)``) taken off, outermost
first; an op's *kind* is that path with the row and segment numbers
dropped (``trunk/seg/bp_row/grad_scatter``), and the innermost scope decides
the classes the per-layer shares read.  A step compiled from a program
with no row scopes gives those shares nothing.
"""

from __future__ import annotations

import functools
import re

from bench import hlo

#: every scope the program opens inside its compiled step
PROGRAM = re.compile(
    r"trunk|head_loss|sgd_update|seg\d+|fp_row\d+|fp_merge|bp_row\d+|replay"
    r"|vjp|grad_scatter|fetch|recompute_chain\d+|place|sd_import|sd_export"
    r"|stage_row\d+")
_WRAPPED = re.compile(r"[\w\-]*\((.*)\)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def path_scopes(op_name: str) -> tuple:
    """The program scopes of one ``op_name``, outermost first."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAPPED.fullmatch(part)):
            part = m.group(1)
        if PROGRAM.fullmatch(part):
            out.append(part)
    return tuple(out)


def kind(scopes: tuple) -> str:
    """``("trunk", "seg2", "bp_row5", "vjp")`` -> ``trunk/seg/bp_row/vjp``;
    ``none`` for an op under no program scope."""
    return "/".join(s.rstrip("0123456789") for s in scopes) or "none"


def _own_scopes(ins) -> tuple:
    m = _OP_NAME.search(ins.attrs)
    return path_scopes(m.group(1)) if m else ()


def _fused_scopes(comps, ins) -> tuple:
    """The scopes of the instruction nearest the root of the computations
    ``ins`` calls that has any: XLA gives a fusion it made around a copy
    or transpose of its own the copy's empty metadata, while the fused
    work keeps the program's."""
    for sub in re.findall(r"calls=%([\w.\-]+)", ins.attrs):
        for inner in reversed(list(comps.get(sub, {}).values())):
            found = _own_scopes(inner) or _fused_scopes(comps, inner)
            if found:
                return found
    return ()


#: ops XLA adds to move or unpack a value: they carry no metadata of their
#: own, and take the scopes of the value they move (their first operand)
_MOVES = frozenset(("copy", "copy-start", "copy-done", "async-start",
                    "async-done", "get-tuple-element", "bitcast"))


@functools.lru_cache(maxsize=2)
def op_scopes(hlo_text: str) -> dict:
    """Entry-computation op name -> its program scopes: its own metadata's,
    else those of the fused work it runs, else, for an op that moves a
    value, those of that value; empty when none names one."""
    comps = hlo.parse_module(hlo_text)
    out = {}
    for name, ins in comps.get("ENTRY", {}).items():  # operands come first
        out[name] = (_own_scopes(ins) or _fused_scopes(comps, ins)
                     or (out.get(ins.operands[0], ())
                         if ins.op in _MOVES and ins.operands else ()))
    return out


def has_rows(scoped: dict) -> bool:
    """Whether the step runs a row program whose backward is scoped."""
    return any(s.startswith("bp_row") for scopes in scoped.values()
               for s in scopes)


def seconds_by_kind(hlo_text: str, trace) -> dict:
    """Kind -> device seconds of its ops in the traced window, summed
    over runs and devices."""
    scoped = op_scopes(hlo_text)
    out = {}
    for name, seconds in trace.op_seconds().items():
        k = kind(scoped.get(name, ()))
        out[k] = out.get(k, 0.0) + seconds
    return out


def share(ctx, innermost) -> float | None:
    """Device time of the ops whose innermost program scope is one of
    ``innermost``, over the busy time of the cell's chips, in percent;
    ``None`` without a trace or a scoped row program."""
    if ctx.trace is None:
        return None
    scoped = op_scopes(ctx.hlo_text)
    if not has_rows(scoped):
        return None
    spent = sum(seconds for name, seconds in ctx.trace.op_seconds().items()
                if scoped.get(name) and
                scoped[name][-1].rstrip("0123456789") in innermost)
    return 100.0 * spent / (ctx.trace.busy_s() * ctx.chips)
