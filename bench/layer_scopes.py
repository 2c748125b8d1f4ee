"""Device time by the scopes a model's own layers open, beside the row
engines' scopes.

``bench/scopes.py`` gives each device op the program scopes of the row
engines (``seg``, ``fp_row``, ``bp_row``, ``vjp``, ...).  A model's layers
open scopes of their own inside those: ConvNeXt's ``stem`` and
``downsample`` layers, and in each block ``dwconv`` (the 7x7 depthwise
convolution), ``norm`` (its LayerNorm) and ``pw_mlp`` (the two 1x1
convolutions, the GELU and the layer scale).  This reads an op's scopes by
the same rule as ``scopes.op_scopes`` (its own metadata, else its fused
work's, else the moved value's) over both sets of names, so that the
innermost of them says which layer an op belongs to.  A step whose ops
carry none of a layer's names gives its shares nothing.
"""

from __future__ import annotations

import functools
import re

from bench import hlo, scopes

#: the row engines' scopes and those of the model's layers
NAMES = re.compile(scopes.PROGRAM.pattern
                   + r"|stem|downsample|dwconv|norm|pw_mlp")


def path_scopes(op_name: str) -> tuple:
    """The scopes of one ``op_name`` named by :data:`NAMES`, outermost
    first, with JAX's transform wrappers taken off."""
    out = []
    for part in op_name.split("/"):
        while (m := scopes._WRAPPED.fullmatch(part)):
            part = m.group(1)
        if NAMES.fullmatch(part):
            out.append(part)
    return tuple(out)


def _own(ins) -> tuple:
    m = scopes._OP_NAME.search(ins.attrs)
    return path_scopes(m.group(1)) if m else ()


def _fused(comps, ins) -> tuple:
    for sub in re.findall(r"calls=%([\w.\-]+)", ins.attrs):
        for inner in reversed(list(comps.get(sub, {}).values())):
            found = _own(inner) or _fused(comps, inner)
            if found:
                return found
    return ()


@functools.lru_cache(maxsize=2)
def op_scopes(hlo_text: str) -> dict:
    """Entry-computation op name -> its scopes, by ``scopes.op_scopes``'
    rule over :data:`NAMES`."""
    comps = hlo.parse_module(hlo_text)
    out = {}
    for name, ins in comps.get("ENTRY", {}).items():
        out[name] = (_own(ins) or _fused(comps, ins)
                     or (out.get(ins.operands[0], ())
                         if ins.op in scopes._MOVES and ins.operands
                         else ()))
    return out


def innermost(ctx, name: str) -> set:
    """The entry ops whose innermost scope is ``name``; empty where no op
    carries it."""
    return {op for op, s in op_scopes(ctx.hlo_text).items()
            if s and s[-1].rstrip("0123456789") == name}


def share(ctx, name: str) -> float | None:
    """Device time of the ops whose innermost scope is ``name``, over the
    busy time of the cell's chips, in percent; ``None`` without a trace
    or an op under that scope."""
    if ctx.trace is None:
        return None
    ops = innermost(ctx, name)
    if not ops:
        return None
    spent = sum(seconds for op, seconds in ctx.trace.op_seconds().items()
                if op in ops)
    return 100.0 * spent / (ctx.trace.busy_s() * ctx.chips)
