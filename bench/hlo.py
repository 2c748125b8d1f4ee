"""Convolution ops of a compiled step, read from its optimized HLO text.

``conv_ops(compiled.as_text())`` maps the name of every top-level op of
the entry computation that runs a convolution (a bare ``convolution`` or
a fusion whose computation holds one) to its operations and bytes:

* flops: ``2 * prod(output dims) * prod(kernel spatial dims) * kernel
  input features`` for each convolution, padded taps included (the same
  count for a forward conv, its input gradient and its kernel gradient,
  which XLA writes as a convolution whose "kernel" is the output
  gradient);
* bytes: the op's output plus its operands, as their HLO shapes give
  them, those in HBM only (a layout's ``S(n)`` memory space, n > 0, is
  on-chip or on the host).  An operand counts the share of it that the
  fused computation reads, followed through elementwise ops and nested
  fusions to the slices, in-place updates and negatively padded
  convolution windows that cut it, so a row program's per-row ops are not
  charged for the whole tensor they index into.
"""

from __future__ import annotations

import dataclasses
import math
import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4,
    "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_ARRAY = re.compile(r"\b(" + "|".join(_DTYPE_BYTES)
                    + r")\[([0-9,]*)\](\{[^}]*\})?")
#: a layout's memory space other than HBM (0): VMEM, SMEM, host, ...
_OFF_HBM = re.compile(r"S\([1-9]\d*\)")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_SLICES = ("slice", "dynamic-slice")


def arrays(shape_text: str):
    """``(dtype, dims)`` of every array in a shape (a tuple has several)."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims, _ in _ARRAY.findall(shape_text)]


def shape_bytes(shape_text: str) -> int:
    """Bytes of the arrays of a shape that live in HBM: an array whose
    layout puts it in another memory space (``S(1)``, the TPU's VMEM,
    where an async copy has already brought it) moves no HBM bytes."""
    return int(sum(
        _DTYPE_BYTES[dt] * math.prod(int(d) for d in dims.split(",") if d)
        for dt, dims, layout in _ARRAY.findall(shape_text)
        if not _OFF_HBM.search(layout)))


@dataclasses.dataclass
class Instr:
    name: str
    shape: str
    op: str
    operands: list
    args: str
    attrs: str
    root: bool


def _split_instr(rest: str):
    """``<shape> <opcode>(<args>)<attrs>`` -> shape, opcode, operand
    names, the text of the args and the attributes."""
    if rest.startswith("("):  # tuple shape: up to its matching paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    op, _, rest = rest.partition("(")
    depth, i = 1, 0
    while i < len(rest) and depth:
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        i += 1
    args = rest[:i - 1]
    return shape, op.strip(), re.findall(r"%([\w.\-]+)", args), args, \
        rest[i:]


def parse_module(text: str) -> dict:
    """Computation name -> ``{instruction name: Instr}``, in text order;
    the entry computation is also under the key ``"ENTRY"``."""
    comps, cur = {}, None
    for line in text.splitlines():
        if cur is None:
            m = _HEADER.match(line)
            if m and "->" in line:
                cur = comps.setdefault(m.group(1), {})
                if line.startswith("ENTRY"):
                    comps["ENTRY"] = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            cur[m.group(2)] = Instr(m.group(2), *_split_instr(m.group(3)),
                                    bool(m.group(1)))
    return comps


def conv_flops(instr: Instr, symbols: dict) -> int:
    """Multiply-adds x 2 of one ``convolution`` instruction."""
    (_, out), = arrays(instr.shape)
    (_, rhs), = arrays(symbols[instr.operands[1]].shape)
    labels = re.search(r"dim_labels=\w+_(\w+)->", instr.attrs).group(1)
    spatial = math.prod(rhs[i] for i, ch in enumerate(labels) if ch.isdigit())
    return 2 * math.prod(out) * spatial * rhs[labels.index("i")]


def _called(instr: Instr):
    return re.findall(r"calls=%([\w.\-]+)", instr.attrs)


def _comp_flops(comps, name, symbols) -> int:
    total = 0
    for ins in comps[name].values():
        if ins.op == "convolution":
            total += conv_flops(ins, symbols)
        for sub in _called(ins):
            total += _comp_flops(comps, sub, symbols)
    return total


#: ops whose operand of the output's shape is read where the output is
_ELEMENTWISE = frozenset((
    "abs", "add", "and", "bitcast", "bitcast-convert", "clamp", "compare",
    "convert", "copy", "divide", "exponential", "log", "maximum", "minimum",
    "multiply", "negate", "not", "or", "power", "reshape", "rsqrt",
    "select", "sqrt", "subtract", "tanh", "xor"))


def _lhs_fraction(conv: Instr, lhs_shape: str) -> float:
    """Share of a convolution's input that its window reads: negative
    padding (how XLA writes a row slice into a convolution) cuts rows."""
    m = re.search(r"pad=(\S+)", conv.attrs)
    if not m:
        return 1.0
    labels = re.search(r"dim_labels=(\w+)_", conv.attrs).group(1)
    ((_, dims),) = arrays(lhs_shape) or [(None, ())]
    spatial = [dims[i] for i, ch in enumerate(labels) if ch.isdigit()]
    frac = 1.0
    for size, pads in zip(spatial, m.group(1).split("x")):
        lo, hi = (int(p) for p in re.findall(r"-?\d+", pads))
        frac *= max(0, size + min(lo, 0) + min(hi, 0)) / size
    return frac


def _read_fractions(comps, comp: dict, root_frac: float = 1.0) -> dict:
    """Instruction name -> the share of its output that the rest of the
    computation reads, from the root (read ``root_frac``) back."""
    frac = {}
    for ins in reversed(list(comp.values())):
        if ins.root:
            frac[ins.name] = root_frac
        mine = frac.get(ins.name, 0.0)
        if ins.op == "fusion":
            (sub,) = _called(ins)
            inner = _read_fractions(comps, comps[sub], mine)
            params = {int(i.args): i.name for i in comps[sub].values()
                      if i.op == "parameter"}
            reads = [inner.get(params[k], 0.0)
                     for k in range(len(ins.operands))]
        elif ins.op in _SLICES:
            full = shape_bytes(comp[ins.operands[0]].shape) \
                if ins.operands[0] in comp else 0
            reads = [shape_bytes(ins.shape) / full if full else 1.0] \
                + [1.0] * (len(ins.operands) - 1)
        elif ins.op == "dynamic-update-slice":
            reads = [0.0, mine] + [1.0] * (len(ins.operands) - 2)
        elif ins.op == "convolution":
            lhs = comp[ins.operands[0]].shape if ins.operands[0] in comp \
                else ""
            reads = [_lhs_fraction(ins, lhs) if mine else 0.0, 1.0]
        elif ins.op in _ELEMENTWISE:
            out = [d for _, d in arrays(ins.shape)]
            reads = [mine if o in comp and [d for _, d in arrays(
                         comp[o].shape)] == out else 1.0
                     for o in ins.operands]
        else:
            reads = [1.0] * len(ins.operands)
        for name, r in zip(ins.operands, reads):
            frac[name] = min(1.0, frac.get(name, 0.0) + r)
    return frac


def _fusion_bytes(comps, ins: Instr, comp: dict, symbols: dict) -> int:
    """Bytes a fusion moves: the share of each operand that it reads, and
    its output (the update alone where the root updates in place)."""
    root = next(i for i in comp.values() if i.root)
    if root.op == "dynamic-update-slice":
        total = shape_bytes(comp[root.operands[1]].shape)
    else:
        total = shape_bytes(ins.shape)
    frac = _read_fractions(comps, comp)
    params = {int(i.args): i.name for i in comp.values()
              if i.op == "parameter"}
    for k, name in enumerate(ins.operands):
        share = frac.get(params[k], 1.0) if k in params else 1.0
        total += share * shape_bytes(symbols[name].shape)
    return int(total)


@dataclasses.dataclass(frozen=True)
class ConvOp:
    flops: int
    bytes: int


def conv_ops(text: str) -> dict:
    """Entry-computation op name -> :class:`ConvOp`, for every op that
    runs at least one convolution."""
    comps = parse_module(text)
    symbols = {n: i for comp in comps.values() for n, i in comp.items()}
    out = {}
    for ins in comps["ENTRY"].values():
        if ins.op == "convolution":
            flops = conv_flops(ins, symbols)
            nbytes = shape_bytes(ins.shape) + sum(
                shape_bytes(symbols[o].shape) for o in ins.operands)
        elif ins.op == "fusion":
            (sub,) = _called(ins)
            flops = _comp_flops(comps, sub, symbols)
            nbytes = _fusion_bytes(comps, ins, comps[sub], symbols) if flops else 0
        else:
            continue
        if flops:
            out[ins.name] = ConvOp(flops, nbytes)
    return out
