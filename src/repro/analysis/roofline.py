"""Three-term roofline analysis from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / peak_FLOP/s            (per-chip program)
  memory term     = HLO_bytes / HBM_bw                 (per-chip program)
  collective term = collective_bytes / link_bw          (per-chip program)

``compiled.cost_analysis()`` reports the *partitioned per-device* program,
so terms are per-chip seconds directly (the brief's "/(chips x ...)" with
global numbers is the same quantity).  collective_bytes is not in
cost_analysis: we parse the optimized HLO and sum the result-buffer sizes
of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops.

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) per training step
(3x fwd matmul flops 2·N·D for fwd+bwd); for decode, 2·N·D per token.
The ratio MODEL_FLOPS / HLO_FLOPs measures how much compiled compute is
"useful" (catches remat/redundancy waste).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-buffer bytes per collective kind from optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w\.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", line)
        if not m:
            continue
        op = m.group(2)
        for kind in _COLLECTIVES:
            # all-gather-start / all-reduce-scatter etc. count once
            if op == kind or op.startswith(kind + "-start"):
                out[kind] += _shape_bytes(m.group(1))
                break
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    hlo_flops: float            # per-chip
    hlo_bytes: float            # per-chip
    coll_bytes: float           # per-chip
    coll_detail: Dict[str, int]
    model_flops_global: float
    temp_bytes: int
    arg_bytes: int
    out_bytes: int

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / (global HLO flops)."""
        total = self.hlo_flops * self.n_chips
        return self.model_flops_global / total if total else 0.0

    def as_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "n_chips": self.n_chips,
            "hlo_flops_per_chip": self.hlo_flops,
            "hlo_bytes_per_chip": self.hlo_bytes,
            "coll_bytes_per_chip": self.coll_bytes,
            "coll_detail": self.coll_detail,
            "model_flops_global": self.model_flops_global,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_ratio,
            "temp_bytes_per_chip": self.temp_bytes,
            "arg_bytes_per_chip": self.arg_bytes,
            "out_bytes_per_chip": self.out_bytes,
        }


def model_flops(cfg, shape) -> float:
    """6·N·D for train (N = active params), 2·N·D for prefill,
    2·N per token for decode."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.batch  # one token per sequence


def analyze(compiled, hlo_text: str, cfg, shape, mesh_name: str,
            n_chips: int) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    mem = compiled.memory_analysis()
    temp = mem.temp_size_in_bytes
    args = mem.argument_size_in_bytes
    outs = mem.output_size_in_bytes
    return Roofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, n_chips=n_chips,
        hlo_flops=flops, hlo_bytes=byts,
        coll_bytes=float(sum(coll.values())), coll_detail=coll,
        model_flops_global=model_flops(cfg, shape),
        temp_bytes=temp, arg_bytes=args, out_bytes=outs)
