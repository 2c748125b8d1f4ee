"""jit'd public wrappers for the Pallas kernels.

Interpret-mode policy is plan-carried, not a module constant: engines pass
``KernelSpec.interpret`` down explicitly, and standalone callers (tests,
benchmarks) leave ``interpret=None`` to get the platform default — the
Pallas interpreter on every backend except a TPU
(:func:`repro.kernels.resolve_interpret`).  CPU CI and TPU runs therefore
share one code path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import resolve_interpret
from repro.kernels.conv2d_rows import conv2d_rows as _conv2d_rows
from repro.kernels.ssd_chunk import ssd_scan as _ssd
from repro.kernels.swa_attention import swa_attention as _swa


#: deterministic tile search spaces, largest first — bigger tiles amortize
#: grid-step dispatch, so enumeration order doubles as the tie-break order
#: for both Planner.kernelize and Planner.autotune_kernel
CONV_BLOCK_HS = (32, 16, 8, 4, 2, 1)
SWA_BLOCKS = (256, 128, 64, 32, 16, 8)
SSD_CHUNKS = (256, 128, 64, 32, 16, 8)


def candidate_tiles(kind: str, *, h_out: int = 0, seq: int = 0) -> tuple:
    """The ONE deterministic tile-candidate enumeration shared by
    ``Planner.kernelize`` and ``Planner.autotune_kernel``: a tuple of
    KernelSpec field dicts, in search/tie-break order.

    ``kind``: ``"conv"`` yields ``{"block_h"}`` candidates (clamped to
    ``h_out`` when given, deduped preserving order); ``"swa"`` yields
    ``{"bq", "bk"}`` pairs satisfying the kernel's divisibility contract
    against ``seq`` (``seq % bq == seq % bk == bq % bk == 0, bk <= bq``);
    ``"ssd"`` yields ``{"chunk"}`` divisors of ``seq``.  Geometry only —
    VMEM/alignment feasibility stays with the planner's pricers.
    """
    if kind == "conv":
        out, seen = [], set()
        for b in CONV_BLOCK_HS:
            b = min(b, h_out) if h_out else b
            if b >= 1 and b not in seen:
                seen.add(b)
                out.append({"block_h": b})
        return tuple(out)
    if kind == "swa":
        out = []
        for bq in SWA_BLOCKS:
            if seq and (bq > seq or seq % bq):
                continue
            for bk in SWA_BLOCKS:
                if bk > bq or bq % bk:
                    continue
                if seq and seq % bk:
                    continue
                out.append({"bq": bq, "bk": bk})
        return tuple(out)
    if kind == "ssd":
        return tuple({"chunk": c} for c in SSD_CHUNKS
                     if not seq or (c <= seq and seq % c == 0))
    raise ValueError(f"unknown tile kind {kind!r}; "
                     f"known: 'conv', 'swa', 'ssd'")


@functools.partial(jax.jit, static_argnames=("stride", "padding", "block_h",
                                             "interpret"))
def _conv2d(x, w, stride, padding, block_h, interpret):
    return _conv2d_rows(x, w, stride=stride, padding=padding,
                        block_h=block_h, interpret=interpret)


def conv2d(x, w, stride: int = 1, padding: int = 0, block_h: int = 8,
           interpret: Optional[bool] = None):
    return _conv2d(x, w, stride, padding, block_h,
                   resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk",
                                             "interpret"))
def _swa_jit(q, k, v, window, bq, bk, interpret):
    return _swa(q, k, v, window=window, bq=bq, bk=bk, interpret=interpret)


def swa_attention(q, k, v, window: int, bq: int = 128, bk: int = 128,
                  interpret: Optional[bool] = None):
    return _swa_jit(q, k, v, window, bq, bk, resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_jit(x, B, C, a, dt, chunk, interpret):
    return _ssd(x, B, C, a, dt, chunk=chunk, interpret=interpret)


def ssd_scan(x, B, C, a, dt, chunk: int = 128,
             interpret: Optional[bool] = None):
    return _ssd_jit(x, B, C, a, dt, chunk, resolve_interpret(interpret))
