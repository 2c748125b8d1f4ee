"""Row-block direct convolution — LR-CNN's row partitioning as VMEM tiling.

TPU adaptation (DESIGN.md §3): the paper partitions feature maps into rows
so limited memory is reused across rows; on TPU the scarce memory is VMEM,
so the same idea becomes the BlockSpec tiling of a Pallas kernel.  The grid
walks (batch, output-row-blocks); each step fetches the input row-block
*plus its receptive-field halo* into VMEM — OverL semantics: replicated
reads, fully independent blocks (2PS's sequential cache maps poorly onto a
systolic grid; see DESIGN.md).

Halo mechanics: overlapping input blocks are not expressible with a single
blocked index_map, so the kernel takes the SAME input array through TWO
in_specs whose index maps point at consecutive row blocks ("dual-block
fetch"); the kernel concatenates them and slices the halo it needs.  Valid
whenever halo (k - s) <= block_h * s, which the wrapper enforces.

Strides: the wrapper splits the padded input into its ``s * s`` stride
phases outside the kernel — phase ``(pi, pj)`` holds pixels
``(pi + s*i, pj + s*j)`` — so tap ``(ki, kj)`` of every output row block
reads phase ``(ki % s, kj % s)`` at offset ``(ki // s, kj // s)`` with unit
stride.  The TPU vector unit supports no strided in-kernel slice; with the
phases outside, stride 1 and stride 2 run the same kernel.

The MUL-SUM accumulation runs as kh*kw dot_generals of shape
(block_h * W_out, Cin) x (Cin, Cout) — MXU-shaped matmuls; W_out*Cout and
Cin should be multiples of (8,128) for full MXU utilisation (the wrapper's
``good_tiling`` reports this).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _conv_kernel(x0_ref, x1_ref, w_ref, o_ref, *, kh, kw, stride, block_h,
                 w_out):
    """One (batch, row-block) grid step.

    x0/x1: (1, block_h, stride**2, W_phase, Cin) consecutive row blocks of
    the phase-split input.  w: (kh, kw, Cin, Cout).
    o: (1, block_h, W_out, Cout).
    """
    x = jnp.concatenate([x0_ref[0], x1_ref[0]], axis=0)
    cin = x.shape[-1]
    cout = w_ref.shape[-1]
    acc = jnp.zeros((block_h, w_out, cout), jnp.float32)
    for ki in range(kh):
        qi, pi = divmod(ki, stride)
        for kj in range(kw):
            qj, pj = divmod(kj, stride)
            rows = x[qi:qi + block_h, pi * stride + pj,
                     qj:qj + w_out, :]                  # (block_h, w_out, Cin)
            wk = w_ref[ki, kj]                          # (Cin, Cout)
            acc += jax.lax.dot_general(
                rows.reshape(block_h * w_out, cin), wk,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).reshape(block_h, w_out, cout)
    o_ref[0] = acc.astype(o_ref.dtype)


#: Mosaic's scoped-VMEM limit for one grid step.  The default (16 MiB)
#: is too small for a 224-wide row block of 8 rows at HIGHEST matmul
#: precision (17.3 MiB on a v5e, whose VMEM holds 128 MiB).
VMEM_LIMIT_BYTES = 64 * 2**20


def halo_ok(k: int, stride: int, block_h: int,
            h_out: int | None = None) -> bool:
    """The dual-block fetch precondition: the receptive-field halo
    ``k - stride`` must fit inside one input row block, i.e.
    ``(k - stride) <= block_h * stride``.  Pass ``h_out`` to apply the
    wrapper's block clamp (``block_h = min(block_h, H_out)``) first —
    that is the block the kernel actually launches with."""
    if h_out is not None:
        block_h = min(block_h, h_out)
    return (k - stride) <= block_h * stride


def conv2d_rows(x, w, *, stride: int = 1, padding: int = 0,
                block_h: int = 8, interpret: Optional[bool] = None):
    """NHWC x HWIO -> NHWC convolution with row-block VMEM tiling.

    ``interpret=None`` compiles on a TPU and runs the Pallas interpreter
    elsewhere (:func:`repro.kernels.resolve_interpret`).
    """
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    s = stride
    H_out = (H + 2 * padding - kh) // s + 1
    W_out = (W + 2 * padding - kw) // s + 1
    block_h = min(block_h, H_out)
    assert halo_ok(kh, s, block_h), (
        f"halo {kh - s} exceeds row block {block_h * s}; increase block_h")
    n_blocks = -(-H_out // block_h)
    # phase rows: every block and its +1 neighbour exist; phase columns
    # cover the padded width.  The halo bound keeps both pads >= 0.
    h_ph = (n_blocks + 1) * block_h
    w_ph = -(-(W + 2 * padding) // s)
    x = jnp.pad(x, ((0, 0), (padding, h_ph * s - H - padding),
                    (padding, w_ph * s - W - padding), (0, 0)))
    x = x.reshape(B, h_ph, s, w_ph, s, Cin).transpose(0, 1, 2, 4, 3, 5) \
        .reshape(B, h_ph, s * s, w_ph, Cin)
    pad_out = n_blocks * block_h - H_out

    kernel = functools.partial(_conv_kernel, kh=kh, kw=kw, stride=s,
                               block_h=block_h, w_out=W_out)
    block = (1, block_h, s * s, w_ph, Cin)
    out = pl.pallas_call(
        kernel,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec(block, lambda b, i: (b, i, 0, 0, 0)),
            pl.BlockSpec(block, lambda b, i: (b, i + 1, 0, 0, 0)),
            pl.BlockSpec((kh, kw, Cin, Cout), lambda b, i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_h, W_out, Cout),
                               lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_blocks * block_h, W_out, Cout),
                                       x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name="conv2d_rows",
    )(x, x, w)
    if pad_out:
        out = out[:, :H_out]
    return out


def vmem_bytes(block_h: int, stride: int, w_in: int, cin: int, w_out: int,
               cout: int, kh: int, kw: int, dtype_bytes: int = 4) -> int:
    """Working-set estimate for the BlockSpec above (2 input blocks +
    weights + acc + out block)."""
    in_blk = block_h * stride * w_in * cin * dtype_bytes
    return (2 * in_blk
            + kh * kw * cin * cout * dtype_bytes
            + block_h * w_out * cout * 4        # fp32 acc
            + block_h * w_out * cout * dtype_bytes)


def good_tiling(cin: int, cout: int) -> bool:
    """MXU alignment check: contraction and output minor dims should be
    multiples of (8, 128)."""
    return cin % 8 == 0 and cout % 128 == 0
