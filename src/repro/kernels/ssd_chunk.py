"""Mamba2 SSD chunked-scan kernel — the SSM-family hot spot, Pallas/TPU.

LR-CNN mapping: the chunk axis is the sequence "row"; the carried state
h (P, N) of one head is the 2PS boundary cache, living in VMEM scratch
across the sequential chunk grid dimension (TPU grids iterate the last
axis sequentially, so the scratch persists chunk-to-chunk — a
hardware-native 2PS carry).  The grid is (batch, head, chunk), so every
grid step works on 2-D tiles only.

Per chunk of one head (all in VMEM; t, s index chunk positions):
  L_t   = cumsum(log a_t), as a lower-triangular matmul (Mosaic has no
          cumsum lowering)
  intra: y_t += Σ_{s<=t} (C_t·B_s) e^{L_t-L_s} dt_s x_s  — the (c, c)
         decay-masked Gram matrix times x on the MXU
  carry: y_t += e^{L_t} C_t · h_in
  state: h_out = h_in·e^{L_c} + Σ_s x_s dt_s e^{L_c - L_s} ⊗ B_s

The wrapper hands the per-head gates ``a``/``dt`` in as both a column
(c, 2) and a row (2, c) tile, so no in-kernel transpose is needed.
Working set ~ 5·c² + c·(2P + 2N + 4) + P·N floats; c=128, P=224, N=64 ->
~0.6 MB per grid step.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_HI = jax.lax.Precision.HIGHEST


def _dot(x, y, contract=((1,), (0,))):
    return jax.lax.dot_general(x, y, (contract, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, b_ref, c_ref, gcol_ref, grow_ref, o_ref, h_scr):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)        # (c, P)
    B = b_ref[0].astype(jnp.float32)           # (c, N)
    C = c_ref[0].astype(jnp.float32)           # (c, N)
    gcol = gcol_ref[0, 0].astype(jnp.float32)  # (c, 2): [a, dt] columns
    grow = grow_ref[0, 0].astype(jnp.float32)  # (2, c): [a, dt] rows
    c = x.shape[0]

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    causal = s_idx <= t_idx                              # [t, s]
    tril = causal.astype(jnp.float32)
    # inclusive cumsums of log a, as a column (tril @ la) and as a row
    # (la @ tril^T); the gates' column and row tiles give both layouts
    la_col = jnp.log(gcol[:, 0:1] + 1e-12)               # (c, 1)
    la_row = jnp.log(grow[0:1, :] + 1e-12)               # (1, c)
    cum_col = _dot(tril, la_col)                         # (c, 1) L_t
    cum_row = _dot(la_row, tril, ((1,), (1,)))           # (1, c) L_s
    dt_row = grow[1:2, :]                                # (1, c)
    dt_col = gcol[:, 1:2]                                # (c, 1)

    decay = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)  # (t, s)
    cb = _dot(C, B, ((1,), (1,)))                        # (t, s)
    y = _dot(cb * decay * dt_row, x)                     # (c, P)
    h_in = h_scr[...]                                    # (P, N)
    y = y + jnp.exp(cum_col) * _dot(C, h_in, ((1,), (1,)))
    cum_last = cum_col[c - 1:c, :]                       # (1, 1)
    coef = dt_col * jnp.exp(cum_last - cum_col)          # (s, 1)
    # widen (1, 1) along lanes first: Mosaic broadcasts one axis at a time
    state_decay = jnp.exp(jnp.broadcast_to(cum_last, (1, h_in.shape[1])))
    h_scr[...] = h_in * state_decay \
        + _dot(x * coef, B, ((0,), (0,)))                # (P, N)
    o_ref[0, 0] = y.astype(o_ref.dtype)


def ssd_scan(x, B, C, a, dt, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    """x: (Bt, S, H, P); B/C: (Bt, S, N); a/dt: (Bt, S, H) -> y like x.

    Exact SSD recurrence  h_t = a_t h_{t-1} + dt_t·x_t⊗B_t ;  y_t = C_t·h_t.
    ``interpret=None`` compiles on a TPU and interprets elsewhere.
    """
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    xh = x.transpose(0, 2, 1, 3)                         # (Bt, H, S, P)
    gates = jnp.stack([a, dt], axis=-1).transpose(0, 2, 1, 3)  # (Bt,H,S,2)
    out = pl.pallas_call(
        _ssd_kernel,
        grid=(Bt, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, i: (b, i, 0)),
            pl.BlockSpec((1, 1, chunk, 2), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 2, chunk), lambda b, h, i: (b, h, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bt, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
        name="ssd_chunk",
    )(xh, B, C, gates, gates.transpose(0, 1, 3, 2))
    return out.transpose(0, 2, 1, 3)


def vmem_bytes(chunk: int, p: int, n: int) -> int:
    """Per-grid-step working set of one head's chunk (the head is a grid
    axis, so the head count does not scale the tile)."""
    return 4 * (5 * chunk * chunk              # causal/tril, decay, cb, scores
                + 2 * chunk * p                # x, y
                + 2 * chunk * n + 4 * chunk    # B, C, gate column + row
                + p * n)                       # state scratch
