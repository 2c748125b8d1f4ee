"""Sliding-window flash attention (forward) — Pallas, TPU target.

Gemma3's local layers and the long_500k path.  Grid: (B*H, n_q_blocks,
n_kv_blocks_per_q); the kv dimension is the innermost (sequential on TPU),
carrying the online-softmax state (m, l, acc) in VMEM scratch across kv
steps — the standard flash pattern, with the kv index map offset so each
query block only visits the kv blocks inside its causal sliding window:
the window IS the LR-CNN halo (OverL), realised at BlockSpec level.

VMEM working set: q block (bq x D) + kv block (bk x D) x 2 + acc (bq x D)
+ scores (bq x bk) — all f32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                bq, bk, n_kv, window, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0].astype(jnp.float32)

    # global positions for masking
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    # visited kv span ENDS at the q block end (diagonal block is the last)
    kv_start = qi * bq + bq - (n_kv - ki) * bk
    k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    ok = (k_pos >= 0) & (k_pos <= q_pos)
    if window > 0:
        ok &= k_pos > (q_pos - window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _final():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


def vmem_bytes(bq: int, bk: int, d: int) -> int:
    """Working-set estimate for one grid step: q block + kv blocks +
    acc scratch + scores, plus the (m, l) online-softmax rows — all f32
    (matches the VMEM note in the module docstring)."""
    return 4 * (bq * d + 2 * bk * d + bq * d + bq * bk + 2 * bq)


def swa_attention(q, k, v, *, window: int, bq: int = 128, bk: int = 128,
                  interpret: Optional[bool] = None):
    """q/k/v: (B, H, S, D) -> (B, H, S, D); causal sliding-window.
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    B, H, S, D = q.shape
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    assert bk <= bq, "kv block must not exceed q block (index-map bound)"
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)

    assert bq % bk == 0, "q block must be a multiple of the kv block"
    # kv blocks each query block must visit, ending at the q block end:
    # window lookback + the diagonal blocks
    if window > 0:
        n_kv = min(-(-(bq + window) // bk), S // bk)
    else:
        n_kv = S // bk
    n_q = S // bq
    # front-pad kv so negative (pre-sequence) block indices resolve to
    # zero blocks; the position mask kills their contribution
    pad_front = max(0, n_kv * bk - bq)
    kp = jnp.pad(kf, ((0, 0), (pad_front, 0), (0, 0)))
    vp = jnp.pad(vf, ((0, 0), (pad_front, 0), (0, 0)))

    def kv_index(b, i, j):
        # padded block idx of visit j for q block i:
        # unpadded start = i*bq + bq - (n_kv - j)*bk ; + pad_front
        return (b, (i * bq) // bk + j, 0)

    kernel = functools.partial(_swa_kernel, bq=bq, bk=bk, n_kv=n_kv,
                               window=window, scale=1.0 / (D ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        name="swa_attention",
    )(qf, kp, vp)
    return out.reshape(B, H, S, D)
