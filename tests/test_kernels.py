"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracles
(interpret=True executes the kernel body on CPU).

The case tables live in tests/conftest.py (``conv_case`` / ``swa_case`` /
``ssd_case`` fixtures) and are shared with the engine-level parity tier in
tests/test_pallas_engines.py, so kernel- and engine-level coverage can
never drift apart."""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.conv2d_rows import good_tiling, halo_ok, vmem_bytes

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_rows_allclose(conv_case, dtype):
    H, W, Cin, Cout, k, s, p, bh = conv_case
    x = jax.random.normal(KEY, (2, H, W, Cin)).astype(dtype)
    w = (jax.random.normal(jax.random.PRNGKey(1), (k, k, Cin, Cout))
         * 0.1).astype(dtype)
    got = ops.conv2d(x, w, stride=s, padding=p, block_h=bh)
    want = ref.conv2d_ref(x, w, stride=s, padding=p)
    assert got.shape == want.shape
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    assert jnp.allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                        atol=tol, rtol=tol), float(
        jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_attention_allclose(swa_case, dtype):
    S, D, window, bq, bk = swa_case
    q = jax.random.normal(KEY, (2, 2, S, D)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 2, S, D)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(3), (2, 2, S, D)).astype(dtype)
    got = ops.swa_attention(q, k, v, window=window, bq=bq, bk=bk)
    want = ref.swa_attention_ref(q, k, v, window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert jnp.allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                        atol=tol, rtol=tol)


def test_ssd_scan_allclose(ssd_case):
    Bt, S, H, P, N, chunk = ssd_case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bt, S, H, P)) * 0.5
    B = jax.random.normal(ks[1], (Bt, S, N)) * 0.5
    C = jax.random.normal(ks[2], (Bt, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (Bt, S, H)))
    a = jnp.exp(-dt * jnp.exp(jax.random.normal(ks[4], (Bt, S, H)) * 0.1))
    got = ops.ssd_scan(x, B, C, a, dt, chunk=chunk)
    want, _ = ref.ssd_scan_ref(x, B, C, a, dt)
    assert jnp.allclose(got, want, atol=1e-3), float(
        jnp.abs(got - want).max())


def test_ssd_vmem_budget():
    from repro.kernels.ssd_chunk import vmem_bytes as ssd_vmem
    assert ssd_vmem(128, 64, 64) < 16 * 2**20


def test_vmem_budget():
    """The default tiling's working set must fit a 16 MiB VMEM target for
    paper-scale layers (224x224x64, 3x3)."""
    b = vmem_bytes(block_h=8, stride=1, w_in=224, cin=64, w_out=224,
                   cout=64, kh=3, kw=3)
    assert b < 16 * 2**20, b


def test_mxu_alignment_helper():
    assert good_tiling(64, 128)
    assert not good_tiling(3, 64)


def test_halo_precondition_helper():
    # 3x3 stride-1 conv: halo 2 needs a block of at least 2 rows
    assert halo_ok(3, 1, 2)
    assert not halo_ok(3, 1, 1)
    # the wrapper's block clamp applies first: a tall block on a short
    # output is really min(block_h, h_out) rows
    assert halo_ok(3, 1, 16, h_out=8)
    assert not halo_ok(7, 1, 16, h_out=4)
    # stride shrinks the halo and widens the input block
    assert halo_ok(7, 2, 4)
