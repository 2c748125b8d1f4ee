"""The Pallas kernels compile for a TPU v5e at the widths the models use.

Nothing runs: a v5e:2x2 topology is described (the TPU compiler ships
with jaxlib) and each kernel is lowered and compiled for one of its chips
with ``interpret=False``, so Mosaic refuses here what it would refuse on
the chip — unaligned or strided in-kernel slices, unsupported primitives,
more VMEM than a kernel may use.  Each compile takes a second or two.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports every test file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hybrid import SegmentSpec, make_hybrid_apply
from repro.kernels.conv2d_rows import conv2d_rows
from repro.kernels.ssd_chunk import ssd_scan
from repro.kernels.swa_attention import swa_attention
from repro.models.cnn.layers import Conv, ReLU, init_trunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


#: (H, Cin, Cout, k, stride, padding): VGG-16's stride-1 convs at the
#: first, second, third-stage and last-stage widths (224x224 input), and
#: ResNet-50's stride-2 stem
CONV_SHAPES = [
    (224, 3, 64, 3, 1, 1),
    (224, 64, 64, 3, 1, 1),
    (56, 256, 256, 3, 1, 1),
    (14, 512, 512, 3, 1, 1),
    (224, 3, 64, 7, 2, 3),
]


@pytest.mark.parametrize("h,cin,cout,k,s,p", CONV_SHAPES,
                         ids=["x".join(map(str, c)) for c in CONV_SHAPES])
def test_conv2d_rows_compiles_for_v5e(one_chip, h, cin, cout, k, s, p):
    fn = functools.partial(conv2d_rows, stride=s, padding=p, block_h=8,
                           interpret=False)
    text = _compiled_text(fn, one_chip, ((2, h, h, cin), jnp.float32),
                          ((k, k, cin, cout), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_swa_attention_compiles_for_v5e(one_chip, dtype):
    """gemma3_4b's local layers: head dim 256, window 1024."""
    fn = functools.partial(swa_attention, window=1024, bq=128, bk=128,
                           interpret=False)
    qkv = ((1, 8, 2048, 256), dtype)
    text = _compiled_text(fn, one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_ssd_chunk_compiles_for_v5e(one_chip):
    """zamba2_7b's Mamba2 mixer: 32 heads of P=224, state N=64."""
    bt, s, h, p, n = 1, 1024, 32, 224, 64
    fn = functools.partial(ssd_scan, chunk=128, interpret=False)
    f32 = jnp.float32
    text = _compiled_text(fn, one_chip, ((bt, s, h, p), f32),
                          ((bt, s, n), f32), ((bt, s, n), f32),
                          ((bt, s, h), f32), ((bt, s, h), f32))
    assert "tpu_custom_call" in text


def test_row_input_gradients_keep_their_window(one_chip):
    """A 2PS row's input gradient is padded to the segment's height
    behind a barrier.  Without it the TPU pipeline folds the pad into the
    convolution that makes the gradient, which then runs at the
    segment's full height (window padding up to 22_4 here): no 3x3
    convolution of the compiled backward may pad by more than k - 1."""
    mods = [Conv(128), ReLU(), Conv(128), ReLU()] * 2
    params, _ = init_trunk(mods, jax.random.PRNGKey(0), (28, 28, 128))
    apply = make_hybrid_apply(mods, 28, [SegmentSpec(0, 4, 7, "twophase"),
                                         SegmentSpec(4, 8, 7, "twophase")])
    shapes = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        params)
    x = jax.ShapeDtypeStruct((8, 28, 28, 128), jnp.float32,
                             sharding=one_chip)
    text = jax.jit(jax.grad(lambda p, x: apply(p, x).sum())).lower(
        shapes, x).compile().as_text()
    pads = re.findall(r" convolution\(.*window=\{size=3x3 pad=([\d_x-]+)",
                      text)
    assert pads
    for pad in pads:
        assert max(int(p) for p in re.findall(r"-?\d+", pad)) <= 2, pad


def test_depthwise_conv_forms_count_their_flops(one_chip):
    """A 7x7 depthwise conv's forward, input gradient and kernel gradient
    compile to three convolutions, the last with ``batch_group_count=C``
    and an output batch of one; ``bench/hlo.py`` counts each at the
    ``2 N H W k^2 C`` FLOPs it needs (XLA:CPU instead rewrites the kernel
    gradient as a full C x C convolution, C times the work)."""
    from bench import hlo

    n, h, c, k = 2, 16, 8, 7
    conv = Conv(c, k=k, p=k // 2, bias=False, groups=c)
    text = _compiled_text(
        jax.grad(lambda w, x: jnp.sum(conv.apply({"w": w}, x) ** 2),
                 argnums=(0, 1)),
        one_chip, ((k, k, 1, c), jnp.float32), ((n, h, h, c), jnp.float32))
    assert re.search(r"convolution\(.*batch_group_count=8", text)
    assert len(re.findall(r"convolution\(.*feature_group_count=8", text)) == 2
    ops = hlo.conv_ops(text)
    assert [op.flops for op in ops.values()] == [2 * n * h * h * k * k * c] * 3
