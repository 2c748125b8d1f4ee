"""The HLO shape parser behind conv_roofline."""

from pathlib import Path

import pytest

from bench import hlo

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def tpu_ops():
    # the optimized HLO of a three-conv SGD step (batch 64, 112x112x64,
    # 3x3 kernels) compiled for a TPU v5e
    return hlo.conv_ops((DATA / "tpu_conv3.hlo.txt").read_text())


def test_forward_conv_fusion(tpu_ops):
    # %fusion.44 = f32[64,112,112,64] fusion(bf16[64,112,112,64] %copy.1,
    #     f32[3,3,64,64]{...S(1)} %copy-done.3), calls=%fused_computation.49
    # the kernel was copied into VMEM (memory space S(1)) before it ran,
    # so only the input and the output move through HBM
    op = tpu_ops["fusion.44"]
    assert op.flops == 2 * 64 * 112 * 112 * 64 * 3 * 3 * 64
    assert op.bytes == 64 * 112 * 112 * 64 * 2 + 64 * 112 * 112 * 64 * 4


def test_step_counts_every_conv(tpu_ops):
    # 3 forward convs, 2 input gradients (none for the first layer) and
    # 3 kernel gradients, each 2 * 64*112*112 * 9 * 64 * 64 FLOPs
    one = 2 * 64 * 112 * 112 * 9 * 64 * 64
    assert sum(op.flops for op in tpu_ops.values()) == 8 * one
    # a kernel-gradient fusion: the "kernel" is the 112x112 output grad
    assert tpu_ops["multiply_subtract_fusion.1"].flops == one


SLICED = """\
HloModule m

%fused_computation (param_0: f32[8,64,64,16], param_1: f32[3,3,16,16]) -> f32[8,8,64,16] {
  %param_0 = f32[8,64,64,16]{3,2,1,0} parameter(0)
  %slice.1 = f32[8,10,64,16]{3,2,1,0} slice(%param_0), slice={[0:8], [0:10], [0:64], [0:16]}
  %param_1 = f32[3,3,16,16]{3,2,1,0} parameter(1)
  ROOT %convolution.1 = f32[8,8,64,16]{3,2,1,0} convolution(%slice.1, %param_1), window={size=3x3 pad=0_0x1_1}, dim_labels=b01f_01io->b01f
}

%fused_update (param_0.1: f32[8,64,64,16], param_1.1: f32[8,8,64,16], param_2.1: f32[3,3,16,16], param_3: s32[]) -> f32[8,64,64,16] {
  %param_0.1 = f32[8,64,64,16]{3,2,1,0} parameter(0)
  %param_1.1 = f32[8,8,64,16]{3,2,1,0} parameter(1)
  %param_2.1 = f32[3,3,16,16]{3,2,1,0} parameter(2)
  %convolution.2 = f32[8,8,64,16]{3,2,1,0} convolution(%param_1.1, %param_2.1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
  %param_3 = s32[] parameter(3)
  %zero = s32[] constant(0)
  ROOT %dynamic-update-slice.1 = f32[8,64,64,16]{3,2,1,0} dynamic-update-slice(%param_0.1, %convolution.2, %zero, %param_3, %zero, %zero)
}

ENTRY %main (x: f32[8,64,64,16], w: f32[3,3,16,16], i: s32[]) -> f32[8,64,64,16] {
  %x = f32[8,64,64,16]{3,2,1,0} parameter(0)
  %w = f32[3,3,16,16]{3,2,1,0} parameter(1)
  %i = s32[] parameter(2)
  %fusion = f32[8,8,64,16]{3,2,1,0} fusion(%x, %w), kind=kOutput, calls=%fused_computation
  ROOT %fusion.1 = f32[8,64,64,16]{3,2,1,0} fusion(%x, %fusion, %w, %i), kind=kOutput, calls=%fused_update
}
"""


def test_row_slices_count_what_they_read():
    ops = hlo.conv_ops(SLICED)
    row_in, row_out, w = 8 * 10 * 64 * 16 * 4, 8 * 8 * 64 * 16 * 4, 9 * 256 * 4
    assert ops["fusion"].flops == 2 * 8 * 8 * 64 * 16 * 9 * 16
    # reads one 10-row slice of x, not all 64 rows
    assert ops["fusion"].bytes == row_in + w + row_out
    # writes its 8 rows in place into x: the update, not the whole buffer
    assert ops["fusion.1"].bytes == row_out + row_out + w + 4


def test_shape_bytes_count_hbm_arrays_of_tuples():
    assert hlo.shape_bytes("(f32[2,3]{1,0}, bf16[4]{0}, u32[]{:S(2)})") \
        == 24 + 8
    assert hlo.shape_bytes("f32[8,128]{1,0:T(8,128)S(1)}") == 0
    assert hlo.shape_bytes("f32[8,128]{1,0:T(8,128)}") == 4096


CUT = """\
HloModule m

%convert (p: f32[8,224,224,3]) -> bf16[8,224,224,3] {
  %p = f32[8,224,224,3]{3,2,1,0} parameter(0)
  ROOT %c = bf16[8,224,224,3]{3,2,1,0} convert(%p)
}

%kernel_grad (x: f32[8,224,224,3], g: f32[8,28,224,64]) -> f32[3,3,3,64] {
  %x = f32[8,224,224,3]{3,2,1,0} parameter(0)
  %xb = bf16[8,224,224,3]{3,2,1,0} fusion(%x), kind=kLoop, calls=%convert
  %g = f32[8,28,224,64]{3,2,1,0} parameter(1)
  ROOT %dw = f32[3,3,3,64]{3,2,1,0} convolution(%xb, %g), window={size=28x224 pad=-30_-164x1_1}, dim_labels=f01b_i01o->01bf
}

ENTRY %main (x: f32[8,224,224,3], g: f32[8,28,224,64]) -> f32[3,3,3,64] {
  %x = f32[8,224,224,3]{3,2,1,0} parameter(0)
  %g = f32[8,28,224,64]{3,2,1,0} parameter(1)
  ROOT %fusion = f32[3,3,3,64]{3,2,1,0} fusion(%x, %g), kind=kOutput, calls=%kernel_grad
}
"""


def test_negative_padding_cuts_what_a_conv_reads():
    # a row's kernel gradient: the padding -30/-164 keeps input rows
    # 30..60 of 224, read through a nested convert
    op = hlo.conv_ops(CUT)["fusion"]
    x_rows = 8 * 30 * 224 * 3 * 4
    assert op.bytes == x_rows + 8 * 28 * 224 * 64 * 4 + 9 * 3 * 64 * 4
    assert op.flops == 2 * (3 * 3 * 3 * 64) * (28 * 224) * 8
