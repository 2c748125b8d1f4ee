"""dwconv_roofline: the device time of ConvNeXt's depthwise convolutions
against the least time the chip could take for them.

Over the traced window's ops whose innermost scope is ``dwconv``
(``bench/layer_scopes.py``) and whose HLO holds a convolution
(``bench/hlo.py``): the sum of each run's least time, the larger of its
FLOPs over the bf16 peak and its bytes over the HBM bandwidth, over the
sum of its device time, in percent.  ``hlo.conv_ops`` counts the three
forms the TPU compiler writes for a depthwise convolution at
``2 * N * H * W * k^2 * C`` FLOPs each: the forward and the input
gradient with ``feature_group_count=C`` and a kernel of one input channel,
the kernel gradient with ``batch_group_count=C`` and an output batch of
one."""

from bench import layer_scopes


def read(ctx):
    if ctx.trace is None:
        return None
    ops = layer_scopes.innermost(ctx, "dwconv")
    peak, bw = ctx.peak["bf16_flops_per_s"], ctx.peak["hbm_bytes_per_s"]
    least = spent = 0.0
    lo, hi = ctx.trace.window
    for run in ctx.trace.ops:
        for start, end, name in run:
            op = ctx.conv_ops.get(name)
            if op is None or name not in ops or start < lo or end > hi:
                continue
            least += max(op.flops / peak, op.bytes / bw)
            spent += end - start
    return 100.0 * least / spent if spent else None
