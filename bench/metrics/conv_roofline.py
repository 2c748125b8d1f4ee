"""conv_roofline: the device time of the ops that run a convolution
against the least time the chip could take for them.

Over the traced window's ops whose HLO holds a convolution
(``bench/hlo.py``): the sum of each run's least time, the larger of its
FLOPs over the bf16 peak and its bytes over the HBM bandwidth, over the
sum of its device time, in percent."""


def read(ctx):
    if ctx.trace is None:
        return None
    peak, bw = ctx.peak["bf16_flops_per_s"], ctx.peak["hbm_bytes_per_s"]
    least = spent = 0.0
    lo, hi = ctx.trace.window
    for ops in ctx.trace.ops:
        for start, end, name in ops:
            op = ctx.conv_ops.get(name)
            if op is None or start < lo or end > hi:
                continue
            least += max(op.flops / peak, op.bytes / bw)
            spent += end - start
    return 100.0 * least / spent if spent else None
