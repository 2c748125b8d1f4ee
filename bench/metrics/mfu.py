"""mfu: the whole training step's share of the chips' bf16 peak.

Model FLOPs per image (``bench/flops.py``: forward, dX and dW of every
convolution and the linear head, no recompute) times the images per
second of the run's untraced window, over chips times the peak.  The
peak is bf16 because the configurations run float32 convolutions at the
default precision, one bf16 pass of the MXU."""


def read(ctx):
    return (100.0 * ctx.model_flops_per_image * ctx.images_per_s
            / (ctx.chips * ctx.peak["bf16_flops_per_s"]))
