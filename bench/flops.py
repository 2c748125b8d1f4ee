"""Model FLOPs of one training step, from a configuration's shapes.

Each convolution counts ``2 * H_out * W_out * K^2 * C_in * C_out`` per
image, padded taps included, once for the forward pass, once for the
input gradient (dX) and once for the kernel gradient (dW); the first
layer has no dX, since nothing upstream wants it.  A linear layer counts
``2 * C_in * C_out`` three times likewise.  Pools, activations and
normalisation are not counted, and neither is any recompute that an
execution plan adds: this is the work the model needs, not the work a
plan does.
"""

from __future__ import annotations


def conv_macs(layer: dict) -> int:
    """Multiply-adds of one convolution's forward pass, per image."""
    return (layer["h_out"] * layer["w_out"] * layer["k"] ** 2
            * layer["cin"] * layer["cout"])


def forward_conv_macs(layers) -> int:
    return sum(conv_macs(l) for l in layers)


def train_flops_per_image(layers, linear) -> int:
    """Forward + dX + dW FLOPs of a step, per image.  ``layers`` in
    order (the first one gets no dX); ``linear`` is ``(C_in, C_out)``."""
    total = 0
    for i, l in enumerate(layers):
        total += 2 * conv_macs(l) * (2 if i == 0 else 3)
    c_in, c_out = linear
    return total + 2 * c_in * c_out * 3
