"""ConvNeXt-T (Liu et al., "A ConvNet for the 2020s", CVPR 2022).

Trunk modules: a stem (4x4/4 conv, LayerNorm) as one :class:`Chain`, then
four stages of :class:`ConvNeXtBlock` at depths (3, 3, 9, 3) and widths
(96, 192, 384, 768), each stage after the first opened by a downsampling
:class:`Chain` (LayerNorm, 2x2/2 conv): 22 row-engine modules.  The head
is global average pooling, LayerNorm and one linear layer.

Layer scale gamma starts at the published 1e-6 unless ``layer_scale``
says otherwise (the trainer's ``--layer-scale-init``).  Departures from the
published model: no stochastic depth (the row engines' reference cannot
draw the same masks), and the repo's He-normal init in place of the
published truncated normal (std 0.02).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from repro.models.cnn.layers import (
    Chain, Conv, ConvNeXtBlock, LayerNorm2d, init_trunk,
)

_WIDTHS = (96, 192, 384, 768)
_DEPTHS = (3, 3, 9, 3)


def convnext_modules(width_mult: float = 1.0,
                     layer_scale: float = 1e-6) -> List:
    widths = [max(4, int(c * width_mult)) for c in _WIDTHS]
    mods: List = [Chain("stem", (Conv(widths[0], k=4, s=4, p=0),
                                 LayerNorm2d()))]
    for i, (c, n) in enumerate(zip(widths, _DEPTHS)):
        if i:
            mods.append(Chain("downsample", (LayerNorm2d(),
                                             Conv(c, k=2, s=2, p=0))))
        mods += [ConvNeXtBlock(c, layer_scale) for _ in range(n)]
    return mods


def init_convnext(key, in_shape=(512, 512, 3), width_mult: float = 1.0,
                  n_classes: int = 10, layer_scale: float = 1e-6):
    mods = convnext_modules(width_mult, layer_scale)
    k1, k2 = jax.random.split(key)
    trunk_params, feat_shape = init_trunk(mods, k1, in_shape)
    c = feat_shape[-1]
    head = {
        "norm": LayerNorm2d().init(None, feat_shape),
        "w": jax.random.normal(k2, (c, n_classes), jnp.float32) / jnp.sqrt(c),
        "b": jnp.zeros((n_classes,), jnp.float32),
    }
    return mods, {"trunk": trunk_params, "head": head}


def head_apply(head, feats):
    pooled = LayerNorm2d().apply(head["norm"], jnp.mean(feats, axis=(1, 2)))
    return pooled @ head["w"] + head["b"]
