"""Plain reference of ``convnext_t.json``: ConvNeXt-T as the benchmark runs
it, in straightforward ``jax.numpy``.

A 4x4/4 stem convolution and LayerNorm, then four stages of blocks, each
stage after the first opened by LayerNorm and a 2x2/2 convolution.  A
block: 7x7 depthwise convolution (pad 3, one group per channel),
LayerNorm over the channels of each pixel, a linear layer C -> 4C, GELU
(erf form), a linear layer 4C -> C, times the layer scale gamma, added to
the block's input.  Every convolution and linear layer has a bias.  Global
average pooling, LayerNorm and one linear layer close it.  Each block is
rematerialised in the backward pass (``jax.checkpoint``), which changes no
value, so a block of 32 images at 512x512 fits beside nothing else.
Parameters are drawn from the seed as the trainer draws them and laid out
as its tree: ``trunk`` is (stem, 3 blocks, downsample, 3 blocks,
downsample, 9 blocks, downsample, 3 blocks), the stem and each downsample
a pair of (conv, norm) or (norm, conv) params.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def _modules(cfg):
    """``(kind, cin, cout, h_in)`` of each trunk module in order; kinds
    ``stem``, ``block``, ``down``."""
    stem, down = cfg["stem"], cfg["downsample"]
    h = cfg["image"] // stem["s"]
    out = [("stem", cfg["channels"], cfg["widths"][0], cfg["image"])]
    for i, (c, n) in enumerate(zip(cfg["widths"], cfg["depths"])):
        if i:
            out.append(("down", cfg["widths"][i - 1], c, h))
            h //= down["s"]
        out += [("block", c, c, h)] * n
    return out


def conv_layers(cfg):
    """Every convolution and per-pixel linear layer, for the FLOP count;
    a depthwise conv counts ``cin`` 1, the channels of one group."""
    stem, down, k = cfg["stem"], cfg["downsample"], cfg["block"]["k"]
    e = cfg["block"]["expansion"]
    out = []
    for kind, cin, cout, h in _modules(cfg):
        if kind == "stem":
            ho = h // stem["s"]
            out.append(dict(h_out=ho, w_out=ho, k=stem["k"], cin=cin,
                            cout=cout))
        elif kind == "down":
            ho = h // down["s"]
            out.append(dict(h_out=ho, w_out=ho, k=down["k"], cin=cin,
                            cout=cout))
        else:
            out += [dict(h_out=h, w_out=h, k=k, cin=1, cout=cout),
                    dict(h_out=h, w_out=h, k=1, cin=cout, cout=e * cout),
                    dict(h_out=h, w_out=h, k=1, cin=e * cout, cout=cout)]
    return out


def linear(cfg):
    return cfg["widths"][-1], cfg["n_classes"]


def _conv_init(key, k, cin, cout):
    wkey, _ = jax.random.split(key)
    return {"w": jax.random.normal(wkey, (k, k, cin, cout), jnp.float32)
            * jnp.sqrt(2.0 / (k * k * cin)).astype(jnp.float32),
            "b": jnp.zeros((cout,), jnp.float32)}


def _norm_init(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def init(cfg, key):
    mods = _modules(cfg)
    k, e = cfg["block"]["k"], cfg["block"]["expansion"]
    k_trunk, k_head = jax.random.split(key)
    keys = jax.random.split(k_trunk, max(2, len(mods)))
    trunk = []
    for key, (kind, cin, cout, _) in zip(keys, mods):
        if kind == "stem":
            k0, _ = jax.random.split(key)
            trunk.append((_conv_init(k0, cfg["stem"]["k"], cin, cout),
                          _norm_init(cout)))
        elif kind == "down":
            _, k1 = jax.random.split(key)
            trunk.append((_norm_init(cin),
                          _conv_init(k1, cfg["downsample"]["k"], cin, cout)))
        else:
            ks = jax.random.split(key, 4)
            trunk.append({"dw": _conv_init(ks[0], k, 1, cout),
                          "norm": _norm_init(cout),
                          "pw1": _conv_init(ks[2], 1, cout, e * cout),
                          "pw2": _conv_init(ks[3], 1, e * cout, cout),
                          "gamma": jnp.full((cout,), cfg["layer_scale_init"],
                                            jnp.float32)})
    c = cfg["widths"][-1]
    head = {"norm": _norm_init(c),
            "w": jax.random.normal(k_head, (c, cfg["n_classes"]),
                                   jnp.float32) / jnp.sqrt(c),
            "b": jnp.zeros((cfg["n_classes"],), jnp.float32)}
    return {"trunk": tuple(trunk), "head": head}


def _conv(x, p, s, pad, precision, groups=1):
    return lax.conv_general_dilated(
        x, p["w"], (s, s), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=precision) + p["b"]


def _norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _linear(x, p, precision):
    return jnp.einsum("nhwc,cd->nhwd", x, p["w"][0, 0],
                      precision=precision) + p["b"]


def _gelu(x):
    return 0.5 * x * (1 + lax.erf(x / math.sqrt(2)))


def forward(cfg, params, x, precision):
    eps, k = cfg["ln_eps"], cfg["block"]["k"]
    stem, down = cfg["stem"], cfg["downsample"]

    @jax.checkpoint
    def block(p, x):
        y = _conv(x, p["dw"], 1, k // 2, precision, groups=x.shape[-1])
        y = _linear(_gelu(_linear(_norm(y, p["norm"], eps), p["pw1"],
                                  precision)), p["pw2"], precision)
        return x + p["gamma"] * y

    for p, (kind, _, _, _) in zip(params["trunk"], _modules(cfg)):
        if kind == "stem":
            x = _norm(_conv(x, p[0], stem["s"], 0, precision), p[1], eps)
        elif kind == "down":
            x = _conv(_norm(x, p[0], eps), p[1], down["s"], 0, precision)
        else:
            x = block(p, x)
    head = params["head"]
    pooled = _norm(jnp.mean(x, axis=(1, 2)), head["norm"], eps)
    return jnp.dot(pooled, head["w"], precision=precision) + head["b"]
