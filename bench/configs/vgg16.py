"""Plain reference of ``vgg16.json``: VGG-16 config D as the benchmark
runs it, in straightforward ``jax.numpy``.

Thirteen 3x3 convolutions (stride 1, padding 1, with bias) each followed
by a ReLU, a 2x2/2 max-pool after each stage, then global average pooling
and one linear layer (the file's ``reduced`` head).  The parameters are
drawn from the seed as the trainer draws them (He-normal kernels, zero
biases, head N(0, 1/C)), and laid out as its tree: ``trunk`` is one entry
per module (conv, ReLU, ..., pool), ``{}`` for those without parameters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv_layers(cfg):
    h, cin, out = cfg["image"], cfg["channels"], []
    for cout, n in cfg["stages"]:
        for _ in range(n):
            out.append(dict(h_out=h, w_out=h, k=3, cin=cin, cout=cout))
            cin = cout
        h //= 2
    return out


def linear(cfg):
    return cfg["stages"][-1][0], cfg["n_classes"]


def init(cfg, key):
    n_modules = sum(2 * n + 1 for _, n in cfg["stages"])
    k_trunk, k_head = jax.random.split(key)
    keys = jax.random.split(k_trunk, max(2, n_modules))
    trunk, i, cin = [], 0, cfg["channels"]
    for cout, n in cfg["stages"]:
        for _ in range(n):
            wkey, _ = jax.random.split(keys[i])
            fan_in = 9 * cin
            w = jax.random.normal(wkey, (3, 3, cin, cout), jnp.float32) \
                * jnp.sqrt(2.0 / fan_in).astype(jnp.float32)
            trunk += [{"w": w, "b": jnp.zeros((cout,), jnp.float32)}, {}]
            i += 2
            cin = cout
        trunk.append({})
        i += 1
    c = cin
    head = {"w": jax.random.normal(k_head, (c, cfg["n_classes"]),
                                   jnp.float32) / jnp.sqrt(c),
            "b": jnp.zeros((cfg["n_classes"],), jnp.float32)}
    return {"trunk": tuple(trunk), "head": head}


def forward(cfg, params, x, precision):
    i = 0
    for _, n in cfg["stages"]:
        for _ in range(n):
            p = params["trunk"][i]
            x = lax.conv_general_dilated(
                x, p["w"], (1, 1), ((1, 1), (1, 1)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=precision) + p["b"]
            x = jnp.maximum(x, 0)
            i += 2
        x = lax.reduce_window(x, -jnp.inf, lax.max,
                              (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        i += 1
    pooled = jnp.mean(x, axis=(1, 2))
    head = params["head"]
    return jnp.dot(pooled, head["w"], precision=precision) + head["b"]
