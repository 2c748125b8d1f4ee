"""Plain reference of ``resnet50.json``: ResNet-50 as the benchmark runs
it, in straightforward ``jax.numpy``.

A 7x7/2 stem convolution, normalisation and ReLU, a 3x3/2 max-pool, then
bottlenecks (1x1, 3x3 with the block's stride, 1x1, each convolution
without bias and followed by normalisation, ReLU after the first two and
after the residual sum), with a 1x1 projection shortcut (stride as the
block) on each stage's first block.  Normalisation uses the parameters'
running statistics, ``x * s + (bias - mean * s)`` with ``s = scale /
sqrt(var + eps)``, all four trained (the file's ``reduced``
``batch_norm``).  Global average pooling and one linear layer close it.
Parameters are drawn from the seed as the trainer draws them and laid out
as its tree: ``trunk`` is (stem conv, norm, ReLU, pool, 16 blocks).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _blocks(cfg):
    """``(cin, cmid, cout, stride, project, h_in)`` of each bottleneck."""
    stem, pool = cfg["stem"], cfg["pool"]
    h = (cfg["image"] + 2 * stem["p"] - stem["k"]) // stem["s"] + 1
    h = (h + 2 * pool["p"] - pool["k"]) // pool["s"] + 1
    cin, out = stem["channels"], []
    for j, (cout, n) in enumerate(cfg["stages"]):
        for i in range(n):
            s = 2 if (i == 0 and j > 0) else 1
            out.append((cin, cout // 4, cout, s, i == 0, h))
            h = (h - 1) // s + 1
            cin = cout
    return out


def conv_layers(cfg):
    stem = cfg["stem"]
    h = (cfg["image"] + 2 * stem["p"] - stem["k"]) // stem["s"] + 1
    out = [dict(h_out=h, w_out=h, k=stem["k"], cin=cfg["channels"],
                cout=stem["channels"])]
    for cin, cmid, cout, s, project, h in _blocks(cfg):
        ho = (h - 1) // s + 1
        out += [dict(h_out=h, w_out=h, k=1, cin=cin, cout=cmid),
                dict(h_out=ho, w_out=ho, k=3, cin=cmid, cout=cmid),
                dict(h_out=ho, w_out=ho, k=1, cin=cmid, cout=cout)]
        if project:
            out.append(dict(h_out=ho, w_out=ho, k=1, cin=cin, cout=cout))
    return out


def linear(cfg):
    return cfg["stages"][-1][0], cfg["n_classes"]


def _conv_init(key, k, cin, cout):
    wkey, _ = jax.random.split(key)
    return {"w": jax.random.normal(wkey, (k, k, cin, cout), jnp.float32)
            * jnp.sqrt(2.0 / (k * k * cin)).astype(jnp.float32)}


def _norm_init(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def init(cfg, key):
    blocks = _blocks(cfg)
    stem = cfg["stem"]
    k_trunk, k_head = jax.random.split(key)
    keys = jax.random.split(k_trunk, 4 + len(blocks))
    trunk = [_conv_init(keys[0], stem["k"], cfg["channels"],
                        stem["channels"]),
             _norm_init(stem["channels"]), {}, {}]
    for key, (cin, cmid, cout, _, project, _) in zip(keys[4:], blocks):
        ks = jax.random.split(key, 8)
        p = {"c1": _conv_init(ks[0], 1, cin, cmid), "c1_bn": _norm_init(cmid),
             "c2": _conv_init(ks[1], 3, cmid, cmid), "c2_bn": _norm_init(cmid),
             "c3": _conv_init(ks[2], 1, cmid, cout), "c3_bn": _norm_init(cout)}
        if project:
            p["sc"] = _conv_init(ks[6], 1, cin, cout)
            p["sc_bn"] = _norm_init(cout)
        trunk.append(p)
    c = cfg["stages"][-1][0]
    head = {"w": jax.random.normal(k_head, (c, cfg["n_classes"]),
                                   jnp.float32) / jnp.sqrt(c),
            "b": jnp.zeros((cfg["n_classes"],), jnp.float32)}
    return {"trunk": tuple(trunk), "head": head}


def _conv(x, p, s, pad, precision):
    return lax.conv_general_dilated(
        x, p["w"], (s, s), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _norm(x, p, eps):
    s = lax.rsqrt(p["var"] + eps) * p["scale"]
    return x * s + (p["bias"] - p["mean"] * s)


def forward(cfg, params, x, precision):
    eps, stem, pool = cfg["bn_eps"], cfg["stem"], cfg["pool"]
    t = params["trunk"]
    x = _conv(x, t[0], stem["s"], stem["p"], precision)
    x = jnp.maximum(_norm(x, t[1], eps), 0)
    x = lax.reduce_window(
        x, -jnp.inf, lax.max,
        (1, pool["k"], pool["k"], 1), (1, pool["s"], pool["s"], 1),
        ((0, 0), (pool["p"], pool["p"]), (pool["p"], pool["p"]), (0, 0)))
    for p, (_, _, _, s, project, _) in zip(t[4:], _blocks(cfg)):
        y = jnp.maximum(_norm(_conv(x, p["c1"], 1, 0, precision),
                              p["c1_bn"], eps), 0)
        y = jnp.maximum(_norm(_conv(y, p["c2"], s, 1, precision),
                              p["c2_bn"], eps), 0)
        y = _norm(_conv(y, p["c3"], 1, 0, precision), p["c3_bn"], eps)
        r = _norm(_conv(x, p["sc"], s, 0, precision), p["sc_bn"], eps) \
            if project else x
        x = jnp.maximum(y + r, 0)
    pooled = jnp.mean(x, axis=(1, 2))
    head = params["head"]
    return jnp.dot(pooled, head["w"], precision=precision) + head["b"]
