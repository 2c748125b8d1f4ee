"""Interval-aware functional CNN layers (NHWC) for row-centric execution.

Every module implements the protocol the row engines (``repro.core.overlap``
/ ``repro.core.twophase``) need:

* ``init(key, in_shape) -> params``            (in_shape = (H, W, C))
* ``out_shape(in_shape) -> (H', W', C')``
* ``apply(params, x) -> y``                    column-centric, full tensor
* ``in_interval(out_iv, h_in) -> Interval``    H-rows needed for an output iv
* ``apply_row(params, x, iv_in, h_in, out_iv) -> y``
      ``x`` covers global input rows ``iv_in``; returns exactly the rows
      ``out_iv`` of the global output, computed with semi-closed padding.

ConvNeXt's modules (:class:`LayerNorm2d`, :class:`ConvNeXtBlock`) keep the
same protocol: LayerNorm works on one pixel at a time, so rows are exact at
the published normalisation.  :class:`Chain` runs a few primitives as one
trunk module (ConvNeXt's stem and downsampling layers).

Norm note (see DESIGN.md): BatchNorm here normalises with running
statistics inside ``apply`` so that row-centric and column-centric
execution are bit-identical; batch-moment *updates* are provided separately
(:func:`batch_moments`, :func:`merge_moments`) so a training loop can keep
exact global statistics by merging per-row moments (Chan's algorithm).
"""

from __future__ import annotations

import dataclasses
import math
from typing import ClassVar, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core.convmath import (
    Geometry,
    IDENTITY,
    Interval,
    backward_intervals,
    interval_union,
)


def _he_init(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.sqrt(2.0 / fan_in).astype(dtype)


# ---------------------------------------------------------------------------
# Primitive modules
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Conv:
    """2-D convolution, square kernel, symmetric W padding, semi-closed H
    padding in row mode.  ``groups`` splits the channels into groups
    convolved apart (``groups == cin == cout`` is a depthwise conv); the
    kernel is ``(k, k, cin // groups, cout)``."""

    cout: int
    k: int = 3
    s: int = 1
    p: int = 1
    bias: bool = True
    dtype: str = "float32"
    groups: int = 1

    @property
    def geometry(self) -> Geometry:
        return Geometry(self.k, self.s, self.p)

    def init(self, key, in_shape):
        h, w, cin = in_shape
        dt = jnp.dtype(self.dtype)
        wkey, _ = jax.random.split(key)
        cin_g = cin // self.groups
        fan_in = self.k * self.k * cin_g
        params = {"w": _he_init(wkey, (self.k, self.k, cin_g, self.cout),
                                fan_in, dt)}
        if self.bias:
            params["b"] = jnp.zeros((self.cout,), dt)
        return params

    def out_shape(self, in_shape):
        h, w, cin = in_shape
        g = self.geometry
        return (g.out_size(h), g.out_size(w), self.cout)

    def in_interval(self, out_iv: Interval, h_in: int) -> Interval:
        return self.geometry.in_interval(out_iv, h_in)

    def _conv(self, params, x, pad_h):
        y = lax.conv_general_dilated(
            x,
            params["w"],
            window_strides=(self.s, self.s),
            padding=(pad_h, (self.p, self.p)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.groups,
        )
        if self.bias:
            y = y + params["b"]
        return y

    def apply(self, params, x):
        return self._conv(params, x, (self.p, self.p))

    def apply_row(self, params, x, iv_in, h_in, out_iv):
        g = self.geometry
        pad_h = g.pad_for_slice(iv_in, h_in)
        y = self._conv(params, x, pad_h)
        first = g.first_out_of_slice(iv_in[0])
        off = out_iv[0] - first
        assert off >= 0, (out_iv, first, iv_in)
        n = out_iv[1] - out_iv[0]
        assert off + n <= y.shape[1], (off, n, y.shape, iv_in, out_iv, h_in)
        return lax.slice_in_dim(y, off, off + n, axis=1)


@dataclasses.dataclass(frozen=True)
class MaxPool:
    k: int = 2
    s: int = 2
    p: int = 0

    @property
    def geometry(self) -> Geometry:
        return Geometry(self.k, self.s, self.p)

    def init(self, key, in_shape):
        return {}

    def out_shape(self, in_shape):
        h, w, c = in_shape
        g = self.geometry
        return (g.out_size(h), g.out_size(w), c)

    def in_interval(self, out_iv, h_in):
        return self.geometry.in_interval(out_iv, h_in)

    def _pool(self, params, x, pad_h):
        return lax.reduce_window(
            x,
            -jnp.inf if x.dtype == jnp.float32 else jnp.finfo(x.dtype).min,
            lax.max,
            window_dimensions=(1, self.k, self.k, 1),
            window_strides=(1, self.s, self.s, 1),
            padding=((0, 0), pad_h, (self.p, self.p), (0, 0)),
        )

    def apply(self, params, x):
        return self._pool(params, x, (self.p, self.p))

    def apply_row(self, params, x, iv_in, h_in, out_iv):
        g = self.geometry
        y = self._pool(params, x, g.pad_for_slice(iv_in, h_in))
        first = g.first_out_of_slice(iv_in[0])
        off = out_iv[0] - first
        n = out_iv[1] - out_iv[0]
        return lax.slice_in_dim(y, off, off + n, axis=1)


@dataclasses.dataclass(frozen=True)
class ReLU:
    def init(self, key, in_shape):
        return {}

    def out_shape(self, in_shape):
        return in_shape

    def in_interval(self, out_iv, h_in):
        return out_iv

    def apply(self, params, x):
        return jnp.maximum(x, 0)

    def apply_row(self, params, x, iv_in, h_in, out_iv):
        off = out_iv[0] - iv_in[0]
        y = jnp.maximum(x, 0)
        return lax.slice_in_dim(y, off, off + (out_iv[1] - out_iv[0]), axis=1)


@dataclasses.dataclass(frozen=True)
class BatchNorm:
    """Running-stats normalisation (row-exact); see module docstring."""

    eps: float = 1e-5

    def init(self, key, in_shape):
        c = in_shape[-1]
        return {
            "scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32),
        }

    def out_shape(self, in_shape):
        return in_shape

    def in_interval(self, out_iv, h_in):
        return out_iv

    def apply(self, params, x):
        inv = lax.rsqrt(params["var"] + self.eps) * params["scale"]
        return x * inv + (params["bias"] - params["mean"] * inv)

    def apply_row(self, params, x, iv_in, h_in, out_iv):
        off = out_iv[0] - iv_in[0]
        y = self.apply(params, x)
        return lax.slice_in_dim(y, off, off + (out_iv[1] - out_iv[0]), axis=1)


@dataclasses.dataclass(frozen=True)
class LayerNorm2d:
    """LayerNorm over the channels of each pixel (channels-last), as
    ConvNeXt normalises: mean and biased variance of one pixel's C values,
    then a trained scale and bias per channel.  Row-exact: no pixel reads
    another."""

    eps: float = 1e-6

    def init(self, key, in_shape):
        c = in_shape[-1]
        return {"scale": jnp.ones((c,), jnp.float32),
                "bias": jnp.zeros((c,), jnp.float32)}

    def out_shape(self, in_shape):
        return in_shape

    def in_interval(self, out_iv, h_in):
        return out_iv

    def apply(self, params, x):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return ((x - mean) * lax.rsqrt(var + self.eps) * params["scale"]
                + params["bias"])

    def apply_row(self, params, x, iv_in, h_in, out_iv):
        off = out_iv[0] - iv_in[0]
        y = self.apply(params, x)
        return lax.slice_in_dim(y, off, off + (out_iv[1] - out_iv[0]), axis=1)


def batch_moments(x):
    """Per-channel (sum, sumsq, count) over (B, H, W) — mergeable."""
    n = x.shape[0] * x.shape[1] * x.shape[2]
    return (jnp.sum(x, axis=(0, 1, 2)), jnp.sum(x * x, axis=(0, 1, 2)), n)


def merge_moments(*ms):
    """Chan's parallel moment merge: exact global mean/var from row moments."""
    s = sum(m[0] for m in ms)
    ss = sum(m[1] for m in ms)
    n = sum(m[2] for m in ms)
    mean = s / n
    var = ss / n - mean * mean
    return mean, var


# ---------------------------------------------------------------------------
# Composite: ResNet bottleneck block (branching interval algebra)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Bottleneck:
    """ResNet-v1 bottleneck: 1x1 -> 3x3(stride) -> 1x1 (+BN, ReLU), with
    identity or projection shortcut.  One trunk "module": the row engines see
    a single unit whose internal halo is replicated (OverL semantics inside
    the block; see DESIGN.md)."""

    cmid: int
    cout: int
    s: int = 1
    project: bool = False

    def _parts(self):
        c1 = Conv(self.cmid, k=1, s=1, p=0, bias=False)
        c2 = Conv(self.cmid, k=3, s=self.s, p=1, bias=False)
        c3 = Conv(self.cout, k=1, s=1, p=0, bias=False)
        sc = Conv(self.cout, k=1, s=self.s, p=0, bias=False) if self.project else None
        return c1, c2, c3, sc

    @property
    def main_geoms(self):
        return [Geometry(1, 1, 0), Geometry(3, self.s, 1), Geometry(1, 1, 0)]

    def init(self, key, in_shape):
        c1, c2, c3, sc = self._parts()
        keys = jax.random.split(key, 8)
        p = {}
        shape = in_shape
        for i, (name, m) in enumerate([("c1", c1), ("c2", c2), ("c3", c3)]):
            p[name] = m.init(keys[i], shape)
            p[name + "_bn"] = BatchNorm().init(keys[i + 3], m.out_shape(shape))
            shape = m.out_shape(shape)
        if sc is not None:
            p["sc"] = sc.init(keys[6], in_shape)
            p["sc_bn"] = BatchNorm().init(keys[7], sc.out_shape(in_shape))
        return p

    def out_shape(self, in_shape):
        h, w, c = in_shape
        g = Geometry(3, self.s, 1)
        return (g.out_size(h), g.out_size(w), self.cout)

    def in_interval(self, out_iv, h_in):
        ivs = backward_intervals(self.main_geoms, h_in, out_iv)
        main_iv = ivs[0]
        sc_iv = Geometry(1, self.s, 0).in_interval(out_iv, h_in)
        return interval_union(main_iv, sc_iv)

    def apply(self, params, x):
        c1, c2, c3, sc = self._parts()
        bn = BatchNorm()
        y = jnp.maximum(bn.apply(params["c1_bn"], c1.apply(params["c1"], x)), 0)
        y = jnp.maximum(bn.apply(params["c2_bn"], c2.apply(params["c2"], y)), 0)
        y = bn.apply(params["c3_bn"], c3.apply(params["c3"], y))
        if sc is not None:
            r = bn.apply(params["sc_bn"], sc.apply(params["sc"], x))
        else:
            r = x
        return jnp.maximum(y + r, 0)

    def apply_row(self, params, x, iv_in, h_in, out_iv):
        c1, c2, c3, sc = self._parts()
        bn = BatchNorm()
        hs_main = [h_in]
        for g in self.main_geoms:
            hs_main.append(g.out_size(hs_main[-1]))
        ivs = backward_intervals(self.main_geoms, h_in, out_iv)

        def local(x_full, iv_needed):
            off = iv_needed[0] - iv_in[0]
            return lax.slice_in_dim(
                x_full, off, off + (iv_needed[1] - iv_needed[0]), axis=1
            )

        # main path
        y = local(x, ivs[0])
        y = c1.apply_row(params["c1"], y, ivs[0], hs_main[0], ivs[1])
        y = jnp.maximum(bn.apply(params["c1_bn"], y), 0)
        y = c2.apply_row(params["c2"], y, ivs[1], hs_main[1], ivs[2])
        y = jnp.maximum(bn.apply(params["c2_bn"], y), 0)
        y = c3.apply_row(params["c3"], y, ivs[2], hs_main[2], ivs[3])
        y = bn.apply(params["c3_bn"], y)
        # shortcut
        sc_g = Geometry(1, self.s, 0)
        sc_iv = sc_g.in_interval(out_iv, h_in)
        xs = local(x, sc_iv)
        if sc is not None:
            r = sc.apply_row(params["sc"], xs, sc_iv, h_in, out_iv)
            r = bn.apply(params["sc_bn"], r)
        else:
            first = sc_g.first_out_of_slice(sc_iv[0])
            off = out_iv[0] - first
            r = lax.slice_in_dim(xs, off, off + (out_iv[1] - out_iv[0]), axis=1)
        return jnp.maximum(y + r, 0)


# ---------------------------------------------------------------------------
# Composites for ConvNeXt (Liu et al., CVPR 2022)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Chain:
    """A few modules run as one trunk module under one program scope
    (``name``): ConvNeXt's stem (4x4/4 conv, LayerNorm) and its
    downsampling layers (LayerNorm, 2x2/2 conv).  Params are the parts'
    params, in order."""

    name: str
    parts: Tuple

    def init(self, key, in_shape):
        params, _ = init_trunk(self.parts, key, in_shape)
        return params

    def out_shape(self, in_shape):
        for m in self.parts:
            in_shape = m.out_shape(in_shape)
        return in_shape

    def in_interval(self, out_iv, h_in):
        return trunk_in_intervals(self.parts, h_in, out_iv)[0]

    def apply(self, params, x):
        with obs.scope(self.name):
            return apply_trunk(self.parts, params, x)

    def apply_row(self, params, x, iv_in, h_in, out_iv):
        ivs = trunk_in_intervals(self.parts, h_in, out_iv)
        hs = trunk_heights(self.parts, h_in)
        with obs.scope(self.name):
            off = ivs[0][0] - iv_in[0]
            y = lax.slice_in_dim(x, off, off + (ivs[0][1] - ivs[0][0]), axis=1)
            for l, (m, p) in enumerate(zip(self.parts, params)):
                y = m.apply_row(p, y, ivs[l], hs[l], ivs[l + 1])
        return y


@dataclasses.dataclass(frozen=True)
class ConvNeXtBlock:
    """ConvNeXt block: 7x7 depthwise conv (pad 3) -> LayerNorm over the
    channels -> 1x1 C->4C -> GELU (erf form) -> 1x1 4C->C -> layer scale
    gamma, added to the block input.  One trunk module whose only spatial
    op is the depthwise conv, so its row interval is that conv's.  The scopes
    ``dwconv``, ``norm`` and ``pw_mlp`` (both 1x1 convs, the GELU and
    gamma) name its ops in the compiled step."""

    c: int
    #: gamma's initial value, the published 1e-6
    layer_scale: float = 1e-6
    k: ClassVar[int] = 7
    expansion: ClassVar[int] = 4

    def _parts(self):
        dw = Conv(self.c, k=self.k, s=1, p=self.k // 2, groups=self.c)
        pw1 = Conv(self.expansion * self.c, k=1, s=1, p=0)
        pw2 = Conv(self.c, k=1, s=1, p=0)
        return dw, LayerNorm2d(), pw1, pw2

    @property
    def held_channels(self) -> int:
        """Channels of the widest activation per output pixel (the
        hidden expansion), which the planner prices in place of ``c``."""
        return self.expansion * self.c

    def init(self, key, in_shape):
        dw, ln, pw1, pw2 = self._parts()
        keys = jax.random.split(key, 4)
        hidden = in_shape[:2] + (self.expansion * self.c,)
        return {"dw": dw.init(keys[0], in_shape),
                "norm": ln.init(keys[1], in_shape),
                "pw1": pw1.init(keys[2], in_shape),
                "pw2": pw2.init(keys[3], hidden),
                "gamma": jnp.full((self.c,), self.layer_scale, jnp.float32)}

    def out_shape(self, in_shape):
        return in_shape

    def in_interval(self, out_iv, h_in):
        return self._parts()[0].in_interval(out_iv, h_in)

    def _branch(self, params, y):
        _, ln, pw1, pw2 = self._parts()
        with obs.scope("norm"):
            y = ln.apply(params["norm"], y)
        with obs.scope("pw_mlp"):
            y = jax.nn.gelu(pw1.apply(params["pw1"], y), approximate=False)
            return pw2.apply(params["pw2"], y) * params["gamma"]

    def apply(self, params, x):
        with obs.scope("dwconv"):
            y = self._parts()[0].apply(params["dw"], x)
        return x + self._branch(params, y)

    def apply_row(self, params, x, iv_in, h_in, out_iv):
        with obs.scope("dwconv"):
            y = self._parts()[0].apply_row(params["dw"], x, iv_in, h_in,
                                           out_iv)
        off = out_iv[0] - iv_in[0]
        r = lax.slice_in_dim(x, off, off + (out_iv[1] - out_iv[0]), axis=1)
        return r + self._branch(params, y)


# ---------------------------------------------------------------------------
# Trunk helpers
# ---------------------------------------------------------------------------


def init_trunk(modules: Sequence, key, in_shape):
    """Initialise a list of modules; returns (params_tuple, out_shape)."""
    params = []
    shape = in_shape
    keys = jax.random.split(key, max(2, len(modules)))
    for m, k in zip(modules, keys):
        params.append(m.init(k, shape))
        shape = m.out_shape(shape)
    return tuple(params), shape


def apply_trunk(modules: Sequence, params, x):
    """Column-centric reference forward."""
    for m, p in zip(modules, params):
        x = m.apply(p, x)
    return x


def trunk_heights(modules: Sequence, h0: int) -> List[int]:
    hs = [h0]
    for m in modules:
        hs.append(_mod_out_h(m, hs[-1]))
    return hs


def _mod_out_h(m, h):
    # every module exposes out_shape((h, w, c)); W/C don't affect H
    return m.out_shape((h, 4096, 1))[0]


def trunk_in_intervals(modules: Sequence, h0: int, out_iv: Interval) -> List[Interval]:
    """Needed interval at every activation level (len = L+1) — module-level
    generalisation of convmath.backward_intervals."""
    hs = trunk_heights(modules, h0)
    ivs = [out_iv]
    for l in range(len(modules) - 1, -1, -1):
        ivs.append(modules[l].in_interval(ivs[-1], hs[l]))
    ivs.reverse()
    return ivs
