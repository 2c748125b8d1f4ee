"""Training inputs made on the device from the seed.

The images follow the repo's synthetic image set
(``repro.data.pipeline.ImageDataset``): one smooth template per class,
``sin(2 pi ((k + 1) x / W + k y / H))`` scaled per channel by an
amplitude drawn from U(0.5, 1), plus N(0, 0.3^2) noise per pixel, with
uniformly drawn labels.  The formula is copied here and drawn with JAX's
generator on the device, so no host generator runs while the chip is
timed and the same seed gives the same batches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def make_ring(seed, n, batch, h, w, c, n_classes):
    """``n`` distinct batches ``(images f32[batch, h, w, c], labels
    s32[batch])``, made in one program from the int32 ``seed``."""
    key_t, key_b = jax.random.split(jax.random.PRNGKey(seed))
    amp = jax.random.uniform(key_t, (n_classes, 1, 1, c), jnp.float32,
                             0.5, 1.0)
    yy, xx = jnp.mgrid[0:h, 0:w].astype(jnp.float32)
    k = jnp.arange(n_classes, dtype=jnp.float32)[:, None, None]
    templates = jnp.sin(2 * jnp.pi * ((k + 1) * xx / w + k * yy / h))
    templates = templates[..., None] * amp
    ring = []
    for i in range(n):
        key_l, key_n = jax.random.split(jax.random.fold_in(key_b, i))
        labels = jax.random.randint(key_l, (batch,), 0, n_classes, jnp.int32)
        noise = 0.3 * jax.random.normal(key_n, (batch, h, w, c), jnp.float32)
        ring.append((templates[labels] + noise, labels))
    return ring
