"""The plain training reference that decides ``correct``.

It follows the first three SGD steps of a cell from the seed with the
configuration's own plain model (``configs/<name>.py``), which imports
nothing of the program: parameters drawn from the seed, the same three
batches, the mean cross-entropy, and SGD with momentum and weight decay
as the configuration states it.  The batch is cut into blocks of rows
and the gradient summed over them in a scan, so the reference fits on
the chip beside nothing else at the timed batch.

``Reference(..., dtype=jnp.bfloat16, precision=None)`` is the control:
the same reference computed in bfloat16 throughout, parameters and
optimizer state included.  ``rows=B/2`` is the half-batch fault: the
mean taken over the first half of each batch.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def leaf_norms(tree) -> dict:
    """``{key path: L2 norm in float32}`` of every leaf."""
    return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(
                jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def host(norms: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(norms).items()}


class Reference:
    """The first three steps of a configuration, from a seed."""

    def __init__(self, model, cfg, lr, *, block, dtype=jnp.float32,
                 precision=HIGHEST, rows=None):
        opt = cfg["optimizer"]
        assert opt["kind"] == "sgd", opt
        mu, wd = opt["momentum"], opt["weight_decay"]

        def loss_sum(params, x, y):
            logits = model.forward(cfg, params, x.astype(dtype), precision)
            logp = jax.nn.log_softmax(logits)
            return -jnp.sum(jnp.take_along_axis(logp, y[:, None], 1))

        def step(params, vel, images, labels):
            n = rows or images.shape[0]
            b = math.gcd(block, n)
            xs = images[:n].reshape(n // b, b, *images.shape[1:])
            ys = labels[:n].reshape(n // b, b)

            def body(acc, xy):
                loss, g = jax.value_and_grad(loss_sum)(params, *xy)
                return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

            zero = (jnp.zeros((), dtype), jax.tree.map(jnp.zeros_like, params))
            (loss, g), _ = lax.scan(body, zero, (xs, ys))
            g = jax.tree.map(lambda t: t / n, g)
            vel = jax.tree.map(lambda v, g, p: mu * v + (g + wd * p),
                               vel, g, params)
            params = jax.tree.map(lambda p, v: p - lr * v, params, vel)
            return params, vel, loss / n, leaf_norms(g)

        def init(seed):
            p = jax.tree.map(lambda t: t.astype(dtype),
                             model.init(cfg, jax.random.PRNGKey(seed)))
            return p, jax.tree.map(jnp.zeros_like, p)

        self._init = jax.jit(init)
        self._step = jax.jit(step)
        self._delta = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))

    def readings(self, seed: int, batches) -> dict:
        """Losses of the three steps, the first gradient's leaf norms (for
        the rule that leaves out leaves that do not move) and the leaf
        norms of the parameters' change over the three."""
        params, vel = self._init(seed)
        p0, losses, grad = params, [], None
        for images, labels in batches[:3]:
            params, vel, loss, g = self._step(params, vel, images, labels)
            losses.append(loss)
            if grad is None:
                grad = g
        delta = self._delta(params, p0)
        return {"losses": [float(x) for x in losses], "grad": host(grad),
                "delta": host(delta)}


def model_readings(step, params, opt, batches):
    """The losses and the parameters' change of the program's own
    compiled ``step(params, opt, images, labels) -> (params, opt, loss,
    metrics)``, driven through the first three batches.  Returns the
    readings and the state after the three steps."""
    diff = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))
    p0, losses = params, []
    for images, labels in batches[:3]:
        params, opt, loss, _ = step(params, opt, images, labels)
        losses.append(loss)
    out = {"losses": [float(x) for x in losses],
           "delta": host(diff(params, p0))}
    return out, params, opt
