"""Plain training steps: MAE loss, gradients, global-norm clipping, Adam with
a warm-up-cosine learning rate (Kingma & Ba 2015; Loshchilov & Hutter 2017).

``follow`` runs the first steps of training from the initial weights over
the windows the system under test was fed, and returns what the comparison
needs: each step's loss, the first step's clipped gradient and the weights
after the last step.  Gradients are computed in blocks of rows (the loss is a
mean over equal blocks, so its gradient is the mean of the blocks'), so that
a batch whose activations would not fit the chip at once still runs.

``dtype`` is the precision the forward and backward passes compute in; the
optimizer always works in float32.  The lower-precision control runs this
same code with ``bfloat16``.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np


def model(family: str):
    return importlib.import_module(f"bench.reference.{family}")


def learning_rate(step: int, o: dict) -> float:
    """Warm-up from 0 to ``lr`` over ``warmup_steps``, then a cosine decay to
    ``min_ratio * lr`` at ``total_steps``; ``step`` counts from 0."""
    lr, warm, total = o["lr"], o["warmup_steps"], o["total_steps"]
    if step < warm:
        return lr * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return lr * (o["min_ratio"] + (1 - o["min_ratio"]) * 0.5 *
                 (1 + np.cos(np.pi * prog)))


def clip(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def adam(params, grads, m, v, step: int, lr: float, o: dict):
    """One Adam step (``step`` counts from 1) on already clipped gradients."""
    b1, b2, eps = o["b1"], o["b2"], o["eps"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, m, v)
    return params, m, v


@functools.partial(jax.jit, static_argnames=("family", "mkey", "dtype"))
def _block_grad(params, supports, x, y, *, family, mkey, dtype):
    m = dict(mkey)
    mod = model(family)
    dt = jnp.dtype(dtype)

    def f(p):
        cast = lambda a: a.astype(dt)
        return mod.loss(jax.tree.map(cast, p), m, tuple(map(cast, supports)),
                        cast(x), cast(y)).astype(jnp.float32)

    loss, grads = jax.value_and_grad(f)(params)
    return loss, grads


def follow(family: str, m: dict, o: dict, params, supports, batches, *,
           block: int, dtype: str = "float32",
           precision: str = "highest") -> dict:
    """Train from ``params`` over ``batches`` (a list of ``(x, y)``, one per
    step) and return ``{"losses", "grad1", "params"}``.

    ``o`` holds the optimizer's constants: ``lr``, ``warmup_steps``,
    ``total_steps``, ``min_ratio``, ``b1``, ``b2``, ``eps``, ``grad_clip``.
    """
    mkey = tuple(sorted(m.items()))
    zeros = jax.tree.map(jnp.zeros_like, params)
    mom, vel = zeros, zeros
    losses, grad1 = [], None
    with jax.default_matmul_precision(precision):
        for i, (x, y) in enumerate(batches):
            rows = x.shape[0]
            if rows % block:
                raise ValueError(f"batch {rows} is not a multiple of {block}")
            loss, grads = 0.0, None
            for lo in range(0, rows, block):
                l_b, g_b = _block_grad(params, supports, x[lo:lo + block],
                                       y[lo:lo + block], family=family,
                                       mkey=mkey, dtype=dtype)
                loss = loss + l_b
                grads = g_b if grads is None else jax.tree.map(jnp.add, grads, g_b)
            n_blocks = rows // block
            loss = loss / n_blocks
            grads = jax.tree.map(lambda g: g / n_blocks, grads)
            if o["grad_clip"] is not None:
                grads = clip(grads, o["grad_clip"])
            if grad1 is None:
                grad1 = grads
            params, mom, vel = adam(params, grads, mom, vel, i + 1,
                                    learning_rate(i, o), o)
            losses.append(float(loss))
    return {"losses": losses, "grad1": grad1, "params": params}
