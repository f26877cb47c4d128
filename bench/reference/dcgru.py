"""Plain diffusion convolution and DCGRU cell (Li et al., ICLR'18, eqs. 2-3).

Straightforward jnp, written from the paper's equations; nothing here comes
from the program.  ``X`` is ``[B, N, C]``; each support ``S`` is ``[N, N]``.

    Z_0 = X,  Z_k = S Z_{k-1}                      (k = 1..K, per support)
    DConv(X) = [Z_0 | S0 hops 1..K | S1 hops 1..K] W + b
    r, u = sigmoid(DConv_ru([X, H]))
    c    = tanh(DConv_c([X, r * H]))
    H'   = u * H + (1 - u) * c
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def normal_weight(key, fan_in: int, fan_out: int):
    """The initialisation both models use: N(0, 1) / sqrt(fan_in)."""
    return jax.random.normal(key, (fan_in, fan_out), jnp.float32) / jnp.sqrt(
        jnp.float32(fan_in))


def dconv(x, supports, w, b, k_hops: int):
    feats = [x]
    for s in supports:
        z = x
        for _ in range(k_hops):
            z = jnp.einsum("mn,bnc->bmc", s, z)
            feats.append(z)
    return jnp.concatenate(feats, axis=-1) @ w + b


def cell(p, supports, x, h, k_hops: int):
    xh = jnp.concatenate([x, h], axis=-1)
    ru = jax.nn.sigmoid(dconv(xh, supports, p["ru"]["w"], p["ru"]["b"], k_hops))
    r, u = jnp.split(ru, 2, axis=-1)
    xc = jnp.concatenate([x, r * h], axis=-1)
    c = jnp.tanh(dconv(xc, supports, p["c"]["w"], p["c"]["b"], k_hops))
    return u * h + (1.0 - u) * c


def mae(pred, target):
    return jnp.mean(jnp.abs(pred - target))
