"""Plain diffusion convolution and DCGRU cell (Li et al., ICLR'18, eqs. 2-3).

Straightforward jnp, written from the paper's equations; nothing here comes
from the program.  ``X`` is ``[B, N, C]``; each support ``S`` is ``[N, N]``.

    Z_0 = X,  Z_k = S Z_{k-1}                      (k = 1..K, per support)
    DConv(X) = [Z_0 | S0 hops 1..K | S1 hops 1..K] W + b
    r, u = sigmoid(DConv_ru([X, H]))
    c    = tanh(DConv_c([X, r * H]))
    H'   = u * H + (1 - u) * c

Beside the cell, its useful FLOPs.  A DCGRU cell runs two diffusion
convolutions, ``ru`` (input width ``C``, output ``2h``) and ``c`` (``C`` ->
``h``).  For one window of ``N`` nodes:

- a diffusion hop ``Z_k = S Z_{k-1}`` costs ``2 nnz(S) C``; there are ``K``
  per support.  Its backward pass is ``S^T dZ_k`` alone, since the supports
  take no gradient: 1x the forward.
- the projection of the ``(1 + S K) C`` stacked hops to the output width
  costs ``2 N (1 + S K) C out`` forward and 2x that backward (input and
  weight gradients): 3x in all.  The read-out (``h`` -> ``out``) likewise.

With ``dense=True`` every support counts ``N^2`` entries, which is what a
dense matmul executes.  Recomputation (remat) is not useful work and is not
counted; nor are element-wise gates.
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


def dconv_forward(nodes: int, c_in: int, c_out: int, k_hops: int,
                  support_entries: list[int]) -> tuple[float, float]:
    """``(hop FLOPs, projection FLOPs)`` of one diffusion convolution over one
    window, forward only."""
    hops = sum(2.0 * e * c_in * k_hops for e in support_entries)
    n_mat = 1 + len(support_entries) * k_hops
    return hops, 2.0 * nodes * n_mat * c_in * c_out


def cell_forward(nodes: int, c_in: int, hidden: int, k_hops: int,
                 support_entries: list[int]) -> tuple[float, float]:
    """``(hop, projection)`` FLOPs of one DCGRU cell (ru + c), forward."""
    ru = dconv_forward(nodes, c_in, 2 * hidden, k_hops, support_entries)
    c = dconv_forward(nodes, c_in, hidden, k_hops, support_entries)
    return ru[0] + c[0], ru[1] + c[1]


def window_flops(m: dict, cells, readouts: int, nnz: list[int], *,
                 dense: bool = False) -> float:
    """Forward plus backward FLOPs of one training window of a DCGRU model.

    ``cells`` is ``[(input width, calls per window), ...]`` and ``readouts``
    the read-out projections per window, both from the model's reference;
    ``nnz`` holds each support's nonzero count.
    """
    n = m["num_nodes"]
    entries = [n * n] * len(nnz) if dense else list(nnz)
    hops = proj = 0.0
    for c_in, calls in cells:
        h, p = cell_forward(n, c_in, m["hidden"], m["max_diffusion_step"],
                            entries)
        hops += calls * h
        proj += calls * p
    proj += readouts * 2.0 * n * m["hidden"] * m["out_features"]
    return 2.0 * hops + 3.0 * proj
