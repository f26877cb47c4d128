"""Plain PGT-DCRNN (PGT-I paper, section 3): one DCGRU layer run stepwise over
the input window, a linear read-out at every step, MAE against the next
``horizon`` steps' feature 0.

Weights are drawn from the key in the order the paper's released model draws
them (ru, c, read-out), so that the reference and the system under test start
from the same weights without the reference reading the system's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import dcgru


def init(key, m: dict):
    f, h, out = m["in_features"], m["hidden"], m["out_features"]
    n_mat = 1 + 2 * m["max_diffusion_step"]
    k_ru, k_c, k_p = jax.random.split(key, 3)
    return {
        "ru": {"w": dcgru.normal_weight(k_ru, (f + h) * n_mat, 2 * h),
               "b": jnp.zeros((2 * h,), jnp.float32)},
        "c": {"w": dcgru.normal_weight(k_c, (f + h) * n_mat, h),
              "b": jnp.zeros((h,), jnp.float32)},
        "proj": {"w": dcgru.normal_weight(k_p, h, out),
                 "b": jnp.zeros((out,), jnp.float32)},
    }


def predict(params, m: dict, supports, x):
    """x: [B, T, N, F] -> [B, T, N, out]."""
    b, _, n, _ = x.shape
    k = m["max_diffusion_step"]

    def step(h, xt):
        h = dcgru.cell(params, supports, xt, h, k)
        return h, h @ params["proj"]["w"] + params["proj"]["b"]

    h0 = jnp.zeros((b, n, m["hidden"]), x.dtype)
    _, outs = jax.lax.scan(step, h0, jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(outs, 0, 1)


def loss(params, m: dict, supports, x, y):
    return dcgru.mae(predict(params, m, supports, x), y[..., :m["out_features"]])


def cells(m: dict) -> list[tuple[int, int]]:
    """``(input width, calls per window)`` of every DCGRU cell the model runs
    for one window, for the FLOP count and ``bench/hop_roofline.py``."""
    return [(m["in_features"] + m["hidden"], m["input_len"])]


def window_flops(m: dict, nnz: list[int], *, dense: bool = False) -> float:
    """Useful FLOPs of one training window, forward plus backward, the
    supports counted at ``nnz`` nonzeros (``dense``: all ``N^2``); the
    read-out runs once per input step."""
    return dcgru.window_flops(m, cells(m), m["input_len"], nnz, dense=dense)
