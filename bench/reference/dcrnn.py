"""Plain DCRNN (Li et al., ICLR'18): an encoder of ``layers`` stacked DCGRU
cells over the input window, a decoder of the same depth that rolls out
``horizon`` steps from a zero "go" symbol, feeding back its own read-out
(no teacher forcing), and MAE against feature 0 of the target window.

Weights are drawn from the key in the released model's order (encoder
layers, decoder layers, read-out; ru then c inside a cell).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import dcgru


def _cell_params(key, m: dict, in_dim: int):
    h = m["hidden"]
    n_mat = 1 + 2 * m["max_diffusion_step"]
    k_ru, _, k_c = jax.random.split(key, 3)
    fan_in = (in_dim + h) * n_mat
    return {
        "ru": {"w": dcgru.normal_weight(jax.random.split(k_ru)[0], fan_in, 2 * h),
               "b": jnp.zeros((2 * h,), jnp.float32)},
        "c": {"w": dcgru.normal_weight(jax.random.split(k_c)[0], fan_in, h),
              "b": jnp.zeros((h,), jnp.float32)},
    }


def init(key, m: dict):
    layers, h = m["layers"], m["hidden"]
    keys = jax.random.split(key, 2 * layers + 1)
    enc = [_cell_params(keys[i], m, m["in_features"] if i == 0 else h)
           for i in range(layers)]
    dec = [_cell_params(keys[layers + i], m, m["out_features"] if i == 0 else h)
           for i in range(layers)]
    proj = {"w": dcgru.normal_weight(keys[-1], h, m["out_features"]),
            "b": jnp.zeros((m["out_features"],), jnp.float32)}
    return {"encoder": enc, "decoder": dec, "proj": proj}


def _stack(cells, supports, x, hs, k):
    new = []
    for p, h in zip(cells, hs):
        x = dcgru.cell(p, supports, x, h, k)
        new.append(x)
    return x, new


def predict(params, m: dict, supports, x):
    """x: [B, T_in, N, F] -> [B, horizon, N, out]."""
    b, _, n, _ = x.shape
    k = m["max_diffusion_step"]
    hs = [jnp.zeros((b, n, m["hidden"]), x.dtype) for _ in range(m["layers"])]

    def enc_step(hs, xt):
        return _stack(params["encoder"], supports, xt, hs, k)[1], None

    hs, _ = jax.lax.scan(enc_step, hs, jnp.swapaxes(x, 0, 1))

    def dec_step(carry, _):
        hs, prev = carry
        top, hs = _stack(params["decoder"], supports, prev, hs, k)
        out = top @ params["proj"]["w"] + params["proj"]["b"]
        return (hs, out), out

    go = jnp.zeros((b, n, m["out_features"]), x.dtype)
    _, outs = jax.lax.scan(dec_step, (hs, go), None, length=m["horizon"])
    return jnp.swapaxes(outs, 0, 1)


def loss(params, m: dict, supports, x, y):
    return dcgru.mae(predict(params, m, supports, x), y[..., :m["out_features"]])


def cells(m: dict) -> list[tuple[int, int]]:
    """``(input width, calls per window)`` of every DCGRU cell the model runs
    for one window, for the FLOP count and ``bench/hop_roofline.py``."""
    h, deep = m["hidden"], m["layers"] - 1
    return [(m["in_features"] + h, m["input_len"]),
            (2 * h, deep * m["input_len"]),
            (m["out_features"] + h, m["horizon"]),
            (2 * h, deep * m["horizon"])]


def window_flops(m: dict, nnz: list[int], *, dense: bool = False) -> float:
    """Useful FLOPs of one training window, forward plus backward, the
    supports counted at ``nnz`` nonzeros (``dense``: all ``N^2``); the
    read-out runs once per output step."""
    return dcgru.window_flops(m, cells(m), m["horizon"], nnz, dense=dense)
