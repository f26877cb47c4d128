"""PGT-DCRNN — the paper's lightweight variant (§3).

A single spatiotemporal diffusion-conv recurrent layer processed *stepwise*:
the hidden state is carried across the input sequence and an output is emitted
at every step, forming a prediction sequence of equal length to the input
(the paper's modification for batched seq2seq prediction).  No encoder-decoder
structure — deliberately simpler and faster than full DCRNN, matching the
15.3x runtime gap reported in Table 2.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro import trace_names
from repro.kernels.diffusion_conv import diffusion_conv


@dataclasses.dataclass(frozen=True)
class PGTDCRNNConfig:
    num_nodes: int
    in_features: int = 2
    out_features: int = 1
    hidden: int = 64
    max_diffusion_step: int = 2
    input_len: int = 12
    horizon: int = 12
    use_pallas: bool = False
    remat: bool = False  # checkpoint each time step (needed at PeMS scale)

    @property
    def n_matrices(self) -> int:
        return 1 + 2 * self.max_diffusion_step


def init(rng, cfg: PGTDCRNNConfig) -> dict[str, Any]:
    kru, kc, kp = jax.random.split(rng, 3)
    in_dim = (cfg.in_features + cfg.hidden) * cfg.n_matrices

    def dconv(k, out):
        return {
            "w": jax.random.normal(k, (in_dim, out), jnp.float32) / jnp.sqrt(in_dim),
            "b": jnp.zeros((out,), jnp.float32),
        }

    return {
        "ru": dconv(kru, 2 * cfg.hidden),
        "c": dconv(kc, cfg.hidden),
        "proj": {
            "w": jax.random.normal(kp, (cfg.hidden, cfg.out_features), jnp.float32)
            / jnp.sqrt(cfg.hidden),
            "b": jnp.zeros((cfg.out_features,), jnp.float32),
        },
    }


@jax.named_scope(trace_names.DCGRU)
def _cell(params, cfg: PGTDCRNNConfig, supports, x, h):
    xh = jnp.concatenate([x, h], axis=-1)
    ru = jax.nn.sigmoid(
        diffusion_conv(xh, supports, params["ru"]["w"], params["ru"]["b"],
                       k_hops=cfg.max_diffusion_step, use_pallas=cfg.use_pallas))
    r, u = jnp.split(ru, 2, axis=-1)
    xc = jnp.concatenate([x, r * h], axis=-1)
    c = jnp.tanh(
        diffusion_conv(xc, supports, params["c"]["w"], params["c"]["b"],
                       k_hops=cfg.max_diffusion_step, use_pallas=cfg.use_pallas))
    return u * h + (1.0 - u) * c


def apply(params, cfg: PGTDCRNNConfig, supports, x_seq: jnp.ndarray) -> jnp.ndarray:
    """x_seq: [B, T, N, F] -> [B, T, N, out_features] (stepwise predictions)."""
    bsz, _, n, _ = x_seq.shape
    h0 = jnp.zeros((bsz, n, cfg.hidden), x_seq.dtype)

    def step(h, xt):
        h2 = _cell(params, cfg, supports, xt, h)
        out = h2 @ params["proj"]["w"] + params["proj"]["b"]
        return h2, out

    if cfg.remat:
        step = jax.checkpoint(step)
    with jax.named_scope(trace_names.RNN_SCAN):
        _, outs = jax.lax.scan(step, h0, jnp.swapaxes(x_seq, 0, 1))
    return jnp.swapaxes(outs, 0, 1)


@partial(jax.jit, static_argnames=("cfg",))
def loss_fn(params, cfg: PGTDCRNNConfig, supports, x, y):
    pred = apply(params, cfg, supports, x)
    return jnp.mean(jnp.abs(pred - y[..., : cfg.out_features]))
