"""The program side of the test-only ``tiny_gcn`` family
(``tiny_gcn_reference.py`` has the equations), written as a program module
is: a config class, ``init(key, cfg)`` and ``loss_fn(params, cfg, supports,
x, y)``."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TinyGCNConfig:
    num_nodes: int
    in_features: int
    out_features: int
    input_len: int
    horizon: int
    channels: int


def init(key, cfg: TinyGCNConfig):
    tf = cfg.input_len * cfg.in_features
    ho = cfg.horizon * cfg.out_features
    k1, k2 = jax.random.split(key)
    w1 = jax.random.normal(k1, (tf, cfg.channels), jnp.float32) / jnp.sqrt(
        jnp.float32(tf))
    w2 = jax.random.normal(k2, (cfg.channels, ho), jnp.float32) / jnp.sqrt(
        jnp.float32(cfg.channels))
    return {"mix": {"w": w1, "b": jnp.zeros((cfg.channels,), jnp.float32)},
            "head": {"w": w2, "b": jnp.zeros((ho,), jnp.float32)}}


def loss_fn(params, cfg: TinyGCNConfig, supports, x, y):
    t, f, c = cfg.input_len, cfg.in_features, cfg.channels
    w1 = params["mix"]["w"].reshape(t, f, c)
    z = jnp.einsum("btnf,tfc->bnc", x, w1) + params["mix"]["b"]
    z = jnp.einsum("snm,bmc->bnc", jnp.stack(supports), z) / len(supports)
    w2 = params["head"]["w"].reshape(c, cfg.horizon, cfg.out_features)
    pred = jnp.einsum("bnc,cho->bhno", jax.nn.relu(z), w2)
    pred = pred + params["head"]["b"].reshape(1, cfg.horizon, 1, cfg.out_features)
    return jnp.mean(jnp.abs(pred - y[..., :cfg.out_features]))
