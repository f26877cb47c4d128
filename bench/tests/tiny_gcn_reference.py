"""Plain reference of a test-only model family that is not DCGRU-shaped: each
node's input window, flattened, projected to ``channels``, averaged over one
hop of every support, then a ReLU and a read-out of the whole horizon.

    Z = relu(mean_s(S_s (X W1 + b1)))        Y = Z W2 + b2

The tests register it as ``bench.reference.tiny_gcn``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _weight(key, fan_in: int, fan_out: int):
    return jax.random.normal(key, (fan_in, fan_out), jnp.float32) / jnp.sqrt(
        jnp.float32(fan_in))


def init(key, m: dict):
    tf = m["input_len"] * m["in_features"]
    c, ho = m["channels"], m["horizon"] * m["out_features"]
    k1, k2 = jax.random.split(key)
    return {"mix": {"w": _weight(k1, tf, c), "b": jnp.zeros((c,), jnp.float32)},
            "head": {"w": _weight(k2, c, ho), "b": jnp.zeros((ho,), jnp.float32)}}


def loss(params, m: dict, supports, x, y):
    b, t, n, f = x.shape
    z = jnp.transpose(x, (0, 2, 1, 3)).reshape(b, n, t * f)
    z = z @ params["mix"]["w"] + params["mix"]["b"]
    z = sum(jnp.einsum("nm,bmc->bnc", s, z) for s in supports) / len(supports)
    out = jax.nn.relu(z) @ params["head"]["w"] + params["head"]["b"]
    pred = jnp.transpose(out.reshape(b, n, m["horizon"], m["out_features"]),
                         (0, 2, 1, 3))
    return jnp.mean(jnp.abs(pred - y[..., :m["out_features"]]))


def window_flops(m: dict, nnz: list[int], *, dense: bool = False) -> float:
    """The hop over each support (``2 nnz C``, backward 1x), the first
    projection (backward: its weight's gradient alone, the input is data) and
    the read-out (backward 2x)."""
    n, c = m["num_nodes"], m["channels"]
    entries = [n * n] * len(nnz) if dense else list(nnz)
    hops = sum(2.0 * e * c for e in entries)
    mix = 2.0 * n * m["input_len"] * m["in_features"] * c
    head = 2.0 * n * c * m["horizon"] * m["out_features"]
    return 2.0 * hops + 2.0 * mix + 3.0 * head
