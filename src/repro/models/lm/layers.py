"""Shared LM layers: norms, embeddings, RoPE, MLP variants."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * gain


def init_linear(rng, d_in, d_out, *, bias=False, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d_in)
    p = {"w": (jax.random.normal(rng, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ----------------------------------------------------------------------- RoPE
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_dim(rotations: float, head_dim: int, theta: float, original: int) -> float:
    """The rotary dimension that turns ``rotations`` times over ``original``
    positions."""
    return (head_dim * math.log(original / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_freqs(head_dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Inverse frequencies ``[D/2]``.  With YaRN ``scaling`` (a
    ``RopeScaling``), frequencies that turn fewer than ``beta_slow`` times
    over the original context are divided by ``factor``, those that turn more
    than ``beta_fast`` times are kept, and a linear ramp joins them."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))
    if scaling is None:
        return freqs
    orig = scaling.original_max_position_embeddings
    low = max(math.floor(_yarn_dim(scaling.beta_fast, head_dim, theta, orig)), 0)
    high = min(math.ceil(_yarn_dim(scaling.beta_slow, head_dim, theta, orig)),
               head_dim - 1)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def yarn_softmax_factor(scaling) -> float:
    """YaRN's factor on the softmax scale: ``mscale(mscale_all_dim)**2``."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling=None) -> jnp.ndarray:
    """x: [B, S, H, D], positions: [B, S] (absolute token positions)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling is not None:
        amp = (yarn_mscale(scaling.factor, scaling.mscale)
               / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if amp != 1.0:
            cos, sin = cos * amp, sin * amp
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------------ MLP
def init_mlp(rng, d_model, d_ff, kind: str, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(rng, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "wi": init_linear(k1, d_model, d_ff, dtype=dtype),
            "wg": init_linear(k2, d_model, d_ff, dtype=dtype),
            "wo": init_linear(k3, d_ff, d_model, dtype=dtype),
        }
    return {
        "wi": init_linear(k1, d_model, d_ff, dtype=dtype),
        "wo": init_linear(k3, d_ff, d_model, dtype=dtype),
    }


def mlp(p, x, kind: str):
    if kind == "swiglu":
        return linear(p["wo"], jax.nn.silu(linear(p["wg"], x)) * linear(p["wi"], x))
    if kind == "geglu":
        return linear(p["wo"], jax.nn.gelu(linear(p["wg"], x)) * linear(p["wi"], x))
    if kind == "gelu":
        return linear(p["wo"], jax.nn.gelu(linear(p["wi"], x)))
    if kind == "relu_sq":
        return linear(p["wo"], jnp.square(jax.nn.relu(linear(p["wi"], x))))
    raise ValueError(kind)
