"""DCRNN (Li et al., ICLR'18) — full encoder-decoder with DCGRU cells.

This is the paper's baseline model ("the original DCRNN"): an encoder stack of
DCGRU layers consumes the input sequence; a decoder stack (with output
projection) rolls out ``horizon`` predictions, teacher-forced during training
via scheduled sampling.

Diffusion convolution (the compute hot spot) follows the paper's dual
random-walk form:

    DConv(X; theta) = sum_{k=0..K} ( (D_O^{-1} A)^k X W_k^{fwd}
                                   + (D_I^{-1} A^T)^k X W_k^{rev} )

realised as a hop recurrence ``Z_k = S @ Z_{k-1}`` feeding one fused
projection.  The recurrence is exposed through ``repro.kernels.diffusion_conv``
so the Pallas TPU kernel and the jnp oracle are interchangeable here.

All functions are functional (params pytree in, arrays out) and jit/pjit-safe;
time loops use ``jax.lax.scan``.
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
class DCRNNConfig:
    num_nodes: int
    in_features: int = 2
    out_features: int = 1
    hidden: int = 64
    layers: int = 2
    max_diffusion_step: int = 2  # K
    input_len: int = 12
    horizon: int = 12
    use_pallas: bool = False  # route DConv through the Pallas kernel
    remat: bool = False  # checkpoint each time step (needed at PeMS scale)

    @property
    def n_supports(self) -> int:
        return 2  # forward + reverse random walks

    @property
    def n_matrices(self) -> int:
        # identity hop + K hops per support
        return 1 + self.n_supports * self.max_diffusion_step


# --------------------------------------------------------------------- params
def _dconv_params(rng, cfg: DCRNNConfig, in_dim: int, out_dim: int):
    k1, _ = jax.random.split(rng)
    fan_in = in_dim * cfg.n_matrices
    w = jax.random.normal(k1, (fan_in, out_dim), jnp.float32) * (1.0 / jnp.sqrt(fan_in))
    b = jnp.zeros((out_dim,), jnp.float32)
    return {"w": w, "b": b}


def _cell_params(rng, cfg: DCRNNConfig, in_dim: int):
    kr, ku, kc = jax.random.split(rng, 3)
    h = cfg.hidden
    return {
        "ru": _dconv_params(kr, cfg, in_dim + h, 2 * h),  # fused reset+update gates
        "c": _dconv_params(kc, cfg, in_dim + h, h),
    }


def init(rng, cfg: DCRNNConfig) -> dict[str, Any]:
    keys = jax.random.split(rng, 2 * cfg.layers + 1)
    enc = [_cell_params(keys[i], cfg, cfg.in_features if i == 0 else cfg.hidden)
           for i in range(cfg.layers)]
    dec = [_cell_params(keys[cfg.layers + i], cfg, cfg.out_features if i == 0 else cfg.hidden)
           for i in range(cfg.layers)]
    kp = keys[-1]
    proj = {
        "w": jax.random.normal(kp, (cfg.hidden, cfg.out_features), jnp.float32)
        * (1.0 / jnp.sqrt(cfg.hidden)),
        "b": jnp.zeros((cfg.out_features,), jnp.float32),
    }
    return {"encoder": enc, "decoder": dec, "proj": proj}


# ---------------------------------------------------------------------- cells
def _dconv(p, cfg: DCRNNConfig, supports, x):
    """x: [B, N, C_in] -> [B, N, C_out] via the shared diffusion-conv op."""
    return diffusion_conv(x, supports, p["w"], p["b"],
                          k_hops=cfg.max_diffusion_step, use_pallas=cfg.use_pallas)


@jax.named_scope(trace_names.DCGRU)
def dcgru_cell(p, cfg: DCRNNConfig, supports, x, h):
    """One DCGRU step.  x: [B, N, C], h: [B, N, H] -> new h."""
    xh = jnp.concatenate([x, h], axis=-1)
    ru = jax.nn.sigmoid(_dconv(p["ru"], cfg, supports, xh))
    r, u = jnp.split(ru, 2, axis=-1)
    xc = jnp.concatenate([x, r * h], axis=-1)
    c = jnp.tanh(_dconv(p["c"], cfg, supports, xc))
    return u * h + (1.0 - u) * c


def _stack_step(cells, cfg, supports, x, hs):
    """Run the layer stack for one time step.  hs: [L, B, N, H] list."""
    new_hs = []
    inp = x
    for p, h in zip(cells, hs):
        h2 = dcgru_cell(p, cfg, supports, inp, h)
        new_hs.append(h2)
        inp = h2
    return inp, new_hs


# -------------------------------------------------------------------- forward
def apply(
    params,
    cfg: DCRNNConfig,
    supports: tuple[jnp.ndarray, jnp.ndarray],
    x_seq: jnp.ndarray,
    *,
    y_teacher: jnp.ndarray | None = None,
    teacher_prob: float = 0.0,
    rng=None,
) -> jnp.ndarray:
    """x_seq: [B, T_in, N, F] -> predictions [B, horizon, N, out_features].

    ``y_teacher`` + ``teacher_prob`` implement scheduled sampling: with prob p
    the decoder input at step t is the ground truth instead of its own output.
    """
    B, _, N, _ = x_seq.shape
    h0 = [jnp.zeros((B, N, cfg.hidden), x_seq.dtype) for _ in range(cfg.layers)]

    # ---- encoder: scan over input time steps
    def enc_step(hs, xt):
        _, hs2 = _stack_step(params["encoder"], cfg, supports, xt, hs)
        return hs2, None

    if cfg.remat:
        # store only per-step carries; recompute DConv intermediates in bwd
        # (without this the scan saves every hop's [B, N, C] — measured
        # 209 GiB/device on the PeMS cell)
        enc_step = jax.checkpoint(enc_step)
    with jax.named_scope(trace_names.RNN_SCAN):
        hs, _ = jax.lax.scan(enc_step, h0, jnp.swapaxes(x_seq, 0, 1))

    # ---- decoder: roll out horizon steps
    go = jnp.zeros((B, N, cfg.out_features), x_seq.dtype)
    use_teacher = y_teacher is not None and teacher_prob > 0.0
    if use_teacher:
        coin = jax.random.bernoulli(rng, teacher_prob, (cfg.horizon,))
        teach = jnp.swapaxes(y_teacher, 0, 1)  # [T, B, N, F_out]
    else:
        coin = jnp.zeros((cfg.horizon,), bool)
        teach = jnp.zeros((cfg.horizon, B, N, cfg.out_features), x_seq.dtype)

    def dec_step(carry, inputs):
        hs, prev = carry
        use_t, y_t = inputs
        inp = jnp.where(use_t, y_t, prev)
        top, hs2 = _stack_step(params["decoder"], cfg, supports, inp, hs)
        out = top @ params["proj"]["w"] + params["proj"]["b"]
        return (hs2, out), out

    if cfg.remat:
        dec_step = jax.checkpoint(dec_step)
    with jax.named_scope(trace_names.RNN_SCAN):
        (_, _), outs = jax.lax.scan(dec_step, (hs, go), (coin, teach))
    return jnp.swapaxes(outs, 0, 1)  # [B, horizon, N, F_out]


# ----------------------------------------------------------------------- loss
def mae_loss(pred: jnp.ndarray, target: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(jnp.abs(pred - target))


@partial(jax.jit, static_argnames=("cfg",))
def loss_fn(params, cfg: DCRNNConfig, supports, x, y):
    pred = apply(params, cfg, supports, x)
    return mae_loss(pred, y[..., : cfg.out_features])
