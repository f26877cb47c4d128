"""ST-LLM (Liu et al. 2024, arXiv 2401.10134) — the paper's §5.5 scaling-study
model, with a registry architecture as its LLM backbone.

Spatial-temporal tokenisation, as the released model does it: each sensor's
input window ``[T', F]`` becomes one token through a linear embedding; a
time-of-day embedding (``steps_per_day`` slots, indexed by the last input
step's feature 1) and a per-sensor node embedding sit beside it; the three
``embed_width`` vectors are concatenated and a linear fusion maps them to the
backbone's width.  The token sequence (one token per sensor, positions the
sensor index) runs through the backbone's blocks under their causal mask, as
ST-LLM runs GPT-2's; a final RMSNorm and a linear regression head map each
sensor's token to its ``horizon`` forecast, MAE against feature 0.

The backbone is an LM architecture of the registry (``repro.configs``) by id,
at its published widths, with no token table and no LM head.  The
benchmark's cell runs DeepSeek-V2-Lite (MLA, 64 routed experts top-6 with 2
shared, YaRN rope) cut in depth, holding 8 of the 64 routed experts.  MoE
layers dispatch per window: each window is one dispatch group with its own
capacity, so windows stay independent.

Departures from the released code: the backbone is the registry's (not
GPT-2); the time-of-day slot rounds ``feature 1 × steps_per_day`` instead of
truncating it (``k/288·288`` can land an ulp below ``k`` in float32); no
day-of-week embedding (the series carries no weekday); no dropout; every
weight trains (no partially frozen attention).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.models.lm import model as lm
from repro.models.lm.config import LMConfig
from repro.models.lm.layers import init_linear, linear


@dataclasses.dataclass(frozen=True)
class STLLMConfig:
    num_nodes: int
    in_features: int = 2
    out_features: int = 1
    input_len: int = 12
    horizon: int = 12
    backbone: str = "deepseek-v2-lite-16b"  # registry id
    layers: int | None = None        # the backbone's depth (None: published)
    experts_held: int | None = None  # routed experts held per MoE layer
    remat: bool = False
    embed_width: int = 256           # ST-LLM's gpt_channel
    steps_per_day: int = 288
    smoke: bool = False              # the registry's small widths (CPU tests)

    def backbone_config(self) -> LMConfig:
        arch = get_arch(self.backbone)
        cfg = arch.smoke_config() if self.smoke else arch.lm
        cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
        if self.layers is not None:
            cfg = dataclasses.replace(cfg, layers=self.layers)
        if self.experts_held is not None:
            cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, experts_held=self.experts_held))
        return cfg


def glorot(key, shape):
    """Xavier-uniform, as the released model initialises its embeddings."""
    limit = (6.0 / (shape[0] + shape[1])) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def init(rng, cfg: STLLMConfig) -> dict[str, Any]:
    k_tok, k_tod, k_node, k_fuse, k_back, k_head = jax.random.split(rng, 6)
    bcfg = cfg.backbone_config()
    w = cfg.embed_width
    return {
        "token": init_linear(k_tok, cfg.input_len * cfg.in_features, w, bias=True),
        "tod": glorot(k_tod, (cfg.steps_per_day, w)),
        "node": glorot(k_node, (cfg.num_nodes, w)),
        "fusion": init_linear(k_fuse, 3 * w, bcfg.d_model, bias=True),
        "backbone": lm.init(k_back, bcfg, tokens=False),
        "head": init_linear(k_head, bcfg.d_model, cfg.horizon * cfg.out_features,
                            bias=True),
    }


def forward(params, cfg: STLLMConfig, x_seq):
    """x_seq: [B, T', N, F] -> (prediction [B, horizon, N, out_features],
    MoE (token, expert) pairs dropped at capacity)."""
    b, t, n, f = x_seq.shape
    tokens = jnp.transpose(x_seq, (0, 2, 1, 3)).reshape(b, n, t * f)
    slot = jnp.clip(jnp.round(x_seq[:, -1, :, 1] * cfg.steps_per_day),
                    0, cfg.steps_per_day - 1).astype(jnp.int32)  # [B, N]
    st = jnp.concatenate([
        linear(params["token"], tokens),
        params["tod"][slot],
        jnp.broadcast_to(params["node"][None], (b, n, cfg.embed_width)),
    ], axis=-1)
    h = linear(params["fusion"], st)
    h, _, dropped = lm.backbone(params["backbone"], cfg.backbone_config(), h,
                                remat=cfg.remat, moe_groups=b)
    out = linear(params["head"], h).reshape(b, n, cfg.horizon, cfg.out_features)
    return jnp.transpose(out, (0, 2, 1, 3)), dropped


def apply(params, cfg: STLLMConfig, x_seq):
    """x_seq: [B, T', N, F] -> [B, horizon, N, out_features]."""
    return forward(params, cfg, x_seq)[0]


@partial(jax.jit, static_argnames=("cfg",))
def loss_fn(params, cfg: STLLMConfig, supports, x, y):
    """MAE of the forecast against ``y``'s feature 0.  ``supports`` is unused:
    ST-LLM learns its graph in the node embedding and the attention; it is
    taken so that every ST model has the same loss signature."""
    pred = apply(params, cfg, x)
    return jnp.mean(jnp.abs(pred - y[..., : cfg.out_features]))
