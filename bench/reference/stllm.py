"""Plain ST-LLM (Liu et al. 2024, arXiv 2401.10134) over a DeepSeek-V2-Lite
backbone (arXiv 2405.04434; the published config.json's numbers below).

Per window of ``N`` sensors (one token each, position = sensor index):

    token   = x[:, :, n, :].flatten() W_tok + b      (T'·F -> w)
    tod     = E_tod[round(x[-1, n, 1] · 288)]        (288 slots, w wide)
    node    = E_node[n]                              (w wide)
    h       = [token | tod | node] W_fuse + b        (3w -> d)
    h       = blocks(h)                              (causal over sensors)
    y_hat   = rms(h) W_head + b                      (d -> horizon)
    loss    = mean |y_hat - y[..., 0]|

A block is ``h + MLA(rms(h))`` then ``+ FFN(rms(h))``.  MLA (no q LoRA):
``q = h W_q`` split into ``[q_nope | q_rope]`` per head; ``[c | k_rope] =
h W_dkv``, ``[k_nope | v] = rms(c) W_ukv`` per head; YaRN rope on ``q_rope``
and the one shared ``k_rope``; scores ``(q_nope·k_nope + q_rope·k_rope) ·
(dn + dr)^-1/2 · mscale(0.707)^2`` with mscale ``0.1·0.707·ln 40 + 1``;
causal softmax; output ``W_o``.  The leading ``first_k_dense_replace`` layers'
FFN is SwiGLU ``W_down(silu(h W_gate) · h W_up)`` at ``intermediate_size``.
The others are MoE: a softmax router over all 64 experts, top-6 weights not
renormalised (``norm_topk_prob`` false, times ``routed_scaling_factor``), and
the SwiGLU of each expert this chip holds, computed on every token and
weighted to the tokens that chose it and that its capacity keeps: per window
and expert, the first ``C`` tokens in token order, ``C = max(128, C')``
rounded up to a multiple of 128 from ``C' = int(N · 6 / 64 · 1.25)``.  The 2
shared experts are one SwiGLU of width ``2 · moe_intermediate_size`` on
every token.  RMSNorm eps 1e-6 throughout.

Weights are drawn from the key in the order the program under test draws
them, written out here without its code.  The rope halves are split
``[first half | second half]`` where the released code interleaves them: a
fixed permutation of the rope columns of ``W_q`` and ``W_dkv``, the same
model under random weights.  Each layer is recomputed in the backward pass
(``jax.checkpoint``), which changes no value.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# config.json of deepseek-ai/DeepSeek-V2-Lite, the keys the model reads.
PUBLISHED = {
    "deepseek-v2-lite-16b": {
        "hidden_size": 2048, "num_attention_heads": 16, "num_hidden_layers": 27,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "intermediate_size": 10944,
        "moe_intermediate_size": 1408, "n_routed_experts": 64,
        "num_experts_per_tok": 6, "n_shared_experts": 2,
        "first_k_dense_replace": 1, "norm_topk_prob": False,
        "routed_scaling_factor": 1.0, "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "rope_scaling": {"factor": 40.0, "original_max_position_embeddings": 4096,
                         "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 0.707,
                         "mscale_all_dim": 0.707},
    },
}
CAPACITY_FACTOR = 1.25  # the program's, not published: DeepSeek-V2 drops none


def arch(m: dict) -> dict:
    """The backbone's published numbers, with the depth and the experts held
    from ``m``."""
    a = {"capacity_factor": CAPACITY_FACTOR, **PUBLISHED[m["backbone"]]}
    a["layers"] = m.get("layers") or a["num_hidden_layers"]
    a["held"] = m.get("experts_held") or a["n_routed_experts"]
    return a


# ---------------------------------------------------------------- weights
def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (1.0 / jnp.sqrt(fan_in))


def _linear(key, d_in, d_out, bias=False):
    p = {"w": _normal(key, (d_in, d_out), d_in)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def _glorot(key, shape):
    limit = (6.0 / (shape[0] + shape[1])) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def _swiglu_init(key, d, width):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wi": _linear(k1, d, width), "wg": _linear(k2, d, width),
            "wo": _linear(k3, width, d)}


def _layer_init(key, a, moe: bool):
    d, h = a["hidden_size"], a["num_attention_heads"]
    dn, dr, dv, r = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                     a["v_head_dim"], a["kv_lora_rank"])
    km, kf = jax.random.split(key)
    kq, kd, ku, ko = jax.random.split(km, 4)
    p = {"norm1": jnp.ones((d,), jnp.float32),
         "attn": {"wq": _linear(kq, d, h * (dn + dr)),
                  "wdkv": _linear(kd, d, r + dr),
                  "ckv_norm": jnp.ones((r,), jnp.float32),
                  "wukv": _linear(ku, r, h * (dn + dv)),
                  "wo": _linear(ko, h * dv, d)},
         "norm2": jnp.ones((d,), jnp.float32)}
    if not moe:
        p["mlp"] = _swiglu_init(kf, d, a["intermediate_size"])
        return p
    e, de, held = a["n_routed_experts"], a["moe_intermediate_size"], a["held"]
    kr, ke, ks = jax.random.split(kf, 3)
    k1, k2, k3 = jax.random.split(ke, 3)
    p["moe"] = {"router": _linear(kr, d, e),
                "wi": _normal(k1, (e, d, de), d)[:held],
                "wg": _normal(k2, (e, d, de), d)[:held],
                "wo": _normal(k3, (e, de, d), d)[:held],
                "shared": _swiglu_init(ks, d, a["n_shared_experts"] * de)}
    return p


def _stages(a):
    """``[(moe, layers)]``: the dense layers, then the MoE layers."""
    dense = min(a["first_k_dense_replace"], a["layers"])
    return [(moe, n) for moe, n in ((False, dense), (True, a["layers"] - dense)) if n]


def init(key, m: dict):
    return _init(key, tuple(sorted(m.items())))


@functools.partial(jax.jit, static_argnums=1)
def _init(key, m_items):
    m = dict(m_items)
    a = arch(m)
    w, d = m["embed_width"], a["hidden_size"]
    k_tok, k_tod, k_node, k_fuse, k_back, k_head = jax.random.split(key, 6)
    plan = _stages(a)
    ks = jax.random.split(k_back, len(plan) + 3)
    stages = []
    for si, (moe, n) in enumerate(plan):
        layers = [_layer_init(jax.random.split(k, 1)[0], a, moe)
                  for k in jax.random.split(ks[3 + si], n)]
        stages.append({"sub0": jax.tree.map(lambda *x: jnp.stack(x), *layers)})
    return {
        "token": _linear(k_tok, m["input_len"] * m["in_features"], w, bias=True),
        "tod": _glorot(k_tod, (m["steps_per_day"], w)),
        "node": _glorot(k_node, (m["num_nodes"], w)),
        "fusion": _linear(k_fuse, 3 * w, d, bias=True),
        "backbone": {"final_norm": jnp.ones((d,), jnp.float32), "stages": stages},
        "head": _linear(k_head, d, m["horizon"] * m["out_features"], bias=True),
    }


# ---------------------------------------------------------------- forward
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, s: dict):
    """YaRN's inverse frequencies (DeepSeek-V2's modelling code)."""
    def turn_dim(rot):
        return (dim * math.log(s["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(theta)))
    low = max(math.floor(turn_dim(s["beta_fast"])), 0)
    high = min(math.ceil(turn_dim(s["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
                    0.0, 1.0)
    return extra / s["factor"] * ramp + extra * (1.0 - ramp)


def _rope(x, a):
    """x: [B, S, H, dr], position = index along S."""
    s = a["rope_scaling"]
    freqs = yarn_inv_freq(x.shape[-1], a["rope_theta"], s)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    amp = _mscale(s["factor"], s["mscale"]) / _mscale(s["factor"], s["mscale_all_dim"])
    cos = (jnp.cos(ang) * amp)[None, :, None].astype(x.dtype)
    sin = (jnp.sin(ang) * amp)[None, :, None].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def softmax_scale(a) -> float:
    s = a["rope_scaling"]
    return ((a["qk_nope_head_dim"] + a["qk_rope_head_dim"]) ** -0.5
            * _mscale(s["factor"], s["mscale_all_dim"]) ** 2)


def _mla(p, x, a):
    b, n, _ = x.shape
    h, dn, dr, dv, r = (a["num_attention_heads"], a["qk_nope_head_dim"],
                        a["qk_rope_head_dim"], a["v_head_dim"], a["kv_lora_rank"])
    q = (x @ p["wq"]["w"]).reshape(b, n, h, dn + dr)
    qn, qr = q[..., :dn], _rope(q[..., dn:], a)
    ckv = x @ p["wdkv"]["w"]
    c = _rms(ckv[..., :r], p["ckv_norm"], a["rms_norm_eps"])
    kr = _rope(ckv[..., None, r:], a)[:, :, 0]          # [B, N, dr], all heads
    kv = (c @ p["wukv"]["w"]).reshape(b, n, h, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn)
              + jnp.einsum("bqhd,bkd->bhqk", qr, kr)) * softmax_scale(a)
    causal = jnp.tril(jnp.ones((n, n), bool))
    scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(b, n, h * dv) @ p["wo"]["w"]


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["wg"]["w"]) * (x @ p["wi"]["w"])) @ p["wo"]["w"]


def capacity(tokens: int, a) -> int:
    c = int(tokens * a["num_experts_per_tok"] / a["n_routed_experts"]
            * a["capacity_factor"])
    return max(128, -(-c // 128) * 128)


def _moe(p, x, a):
    """x: [B, N, d], one dispatch group per window."""
    probs = jax.nn.softmax(x @ p["router"]["w"], axis=-1)       # [B, N, 64]
    top_w, top_ix = jax.lax.top_k(probs, a["num_experts_per_tok"])
    if a["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    else:
        top_w = top_w * a["routed_scaling_factor"]
    experts = jnp.arange(a["held"])[:, None, None, None]
    chose = top_ix[None] == experts                               # [held, B, N, k]
    picked = jnp.any(chose, axis=-1)
    rank = jnp.cumsum(picked, axis=2) - 1                         # token order
    weight = jnp.where(picked & (rank < capacity(x.shape[1], a)),
                       jnp.sum(jnp.where(chose, top_w[None], 0), axis=-1), 0)
    up = jnp.einsum("bnd,edf->ebnf", x, p["wi"])
    gate = jnp.einsum("bnd,edf->ebnf", x, p["wg"])
    out = jnp.einsum("ebnf,efd->ebnd", jax.nn.silu(gate) * up, p["wo"])
    return _swiglu(p["shared"], x) + jnp.einsum("ebn,ebnd->bnd", weight, out)


def _layer(p, x, a, moe: bool):
    eps = a["rms_norm_eps"]
    x = x + _mla(p["attn"], _rms(x, p["norm1"], eps), a)
    h = _rms(x, p["norm2"], eps)
    return x + (_moe(p["moe"], h, a) if moe else _swiglu(p["mlp"], h))


def predict(params, m: dict, x):
    """x: [B, T, N, F] -> [B, horizon, N, out]."""
    a = arch(m)
    b, t, n, f = x.shape
    tokens = jnp.transpose(x, (0, 2, 1, 3)).reshape(b, n, t * f)
    slot = jnp.clip(jnp.round(x[:, -1, :, 1] * m["steps_per_day"]),
                    0, m["steps_per_day"] - 1).astype(jnp.int32)
    node = jnp.broadcast_to(params["node"][None], (b, n, params["node"].shape[1]))
    st = jnp.concatenate([tokens @ params["token"]["w"] + params["token"]["b"],
                          params["tod"][slot], node], axis=-1)
    h = st @ params["fusion"]["w"] + params["fusion"]["b"]
    for (moe, count), stage in zip(_stages(a), params["backbone"]["stages"]):
        for i in range(count):
            layer = jax.tree.map(lambda w: w[i], stage["sub0"])
            h = jax.checkpoint(_layer, static_argnums=(2, 3))(layer, h, a, moe)
    h = _rms(h, params["backbone"]["final_norm"], a["rms_norm_eps"])
    out = (h @ params["head"]["w"] + params["head"]["b"]).reshape(
        b, n, m["horizon"], m["out_features"])
    return jnp.transpose(out, (0, 2, 1, 3))


def loss(params, m: dict, supports, x, y):
    """MAE against ``y``'s feature 0; ``supports`` is unused (ST-LLM learns
    its graph in the node embedding and the attention)."""
    return jnp.mean(jnp.abs(predict(params, m, x) - y[..., :m["out_features"]]))


# ------------------------------------------------------------------ FLOPs
def window_flops(m: dict, nnz: list[int], *, dense: bool = False) -> float:
    """FLOPs of one training window, forward plus backward (2x the forward:
    input and weight gradients); ``nnz`` is unused.

    Useful: every matmul at its size, causal attention at the ``N(N+1)/2``
    query-key pairs it needs, and the routed experts at their expected
    ``top_k · held / E`` evaluations a token (0.75 at 6 of 64 with 8 held).
    ``dense``: what the program executes instead, the attention's full
    ``512``-blocks over the sequence padded to them with ``v`` padded to
    the query width, and every capacity slot of the held experts.
    Recomputation (remat) is counted in neither."""
    a = arch(m)
    n, d, w = m["num_nodes"], a["hidden_size"], m["embed_width"]
    h, dn, dr, dv, r = (a["num_attention_heads"], a["qk_nope_head_dim"],
                        a["qk_rope_head_dim"], a["v_head_dim"], a["kv_lora_rank"])
    mm = lambda rows, k, cols: 2.0 * rows * k * cols
    fwd = (mm(n, m["input_len"] * m["in_features"], w) + mm(n, 3 * w, d)
           + mm(n, d, m["horizon"] * m["out_features"]))
    proj = (mm(n, d, h * (dn + dr)) + mm(n, d, r + dr) + mm(n, r, h * (dn + dv))
            + mm(n, h * dv, d))
    if dense:
        s = -(-n // 512) * 512
        attn = 2.0 * h * s * s * 2 * (dn + dr)
    else:
        attn = 2.0 * h * n * (n + 1) / 2 * (dn + dr + dv)
    de, e, k = a["moe_intermediate_size"], a["n_routed_experts"], a["num_experts_per_tok"]
    for moe, count in _stages(a):
        ffn = mm(n, d, e) + 3 * mm(n, d, a["n_shared_experts"] * de)
        if not moe:
            ffn = 3 * mm(n, d, a["intermediate_size"])
        elif dense:
            ffn += 3 * mm(a["held"] * capacity(n, a), d, de)
        else:
            ffn += 3 * mm(n * k * a["held"] / e, d, de)
        fwd += count * (proj + attn + ffn)
    return 3.0 * fwd
