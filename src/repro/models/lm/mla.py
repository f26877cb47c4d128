"""Multi-head Latent Attention (DeepSeek-V2) — compressed KV cache.

Projections:  q = W_q x  -> per-head (nope ‖ rope) query
              [c_kv ‖ k_pe] = W_dkv x   (kv_lora_rank + rope_dim — the CACHE)
              k_nope, v = W_ukv · rmsnorm(c_kv)

Prefill/train decompress k, v and run standard attention.  Decode uses the
*absorbed* form: q_nope is folded through W_uk into the latent space, scores
are taken against the cached ``c_kv`` directly, and the value projection W_uv
is applied to the attended latent — so the per-token cache cost is
``kv_lora_rank + rope_dim`` (576) instead of ``2·H·D`` (4096 for 16 heads):
the paper-relevant memory saving that makes decode_32k × batch 128 fit.

With ``cfg.rope_scaling`` (YaRN, as DeepSeek-V2 publishes it) the rope parts
use YaRN's inverse frequencies and the softmax scale ``1/sqrt(dn + dr)`` is
multiplied by ``mscale(mscale_all_dim)**2``, in every path alike.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import trace_names
from repro.models.lm.attention import NEG_INF, blockwise_attention, full_attention
from repro.models.lm.config import LMConfig
from repro.models.lm.layers import (apply_rope, init_linear, linear, rms_norm,
                                    yarn_softmax_factor)


def init_mla(rng, cfg: LMConfig, dtype=jnp.float32):
    m = cfg.mla
    h = cfg.n_heads
    kq, kd, ku, ko = jax.random.split(rng, 4)
    return {
        "wq": init_linear(kq, cfg.d_model, h * (m.qk_nope_head_dim + m.qk_rope_head_dim), dtype=dtype),
        "wdkv": init_linear(kd, cfg.d_model, m.kv_lora_rank + m.qk_rope_head_dim, dtype=dtype),
        "ckv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wukv": init_linear(ku, m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim), dtype=dtype),
        "wo": init_linear(ko, h * m.v_head_dim, cfg.d_model, dtype=dtype),
    }


def _project_q(p, cfg: LMConfig, x, positions):
    m = cfg.mla
    b, s, _ = x.shape
    q = linear(p["wq"], x).reshape(b, s, cfg.n_heads, m.qk_nope_head_dim + m.qk_rope_head_dim)
    qn, qr = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    qr = apply_rope(qr, positions, cfg.rope_theta, cfg.rope_scaling)
    return qn, qr


def _project_ckv(p, cfg: LMConfig, x, positions):
    m = cfg.mla
    ckv_full = linear(p["wdkv"], x)
    c_kv, k_pe = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["ckv_norm"].astype(x.dtype), cfg.norm_eps)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta,
                      cfg.rope_scaling)[:, :, 0]
    return c_kv, k_pe  # [B,S,r], [B,S,dr]


def softmax_scale(cfg: LMConfig) -> float:
    m = cfg.mla
    return ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
            * yarn_softmax_factor(cfg.rope_scaling))


@jax.named_scope(trace_names.ATTN_MLA)
def mla_attention(p, cfg: LMConfig, x, positions, *, blockwise: bool = False):
    """Train/prefill path (decompressed).  x: [B, S, d] -> ([B, S, d], (c_kv, k_pe))."""
    m = cfg.mla
    b, s, _ = x.shape
    qn, qr = _project_q(p, cfg, x, positions)
    c_kv, k_pe = _project_ckv(p, cfg, x, positions)
    kv = linear(p["wukv"], c_kv).reshape(b, s, cfg.n_heads, m.qk_nope_head_dim + m.v_head_dim)
    kn, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate([kn, jnp.broadcast_to(k_pe[:, :, None, :], qr.shape)], axis=-1)
    q = jnp.concatenate([qn, qr], axis=-1)
    # v head dim may differ from qk head dim: pad v for the shared kernels.
    dq = q.shape[-1]
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dq - m.v_head_dim)))
    fn = blockwise_attention if blockwise else full_attention
    out = fn(q, k, vp, causal=True, scale=softmax_scale(cfg))[..., : m.v_head_dim]
    y = linear(p["wo"], out.reshape(b, s, -1))
    return y, (c_kv, k_pe)


@jax.named_scope(trace_names.ATTN_MLA)
def mla_decode(p, cfg: LMConfig, x1, ckv_cache, kpe_cache, lengths, *, paged=None):
    """Absorbed one-token decode.  x1: [B, 1, d]; caches: [B, S_max, r]/[B, S_max, dr].

    Returns (y [B,1,d], updated ckv_cache, updated kpe_cache).

    ``paged``: optional ``(tables, block_size)`` when the caches are paged
    pools ``[num_blocks, block_size, r]`` — the new latent is written at its
    (physical block, offset) and attention runs over the block-table gathered
    view; the returned caches stay in pool layout.
    """
    m = cfg.mla
    b = x1.shape[0]
    pos = lengths[:, None]  # [B,1] absolute position of the new token
    qn, qr = _project_q(p, cfg, x1, pos)
    c_new, kpe_new = _project_ckv(p, cfg, x1, pos)
    if paged is None:
        ckv_upd = ckv_cache.at[jnp.arange(b), lengths].set(c_new[:, 0])
        kpe_upd = kpe_cache.at[jnp.arange(b), lengths].set(kpe_new[:, 0])
        ckv, kpe = ckv_upd, kpe_upd
    else:
        tables, bs = paged
        phys = tables[jnp.arange(b), lengths // bs]
        off = lengths % bs
        ckv_upd = ckv_cache.at[phys, off].set(c_new[:, 0])
        kpe_upd = kpe_cache.at[phys, off].set(kpe_new[:, 0])
        # per-lane gathered view [B, max_blocks*bs, r]; positions past
        # lengths are masked below, so stale block tails cannot contribute
        ckv = ckv_upd[tables].reshape(b, -1, ckv_upd.shape[-1])
        kpe = kpe_upd[tables].reshape(b, -1, kpe_upd.shape[-1])

    # Absorb W_uk: q_lat[h] = W_uk[h]^T q_nope[h]  -> score against c_kv directly.
    wukv = p["wukv"]["w"].reshape(m.kv_lora_rank, cfg.n_heads, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = wukv[..., : m.qk_nope_head_dim]  # [r, H, dn]
    w_uv = wukv[..., m.qk_nope_head_dim :]  # [r, H, dv]
    q_lat = jnp.einsum("bqhd,rhd->bqhr", qn, w_uk.astype(x1.dtype))  # [B,1,H,r]
    scale = softmax_scale(cfg)
    scores = (jnp.einsum("bqhr,bkr->bhqk", q_lat.astype(jnp.float32), ckv.astype(jnp.float32))
              + jnp.einsum("bqhd,bkd->bhqk", qr.astype(jnp.float32), kpe.astype(jnp.float32))
              ) * scale
    kpos = jnp.arange(ckv.shape[1])[None, None, None, :]
    mask = kpos <= lengths[:, None, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkr->bqhr", probs.astype(ckv.dtype), ckv)
    v = jnp.einsum("bqhr,rhd->bqhd", ctx, w_uv.astype(x1.dtype))
    y = linear(p["wo"], v.reshape(b, 1, -1))
    return y, ckv_upd, kpe_upd
