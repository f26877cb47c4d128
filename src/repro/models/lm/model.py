"""LM backbone assembly: stage-planned block stacks with scan-over-layers.

Layers are grouped into *stages* — maximal runs of a repeating block pattern —
so params stack over a leading ``repeats`` dim and the forward pass is a
``lax.scan`` per stage (one compiled block body per stage regardless of depth;
essential for 88-layer granite compile times and for remat policies).

Block spec = (mixer, ffn):
    mixer ∈ full | swa | mla | rec | rwkv      ffn ∈ dense | moe | rwkv
Examples: grok = ("full","moe")×64; deepseek = ("mla","dense") + ("mla","moe")×26;
recurrentgemma = [("rec","dense"),("rec","dense"),("swa","dense")]×8 + 2 rec.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any

import jax
import jax.numpy as jnp

from repro import trace_names
from repro.models.lm import rglru, rwkv6
from repro.models.lm.attention import (
    NEG_INF,
    banded_attention,
    blockwise_attention,
    decode_attention,
    full_attention,
)
from repro.models.lm.config import LMConfig
from repro.models.lm.layers import apply_rope, init_linear, init_mlp, linear, mlp, rms_norm
from repro.models.lm.mla import init_mla, mla_attention, mla_decode
from repro.models.lm.moe import init_moe, moe_ffn

BLOCKWISE_THRESHOLD = 2048  # switch to flash-style attention above this seq len


def _constrain(x, shardings, key):
    """Pin an activation's sharding (no-op off-mesh).

    GSPMD/Shardy propagation alone does NOT keep the batch dim sharded once
    FSDP param shardings pull feature dims toward 'data' (measured: 370
    GiB/device temps on qwen train_4k without these pins).  Production
    frameworks (MaxText et al.) pin activations at block boundaries for
    exactly this reason; ``shardings`` is the launcher-provided hint dict
    {"act": NamedSharding, "logits": NamedSharding}.
    """
    if shardings is None or shardings.get(key) is None:
        return x
    return jax.lax.with_sharding_constraint(x, shardings[key])


# ------------------------------------------------------------------ stage plan
@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str  # full | swa | mla | rec | rwkv
    ffn: str  # dense | moe | rwkv


def layer_specs(cfg: LMConfig) -> list[LayerSpec]:
    specs = []
    for i, kind in enumerate(cfg.block_types()):
        if kind == "rwkv":
            specs.append(LayerSpec("rwkv", "rwkv"))
            continue
        mixer = {"attn": "full"}.get(kind, kind)
        if cfg.moe is not None and i >= cfg.moe.first_k_dense:
            ffn = "moe"
        else:
            ffn = "dense"
        specs.append(LayerSpec(mixer, ffn))
    return specs


def stage_plan(cfg: LMConfig) -> list[tuple[tuple[LayerSpec, ...], int]]:
    """[(super-layer spec tuple, repeats), ...] covering all layers in order."""
    specs = layer_specs(cfg)
    if cfg.block_pattern is not None:
        period = len(cfg.block_pattern)
        n_full, rem = divmod(len(specs), period)
        plan = [(tuple(specs[:period]), n_full)]
        if rem:
            plan.append((tuple(specs[n_full * period :]), 1))
        return plan
    # group maximal runs of identical specs
    plan = []
    for spec, grp in itertools.groupby(specs):
        plan.append(((spec,), len(list(grp))))
    return plan


# ----------------------------------------------------------------------- init
def _init_attn(rng, cfg: LMConfig, dtype):
    kq, kk, kv, ko = jax.random.split(rng, 4)
    hd = cfg.hd
    return {
        "wq": init_linear(kq, cfg.d_model, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_linear(kk, cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_linear(kv, cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_linear(ko, cfg.n_heads * hd, cfg.d_model, dtype=dtype),
    }


def _init_layer(rng, cfg: LMConfig, spec: LayerSpec, dtype):
    km, kf = jax.random.split(rng)
    p: dict[str, Any] = {"norm1": jnp.ones((cfg.d_model,), dtype)}
    if spec.mixer in ("full", "swa"):
        p["attn"] = _init_attn(km, cfg, dtype)
    elif spec.mixer == "mla":
        p["attn"] = init_mla(km, cfg, dtype)
    elif spec.mixer == "rec":
        p["rec"] = rglru.init_rglru_block(km, cfg, dtype)
    elif spec.mixer == "rwkv":
        p["rwkv"] = rwkv6.init_rwkv_block(km, cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    p["norm2"] = jnp.ones((cfg.d_model,), dtype)
    if spec.ffn == "dense":
        d_ff = cfg.d_ff
        if cfg.moe is not None and cfg.moe.dense_d_ff is not None:
            d_ff = cfg.moe.dense_d_ff
        p["mlp"] = init_mlp(kf, cfg.d_model, d_ff, cfg.mlp, dtype=dtype)
    elif spec.ffn == "moe":
        p["moe"] = init_moe(kf, cfg.d_model, cfg.moe, cfg.d_ff, cfg.mlp, dtype=dtype)
    return p


def init(rng, cfg: LMConfig, *, tokens: bool = True):
    """The model's parameters; ``tokens=False`` leaves out the token table,
    the learned positions and the LM head, for a model that feeds the blocks
    its own embeddings and reads their hidden states (``backbone``)."""
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(rng, len(stage_plan(cfg)) + 3)
    params: dict[str, Any] = {"final_norm": jnp.ones((cfg.d_model,), dtype)}
    if tokens:
        params["embed"] = (jax.random.normal(
            ks[0], (cfg.padded_vocab, cfg.d_model), jnp.float32) * 0.02).astype(dtype)
        if cfg.pos == "learned":
            params["pos"] = (jax.random.normal(ks[1], (cfg.max_seq_len, cfg.d_model),
                                               jnp.float32) * 0.02).astype(dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = init_linear(ks[2], cfg.d_model, cfg.padded_vocab,
                                            dtype=dtype)
    stages = []
    for si, (specs, repeats) in enumerate(stage_plan(cfg)):
        layer_keys = jax.random.split(ks[3 + si], repeats)

        def init_super(k, _specs=specs):
            sub_keys = jax.random.split(k, len(_specs))
            return {f"sub{i}": _init_layer(sub_keys[i], cfg, sp, dtype)
                    for i, sp in enumerate(_specs)}

        stages.append(jax.vmap(init_super)(layer_keys))
    params["stages"] = stages
    return params


# -------------------------------------------------------------------- mixers
def _attn_mixer(p, cfg: LMConfig, spec: LayerSpec, x, positions, *, mode,
                cache=None, lengths=None, shardings=None, paged=None):
    """Returns (out, new_cache).  cache layout depends on mixer/mode.

    ``paged``: optional ``(tables, block_size)`` for decode against a paged
    pool (``init_paged_cache``) — ``tables`` is int32 ``[B, max_blocks]``
    mapping each lane's logical block index to a physical block.  Applies to
    seq-dim caches only (full-attn k/v, MLA ckv/kpe); swa rings and recurrent
    state stay per-lane.
    """
    b, s, _ = x.shape
    hd = cfg.hd
    window = cfg.window if spec.mixer == "swa" else None

    if spec.mixer == "mla":
        if mode == "decode":
            y, ckv, kpe = mla_decode(p["attn"], cfg, x, cache["ckv"], cache["kpe"], lengths,
                                     paged=paged)
            return y, {"ckv": ckv, "kpe": kpe}
        blockwise = s > BLOCKWISE_THRESHOLD
        y, (c_kv, k_pe) = mla_attention(p["attn"], cfg, x, positions, blockwise=blockwise)
        if mode == "prefill":
            ckv_w = _constrain(c_kv.astype(cache["ckv"].dtype), shardings, "ckv")
            kpe_w = _constrain(k_pe.astype(cache["kpe"].dtype), shardings, "ckv")
            new = {"ckv": cache["ckv"].at[:, :s].set(ckv_w),
                   "kpe": cache["kpe"].at[:, :s].set(kpe_w)}
            return y, new
        return y, None

    a = p["attn"]
    q = linear(a["wq"], x).reshape(b, s, cfg.n_heads, hd)
    k = linear(a["wk"], x).reshape(b, s, cfg.n_kv_heads, hd)
    v = linear(a["wv"], x).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if mode == "prefill":
        # compute-path q/k/v stay batch-sharded; without this the S-sharded
        # cache write (kv hint) back-propagates onto q and the chunked
        # attention all-gathers the whole q stack EVERY chunk iteration
        # (measured 3.2 TiB/device on prefill_32k).  Train mode needs no pin
        # (no cache write) and pinning there pessimizes the backward loop.
        q = _constrain(q, shardings, "qkv")
        k = _constrain(k, shardings, "qkv")
        v = _constrain(v, shardings, "qkv")

    if mode == "decode":
        if window is not None:  # ring buffer of size window
            slot = lengths % window
            kc = cache["k"].at[jnp.arange(b), slot].set(k[:, 0])
            vc = cache["v"].at[jnp.arange(b), slot].set(v[:, 0])
            n_valid = jnp.minimum(lengths + 1, window)
            out = _ring_decode(q, kc, vc, n_valid)
            new_cache = {"k": kc, "v": vc}
        elif paged is not None:
            # paged pool: write the token's k/v at (physical block, offset),
            # then attend against the block-table gathered view.  The gather
            # happens HERE, per layer inside the scan body, so the transient
            # is one layer's [B, max_blocks*bs] view — never the whole pool.
            tables, bs = paged
            phys = tables[jnp.arange(b), lengths // bs]
            off = lengths % bs
            kc = cache["k"].at[phys, off].set(k[:, 0])
            vc = cache["v"].at[phys, off].set(v[:, 0])
            kv = kc[tables].reshape(b, -1, cfg.n_kv_heads, hd)
            vv = vc[tables].reshape(b, -1, cfg.n_kv_heads, hd)
            # positions >= lengths+1 (unwritten block tails, null-block rows
            # of dead lanes) hold stale-but-finite garbage; the mask zeroes
            # them exactly, so the view is bit-equivalent to the contiguous
            # cache whenever max_blocks*bs == max_len
            out = decode_attention(q, kv, vv, lengths + 1)
            new_cache = {"k": kc, "v": vc}
        else:
            kc = cache["k"].at[jnp.arange(b), lengths].set(k[:, 0])
            vc = cache["v"].at[jnp.arange(b), lengths].set(v[:, 0])
            out = decode_attention(q, kc, vc, lengths + 1)
            new_cache = {"k": kc, "v": vc}
        return linear(a["wo"], out.reshape(b, 1, -1)), new_cache

    # train / prefill
    if window is not None and s > 2 * window:
        out = banded_attention(q, k, v, window=window,
                               q_chunk=min(cfg.q_chunk, window))
    elif s > BLOCKWISE_THRESHOLD:
        out = blockwise_attention(q, k, v, causal=True, window=window,
                                  q_chunk=min(cfg.q_chunk, s),
                                  kv_chunk=min(cfg.kv_chunk, s))
    else:
        out = full_attention(q, k, v, causal=True, window=window)
    y = linear(a["wo"], out.reshape(b, s, -1))

    new_cache = None
    if mode == "prefill":
        if window is not None:
            w = window
            tail = min(s, w)
            slots = (positions[:, -tail:]) % w  # [B, tail]
            kc = jnp.zeros_like(cache["k"]).at[jnp.arange(b)[:, None], slots].set(k[:, -tail:])
            vc = jnp.zeros_like(cache["v"]).at[jnp.arange(b)[:, None], slots].set(v[:, -tail:])
            new_cache = {"k": kc, "v": vc}
        else:
            # pin the written k/v to the cache's own sharding BEFORE the
            # update: the reshard is then a local slice instead of a
            # full-tensor involuntary rematerialization per layer
            kw = _constrain(k.astype(cache["k"].dtype), shardings, "kv")
            vw = _constrain(v.astype(cache["v"].dtype), shardings, "kv")
            new_cache = {"k": cache["k"].at[:, :s].set(kw),
                         "v": cache["v"].at[:, :s].set(vw)}
    return y, new_cache


def _ring_decode(q1, k_ring, v_ring, n_valid):
    """Decode against a ring buffer: all slots < n_valid (per batch) are live;
    slot order is irrelevant to attention."""
    b = q1.shape[0]
    kpos = jnp.arange(k_ring.shape[1])[None, :]
    mask = kpos < n_valid[:, None]
    # reuse decode_attention by passing per-batch "length" = window validity
    return decode_attention(q1, jnp.where(mask[..., None, None], k_ring, 0),
                            v_ring, n_valid)


# --------------------------------------------------------------------- layers
def _layer_apply(p, cfg: LMConfig, spec: LayerSpec, x, positions, *, mode,
                 cache=None, lengths=None, shardings=None, paged=None,
                 moe_groups: int = 1):
    """One block.  Returns (x, new_cache, aux_loss, dropped MoE slots)."""
    aux = jnp.zeros((), jnp.float32)
    dropped = jnp.zeros((), jnp.int32)
    h = rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)

    if spec.mixer == "rec":
        out, new_mix_cache = rglru.rglru_block(p["rec"], cfg, h,
                                               cache=None if mode == "train" else cache)
    elif spec.mixer == "rwkv":
        out, new_mix_cache = rwkv6.time_mix(
            p["rwkv"], cfg, h, cache=None if mode == "train" else cache and cache["tm"])
    else:
        out, new_mix_cache = _attn_mixer(p, cfg, spec, h, positions, mode=mode,
                                         cache=cache, lengths=lengths,
                                         shardings=shardings, paged=paged)
    x = x + out

    if spec.ffn == "rwkv":
        h2 = rms_norm(x, p["norm2"].astype(x.dtype), cfg.norm_eps)
        out2, new_cm_cache = rwkv6.channel_mix(
            p["rwkv"], cfg, h2, cache=None if mode == "train" else cache and cache["cm"])
        x = x + out2
        new_cache = None if mode == "train" else {"tm": new_mix_cache, "cm": new_cm_cache}
        return x, new_cache, aux, dropped

    h2 = rms_norm(x, p["norm2"].astype(x.dtype), cfg.norm_eps)
    if spec.ffn == "moe":
        out2, aux, dropped = moe_ffn(p["moe"], h2, cfg.moe, cfg.mlp,
                                     shardings=shardings, groups=moe_groups)
    else:
        with jax.named_scope(trace_names.FFN_DENSE):
            out2 = mlp(p["mlp"], h2, cfg.mlp)
    x = x + out2
    return x, new_mix_cache, aux, dropped


def _run_stages(params, cfg: LMConfig, x, positions, *, mode, caches=None,
                lengths=None, remat=False, shardings=None, paged=None,
                moe_groups: int = 1):
    """Scan over each stage's repeats.  Returns (x, new_caches, aux_total,
    dropped): ``dropped`` counts the (token, expert) pairs that MoE layers'
    capacity turned away; ``moe_groups`` is their number of dispatch groups."""
    plan = stage_plan(cfg)
    totals = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    new_caches = []
    for (specs, repeats), stage_p, stage_c in zip(
            plan, params["stages"], caches or [None] * len(plan)):

        def body(carry, layer_in):
            xx, (aux_acc, drop_acc) = carry
            lp, lc = layer_in
            out_caches = {}
            for i, sp in enumerate(specs):
                sub_c = None if lc is None else lc[f"sub{i}"]
                xx, nc, aux, dropped = _layer_apply(
                    lp[f"sub{i}"], cfg, sp, xx, positions, mode=mode, cache=sub_c,
                    lengths=lengths, shardings=shardings, paged=paged,
                    moe_groups=moe_groups)
                xx = _constrain(xx, shardings, "act")
                out_caches[f"sub{i}"] = nc
                aux_acc, drop_acc = aux_acc + aux, drop_acc + dropped
            return (xx, (aux_acc, drop_acc)), out_caches

        if remat:
            body = jax.checkpoint(body)
        if stage_c is None:
            (x, totals), scanned = jax.lax.scan(
                lambda c, lp: body(c, (lp, None)), (x, totals), stage_p)
            new_caches.append(scanned if mode != "train" else None)
        else:
            (x, totals), scanned = jax.lax.scan(
                body, (x, totals), (stage_p, stage_c))
            new_caches.append(scanned)
    return (x, new_caches) + totals


# ----------------------------------------------------------------- public API
def embed_tokens(params, cfg: LMConfig, tokens, *, prefix_embeds=None,
                 pos_offset=None):
    """tokens: [B, S] int32 -> (x [B, S(+P), d] in compute dtype, positions)."""
    cdtype = jnp.dtype(cfg.dtype)
    x = params["embed"][tokens].astype(cdtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(cdtype), x], axis=1)
    b, s, _ = x.shape
    if pos_offset is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    else:
        positions = pos_offset[:, None] + jnp.arange(s)[None]
    if cfg.pos == "learned":
        x = x + params["pos"][positions].astype(cdtype)
    return x, positions


def logits_fn(params, cfg: LMConfig, x):
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"]["w"])
    logits = x @ w.astype(x.dtype)
    if cfg.padded_vocab != cfg.vocab:
        # mask padding columns so softmax/argmax never see them
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad_mask, jnp.asarray(NEG_INF, logits.dtype), logits)
    return logits


def forward(params, cfg: LMConfig, tokens, *, prefix_embeds=None, remat=False,
            shardings=None):
    """Training forward.  Returns (logits [B, S, V], aux_loss)."""
    x, positions = embed_tokens(params, cfg, tokens, prefix_embeds=prefix_embeds)
    x = _constrain(x, shardings, "act")
    x, _, aux, _ = _run_stages(params, cfg, x, positions, mode="train",
                               remat=remat, shardings=shardings)
    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits = _constrain(logits_fn(params, cfg, x), shardings, "logits")
    return logits, aux


def backbone(params, cfg: LMConfig, x_embeds, *, remat=False, shardings=None,
             moe_groups: int = 1):
    """Run the block stack on precomputed embeddings (ST-LLM / modality
    frontends), causal over the sequence, positions 0..S-1.
    x_embeds: [B, S, d] -> (final-normed hidden [B, S, d], aux, dropped)."""
    b, s, _ = x_embeds.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = _constrain(x_embeds.astype(jnp.dtype(cfg.dtype)), shardings, "act")
    x, _, aux, dropped = _run_stages(params, cfg, x, positions, mode="train",
                                     remat=remat, shardings=shardings,
                                     moe_groups=moe_groups)
    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    return x, aux, dropped


def loss_fn(params, cfg: LMConfig, tokens_in, labels, *, prefix_embeds=None,
            remat=False, shardings=None):
    """Next-token cross-entropy (+ MoE aux).  labels: [B, S] (-1 = ignore)."""
    logits, aux = forward(params, cfg, tokens_in, prefix_embeds=prefix_embeds,
                          remat=remat, shardings=shardings)
    if prefix_embeds is not None:
        logits = logits[:, prefix_embeds.shape[1] :]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    # one-hot-select instead of take_along_axis: stays sharded over a
    # vocab-partitioned logits axis (gather along a sharded dim would
    # all-gather the full [B,S,V] f32 logits)
    vocab_iota = jnp.arange(logits.shape[-1], dtype=jnp.int32)
    hit = labels[..., None] == vocab_iota
    gold = jnp.sum(jnp.where(hit, logits.astype(jnp.float32), 0.0), axis=-1)
    valid = labels >= 0
    nll = jnp.where(valid, lse - gold, 0.0)
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)
    return loss + aux, {"nll": loss, "aux": aux}


# -------------------------------------------------------------------- serving
def init_cache(cfg: LMConfig, batch: int, max_len: int):
    """Cache pytree mirroring the stage plan (stacked over repeats)."""
    cdtype = jnp.dtype(cfg.dtype)
    hd = cfg.hd

    def one_layer(spec: LayerSpec):
        c: dict[str, Any] = {}
        if spec.mixer in ("full",):
            c = {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), cdtype),
                 "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), cdtype)}
        elif spec.mixer == "swa":
            w = min(cfg.window, max_len)
            c = {"k": jnp.zeros((batch, w, cfg.n_kv_heads, hd), cdtype),
                 "v": jnp.zeros((batch, w, cfg.n_kv_heads, hd), cdtype)}
        elif spec.mixer == "mla":
            m = cfg.mla
            c = {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), cdtype),
                 "kpe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), cdtype)}
        elif spec.mixer == "rec":
            c = rglru.init_rglru_cache(cfg, batch, cdtype)
        elif spec.mixer == "rwkv":
            c = rwkv6.init_rwkv_cache(cfg, batch, cdtype)
        return c

    caches = []
    for specs, repeats in stage_plan(cfg):
        layer = {f"sub{i}": one_layer(sp) for i, sp in enumerate(specs)}
        caches.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (repeats,) + a.shape), layer))
    return caches


def scatter_cache(cache, sub, slots):
    """Scatter a k-batch cache pytree into k (arbitrary, non-contiguous)
    lanes of a pool cache.

    ``cache``: the slot-pool cache from ``init_cache`` — every leaf is
    stage-stacked ``[repeats, batch, ...]`` with batch at axis 1.  ``sub``:
    the same pytree with batch ``k`` (a batched-prefill output).  ``slots``:
    int32 ``[k]`` lane indices.  One fused scatter per leaf replaces the
    per-request ``dynamic_update_slice`` chain the single-lane fill path
    pays k times.
    """
    slots = jnp.asarray(slots, jnp.int32)

    def put(big, small):
        return big.at[:, slots].set(small.astype(big.dtype))

    return jax.tree.map(put, cache, sub)


# ------------------------------------------------------------ paged KV-cache
def init_paged_cache(cfg: LMConfig, batch: int, max_len: int, *,
                     num_blocks: int, block_size: int):
    """Paged cache pool: seq-dim leaves become shared block pools.

    Full-attn k/v and MLA ckv/kpe leaves are ``[repeats, num_blocks,
    block_size, ...]`` — one pool per layer, shared by every lane through
    per-lane block tables (``serve.blocks.BlockPool`` owns the allocation;
    physical block 0 is the null block).  Per-lane state with no paged seq
    dim (swa rings, RG-LRU / RWKV recurrent state) keeps the ``init_cache``
    layout ``[repeats, batch, ...]``.
    """
    cdtype = jnp.dtype(cfg.dtype)
    hd = cfg.hd

    def one_layer(spec: LayerSpec):
        if spec.mixer == "full":
            return {"k": jnp.zeros((num_blocks, block_size, cfg.n_kv_heads, hd), cdtype),
                    "v": jnp.zeros((num_blocks, block_size, cfg.n_kv_heads, hd), cdtype)}
        if spec.mixer == "swa":
            w = min(cfg.window, max_len)
            return {"k": jnp.zeros((batch, w, cfg.n_kv_heads, hd), cdtype),
                    "v": jnp.zeros((batch, w, cfg.n_kv_heads, hd), cdtype)}
        if spec.mixer == "mla":
            m = cfg.mla
            return {"ckv": jnp.zeros((num_blocks, block_size, m.kv_lora_rank), cdtype),
                    "kpe": jnp.zeros((num_blocks, block_size, m.qk_rope_head_dim), cdtype)}
        if spec.mixer == "rec":
            return rglru.init_rglru_cache(cfg, batch, cdtype)
        if spec.mixer == "rwkv":
            return rwkv6.init_rwkv_cache(cfg, batch, cdtype)
        return {}

    caches = []
    for specs, repeats in stage_plan(cfg):
        layer = {f"sub{i}": one_layer(sp) for i, sp in enumerate(specs)}
        caches.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (repeats,) + a.shape), layer))
    return caches


def paged_cache_mask(cfg: LMConfig):
    """Bool pytree congruent with the cache: True at paged (seq-dim) leaves.

    Decided per layer SPEC, not by shape — a swa ring whose window happens to
    equal ``max_len`` must still take the ring decode path, not the paged one.
    """
    def one_layer(spec: LayerSpec):
        if spec.mixer == "full":
            return {"k": True, "v": True}
        if spec.mixer == "swa":
            return {"k": False, "v": False}
        if spec.mixer == "mla":
            return {"ckv": True, "kpe": True}
        if spec.mixer == "rec":
            shapes = jax.eval_shape(lambda: rglru.init_rglru_cache(cfg, 1, jnp.float32))
            return jax.tree.map(lambda _: False, shapes)
        if spec.mixer == "rwkv":
            shapes = jax.eval_shape(lambda: rwkv6.init_rwkv_cache(cfg, 1, jnp.float32))
            return jax.tree.map(lambda _: False, shapes)
        return {}

    return [{f"sub{i}": one_layer(sp) for i, sp in enumerate(specs)}
            for specs, _ in stage_plan(cfg)]


def scatter_cache_paged(cache, sub, slots, phys, *, block_size: int, mask):
    """Land a k-batch contiguous prefill cache into a paged pool.

    ``cache``: pool from ``init_paged_cache``.  ``sub``: a contiguous
    prefill-output cache with batch k (seq dim = the sub cache's line
    length).  ``slots``: int32 ``[k]`` lane ids, used for the per-lane
    (unpaged) leaves exactly like ``scatter_cache``.  ``phys``: int32
    ``[k, nb]`` physical block ids covering logical positions
    ``0..nb*block_size`` of each lane — the prompt's blocks.  ``mask``:
    ``paged_cache_mask(cfg)``.

    Paged leaves reshape the sub line into ``nb`` blocks and scatter them to
    their physical rows in one fused update; positions past the prompt inside
    the last block are zero-filled (masked by lane lengths until decode
    overwrites them).
    """
    slots = jnp.asarray(slots, jnp.int32)
    phys = jnp.asarray(phys, jnp.int32)
    nb = phys.shape[1]

    def put(is_paged, big, small):
        small = small.astype(big.dtype)
        if not is_paged:
            return big.at[:, slots].set(small)
        r, k, s = small.shape[:3]
        want = nb * block_size
        if s > want:
            small = small[:, :, :want]
        elif s < want:
            widths = [(0, 0), (0, 0), (0, want - s)] + [(0, 0)] * (small.ndim - 3)
            small = jnp.pad(small, widths)
        small = small.reshape((r, k, nb, block_size) + small.shape[3:])
        return big.at[:, phys].set(small)

    return jax.tree.map(put, mask, cache, sub)


def prefill(params, cfg: LMConfig, tokens, cache, *, prefix_embeds=None,
            shardings=None, moe_groups: int = 1):
    """Fill the cache from a prompt.  Returns (last-token logits, cache, lengths)."""
    x, positions = embed_tokens(params, cfg, tokens, prefix_embeds=prefix_embeds)
    x = _constrain(x, shardings, "act")
    x, new_caches, _, _ = _run_stages(params, cfg, x, positions, mode="prefill",
                                      caches=cache, shardings=shardings,
                                      moe_groups=moe_groups)
    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits = logits_fn(params, cfg, x[:, -1:])[:, 0]
    lengths = jnp.full((tokens.shape[0],), x.shape[1], jnp.int32)
    return logits, new_caches, lengths


def decode_step(params, cfg: LMConfig, token, cache, lengths, *, shardings=None,
                paged=None):
    """One decode step.  token: [B, 1] -> (logits [B, V], new cache).

    ``paged``: optional ``(tables, block_size)`` when ``cache`` is a paged
    pool from ``init_paged_cache`` — tables map each lane's logical blocks to
    physical pool blocks; per-layer writes/gathers go through them inside the
    stage scan (see ``_attn_mixer``).
    """
    x, positions = embed_tokens(params, cfg, token, pos_offset=lengths)
    x = _constrain(x, shardings, "act")
    x, new_caches, _, _ = _run_stages(params, cfg, x, positions, mode="decode",
                                      caches=cache, lengths=lengths,
                                      shardings=shardings, paged=paged)
    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    return logits_fn(params, cfg, x[:, 0]), new_caches
