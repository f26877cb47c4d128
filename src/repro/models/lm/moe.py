"""Mixture-of-Experts FFN (grok-1: 8e top-2; deepseek-v2-lite: 64e top-6 + 2 shared).

Dispatch is sort-based with static capacity (dropless up to
``capacity_factor``): tokens are ordered by expert id (stable sort keeps
earlier tokens at higher priority), positions within each expert's queue are
computed from segment starts, and tokens beyond capacity are dropped (they
keep their residual + shared-expert path).  Expert compute is one batched
einsum ``[E, C, d] x [E, d, de]`` that maps cleanly onto the MXU and shards
over the model axis (TP on ``de``) or the expert axis (EP) — see
``launch/sharding.py``.

Tokens are split into ``groups`` independent dispatch domains, each with its
own capacity: one per data shard, or one per ST-LLM window.  The sort,
gather and scatter then never cross groups — the per-core dispatch of
Switch/GShard.

A layer may hold a share of the experts (``MoEConfig.experts_held`` from
``first_expert``): the router keeps all ``n_experts`` outputs and its top-k,
and the layer computes only its held experts' part of the result, for the
tokens routed to them; the shared experts run on every token.  Capacity is
per expert, from all ``n_experts``, so a share keeps the capacity it would
have in the whole layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import trace_names
from repro.models.lm.config import MoEConfig
from repro.models.lm.layers import init_linear, init_mlp, mlp


def init_moe(rng, d_model: int, moe: MoEConfig, d_ff: int, mlp_kind: str,
             dtype=jnp.float32):
    """Router over all ``n_experts``; expert stacks of the held share only,
    the same weights the whole layer's init gives those experts."""
    de = moe.d_expert or d_ff
    kr, ke, ks = jax.random.split(rng, 3)
    scale = 1.0 / jnp.sqrt(d_model)
    e = moe.n_experts
    held = slice(moe.first_expert, moe.first_expert + moe.held)

    def stack(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)[held] * scale).astype(dtype)

    k1, k2, k3 = jax.random.split(ke, 3)
    p = {
        "router": init_linear(kr, d_model, e, dtype=jnp.float32),  # router in f32
        "wi": stack(k1, (e, d_model, de)),
        "wg": stack(k2, (e, d_model, de)),
        "wo": stack(k3, (e, de, d_model)),
    }
    if moe.n_shared:
        p["shared"] = init_mlp(ks, d_model, moe.n_shared * de, mlp_kind, dtype=dtype)
    return p


def capacity(tokens: int, moe: MoEConfig) -> int:
    """Slots per expert in a dispatch group of ``tokens`` tokens."""
    c = int(tokens * moe.top_k / moe.n_experts * moe.capacity_factor)
    return max(128, -(-c // 128) * 128)  # round up (128: shardable)


def _constrain(x, shardings, key):
    if shardings is None or shardings.get(key) is None:
        return x
    return jax.lax.with_sharding_constraint(x, shardings[key])


def moe_ffn(p, x: jnp.ndarray, moe: MoEConfig, mlp_kind: str, *,
            shardings=None, groups: int = 1):
    """x: [B, S, d] -> (y, aux_loss, dropped).

    ``dropped``: the (token, expert) pairs routed to a held expert that its
    capacity turned away.  All dispatch math carries the leading group dim
    and the "moe_group*" hints pin it to the data axes, so every dispatch op
    stays shard-local (a vmap'd formulation loses the sharding at the
    gather — measured: the partitioner all-gathers the 60 GB xe buffer per
    layer).  Expert einsums are 2D-sharded: groups × data, d_expert × model.
    """
    b, s, d = x.shape
    t = b * s
    assert t % groups == 0, (t, groups)
    tg = t // groups
    e, k, h = moe.n_experts, moe.top_k, moe.held
    xg = _constrain(x.reshape(groups, tg, d), shardings, "moe_group")

    with jax.named_scope(trace_names.MOE_ROUTE):
        logits = xg.astype(jnp.float32) @ p["router"]["w"]  # [g, tg, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_ix = jax.lax.top_k(probs, k)  # [g, tg, k]
        if moe.norm_topk_prob:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        else:
            top_w = top_w * moe.routed_scaling_factor

    cap = capacity(tg, moe)
    with jax.named_scope(trace_names.MOE_DISPATCH):
        # held experts are 0..h-1 here; the others share the bucket h
        local = top_ix.reshape(groups, tg * k) - moe.first_expert
        local = jnp.where((local >= 0) & (local < h), local, h)
        order = jnp.argsort(local, axis=1, stable=True)  # [g, tg*k]
        sorted_e = jnp.take_along_axis(local, order, axis=1)
        counts = jnp.zeros((groups, h + 1), jnp.int32).at[
            jnp.arange(groups)[:, None], local].add(1)
        starts = jnp.cumsum(counts, axis=1) - counts  # [g, h+1]
        pos = jnp.arange(tg * k)[None] - jnp.take_along_axis(starts, sorted_e, axis=1)
        held = sorted_e < h
        keep = held & (pos < cap)
        dropped = jnp.sum(held & ~keep)
        # pairs not kept aim past the last slot, so the scatter drops them
        slot_src = jnp.full((groups, h, cap), tg * k, jnp.int32)
        slot_src = slot_src.at[
            jnp.arange(groups)[:, None], jnp.where(keep, sorted_e, 0),
            jnp.where(keep, pos, cap)
        ].set(order.astype(jnp.int32), mode="drop")

        token_of = slot_src // k  # [g, h, C]
        valid = slot_src < tg * k
        gather_ix = jnp.where(valid, token_of, 0).reshape(groups, h * cap)
        xe = jnp.take_along_axis(xg, gather_ix[..., None], axis=1)
        xe = _constrain(xe.reshape(groups, h, cap, d), shardings, "moe_disp")
        w_flat = top_w.reshape(groups, tg * k)
        w_slot = jnp.where(
            valid, jnp.take_along_axis(
                w_flat, jnp.where(valid, slot_src, 0).reshape(groups, h * cap),
                axis=1).reshape(groups, h, cap), 0.0)

    with jax.named_scope(trace_names.MOE_EXPERTS):
        if mlp_kind in ("swiglu", "geglu"):
            act = jax.nn.silu if mlp_kind == "swiglu" else jax.nn.gelu
            hi = jnp.einsum("gecd,edf->gecf", xe, p["wi"].astype(x.dtype))
            hg = jnp.einsum("gecd,edf->gecf", xe, p["wg"].astype(x.dtype))
            he = act(hg) * hi
        else:
            he = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", xe, p["wi"].astype(x.dtype)))
        ye = jnp.einsum("gecf,efd->gecd", he, p["wo"].astype(x.dtype))
        ye = _constrain(ye, shardings, "moe_disp")

    with jax.named_scope(trace_names.MOE_COMBINE):
        # the unweighted slot outputs go through the weights here, so the
        # experts' scope holds the expert FFNs alone
        yf = jnp.zeros((groups, tg + 1, d), x.dtype)  # +1 dump row per group
        scatter_ix = jnp.where(valid, token_of, tg).reshape(groups, h * cap)
        contrib = (ye * w_slot[..., None].astype(x.dtype)).reshape(groups, h * cap, d)
        yf = yf.at[jnp.arange(groups)[:, None], scatter_ix].add(contrib)
        y = _constrain(yf[:, :tg], shardings, "moe_group").reshape(b, s, d)

    if moe.n_shared:
        with jax.named_scope(trace_names.MOE_EXPERTS):
            y = y + mlp(p["shared"], x, mlp_kind)

    # Load-balancing aux loss (Switch-style): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=(0, 1))
    fe = jnp.mean(jnp.sum(jax.nn.one_hot(top_ix, e, dtype=jnp.float32), axis=2),
                  axis=(0, 1))
    aux = moe.aux_loss_coef * e * jnp.sum(fe * me)
    return y, aux, dropped
