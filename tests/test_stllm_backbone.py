"""The DeepSeek-V2-Lite pieces ST-LLM's backbone needs, at small widths: a
layer that holds a share of the experts, the published gate (top-k weights
not renormalised), per-window dispatch groups, the dropped-slot counter,
YaRN rope and softmax scale, and blockwise attention over a sequence that
does not divide its chunks."""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.lm.attention import blockwise_attention, full_attention
from repro.models.lm.config import MoEConfig
from repro.models.lm.layers import mlp, rope_freqs
from repro.models.lm.mla import softmax_scale
from repro.models.lm.moe import capacity, init_moe, moe_ffn

KEY = jax.random.PRNGKey(0)
D, DE = 32, 16


def _moe(**kw):
    base = dict(n_experts=8, top_k=2, n_shared=1, d_expert=DE,
                capacity_factor=1.0, norm_topk_prob=False)
    return MoEConfig(**{**base, **kw})


def _x(b, s, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, D))


def test_expert_shares_add_up_to_the_whole_layer():
    """Eight disjoint one-expert shares: their held parts, with the shared
    expert counted once, give the uncut layer, drops included."""
    whole = _moe()
    p = init_moe(KEY, D, whole, DE, "swiglu")
    x = _x(2, 512)
    y_whole, _, dropped_whole = moe_ffn(p, x, whole, "swiglu", groups=2)
    assert int(dropped_whole) > 0  # capacity 128 turns some pairs away
    shared = mlp(p["shared"], x, "swiglu")
    total, dropped = shared, 0
    for first in range(whole.n_experts):
        share = dataclasses.replace(whole, experts_held=1, first_expert=first)
        ps = init_moe(KEY, D, share, DE, "swiglu")
        for name in ("wi", "wg", "wo"):
            np.testing.assert_array_equal(ps[name], p[name][first:first + 1])
        y, _, d = moe_ffn(ps, x, share, "swiglu", groups=2)
        total, dropped = total + (y - shared), dropped + int(d)
    np.testing.assert_allclose(total, y_whole, atol=2e-5)
    assert dropped == int(dropped_whole)


@pytest.mark.parametrize("norm,tokens", [(False, 64), (True, 64), (False, 512)])
def test_gate_weights_and_capacity(norm, tokens):
    """The layer is each token's top-k experts' SwiGLU weighted by the raw
    softmax probabilities (DeepSeek-V2's ``norm_topk_prob`` false) or by them
    renormalised to sum 1, over the pairs each expert's capacity keeps: its
    first ``C`` tokens in token order (at 512 tokens ``C`` = 128 binds)."""
    moe = _moe(n_shared=0, norm_topk_prob=norm)
    p = init_moe(KEY, D, moe, DE, "swiglu")
    x = _x(1, tokens)
    y, _, dropped = moe_ffn(p, x, moe, "swiglu")
    probs = jax.nn.softmax(x[0] @ p["router"]["w"], axis=-1)
    top_w, top_ix = jax.lax.top_k(probs, moe.top_k)
    if norm:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    cap = capacity(tokens, moe)
    want, turned_away = np.zeros((tokens, D), np.float32), 0
    for e in range(moe.n_experts):
        chose = np.asarray(top_ix == e)
        picked = chose.any(-1)
        kept = picked & (np.cumsum(picked) <= cap)
        turned_away += int(picked.sum() - kept.sum())
        w = np.where(kept, np.where(chose, top_w, 0).sum(-1), 0)
        ffn = {k: {"w": p[k][e]} for k in ("wi", "wg", "wo")}
        want += w[:, None] * np.asarray(mlp(ffn, x[0], "swiglu"))
    assert int(dropped) == turned_away and (turned_away > 0) == (tokens == 512)
    np.testing.assert_allclose(y[0], want, atol=2e-5)


def test_dispatch_groups_keep_windows_independent():
    """One group per window: a window's output is what it gives alone, and
    changing another window changes nothing in it, with capacity binding."""
    moe = _moe(experts_held=4, first_expert=2)
    p = init_moe(KEY, D, moe, DE, "swiglu")
    x = _x(4, 512)
    y, _, dropped = moe_ffn(p, x, moe, "swiglu", groups=4)
    assert int(dropped) > 0
    alone = jnp.concatenate([moe_ffn(p, x[i:i + 1], moe, "swiglu")[0]
                             for i in range(4)])
    np.testing.assert_allclose(y, alone, atol=1e-5)
    x2 = x.at[2].set(_x(1, 512, seed=9)[0])
    y2 = moe_ffn(p, x2, moe, "swiglu", groups=4)[0]
    np.testing.assert_array_equal(np.asarray(y2)[[0, 1, 3]], np.asarray(y)[[0, 1, 3]])
    assert not np.allclose(y2[2], y[2])


def test_dropped_slots_match_a_hand_count():
    moe = _moe(experts_held=3, first_expert=4)
    p = init_moe(KEY, D, moe, DE, "swiglu")
    x = _x(2, 512)
    _, _, dropped = moe_ffn(p, x, moe, "swiglu", groups=2)
    cap = capacity(512, moe)
    top_ix = np.asarray(jax.lax.top_k(
        jax.nn.softmax(x @ p["router"]["w"], axis=-1), moe.top_k)[1])
    want = 0
    for window in top_ix:
        for e in range(4, 7):
            want += max(int((window == e).sum()) - cap, 0)
    assert want > 0
    assert int(dropped) == want


def test_yarn_frequencies_and_softmax_scale_follow_the_formula():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4,096 positions, beta 32/1,
    mscale 0.707 on both terms), written out in float64."""
    cfg = get_arch("deepseek-v2-lite-16b").lm
    s, dim, base = cfg.rope_scaling, cfg.mla.qk_rope_head_dim, cfg.rope_theta
    assert (s.factor, s.original_max_position_embeddings) == (40.0, 4096)

    def turn_dim(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = math.floor(turn_dim(32)), math.ceil(turn_dim(1))
    assert (low, high) == (10, 23)
    extra = base ** -(np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(rope_freqs(dim, base, s), want, rtol=2e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2, rel=1e-12)
    assert not get_arch("deepseek-v2-lite-16b").lm.moe.norm_topk_prob


def test_blockwise_attention_pads_a_sequence_that_does_not_divide_its_chunks():
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 600, 2, 16))
               for i in range(3))
    for causal in (True, False):
        got = blockwise_attention(q, k, v, causal=causal, q_chunk=256,
                                  kv_chunk=256, scale=0.3)
        want = full_attention(q, k, v, causal=causal, scale=0.3)
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_train_step_carries_every_backbone_scope():
    """Each of ``trace_names.LM_SCOPES`` names operations of ST-LLM's
    compiled gradient step, forward and backward."""
    from repro import trace_names
    from repro.models import stllm
    cfg = stllm.STLLMConfig(num_nodes=24, input_len=4, horizon=4, smoke=True,
                            embed_width=16, layers=3, experts_held=4, remat=True)
    params = jax.eval_shape(lambda: stllm.init(KEY, cfg))
    x = jax.ShapeDtypeStruct((2, 4, 24, 2), jnp.float32)
    text = jax.jit(jax.grad(stllm.loss_fn), static_argnums=1).lower(
        params, cfg, None, x, x).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in trace_names.LM_SCOPES:
        assert any(scope in n and "transpose" not in n for n in names), scope
        assert any(scope in n and "transpose" in n for n in names), scope
