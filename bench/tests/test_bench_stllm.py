"""ST-LLM over DeepSeek-V2-Lite against its plain reference, at the registry's
small widths: the same weights from the same key, the same loss and
gradients, and a whole run of the cell through ``Engine.fit`` whose three
Adam steps the reference follows.  At the cell's own size, the configuration
file keeps the published numbers the program and the reference use."""
import copy
import dataclasses
import time
from unittest import mock

import jax
import numpy as np
import pytest

from bench import harness, spec
from bench.reference import stllm as ref
from bench.tests import small
from repro.configs import get_arch
from repro.models import stllm

CELL = "stllm-dsv2-lite-all-la.b4"


def _published(lm) -> dict:
    """``lm``'s numbers under the reference's (config.json) names."""
    moe, mla = lm.moe, lm.mla
    return {"hidden_size": lm.d_model, "num_attention_heads": lm.n_heads,
            "kv_lora_rank": mla.kv_lora_rank,
            "qk_nope_head_dim": mla.qk_nope_head_dim,
            "qk_rope_head_dim": mla.qk_rope_head_dim,
            "v_head_dim": mla.v_head_dim, "intermediate_size": moe.dense_d_ff,
            "moe_intermediate_size": moe.d_expert,
            "n_routed_experts": moe.n_experts, "num_experts_per_tok": moe.top_k,
            "n_shared_experts": moe.n_shared,
            "first_k_dense_replace": moe.first_k_dense,
            "norm_topk_prob": moe.norm_topk_prob,
            "routed_scaling_factor": moe.routed_scaling_factor,
            "rms_norm_eps": lm.norm_eps, "rope_theta": lm.rope_theta,
            "rope_scaling": {k: v for k, v in dataclasses.asdict(
                lm.rope_scaling).items()}}


@pytest.fixture
def small_widths(monkeypatch):
    """The reference's table at the registry's small DeepSeek widths."""
    lm = get_arch("deepseek-v2-lite-16b").smoke_config()
    table = {**ref.PUBLISHED["deepseek-v2-lite-16b"], **_published(lm),
             "capacity_factor": lm.moe.capacity_factor}
    monkeypatch.setitem(ref.PUBLISHED, "deepseek-v2-lite-16b", table)


def _model(**kw) -> dict:
    m = dict(spec.config("stllm-dsv2-lite-all-la")["model"], num_nodes=48,
             input_len=4, horizon=4, embed_width=16, smoke=True, experts_held=4)
    return {**m, **kw}


def test_program_and_reference_agree_on_weights_loss_and_gradients(small_widths):
    m = _model()
    cfg = stllm.STLLMConfig(**m)
    key = jax.random.PRNGKey(7)
    # jitted, as the harness draws the program's weights
    p, r = jax.jit(stllm.init, static_argnums=1)(key, cfg), ref.init(key, m)
    assert jax.tree.structure(p) == jax.tree.structure(r)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(r)):
        np.testing.assert_array_equal(a, b)
    kx, kt, ky = jax.random.split(jax.random.PRNGKey(8), 3)
    x = jax.random.normal(kx, (3, 4, 48, 2))
    x = x.at[..., 1].set(jax.random.randint(kt, (3, 4, 48), 0, 288) / 288)
    y = jax.random.normal(ky, (3, 4, 48, 2))
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(stllm.loss_fn)(p, cfg, None, x, y)
        lr, gr = jax.value_and_grad(ref.loss)(r, m, None, x, y)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * float(np.abs(b).max()))


def test_a_whole_run_through_the_engine_follows_the_reference(small_widths):
    cfg = small.config(CELL)
    cfg["model"].update(smoke=True, experts_held=4)
    checks = copy.deepcopy(spec.checks(CELL))
    with small.jax_config_kept(), mock.patch(
            "repro.launch.compile_cache.enable_compile_cache", lambda: "off"):
        out = harness.run(CELL, 3_000_000_017, 0.2, False,
                          t_start=time.perf_counter(), require_tpu=False,
                          cfg=cfg, traffic=dict(spec.traffic("b4")),
                          checks=checks)
    got = {k: c["value"] for k, c in out["checks"].items()}
    assert out["correct"], got
    assert got["loss_gap"] < 1e-4 and got["update_gap"] < 1e-4
    assert got["grad1_gap"] < 1e-4


def test_the_configuration_keeps_the_published_numbers():
    """The file's catalog keys, the reference's table and the program's
    registry agree; only the depth is cut, and the FLOP count executes at
    least what it counts as useful."""
    cfg = spec.config("stllm-dsv2-lite-all-la")
    table = ref.PUBLISHED["deepseek-v2-lite-16b"]
    cut = {"num_hidden_layers": 3,
           "rope_scaling": {**table["rope_scaling"], "type": "yarn"}}
    for k, v in table.items():
        assert cfg[k] == cut.get(k, v), k
    lm = stllm.STLLMConfig(**cfg["model"]).backbone_config()
    assert _published(lm) == {k: table[k] for k in _published(lm)}
    assert (lm.layers, lm.moe.experts_held) == (cfg["num_hidden_layers"],
                                                cfg["experts_held"])
    useful, dense = harness.window_flops(cfg, [0, 0])
    assert 0 < useful < dense
