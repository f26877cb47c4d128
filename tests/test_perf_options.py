"""Regression coverage for the §Perf opt-in knobs: they must keep producing
valid programs/shardings and numerically-identical math where claimed."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import LM_ARCHS, get_arch
from repro.launch import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models.lm import model as lm
from repro.models.lm.config import MoEConfig
from repro.models.lm.moe import init_moe, moe_ffn

KEY = jax.random.PRNGKey(0)


def test_tp_rules_disabled_no_model_axis():
    """mode2d: with tp_rules off no param spec may reference 'model'."""
    cfg = get_arch("qwen1.5-4b").lm
    import numpy as _np
    from jax.sharding import Mesh

    mesh = Mesh(_np.array(jax.devices() * 16)[:16].reshape(4, 4),
                ("data", "model"))
    for path, shape in [("lm_head/w", (2560, 151936)),
                        ("stages/0/sub0/attn/wq/w", (40, 2560, 2560)),
                        ("stages/0/sub0/mlp/wi/w", (40, 2560, 6912)),
                        ("embed", (151936, 2560))]:
        spec = shd.lm_param_spec(path, shape, cfg, mesh, tp_rules=False,
                                 fsdp=("data", "model"))
        flat = [a for e in spec if e for a in (e if isinstance(e, tuple) else (e,))]
        # FSDP may use model as a *data-like* axis, but never two dims
        assert len(flat) == len(set(flat))


def test_grouped_moe_matches_global_exact():
    moe = MoEConfig(n_experts=8, top_k=2, capacity_factor=8.0, n_shared=2)
    p = init_moe(KEY, 64, moe, 128, "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 32, 64))
    y1, a1, _ = moe_ffn(p, x, moe, "swiglu")
    for g in (2, 4, 8):
        y2, a2, _ = moe_ffn(p, x, moe, "swiglu", groups=g)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5,
                                   err_msg=f"groups={g}")
        assert abs(float(a1) - float(a2)) < 1e-6


def test_grouped_moe_grad_flows():
    moe = MoEConfig(n_experts=4, top_k=2, capacity_factor=4.0)
    p = init_moe(KEY, 32, moe, 64, "swiglu")
    x = jax.random.normal(KEY, (2, 16, 32))

    def loss(p):
        y, aux, _ = moe_ffn(p, x, moe, "swiglu", groups=4)
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(p)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))


@pytest.mark.parametrize("q_chunk,kv_chunk", [(512, 512), (1024, 4096)])
def test_attention_chunk_config_equivalence(q_chunk, kv_chunk):
    """The §Perf chunk knobs change tiling, never values."""
    cfg = dataclasses.replace(LM_ARCHS["minitron-8b"].smoke_config(),
                              max_seq_len=4096)
    cfg_t = dataclasses.replace(cfg, q_chunk=q_chunk, kv_chunk=kv_chunk)
    params = lm.init(KEY, cfg)
    # force the blockwise path: seq > BLOCKWISE_THRESHOLD
    toks = jax.random.randint(KEY, (1, 4096), 0, cfg.vocab)
    l1, _ = lm.loss_fn(params, cfg, toks, toks)
    l2, _ = lm.loss_fn(params, cfg_t, toks, toks)
    assert abs(float(l1) - float(l2)) < 1e-3


def test_mode2d_program_runs_on_host_mesh():
    """mode2d cell program lowers + executes on a small host mesh."""
    import dataclasses as dc

    from repro.launch.specs import build_lm_train
    from repro.configs.base import ShapeCell
    from repro.launch.mesh import shrink_mesh

    arch = get_arch("minitron-8b")
    arch = dc.replace(arch, lm=arch.smoke_config())
    cell = ShapeCell("tiny", "train", 64, 2)
    # cap the data axis at the global batch (2) so the batch stays divisible
    # on forced multi-device hosts (the CI mesh-8 leg)
    mesh = shrink_mesh(make_host_mesh(model=1), cell.global_batch)
    prog = build_lm_train(arch, cell, mesh, mode2d=True, microbatches=1)
    rng = np.random.default_rng(0)

    def realize(x):
        if np.issubdtype(x.dtype, np.integer):
            hi = 100 if x.shape == () or x.ndim <= 1 else 100
            return jnp.asarray(rng.integers(0, hi, size=x.shape).astype(x.dtype))
        return jnp.asarray((rng.standard_normal(x.shape) * 0.02).astype(x.dtype))

    args = jax.tree.map(realize, prog.args,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    with mesh:
        step = jax.jit(prog.fn, in_shardings=prog.in_shardings,
                       out_shardings=prog.out_shardings)
        state, loss = step(*args)
    assert np.isfinite(float(loss))
