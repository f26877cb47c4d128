"""The plain reference against the program: the same weights from the same
key, the same loss, and one engine step followed by the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.reference import dcrnn as ref_dcrnn
from bench.reference import pgt_dcrnn as ref_pgt
from bench.reference import train as ref_train
from bench.tests import small

MODELS = [("pgt-dcrnn-all-la", ref_pgt), ("dcrnn-pems", ref_dcrnn)]


def _program(name):
    import importlib
    cfg = spec.config(name)
    m = dict(cfg["model"], num_nodes=small.NODES, remat=False)
    mod = importlib.import_module(cfg["program"]["module"])
    return mod, getattr(mod, cfg["program"]["config"])(**m), m


@pytest.mark.parametrize("name,ref", MODELS)
def test_reference_init_and_loss_match_the_program(name, ref):
    mod, mcfg, m = _program(name)
    key = jax.random.PRNGKey(11)
    p_prog, p_ref = mod.init(key, mcfg), ref.init(key, m)
    assert jax.tree.structure(p_prog) == jax.tree.structure(p_ref)
    for a, b in zip(jax.tree.leaves(p_prog), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)
    k1, k2, k3 = jax.random.split(key, 3)
    n = small.NODES
    sup = tuple(jax.nn.softmax(jax.random.normal(k, (n, n)), axis=1)
                for k in (k1, k2))
    x = jax.random.normal(k3, (3, 12, n, 2))
    y = jax.random.normal(k1, (3, 12, n, 2))
    with jax.default_matmul_precision("highest"):
        want = float(mod.loss_fn(p_prog, mcfg, sup, x, y))
        got = float(ref.loss(p_ref, m, sup, x, y))
        g_prog = jax.grad(lambda p: mod.loss_fn(p, mcfg, sup, x, y))(p_prog)
        g_ref = jax.grad(lambda p: ref.loss(p, m, sup, x, y))(p_ref)
    assert got == pytest.approx(want, rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6)


def test_reference_adam_matches_the_program_optimizer():
    from repro.optim import AdamConfig, apply_updates, init_opt_state
    from repro.optim.schedule import warmup_cosine
    o = spec.config("pgt-dcrnn-all-la")["optimizer"]
    params = {"a": jnp.linspace(-1, 1, 6), "b": jnp.ones((2, 3))}
    grads = [jax.tree.map(lambda p: (i + 1.5) * jnp.sin(p + i), params)
             for i in range(3)]
    cfg = AdamConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                     grad_clip=o["grad_clip"])
    state, p_prog = init_opt_state(params, cfg), params
    zeros = jax.tree.map(jnp.zeros_like, params)
    p_ref, m, v = params, zeros, zeros
    for i, g in enumerate(grads):
        lr = warmup_cosine(i, base_lr=o["lr"], warmup_steps=o["warmup_steps"],
                           total_steps=o["total_steps"])
        assert float(lr) == pytest.approx(ref_train.learning_rate(i, o),
                                          rel=1e-6, abs=1e-12)
        p_prog, state, _ = apply_updates(p_prog, g, state, cfg, lr)
        p_ref, m, v = ref_train.adam(p_ref, ref_train.clip(g, o["grad_clip"]),
                                     m, v, i + 1, ref_train.learning_rate(i, o), o)
    for a, b in zip(jax.tree.leaves(p_prog), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@pytest.mark.parametrize("cell", ["pgt-dcrnn-all-la.b64", "dcrnn-pems.b8"])
def test_a_whole_run_is_correct_against_the_reference(cell):
    result = small.run(cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"windows_per_s", "hbm_peak_gib",
                                      "setup_s"}
    assert list(result)[-1] == "checks"
