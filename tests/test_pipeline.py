"""The `repro.pipeline` contract, per placement:

(a) the sampler/sharding pairing instantiated by `build_pipeline` matches the
    definition in `core/distributed.py`'s docstring;
(b) a 2-epoch CPU run is bit-identical to a kill-and-resume run through the
    checkpointer (deterministic (seed, epoch) sampling + step-granular
    checkpoints);
(c) every selectable gather reconstructs the same batches from the same
    starts.

Plus regression tests for the train-loop resume fixes and the microbatch
accumulator dtype policy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Placement, WindowSpec
from repro.core.distributed import data_axes, local_time_range
from repro.data import make_traffic_series
from repro.launch.mesh import make_host_mesh
from repro.optim import AdamConfig
from repro.pipeline import GATHERS, PipelineConfig, build_pipeline
from repro.train import TrainLoopConfig
from repro.train.loop import (init_train_state, make_train_step, run_training,
                              zero_grads_like)

ENTRIES, NODES, HORIZON, B, WORLD = 120, 3, 2, 4, 2
SPEC = WindowSpec(horizon=HORIZON, input_len=HORIZON)

EXPECTED_SAMPLER = {
    Placement.REPLICATED: "GlobalShuffleSampler",
    Placement.PARTITIONED: "ShardAlignedBatchSampler",
    Placement.ONDEMAND: "GlobalShuffleSampler",
}


def _params():
    return {"w": jnp.full((NODES, 2), 0.1, jnp.float32)}


def _loss_fn(p, x, y):
    pred = x[:, -1] * p["w"]  # [B, N, F]
    return jnp.mean((pred - y[:, 0]) ** 2), {}


def _pipe(placement, *, ckpt_dir=None, gather="slice", epochs=2, halo=True):
    return build_pipeline(
        make_traffic_series(ENTRIES, NODES), SPEC, make_host_mesh(),
        _loss_fn, _params(),
        PipelineConfig(
            batch_per_rank=B, placement=placement, world=WORLD, gather=gather,
            halo=halo, seed=11, adam=AdamConfig(lr=1e-2),
            loop=TrainLoopConfig(epochs=epochs, log_every=0,
                                 ckpt_dir=ckpt_dir)))


# ------------------------------------------------------- (a) placement pairing
@pytest.mark.parametrize("placement", list(Placement))
def test_sampler_sharding_pairing(placement):
    pipe = _pipe(placement)
    desc = pipe.describe()
    assert desc["sampler"] == EXPECTED_SAMPLER[placement]

    spec = desc["series_spec"]
    if placement is Placement.REPLICATED:
        # full series on every device: PartitionSpec() — no sharded axis
        assert spec == ()
    else:
        # PARTITIONED and ONDEMAND shard the TIME axis over the data axes
        first = spec[0]
        axes = set(first) if isinstance(first, tuple) else {first}
        assert axes == set(data_axes(pipe.mesh))

    grid = pipe.sampler.epoch_global(0)
    assert grid.shape == (pipe.steps_per_epoch, WORLD * B)
    if placement is Placement.PARTITIONED:
        # rank r's draws must start inside the time range of the series
        # shard rank r's device actually owns (local gathers, §5.4) — the
        # same boundaries series_sharding induces (local_time_range)
        blocks = grid.reshape(-1, WORLD, B)
        for r in range(WORLD):
            lo, hi = local_time_range(ENTRIES, r, WORLD)
            assert blocks[:, r, :].min() >= lo
            assert blocks[:, r, :].max() < hi
        # batch CONTENT is fixed (local batch shuffling): every drawn batch
        # in any epoch is one of the rank's pre-built batches; only the
        # choice/order rotates with the epoch
        for epoch in (0, 1):
            b1 = pipe.sampler.epoch_global(epoch).reshape(-1, WORLD, B)
            for r in range(WORLD):
                fixed = {tuple(row) for row in pipe.sampler.rank_batches[r]}
                assert {tuple(row) for row in b1[:, r, :]} <= fixed
        # cyclic rotation: an uneven rank's surplus batches are all visited
        # within ceil(n_batches / steps) epochs (no permanent truncation)
        for r in range(WORLD):
            fixed = {tuple(row) for row in pipe.sampler.rank_batches[r]}
            n_b = pipe.sampler.rank_batches[r].shape[0]
            need = -(-n_b // pipe.steps_per_epoch)
            seen = set()
            for e in range(need):
                rows = pipe.sampler.epoch_global(e).reshape(-1, WORLD, B)[:, r, :]
                seen |= {tuple(row) for row in rows}
            assert seen == fixed
    else:
        # global shuffling: different epochs draw different permutations
        assert not np.array_equal(grid, pipe.sampler.epoch_global(1))


# --------------------------------------------- (b) kill-and-resume determinism
@pytest.mark.parametrize("placement", list(Placement))
def test_resume_bit_identical(placement, tmp_path):
    straight, _ = _pipe(placement).fit(epochs=2, eval_fn=None)

    ckpt = str(tmp_path / placement.value)
    killed = _pipe(placement, ckpt_dir=ckpt)
    killed.fit(epochs=1, eval_fn=None)  # "killed" after epoch 0's checkpoint
    resumed, history = _pipe(placement, ckpt_dir=ckpt).fit(epochs=2,
                                                           eval_fn=None)
    # only epoch 1 ran after the resume
    assert [h["epoch"] for h in history if "epoch_time_s" in h] == [1]
    for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(resumed)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- (c) gather agreement
@pytest.mark.parametrize("placement", list(Placement))
def test_gather_variants_agree_on_pipeline_batches(placement):
    pipe = _pipe(placement)
    starts = pipe.batch_of_starts(pipe.sampler.epoch_global(0)[0])
    results = {
        name: fn(pipe.dataset.series, starts,
                 input_len=SPEC.in_len, horizon=SPEC.horizon)
        for name, fn in GATHERS.items()
        if name != "lm"  # different contract: y = shift(x), token streams
    }
    ref_x, ref_y = results.pop("slice")
    assert ref_x.shape == (WORLD * B, SPEC.in_len, NODES, 2)
    for name, (x, y) in results.items():
        assert np.array_equal(np.asarray(ref_x), np.asarray(x)), name
        assert np.array_equal(np.asarray(ref_y), np.asarray(y)), name


@pytest.mark.parametrize("placement", list(Placement))
def test_fit_with_auto_gather_bit_identical_to_slice(placement, tmp_path):
    """gather="auto" fused into the train step (dispatch fires at trace
    time, tuning into a throwaway cache) must leave the training RESULT
    bit-identical to gather="slice" — every candidate the tuner can crown
    is exact data movement, so auto only ever changes speed, never values."""
    from repro.kernels.autotune import autotuning

    base, _ = _pipe(placement, gather="slice").fit(eval_fn=None)
    with autotuning(mode="tune", cache_dir=str(tmp_path), warmup=0, iters=1):
        tuned, _ = _pipe(placement, gather="auto").fit(eval_fn=None)
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(tuned)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ per-rank feed contract
@pytest.mark.parametrize("placement", list(Placement))
def test_per_rank_feeds_assemble_epoch_global(placement):
    """epoch_global is ONLY the single-host assembly of the per-rank feed
    columns: concat([feed(r, e) for r in ranks], axis=1) == epoch_global(e)."""
    dp = _pipe(placement).dataplane
    for epoch in (0, 1, 5):
        cols = np.concatenate([dp.feed(r, epoch) for r in range(WORLD)], axis=1)
        assert np.array_equal(cols, dp.epoch_global(epoch))
        assert np.array_equal(dp.epoch_grid(epoch), dp.epoch_global(epoch))


# ------------------------------------------------------------ PARTITIONED halo
def test_partitioned_halo_knob_strictly_interior():
    """halo=False confines every sampled window to its rank's series shard
    (zero data communication); halo=True may spill span−1 steps (more
    samples).  Both surface through PipelineConfig."""
    from repro.core.distributed import local_time_range as ltr

    interior = _pipe(Placement.PARTITIONED, halo=False)
    spilling = _pipe(Placement.PARTITIONED, halo=True)
    assert interior.describe()["halo"] is False
    assert spilling.describe()["halo"] is True
    for r in range(WORLD):
        lo, hi = ltr(ENTRIES, r, WORLD)
        ids = interior.sampler.rank_ids[r]
        assert len(ids) > 0
        assert ids.min() >= lo and ids.max() + SPEC.span <= hi
    n_interior = sum(len(i) for i in interior.sampler.rank_ids)
    n_halo = sum(len(i) for i in spilling.sampler.rank_ids)
    assert n_halo >= n_interior


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="collectives need a >1-device mesh")
def test_partitioned_halo_false_step_is_communication_free():
    """With aligned feeds and halo=False (one rank per device shard), the
    ENGINE's compiled train step must contain zero data collectives — only
    the gradient all-reduce — while halo=True keeps the global-index
    lowering whose gather crosses shards.  Same starts, same loss."""
    from repro.core.distributed import dp_size
    from repro.core.index_dataset import IndexDataset
    from repro.launch.dryrun import collective_bytes
    from repro.train.loop import init_train_state

    mesh = make_host_mesh()
    dp = dp_size(mesh)
    raw = make_traffic_series(16 * dp, NODES)
    # widen the train split so every device shard holds train windows
    ds = IndexDataset.from_raw(raw, SPEC, train=0.97, val=0.01)

    def build(halo):
        return build_pipeline(
            raw, SPEC, mesh, _loss_fn, _params(),
            PipelineConfig(batch_per_rank=2, placement=Placement.PARTITIONED,
                           halo=halo, seed=0, adam=AdamConfig(lr=1e-2),
                           loop=TrainLoopConfig(epochs=1, log_every=0)),
            dataset=ds)

    interior, spilling = build(False), build(True)
    assert interior.describe()["sampler"] == "ShardAlignedBatchSampler"
    starts = interior.batch_of_starts(interior.sampler.epoch_global(0)[0])

    def data_bytes(pipe):
        state = init_train_state(_params(), pipe.config.adam)
        hlo = pipe.train_step.lower(state, starts).compile().as_text()
        coll = collective_bytes(hlo)
        return coll["total"] - coll["all-reduce"]

    assert data_bytes(interior) == 0
    assert data_bytes(spilling) > 0
    # both lowerings see the same windows -> same loss
    _, m_i = interior.train_step(init_train_state(_params(),
                                                  interior.config.adam), starts)
    _, m_s = spilling.train_step(init_train_state(_params(),
                                                  spilling.config.adam), starts)
    np.testing.assert_allclose(float(m_i["loss"]), float(m_s["loss"]),
                               rtol=1e-6)


# -------------------------------------------------------- evaluate ragged tail
def test_evaluate_includes_ragged_tail():
    """The final partial batch of a small split must contribute (window-
    weighted), not be silently dropped — the old loop truncated it and
    biased reported val/test MAE."""
    pipe = _pipe(Placement.REPLICATED)
    params = _params()
    pool = pipe.dataset.val_windows
    b = pipe.global_batch
    assert len(pool) % b != 0 and len(pool) > b  # the split has a ragged tail
    chunks = [pool[i:i + b] for i in range(0, len(pool), b)]
    losses = [float(pipe._eval_loss(params, pipe.batch_of_starts(c))[0])
              for c in chunks]
    expected = float(np.average(losses, weights=[len(c) for c in chunks]))
    got = pipe.evaluate(params)
    assert got == pytest.approx(expected)
    assert got != pytest.approx(losses[0])  # the old full-batches-only value


def test_evaluate_matches_hand_computed_ragged_tail():
    """Window-weighted ``evaluate`` against an expectation computed with
    NOTHING from the pipeline's compute path: numpy gathers on the
    standardized series, a numpy loss, a numpy weighted mean.  Pins the
    PR-2 behavior change (the tail contributes, weighted by its true window
    count) to first principles rather than to the jitted loss itself."""
    pipe = _pipe(Placement.REPLICATED)
    params = _params()
    pool = np.asarray(pipe.dataset.val_windows)
    series = np.asarray(pipe.dataset.series)         # [T, N, F], standardized
    starts = np.asarray(pipe.dataset.starts)
    b = pipe.global_batch
    assert len(pool) % b != 0 and len(pool) > b      # a genuine ragged tail
    w = np.asarray(params["w"], np.float32)

    def hand_loss(chunk):
        s = starts[chunk]
        x = np.stack([series[i:i + SPEC.in_len] for i in s])      # [c, L, N, F]
        y = np.stack([series[i + SPEC.in_len:i + SPEC.in_len + SPEC.horizon]
                      for i in s])                                # [c, H, N, F]
        return np.mean((x[:, -1] * w - y[:, 0]) ** 2, dtype=np.float32)

    chunks = [pool[i:i + b] for i in range(0, len(pool), b)]
    expected = float(np.average([hand_loss(c) for c in chunks],
                                weights=[len(c) for c in chunks]))
    assert pipe.evaluate(params) == pytest.approx(expected, rel=1e-5)
    # the tail really moves the answer: full-batches-only would be wrong
    full_only = float(np.mean([hand_loss(c) for c in chunks if len(c) == b]))
    assert expected != pytest.approx(full_only)


def test_distributed_eval_hand_computed_ragged_tail_weighting():
    """The DISTRIBUTED eval semantics from first principles (ISSUE 4): the
    expectation is computed with NOTHING from the pipeline — numpy gathers
    on the standardized series, a numpy loss, and the explicit
    (weighted_sum, weight) pair reduction over the per-rank eval-feed
    chunks + the ragged tail — exactly the psum-style combine evaluate()
    performs.  Also pins that the per-rank eval_feed columns reassemble
    precisely the chunks the reference scores (nothing dropped, nothing
    double-counted)."""
    pipe = _pipe(Placement.REPLICATED)  # world 2: a genuinely multi-rank plan
    params = _params()
    dp = pipe.dataplane
    pool = dp.eval_pool("val")
    series = np.asarray(pipe.dataset.series)
    starts = np.asarray(pipe.dataset.starts)
    b = pipe.global_batch
    steps = len(pool) // b
    tail = pool[steps * b:]
    assert steps >= 1 and len(tail) > 0  # full chunks AND a ragged tail
    w = np.asarray(params["w"], np.float32)

    def hand_loss(chunk):
        s = starts[np.asarray(chunk)]
        x = np.stack([series[i:i + SPEC.in_len] for i in s])
        y = np.stack([series[i + SPEC.in_len:i + SPEC.in_len + SPEC.horizon]
                      for i in s])
        return np.mean((x[:, -1] * w - y[:, 0]) ** 2, dtype=np.float32)

    # the chunks evaluate() scores are EXACTLY the rank-major assembly of
    # the per-rank eval feeds — the multi-process contract, checked here
    # against the raw pool slices
    rows = np.concatenate([dp.eval_feed(r) for r in range(WORLD)], axis=1)
    assert np.array_equal(rows, pool[:steps * b].reshape(steps, b))
    assert np.array_equal(np.concatenate([rows.ravel(), dp.eval_tail()]), pool)

    # the explicit (weighted_sum, weight) reduction: full chunks weigh b,
    # the tail weighs its true window count
    weighted_sum = np.float64(0.0)
    weight = np.float64(0.0)
    for i in range(steps):
        weighted_sum += np.float64(hand_loss(rows[i])) * b
        weight += b
    weighted_sum += np.float64(hand_loss(tail)) * len(tail)
    weight += len(tail)
    expected = float(weighted_sum / weight)

    assert pipe.evaluate(params) == pytest.approx(expected, rel=1e-5)
    # dropping the tail from the reduction must move the answer — the
    # ragged windows really are weighted in, not truncated
    assert expected != pytest.approx(float((weighted_sum - np.float64(
        hand_loss(tail)) * len(tail)) / (weight - len(tail))))


# ------------------------------------------------------------- LM gather entry
def test_lm_gather_entry_shift_windows():
    stream = jnp.arange(40, dtype=jnp.int32)
    starts = jnp.asarray([0, 3, 7], dtype=jnp.int32)
    x, y = GATHERS["lm"](stream, starts, input_len=5, horizon=1)
    np.testing.assert_array_equal(
        np.asarray(x), [np.arange(s, s + 5) for s in (0, 3, 7)])
    np.testing.assert_array_equal(
        np.asarray(y), [np.arange(s + 1, s + 6) for s in (0, 3, 7)])


def test_lm_pipeline_end_to_end():
    """The LM token-stream workload rides the pipeline via gather='lm'."""
    import dataclasses

    from repro.core.index_dataset import IndexDataset

    rng = np.random.default_rng(0)
    stream = rng.integers(0, 16, size=400).astype(np.int32)
    spec = WindowSpec(horizon=1, input_len=8)
    ds = IndexDataset.from_raw(stream, spec, scale_feature=None)
    ds = dataclasses.replace(ds, series=stream)  # tokens: no standardisation
    params = {"emb": jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)}

    def loss_fn(p, toks, labels):
        return jnp.mean((p["emb"][toks] - p["emb"][labels]) ** 2), {}

    pipe = build_pipeline(
        stream, spec, make_host_mesh(), loss_fn, params,
        PipelineConfig(batch_per_rank=4, world=1, gather="lm", seed=3,
                       adam=AdamConfig(lr=1e-2),
                       loop=TrainLoopConfig(epochs=1, log_every=1)),
        dataset=ds)
    state, history = pipe.fit(eval_fn=None)
    losses = [h["loss"] for h in history if "loss" in h]
    assert losses and all(np.isfinite(l) for l in losses)


def test_lm_eval_feeds_held_out_perplexity_parity():
    """LM epoch-end eval rides the SAME eval-feed machinery as the ST-GNN
    path (ISSUE 5 satellite, ex-ROADMAP item): ``Engine.evaluate`` over the
    ``lm`` gather's val pool must equal a from-first-principles numpy
    expectation — full chunks in pool order plus the ragged tail, combined
    through the explicit (weighted_sum, weight) reduction — and the
    launcher's ``val_ppl`` is just exp() of that number."""
    import dataclasses

    from repro.core.index_dataset import IndexDataset
    from repro.train.loop import combine_weighted

    rng = np.random.default_rng(1)
    vocab = 16
    stream = rng.integers(0, vocab, size=150).astype(np.int32)
    spec = WindowSpec(horizon=1, input_len=8)
    ds = IndexDataset.from_raw(stream, spec, scale_feature=None)
    ds = dataclasses.replace(ds, series=stream)  # tokens: no standardisation
    logits_w = rng.normal(size=(vocab, vocab)).astype(np.float32)
    params = {"w": jnp.asarray(logits_w)}

    def loss_fn(p, toks, labels):
        logp = jax.nn.log_softmax(p["w"][toks], axis=-1)      # [B, L, V]
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return jnp.mean(nll), {}

    pipe = build_pipeline(
        stream, spec, make_host_mesh(), loss_fn, params,
        PipelineConfig(batch_per_rank=4, world=1, gather="lm", seed=3,
                       adam=AdamConfig(lr=1e-2),
                       loop=TrainLoopConfig(epochs=1)),
        dataset=ds)
    pool = np.asarray(ds.val_windows)
    b = pipe.global_batch
    n_full = len(pool) // b
    # both eval paths in play: full chunks AND a ragged tail inside the
    # default max_batches budget
    assert 0 < n_full < 4 and len(pool) % b

    starts = np.asarray(ds.starts)

    def hand_nll(chunk):
        s = starts[chunk]
        x = np.stack([stream[i:i + spec.in_len] for i in s])
        y = np.stack([stream[i + 1:i + 1 + spec.in_len] for i in s])
        logits = logits_w[x].astype(np.float64)
        logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        return float(np.mean(-np.take_along_axis(logp, y[..., None], -1)))

    pairs = [(hand_nll(pool[i * b:(i + 1) * b]), b) for i in range(n_full)]
    pairs.append((hand_nll(pool[n_full * b:]), len(pool) - n_full * b))
    expected = combine_weighted(pairs)
    got = pipe.evaluate(params, split="val")
    assert got == pytest.approx(expected, rel=1e-5)
    assert np.isfinite(np.exp(got))  # the perplexity _train_lm logs


# ------------------------------------------------- train-loop resume hardening
class _StubSampler:
    steps_per_epoch = 4

    def epoch_global(self, epoch):
        return np.arange(4)[:, None] + 10 * epoch


def test_resume_past_partial_epoch_skips_cleanly():
    """start_step beyond an epoch must skip it wholesale: no over-large done
    count, no unbound-metrics crash on the fully-skipped epoch's summary."""
    ran = []

    def train_step(state, batch):
        ran.append(int(batch[0]))
        return state, {"loss": jnp.zeros(())}

    _, history = run_training(
        state={}, train_step=train_step, sampler=_StubSampler(),
        batch_of_starts=lambda row: row,
        loop=TrainLoopConfig(epochs=2, log_every=0),
        start_epoch=0, start_step=6)
    # epoch 0 (4 steps) fully done; epoch 1 resumes at its step 2
    assert ran == [12, 13]
    epochs_logged = [h["epoch"] for h in history if "epoch_time_s" in h]
    assert epochs_logged == [1]


def test_eval_every_sets_epoch_end_eval_cadence():
    """loop.eval_every gates eval_fn by EPOCH INDEX (resume-safe), not by
    call count: every 2nd epoch here, and 0 disables eval entirely."""
    def train_step(state, batch):
        return state, {"loss": jnp.zeros(())}

    def run(eval_every):
        calls = []
        _, history = run_training(
            state={}, train_step=train_step, sampler=_StubSampler(),
            batch_of_starts=lambda row: row,
            loop=TrainLoopConfig(epochs=4, log_every=0,
                                 eval_every=eval_every),
            eval_fn=lambda st: (calls.append(1), {"val_mae": 1.0})[1])
        evald = [h["epoch"] for h in history if "val_mae" in h]
        return calls, evald

    calls, evald = run(2)
    assert evald == [1, 3] and len(calls) == 2  # after epochs 2 and 4
    calls, evald = run(0)
    assert evald == [] and not calls
    calls, evald = run(1)
    assert evald == [0, 1, 2, 3]


def test_resume_mid_epoch_runs_remaining_steps():
    ran = []

    def train_step(state, batch):
        ran.append(int(batch[0]))
        return state, {"loss": jnp.zeros(())}

    run_training(
        state={}, train_step=train_step, sampler=_StubSampler(),
        batch_of_starts=lambda row: row,
        loop=TrainLoopConfig(epochs=1, log_every=0),
        start_epoch=0, start_step=3)
    assert ran == [3]


# --------------------------------------------- microbatch accumulator dtype
def test_zero_grads_match_gradient_dtypes():
    params = {"a": jnp.zeros((2,), jnp.bfloat16), "b": jnp.zeros((3,), jnp.float32)}
    z = zero_grads_like(params, None)
    assert z["a"].dtype == jnp.bfloat16 and z["b"].dtype == jnp.float32
    z16 = zero_grads_like(params, "bfloat16")
    assert z16["a"].dtype == jnp.bfloat16 and z16["b"].dtype == jnp.bfloat16


def test_microbatched_step_keeps_bf16_grad_tree():
    params = {"w": jnp.ones((4,), jnp.bfloat16)}

    def loss_fn(p, batch):
        return jnp.sum(p["w"].astype(jnp.float32)) * jnp.sum(batch), {}

    adam = AdamConfig(lr=1e-2, grad_clip=None)
    step = make_train_step(loss_fn, adam, lambda s: 1e-2, microbatches=2,
                           donate=False)
    state, metrics = step(init_train_state(params, adam),
                          jnp.ones((4,), jnp.float32))
    assert state["params"]["w"].dtype == jnp.bfloat16
    assert np.isfinite(float(metrics["loss"]))


# ------------------------------------------- resident arrays are arguments
def test_train_step_takes_series_and_supports_as_arguments():
    """The lowered train/eval programs take the resident series and the
    loss's Partial-bound supports as arguments and embed neither: no
    constant in them is as large as either array."""
    from repro.launch.dryrun import largest_constant_bytes

    nodes = 32
    supports = (jnp.full((nodes, nodes), 1.0 / nodes, jnp.float32),
                jnp.eye(nodes, dtype=jnp.float32))

    def loss(sup, p, x, y):
        h = jnp.einsum("mn,btnf->btmf", sup[0] @ sup[1], x)
        return jnp.mean((h[:, -1] * p["w"] - y[:, 0]) ** 2), {}

    pipe = build_pipeline(
        make_traffic_series(ENTRIES, nodes), SPEC, make_host_mesh(),
        jax.tree_util.Partial(loss, supports),
        {"w": jnp.full((nodes, 2), 0.1, jnp.float32)},
        PipelineConfig(batch_per_rank=B, adam=AdamConfig(lr=1e-2)))
    state = init_train_state(pipe.init_params, pipe.config.adam)
    starts = pipe.batch_of_starts(pipe.sampler.epoch_global(0)[0])
    series = pipe.dataset.series
    smallest = min(series.nbytes, supports[0].nbytes)
    for lowered in (pipe.train_step.lower(state, starts),
                    pipe._eval_loss.lower(state["params"], starts)):
        shapes = [a.shape for a in jax.tree.leaves(lowered.args_info)]
        assert series.shape in shapes
        assert shapes.count((nodes, nodes)) == 2
        assert largest_constant_bytes(lowered) < smallest


def test_largest_constant_bytes_sees_a_closed_over_array():
    from repro.launch.dryrun import largest_constant_bytes

    big = jnp.ones((64, 32), jnp.float32)
    lowered = jax.jit(lambda x: (x + big).sum()).lower(jnp.ones((64, 32)))
    assert largest_constant_bytes(lowered) == big.nbytes


@pytest.mark.parametrize("max_steps", [1, 7, 12])
def test_max_steps_caps_training(max_steps, tmp_path):
    """TrainLoopConfig.max_steps stops mid-epoch; the final checkpoint
    records where, so a resume with a larger cap picks up from there."""
    from repro.distributed import checkpoint_meta, latest_step

    capped = build_pipeline(
        make_traffic_series(ENTRIES, NODES), SPEC, make_host_mesh(),
        _loss_fn, _params(),
        PipelineConfig(
            batch_per_rank=B, placement=Placement.REPLICATED, world=WORLD,
            seed=11, adam=AdamConfig(lr=1e-2),
            loop=TrainLoopConfig(epochs=3, log_every=1, max_steps=max_steps,
                                 ckpt_dir=str(tmp_path))))
    spe = capped.steps_per_epoch
    assert spe < 12 < 3 * spe
    state, history = capped.fit(eval_fn=None)
    steps = [h["step"] for h in history if "epoch_time_s" not in h]
    assert steps == list(range(1, max_steps + 1))
    assert int(state["opt"]["step"]) == max_steps
    assert latest_step(str(tmp_path)) == max_steps
    epoch, done = divmod(max_steps, spe)
    assert checkpoint_meta(str(tmp_path)) == {"epoch": epoch,
                                              "done_in_epoch": done}
