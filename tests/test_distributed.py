"""Distributed substrate: checkpoint atomicity/elastic restore, heartbeat and
re-mesh policy (including the shrink/grow round-trip property), gradient
equivalence of the DP step, placement helpers."""
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import WindowSpec
from repro.core.distributed import (Placement, local_time_range, local_window_ids,
                                    series_sharding)
from repro.distributed import (Checkpointer, ElasticPlan, HeartbeatMonitor,
                               latest_step, plan_remesh, restore)
from repro.distributed.elastic import scale_batch_or_steps


# ------------------------------------------------------------------ checkpoint
def _tiny_state():
    k = jax.random.PRNGKey(0)
    return {"params": {"w": jax.random.normal(k, (4, 3)),
                       "stack": [jnp.arange(5.0), jnp.ones((2, 2))]},
            "opt": {"step": jnp.asarray(7, jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    state = _tiny_state()
    ck.save(state, step=10)
    ck.wait()
    restored, step = restore(str(tmp_path), state)
    assert step == 10
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_write=False)
    state = _tiny_state()
    for s in (1, 2, 3, 4):
        ck.save(state, step=s)
    assert ck.steps() == [3, 4]
    assert latest_step(str(tmp_path)) == 4


def test_checkpoint_detects_corruption(tmp_path):
    ck = Checkpointer(str(tmp_path), async_write=False)
    state = _tiny_state()
    ck.save(state, step=5)
    path = os.path.join(str(tmp_path), "step_0000000005", "arrays.npz")
    with open(path, "r+b") as f:
        f.seek(30)
        f.write(b"\xde\xad")
    with pytest.raises(IOError, match="checksum"):
        restore(str(tmp_path), state)


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    ck = Checkpointer(str(tmp_path), async_write=False)
    ck.save(_tiny_state(), step=1)
    bad = _tiny_state()
    bad["params"]["w"] = jnp.zeros((5, 5))
    with pytest.raises(ValueError, match="shape"):
        restore(str(tmp_path), bad)


def test_checkpoint_async_overlaps_and_surfaces_errors(tmp_path):
    ck = Checkpointer(str(tmp_path / "ok"), keep=1)
    ck.save(_tiny_state(), step=1)  # async
    ck.save(_tiny_state(), step=2)  # waits for 1, then writes 2
    ck.wait()
    assert ck.steps() == [2]


def test_checkpoint_meta_roundtrip(tmp_path):
    """Run coordinates ride the manifest so an elastic restart can resume at
    the same (epoch, step) even when steps_per_epoch changed."""
    from repro.distributed import checkpoint_meta

    ck = Checkpointer(str(tmp_path), async_write=False)
    ck.save(_tiny_state(), step=4, meta={"epoch": 1, "done_in_epoch": 2})
    assert checkpoint_meta(str(tmp_path)) == {"epoch": 1, "done_in_epoch": 2}
    ck.save(_tiny_state(), step=9)  # meta-less saves read back empty
    assert checkpoint_meta(str(tmp_path)) == {}
    assert checkpoint_meta(str(tmp_path), step=4) == {"epoch": 1,
                                                      "done_in_epoch": 2}


def test_elastic_restore_into_new_sharding(tmp_path):
    """Restart on a different topology: restore re-device_puts every leaf."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    ck = Checkpointer(str(tmp_path), async_write=False)
    state = _tiny_state()
    ck.save(state, step=3)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    sh = NamedSharding(mesh, P())
    restored, _ = restore(str(tmp_path), state, shardings=sh)
    leaf = restored["params"]["w"]
    assert leaf.sharding == sh


# --------------------------------------------------------------------- elastic
def test_heartbeat_dead_and_straggler():
    t = [0.0]
    mon = HeartbeatMonitor(4, timeout=10.0, straggler_factor=3.0,
                           clock=lambda: t[0])
    for step in range(1, 6):
        for w in range(3):  # worker 3 goes silent after step 1
            t[0] += 0.1
            mon.beat(w, step)
        if step == 1:
            mon.beat(3, 1)
    t[0] += 20.0
    for w in range(3):  # live workers keep beating after the gap
        mon.beat(w, 6)
    assert mon.dead() == [3]

    # straggler: worker 2 self-reports 10x slower compute per step
    t2 = [0.0]
    mon2 = HeartbeatMonitor(4, timeout=1e9, clock=lambda: t2[0])
    for step in range(1, 8):
        for w in range(4):
            t2[0] += 10.0  # wall time is the same for everyone (sync SPMD)
            mon2.beat(w, step, step_time=1.0 if w != 2 else 10.0)
    assert mon2.stragglers() == [2]
    assert mon2.unhealthy() == [2]


def test_plan_remesh_keeps_tp_groups_whole():
    # 16 hosts x 4 chips, TP=16 -> 4 hosts per group, 4 groups
    plan = plan_remesh(16, [5], model_parallel=16, chips_per_host=4)
    assert isinstance(plan, ElasticPlan)
    assert plan.mesh_shape == (3, 16)
    # the whole group containing host 5 (hosts 4-7) is dropped
    assert plan.dropped_workers == (4, 5, 6, 7)
    assert plan_remesh(16, [], model_parallel=16) is None


def test_plan_remesh_exhausted():
    with pytest.raises(RuntimeError):
        plan_remesh(4, [0, 1, 2, 3], model_parallel=4, chips_per_host=4)


def test_scale_batch_rules():
    per, glob = scale_batch_or_steps(1024, old_dp=16, new_dp=12)
    assert per * 12 >= 1024  # keep-global rounds up
    per2, glob2 = scale_batch_or_steps(1024, 16, 12, keep_global_batch=False)
    assert per2 == 64 and glob2 == 768


# -------------------------------------------- shrink/grow round-trip property
@settings(max_examples=80, deadline=None)
@given(base_world=st.integers(2, 8),
       batch_per_rank=st.integers(1, 8),
       events=st.lists(st.integers(0, 999), min_size=1, max_size=12))
def test_plan_roundtrip_restores_base_topology(base_world, batch_per_rank,
                                               events):
    """Arbitrary shrink/grow sequences through ``plan_remesh`` +
    ``scale_batch_or_steps`` (the engine's contract: ALWAYS re-scale
    against the BASE global batch) restore the BASE topology and global
    batch exactly once every worker has returned — and never compound the
    ceil inflation mid-sequence.  The victim of each shrink is drawn from
    the whole world INCLUDING rank 0 (the leader): the planner is
    rank-agnostic, succession (lowest surviving rank decides) is always
    well-defined, and a sequence that kills every leader in turn still
    round-trips."""
    base_global = base_world * batch_per_rank
    world = base_world
    for ev in events:
        shrink = (ev % 2 == 0 and world > 1) or world == base_world
        if shrink:
            victim = ev % world                   # may be 0 — the leader
            successor = 0 if victim else (1 if world > 1 else 0)
            plan = plan_remesh(world, [victim], model_parallel=1,
                               chips_per_host=1, decided_by=successor)
            assert plan.kind == "shrink"
            assert plan.dropped_workers == (victim,)
            assert plan.decided_by == successor   # rank 0's death included
            world -= 1
        else:
            back = 1 + ev % (base_world - world)  # grow by 1..missing
            plan = plan_remesh(world, [],
                               recovered=list(range(world, world + back)),
                               model_parallel=1, chips_per_host=1)
            assert plan.kind == "grow"
            assert len(plan.readmitted_workers) == back
            world += back
        # the engine's invariant: per-worker batch is ceil(BASE/world) at
        # every intermediate topology — scaling from the base never
        # compounds (feeding the inflated global back in WOULD)
        per, glob = scale_batch_or_steps(base_global, old_dp=base_world,
                                         new_dp=world)
        assert per == -(-base_global // world)
        assert glob == per * world
        assert glob >= base_global                # never loses windows
        assert glob - base_global < world         # inflation bounded < world
    # every worker returns: the inverse plans restore the base exactly
    while world < base_world:
        plan = plan_remesh(world, [],
                           recovered=list(range(world, base_world)),
                           model_parallel=1, chips_per_host=1)
        world += len(plan.readmitted_workers)
    per, glob = scale_batch_or_steps(base_global, old_dp=base_world,
                                     new_dp=world)
    assert world == base_world
    assert (per, glob) == (batch_per_rank, base_global)


@settings(max_examples=40, deadline=None)
@given(base_world=st.integers(2, 6), shrinks=st.integers(1, 4),
       batch_per_rank=st.integers(1, 5))
def test_compounding_ceil_inflation_is_real_and_avoided(base_world, shrinks,
                                                        batch_per_rank):
    """The failure mode the BASE-scaling contract exists to prevent: chain
    the scaling through each re-mesh's inflated output and the global batch
    is non-decreasing (and on non-dividing worlds grows); scale from the
    base and the round trip is exact."""
    base_global = base_world * batch_per_rank
    n = min(shrinks, base_world - 1)
    # the WRONG way: feed each inflated global back in
    chained = base_global
    for w in range(base_world - 1, base_world - 1 - n, -1):
        chained = scale_batch_or_steps(chained, old_dp=w + 1, new_dp=w)[1]
    for w in range(base_world - n + 1, base_world + 1):
        chained = scale_batch_or_steps(chained, old_dp=w - 1, new_dp=w)[1]
    assert chained >= base_global
    # the engine's way: always from the base — exact after the round trip
    assert scale_batch_or_steps(base_global, old_dp=base_world,
                                new_dp=base_world) == (batch_per_rank,
                                                       base_global)


# ------------------------------------------------------------------ placements
def test_local_time_ranges_partition():
    ranges = [local_time_range(105, r, 4) for r in range(4)]
    assert ranges[0][0] == 0 and ranges[-1][1] == 105
    for (a, b), (c, d) in zip(ranges, ranges[1:]):
        assert b == c  # contiguous, disjoint


def test_local_window_ids_interior_vs_halo():
    spec = WindowSpec(horizon=3, input_len=3)  # span 6
    world, entries = 4, 100
    interior = [local_window_ids(entries, spec, r, world, halo=False)
                for r in range(world)]
    halo = [local_window_ids(entries, spec, r, world, halo=True)
            for r in range(world)]
    # interior windows never leave the shard
    for r, ids in enumerate(interior):
        lo, hi = local_time_range(entries, r, world)
        assert all(lo <= s and s + spec.span <= hi for s in ids)
    # halo covers every global window exactly once
    all_halo = np.concatenate(halo)
    assert np.array_equal(np.sort(all_halo), np.arange(entries - spec.span + 1))


def test_dp_grad_equivalence_single_vs_sharded():
    """DP-sharded loss grads == single-device grads (the all-reduce inserted
    by the partitioner computes exactly the global batch gradient)."""
    from repro.optim import AdamConfig
    from repro.train.loop import init_train_state, make_train_step

    k = jax.random.PRNGKey(0)
    w0 = jax.random.normal(k, (8, 8))

    def loss_fn(p, batch):
        return jnp.mean((batch @ p["w"]) ** 2), {}

    adam = AdamConfig(lr=1e-2, grad_clip=None)
    step = make_train_step(loss_fn, adam, lambda s: 1e-2, donate=False)
    batch = jax.random.normal(k, (16, 8))
    s1, _ = step(init_train_state({"w": w0}, adam), batch)
    # microbatched (sequential halves) must agree bitwise-ish
    step2 = make_train_step(loss_fn, adam, lambda s: 1e-2, microbatches=2,
                            donate=False)
    s2, _ = step2(init_train_state({"w": w0}, adam), batch)
    np.testing.assert_allclose(np.asarray(s1["params"]["w"]),
                               np.asarray(s2["params"]["w"]), atol=1e-6)
