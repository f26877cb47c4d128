"""Engine — the execution half of the pipeline: jitted step, checkpoints,
topology, and elastic restarts.

The engine owns everything the :class:`~repro.pipeline.dataplane.DataPlane`
deliberately does not: the fused gather/loss train step, the checkpointer,
and — when an :class:`ElasticConfig` is attached — the fault-tolerance loop
that lets a run survive worker loss:

1. every step, worker heartbeats reach the :class:`HeartbeatMonitor`
   (``ElasticConfig.step_feed`` is the transport — a real collector on a
   fleet, a deterministic fake for single-host fault-injection tests);
2. when the monitor flags a worker, ``plan_remesh`` computes the largest
   healthy sub-mesh (TP groups whole, data axis shrunk) and the in-flight
   state is checkpointed with its (epoch, done_in_epoch) coordinates;
3. the engine shrinks the mesh (``shrink_mesh``), rebuilds the data plane
   for the new world (series re-placed via ``series_sharding``, sampler
   rebuilt, per-worker batch re-scaled by ``scale_batch_or_steps``),
   re-jits the step, and restores the latest checkpoint into the new
   topology (``restore(..., shardings=...)`` re-shards on the way in);
4. training resumes from the same (seed, epoch, step) coordinates —
   samplers are deterministic functions of (seed, epoch), so the resumed
   schedule is reproducible.  (Within the interrupted epoch the coverage is
   approximate when the batch re-scales: the same permutation re-rows into
   a different grid, so a few boundary windows may repeat or drop.  The
   global step counter stays monotonic across re-meshes.)

``Engine`` also keeps the whole legacy ``Pipeline`` surface (``.sampler``,
``.dataset``, ``.describe()``, ``.fit``, ``.evaluate``, …) so
``build_pipeline`` remains a working compatibility constructor.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import trace_names
from repro.core.distributed import Placement, data_axes, dp_size
from repro.core.index_dataset import IndexDataset
from repro.core.windows import WindowSpec
from repro.distributed import (Checkpointer, HeartbeatMonitor,
                               LeaderCheckpointer, checkpoint_meta,
                               latest_step, plan_remesh, restore,
                               scale_batch_or_steps)
from repro.launch.mesh import shrink_mesh
from repro.pipeline.dataplane import DataPlane, PipelineConfig, build_dataplane
from repro.pipeline.gathers import resolve_gather
from repro.pipeline.prefetch import FeedPrefetcher, PrefetchPlan
from repro.pipeline.samplers import ShardAlignedBatchSampler
from repro.train.loop import (RestartSignal, combine_weighted,
                              init_train_state, make_train_step, run_training)


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Fault-tolerance policy for :meth:`Engine.fit`.

    Heartbeat workers are indexed by DATA-PARALLEL rank (0..world−1); with
    the defaults ``model_parallel == chips_per_host`` each worker is its own
    TP group, so losing one drops exactly one data rank.  Set them per your
    fleet layout when a TP group spans hosts — ``plan_remesh`` then drops
    whole groups and the engine shrinks the world by the dropped-rank count.

    ``step_feed(global_step, world) -> {rank: (step, step_time | None)}`` is
    the heartbeat transport: which workers reported in since the last step.
    None (the default) simulates an all-healthy fleet — every rank beats
    every step — which is correct for single-process runs and lets tests
    inject faults by omitting ranks (and driving ``clock``) instead.  Real
    transports live in :mod:`repro.distributed.transport`.  A beat from a
    rank OUTSIDE the current world is a dropped worker announcing its
    return: the engine plans the inverse GROW re-mesh (up to
    ``target_world``, defaulting to the world the engine was built with) and
    the per-worker batch scales back down against the BASE global batch —
    shrink and grow round-trip to the original topology.

    ``emitter(global_step)`` is the worker-side half of a real transport:
    called once per step so THIS process's ranks heartbeat out (wire it to
    ``transport.emit``); None for single-process fakes.

    ``remesh`` selects who executes a plan: ``"inprocess"`` (default) has
    the engine shrink/grow the mesh and resume inside this process — valid
    single-host, where every shard stays addressable; ``"relaunch"`` makes
    :meth:`Engine.fit` re-raise the checkpoint-annotated
    :class:`RestartSignal` so an external launcher can tear the gang down
    and relaunch into the planned topology (the only sound option under a
    real ``jax.distributed`` fleet, where a dead peer's shards are gone and
    the next collective would hang).

    On shrink with ``keep_global_batch=True`` the per-worker batch is
    ``ceil(global/new_dp)``, so the global batch can GROW by up to
    ``new_dp − 1`` windows (no ragged trim exists — uniform SPMD batches);
    ``False`` keeps the per-worker batch and shrinks the global batch.
    Both directions always re-scale from the engine's BASE global batch, so
    repeated re-meshes never compound the ceil rounding.
    """

    check_every: int = 1           # poll the monitor every N steps
    heartbeat_timeout: float = 60.0
    straggler_factor: float = 3.0
    model_parallel: int = 1        # TP group size, kept whole by plan_remesh
    chips_per_host: int = 1
    keep_global_batch: bool = True  # scale_batch_or_steps policy on re-mesh
    max_restarts: int = 8
    clock: Callable[[], float] = time.monotonic
    step_feed: Callable[[int, int], dict] | None = None
    emitter: Callable[[int], None] | None = None
    target_world: int | None = None  # grow ceiling; None = the build world
    remesh: str = "inprocess"      # or "relaunch" (external launcher re-meshes)
    # A returned worker must announce on this many polls (and still be
    # fresh) before a grow is planned — one stray beat from a crash-looping
    # host must not trigger a grow that immediately shrinks back.  The
    # launcher owns any stronger quarantine policy (e.g. exponential rejoin
    # backoff across relaunches); this is the in-process debounce.
    readmit_after_beats: int = 3
    # Leader succession (repro.distributed.leader.LeaderTracker): when set,
    # every single-writer duty — checkpoint writes, plan decisions, plan/
    # history emission — follows `leader.is_leader()` instead of the fixed
    # `jax.process_index() == 0`, so the death of process 0 hands the
    # decider role to the lowest surviving rank (whose transport state is
    # already primed: the file transport is symmetric, the TCP collectors
    # peer-mirror).  None keeps the classic process-0 gating.
    leader: Any | None = None


@dataclasses.dataclass
class Engine:
    """Jitted step + checkpointing + topology over a rebuildable DataPlane."""

    dataplane: DataPlane
    loss_fn: Callable
    init_params: Any
    train_step: BoundStep  # (state, starts) -> (state, metrics)
    _eval_loss: BoundStep  # (params, starts) -> (loss, metrics)
    elastic: ElasticConfig | None = None
    # One record per elastic restart: the plan plus the resume coordinates.
    restarts: list = dataclasses.field(default_factory=list)

    def __post_init__(self):
        # The BASE topology: re-mesh scaling is always computed against it
        # (never against the previous re-mesh's inflated output) and grow
        # plans re-expand its mesh — a shrink→grow round trip restores the
        # original (mesh, world, per-worker batch) exactly.
        self._base_mesh = self.dataplane.mesh
        self._base_world = self.dataplane.world
        self._base_global_batch = self.dataplane.global_batch
        self._checkpointer: Any = None  # fit's writer, kept for succession

    # -------------------------------------------------------------- leadership
    def is_leader(self) -> bool:
        """Whether THIS process currently owns the single-writer duties
        (checkpoints, plan emission, durable history).  With an
        ``ElasticConfig.leader`` tracker attached the verdict follows the
        succession rule (lowest live rank wins); without one it is the
        classic fixed gate, process 0."""
        el = self.elastic
        if el is not None and el.leader is not None:
            return el.leader.is_leader()
        return jax.process_index() == 0

    def leader_rank(self) -> int:
        el = self.elastic
        if el is not None and el.leader is not None:
            return el.leader.leader()
        return 0

    # ------------------------------------------- legacy Pipeline surface
    @property
    def config(self) -> PipelineConfig:
        return self.dataplane.config

    @property
    def mesh(self):
        return self.dataplane.mesh

    @property
    def spec(self) -> WindowSpec:
        return self.dataplane.spec

    @property
    def dataset(self) -> IndexDataset:
        return self.dataplane.dataset

    @property
    def sampler(self):
        return self.dataplane.sampler

    @property
    def series_sharding(self):
        return self.dataplane.series_sharding

    @property
    def world(self) -> int:
        return self.dataplane.world

    @property
    def steps_per_epoch(self) -> int:
        return self.dataplane.steps_per_epoch

    @property
    def global_batch(self) -> int:
        return self.dataplane.global_batch

    def describe(self) -> dict:
        return {**self.dataplane.describe(),
                "gather_lowering": gather_lowering(self.dataplane, self.config)}

    def batch_of_starts(self, window_ids: np.ndarray, *,
                        replicate: bool = False) -> jnp.ndarray:
        return self.dataplane.batch_of_starts(window_ids, replicate=replicate)

    # --------------------------------------------------------------- training
    def fit(
        self,
        *,
        epochs: int | None = None,
        eval_fn: Callable[[Any], dict] | None | str = "auto",
        resume: bool = True,
        history_sink: list | None = None,
    ) -> tuple[Any, list[dict]]:
        """Train (resuming from ``loop.ckpt_dir`` when a checkpoint exists).

        Returns ``(state, history)`` exactly like ``run_training``.
        ``eval_fn="auto"`` evaluates val-split MAE at every epoch end.  With
        an :class:`ElasticConfig` attached, worker loss mid-run triggers a
        re-mesh-and-resume instead of killing the run (requires ``ckpt_dir``).
        ``history_sink`` mirrors every logged row into a caller-owned list
        that survives non-elastic crashes (see ``run_training``).

        Under ``jax.distributed``, every process restores from ``ckpt_dir``
        but only the LEADER writes to it — one writer, no torn manifests.
        Without an ``ElasticConfig.leader`` tracker the leader is fixed at
        process 0 (the historical behavior); with one, every process keeps
        a warm-standby :class:`LeaderCheckpointer` so checkpoint-writer
        duty survives the leader's death (``succeed_as_leader``).
        """
        loop = self.config.loop
        if epochs is not None:
            loop = dataclasses.replace(loop, epochs=epochs)
        if self.elastic is not None and not loop.ckpt_dir:
            raise ValueError("elastic fit needs loop.ckpt_dir: the re-mesh "
                             "path restores from the latest checkpoint")
        if (self.elastic is not None and self.elastic.remesh == "inprocess"
                and jax.process_count() > 1):
            raise ValueError(
                "elastic remesh='inprocess' cannot run under jax.distributed: "
                "a dead peer's shards are unaddressable and its collectives "
                "would hang; use ElasticConfig(remesh='relaunch') so the "
                "launcher tears the gang down and relaunches into the "
                "planned topology (see tests/multihost.py)")
        # Open until run_training's first step: the state below, a restore,
        # and the first epoch's prefetcher.
        start_span = jax.profiler.TraceAnnotation(trace_names.FIT_START)
        start_span.__enter__()
        # Copy params into the fresh state: the jitted step donates its state
        # argument, and aliasing the caller's arrays would delete them after
        # the first step (breaking re-fits and sibling pipelines).
        params = jax.tree.map(jnp.copy, self.init_params)
        state = init_train_state(params, self.config.adam)
        # Every process that could ever become the leader drives a
        # (leader-gated) checkpointer: the current leader's saves land on
        # disk, standbys hold warm host snapshots for succession.  Without
        # a tracker only process 0 can lead, so other processes skip the
        # snapshot work entirely (the historical single-writer setup).
        has_tracker = self.elastic is not None and self.elastic.leader is not None
        checkpointer = (LeaderCheckpointer(Checkpointer(loop.ckpt_dir),
                                           self.is_leader)
                        if loop.ckpt_dir
                        and (has_tracker or jax.process_index() == 0)
                        else None)
        self._checkpointer = checkpointer
        start_step, start_epoch, start_done = 0, 0, None
        if resume and loop.ckpt_dir and latest_step(loop.ckpt_dir) is not None:
            state, start_step = restore(loop.ckpt_dir, state)
            # Prefer the checkpoint's own (epoch, done_in_epoch) coordinates
            # over deriving them from the raw step: after an elastic shrink
            # changed steps_per_epoch the derivation would land on the wrong
            # (epoch, position).  start_step stays the raw checkpoint step —
            # a monotonic counter — so later saves always outrank this one.
            meta = checkpoint_meta(loop.ckpt_dir)
            if "epoch" in meta:
                start_epoch = int(meta["epoch"])
                start_done = max(int(meta.get("done_in_epoch", 0)), 0)
            else:
                start_epoch = start_step // self.steps_per_epoch
        if eval_fn == "auto":
            # Works single- AND multi-process: evaluate() rides the per-rank
            # eval feeds, and every process derives the identical chunk plan
            # from the pool alone, so the epoch-end eval collectives stay in
            # lock step across the fleet.
            eval_fn = (lambda st: {"val_mae": self.evaluate(st["params"])}) \
                if len(self.dataset.val_windows) > 0 else None
        if eval_fn is not None and self.elastic is not None \
                and self.elastic.emitter is not None:
            # Epoch-end eval is a coordinated pause of the lockstep program:
            # nobody steps, so nobody heartbeats, and an eval (or its first
            # compile) longer than heartbeat_timeout would make the first
            # post-eval poll read the whole HEALTHY fleet as stale and plan a
            # bogus shrink.  Re-announce liveness the moment eval returns —
            # every process runs eval_fn, so every rank re-beats before the
            # decider's next poll.
            inner_eval = eval_fn

            def eval_fn(st):
                out = inner_eval(st)
                try:
                    self.elastic.emitter(self._hb_step)
                except OSError:
                    pass  # fire-and-forget, like the per-step emit
                return out
        history: list[dict] = []
        self._hb_step = start_step  # last health-polled step (eval re-beats)
        monitor = self._make_monitor()
        restarts_this_fit = 0
        # Feed the step loop through the async prefetch pipeline when the
        # loop config asks for it.  The factory reads self.dataplane at CALL
        # time (once per epoch), so after an elastic re-mesh the next epoch's
        # stream is built over the new plane — the old stream was already
        # drained by run_training's finally when the RestartSignal unwound.
        batch_stream = None
        if loop.prefetch_depth >= 1:
            plan = PrefetchPlan(depth=loop.prefetch_depth,
                                staleness=loop.staleness,
                                chunk=loop.prefetch_chunk)

            def batch_stream(epoch: int, done: int) -> FeedPrefetcher:
                dp = self.dataplane
                return FeedPrefetcher(
                    dp.grid_stream(epoch, start=done, chunk=plan.chunk),
                    dp.prefetch_transfer(plan.staleness), plan)
        while True:
            try:
                state, hist = run_training(
                    state=state,
                    train_step=self.train_step,
                    sampler=self.dataplane,
                    batch_of_starts=self.dataplane.batch_of_starts,
                    loop=loop,
                    eval_fn=eval_fn,
                    checkpointer=checkpointer,
                    start_epoch=start_epoch,
                    start_step=start_step,
                    start_done_in_epoch=start_done,
                    health_cb=self._health_cb(monitor),
                    history_sink=history_sink,
                    batch_stream=batch_stream,
                    start_span=start_span,
                )
                history.extend(hist)
                return state, history
            except RestartSignal as sig:
                history.extend(sig.history)
                sig.leader = self.is_leader()
                if self.elastic.remesh == "relaunch":
                    # The external launcher owns re-meshing: run_training
                    # already checkpointed the in-flight state with its
                    # (epoch, done_in_epoch) coordinates, so hand the
                    # annotated signal (plan + resume coordinates +
                    # whether THIS process is the deciding leader) up.
                    raise
                if restarts_this_fit >= self.elastic.max_restarts:
                    raise RuntimeError(
                        f"elastic restart budget exhausted "
                        f"({self.elastic.max_restarts})") from sig
                restarts_this_fit += 1
                state, start_epoch, start_step, start_done = \
                    self._apply_plan(sig, loop)
                monitor = self._make_monitor()
                if self.elastic.emitter is not None:
                    # Draining the prefetcher + re-meshing + re-jitting is a
                    # coordinated pause just like epoch-end eval: nobody
                    # steps, so nobody heartbeats.  Re-announce liveness
                    # before resuming so the first post-restart poll doesn't
                    # read the healthy fleet as stale.
                    try:
                        self.elastic.emitter(self._hb_step)
                    except OSError:
                        pass
            except BaseException:
                # A non-elastic failure (e.g. a collective erroring out when
                # a real peer died) must not strand the in-flight async
                # checkpoint write: flush it so a relaunch resumes from the
                # newest durable step instead of one step earlier.
                if checkpointer is not None:
                    try:
                        checkpointer.wait()
                    except Exception:
                        pass
                raise

    # ------------------------------------------------------------- evaluation
    def evaluate(self, params, *, split: str = "val", max_batches: int = 4) -> float:
        """Window-weighted mean loss over up to ``max_batches`` eval chunks.

        Rides the distributed eval feeds (``DataPlane.eval_grid``): full
        chunks are the pool's global batches, assembled from each process's
        own ``eval_feed`` rank-block columns under ``jax.distributed`` (no
        process ever materialises — or gathers windows for — more than its
        own shard of a chunk), and the ragged tail is scored once as a small
        replicated batch (one extra compile for its shape) so small splits
        are never silently truncated.  Per-chunk ``(loss, windows)`` pairs
        combine through :func:`repro.train.loop.combine_weighted`, making
        the multi-process result bit-identical to the single-host
        window-weighted reference.
        """
        dp = self.dataplane
        pool = dp.eval_pool(split)
        if len(pool) == 0:
            return float("nan")
        rows, tail = dp.eval_grid(split)
        pairs = []
        for i in range(min(rows.shape[0], max_batches)):
            loss, _ = self._eval_loss(params, dp.batch_of_starts(rows[i]))
            pairs.append((float(loss), self.global_batch))
        # The tail only contributes when the budget was not already spent on
        # full chunks — the same coverage the pre-distributed evaluate gave.
        # Its replicated device row is identical every call, so it comes
        # from the data plane's per-split cache (one transfer per plane).
        if len(tail) and rows.shape[0] < max_batches:
            tail_len, tail_batch = dp.eval_tail_batch(split)
            loss, _ = self._eval_loss(params, tail_batch)
            pairs.append((float(loss), tail_len))
        return combine_weighted(pairs)

    # ---------------------------------------------------------------- elastic
    def succeed_as_leader(self, dead_ranks) -> dict | None:
        """Post-collective-failure leader succession.

        A peer's death surfaces to the survivors as a failed collective —
        a plain exception out of :meth:`fit` — and the launcher attributes
        WHO died through the transport's ``snapshot()`` (whose beats went
        silent).  It then hands the verdict here: the tracker marks the
        dead ranks (immediately — the survivor must not wait out a
        heartbeat timeout to start writing), and if the lowest live rank
        is now ours, this process takes over every single-writer duty the
        dead leader held:

        - the warm-standby checkpoint (the exact failure-step state,
          snapshotted to host before the buffers could be donated or
          poisoned) is durably written — ``ckpt_step``;
        - the SHRINK plan is decided by the successor and returned for the
          launcher to relaunch against.

        Returns ``{"leader", "plan", "ckpt_step"}`` when this process is
        now the leader, else None.  (History succession is the sink's job:
        call ``LeaderHistorySink.flush_as_leader()`` alongside this.)
        """
        el = self.elastic
        dead = sorted({int(r) for r in dead_ranks})
        if el is not None and el.leader is not None:
            el.leader.note_dead(dead)
        if not self.is_leader():
            return None
        ckpt_step = None
        if isinstance(self._checkpointer, LeaderCheckpointer):
            try:
                self._checkpointer.wait()
            except Exception:
                pass  # an earlier async write failing must not block takeover
            ckpt_step = self._checkpointer.takeover()
        plan = None
        if el is not None and dead:
            try:
                plan = plan_remesh(self.world, dead,
                                   model_parallel=el.model_parallel,
                                   chips_per_host=el.chips_per_host,
                                   decided_by=self.leader_rank())
            except RuntimeError:
                plan = None  # no healthy TP group left: nothing to relaunch
        return {"leader": self.leader_rank(), "plan": plan,
                "ckpt_step": ckpt_step}

    def _make_monitor(self) -> HeartbeatMonitor | None:
        if self.elastic is None:
            return None
        el = self.elastic
        return HeartbeatMonitor(self.world, timeout=el.heartbeat_timeout,
                                straggler_factor=el.straggler_factor,
                                clock=el.clock)

    def _health_cb(self, monitor: HeartbeatMonitor | None):
        if monitor is None:
            return None
        el = self.elastic
        world = self.world
        target = el.target_world or self._base_world
        returned: dict[int, list] = {}  # rank -> [poll count, last clock]
        announced: set[int] = set()     # out-of-world beats since last poll

        def cb(global_step: int) -> None:
            self._hb_step = global_step
            if el.emitter is not None:
                try:
                    el.emitter(global_step)  # this process's ranks beat out
                except OSError:
                    # Fire-and-forget, like the transports themselves: a
                    # transient emit failure (NFS stall, ENOSPC) makes this
                    # worker look late to the MONITOR — it must not crash a
                    # healthy training process.
                    pass
            beats = (el.step_feed(global_step, world)
                     if el.step_feed is not None
                     else {r: (global_step, None) for r in range(world)})
            if el.leader is not None:
                # Leadership derives from the SAME seq-gated beat stream the
                # monitor consumes — every survivor reaches the same verdict
                # from the same state, no election round-trips.
                el.leader.observe(beats)
            for rank, (step, step_time) in beats.items():
                if rank in monitor.workers:
                    monitor.beat(rank, step, step_time)
                else:
                    # A beat from outside the current world: a dropped
                    # worker announcing its return.  REJOIN CONTRACT: the
                    # announcement must use the TARGET fleet's numbering
                    # (anything ≥ world) — a rebooted host re-using an id
                    # below the current world is indistinguishable from the
                    # live rank that now owns that id, so the launcher's
                    # rejoin agent assigns out-of-world ids (see
                    # tests/multihost.py's announcer).
                    announced.add(rank)
            if el.check_every > 1 and global_step % el.check_every:
                return
            # A returned worker is only re-admitted once it has announced
            # across ``readmit_after_beats`` DISTINCT decision polls AND is
            # still fresh: a worker that beat once and went silent — or a
            # crash-looping host burst-announcing inside one poll window —
            # is flapping, and growing toward it would just shrink right
            # back, burning restart budget each time.
            now = el.clock()
            for rank in announced:
                seen = returned.setdefault(rank, [0, 0.0])
                seen[0] += 1
                seen[1] = now
            announced.clear()
            unhealthy = monitor.unhealthy()
            fresh = sorted(r for r, (n, t) in returned.items()
                           if n >= el.readmit_after_beats
                           and now - t <= el.heartbeat_timeout)
            recovered = (fresh[: target - world]
                         if not unhealthy and world < target else [])
            if not unhealthy and not recovered:
                return
            # Only the CURRENT leader turns a verdict into a plan.  Every
            # survivor keeps polling (its monitor/tracker state stays primed
            # — that is what makes it a viable successor), but a non-leader
            # acting on the same verdict would race a divergent plan and
            # checkpoint coordinates against the leader's.  When the leader
            # itself is what died, the tracker times it out right here and
            # the successor's NEXT poll passes this gate: a dead rank 0
            # yields a shrink plan decided by rank 1, not a hung fleet.
            if not self.is_leader():
                return
            plan = plan_remesh(world, unhealthy, recovered=recovered,
                               model_parallel=el.model_parallel,
                               chips_per_host=el.chips_per_host,
                               decided_by=self.leader_rank())
            if plan is not None:
                raise RestartSignal(plan)

        return cb

    def _apply_plan(self, sig: RestartSignal, loop
                    ) -> tuple[Any, int, int, int]:
        """Re-mesh to the plan's topology and restore the latest checkpoint.

        Shrink plans drop the plan's dead workers; grow plans re-admit the
        plan's returned workers (capped at ``target_world``) and
        inverse-apply the batch scaling.  Both directions re-scale against
        the BASE global batch and carve the new mesh out of the BASE mesh,
        so shrink→grow restores the original topology exactly.

        Returns ``(state, start_epoch, start_step, start_done_in_epoch)``:
        the same (seed, epoch) and completed-step count within the
        interrupted epoch, with ``start_step`` continuing the MONOTONIC
        global counter from the failure checkpoint — step numbers never go
        backwards, so ``latest_step`` can never resurrect a stale
        pre-restart checkpoint.
        """
        el = self.elastic
        plan = sig.plan
        old_spe = self.steps_per_epoch
        # Workers ARE data-parallel ranks here, so the new world is simply
        # the surviving (or re-admitted) rank count.  (plan.mesh_shape[0]
        # counts TP GROUPS — the same number only when model_parallel ==
        # chips_per_host.)
        if plan.kind == "grow":
            target = el.target_world or self._base_world
            new_world = min(self.world + len(set(plan.readmitted_workers)),
                            target)
        else:
            new_world = self.world - len(set(plan.dropped_workers))
        per_new, _ = scale_batch_or_steps(
            self._base_global_batch, old_dp=self._base_world,
            new_dp=new_world, keep_global_batch=el.keep_global_batch)
        new_mesh = shrink_mesh(self._base_mesh, new_world)
        self.dataplane = self.dataplane.remesh(
            new_mesh, world=new_world, batch_per_rank=per_new)
        if el.leader is not None:
            # Ranks renumber with the topology; in-process re-meshing is
            # single-host (fit() enforces it), so this process owns every
            # rank of the new world and stays the leader.
            el.leader.reset(new_world)
        self.train_step, self._eval_loss = _compile(
            self.dataplane, self.loss_fn, self.config)
        # Restore the failure-step checkpoint into the new topology: params
        # and opt state are replicated in this runtime, so one re-sharding
        # NamedSharding covers every leaf.
        template = init_train_state(
            jax.tree.map(jnp.copy, self.init_params), self.config.adam)
        state, ckpt_step = restore(
            loop.ckpt_dir, template,
            shardings=NamedSharding(new_mesh, P()))
        meta = checkpoint_meta(loop.ckpt_dir)
        epoch = int(meta.get("epoch", sig.epoch))
        done = max(int(meta.get("done_in_epoch", ckpt_step - epoch * old_spe)),
                   0)
        self.restarts.append({
            "plan": plan, "kind": plan.kind, "epoch": epoch,
            "step": ckpt_step, "world": new_world, "batch_per_rank": per_new,
            "global_batch": self.global_batch,
        })
        return state, epoch, ckpt_step, done


def _shard_local_gather_ok(dataplane: DataPlane, config: PipelineConfig) -> bool:
    """Whether the train-step gather can lower as a shard_map (§5.4 proof).

    The global-index gather over a time-sharded series makes XLA all-gather
    the series (it cannot prove locality from runtime start values).  When
    every sampled window is GUARANTEED interior to its rank's shard — the
    aligned sampler with halo=False, one feed rank per device shard, even
    time split — the gather can instead run per-shard with local offsets,
    and the compiled program's only collective is the gradient all-reduce
    (see launch/dryrun.py --halo-evidence for the byte counts).
    """
    mesh = dataplane.mesh
    dp = dp_size(mesh)
    return (config.placement is Placement.PARTITIONED
            and not config.halo
            and isinstance(dataplane.sampler, ShardAlignedBatchSampler)
            and dp > 1
            and dataplane.world == dp
            and len(data_axes(mesh)) == 1
            and dataplane.dataset.entries % dp == 0
            and config.loop.microbatches == 1)


def _shard_local_gather(gather: Callable, dataplane: DataPlane) -> Callable:
    """Wrap ``gather`` in a shard_map: each rank gathers from ITS series
    shard with shard-local offsets (global start − shard origin)."""
    mesh = dataplane.mesh
    axis = data_axes(mesh)[0]
    shard_len = dataplane.dataset.entries // int(mesh.shape[axis])

    def local(series_shard, starts_shard, *, input_len, horizon):
        lo = jax.lax.axis_index(axis) * shard_len
        return gather(series_shard, starts_shard - lo,
                      input_len=input_len, horizon=horizon)

    def fn(series, starts, *, input_len, horizon):
        import functools
        body = functools.partial(local, input_len=input_len, horizon=horizon)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(axis), P(axis)),
                             out_specs=(P(axis), P(axis)),
                             check_vma=False)(series, starts)

    return fn


@dataclasses.dataclass(frozen=True)
class BoundStep:
    """A jitted ``fn(first, batch, *args)`` with ``args`` bound.

    The bound arrays (the resident series, the loss's constants) enter the
    program as jit ARGUMENTS: a closed-over concrete array would be embedded
    in the program as a literal — a second device copy of the series and a
    compile that grows with its size.  ``lower`` exposes the program the
    call runs, for inspection.
    """

    fn: Callable
    args: tuple

    def __call__(self, first, batch):
        return self.fn(first, batch, *self.args)

    def lower(self, first, batch):
        return self.fn.lower(first, batch, *self.args)


def _as_partial(loss_fn: Callable) -> jax.tree_util.Partial:
    """``loss_fn`` as a pytree: a ``jax.tree_util.Partial`` keeps its bound
    arrays (e.g. a graph's supports) as leaves, so passing it to jit makes
    them arguments of the step; a plain function becomes a leafless one."""
    if isinstance(loss_fn, jax.tree_util.Partial):
        return loss_fn
    return jax.tree_util.Partial(loss_fn)


def gather_lowering(dataplane: DataPlane, config: PipelineConfig) -> str:
    """Which train-step gather lowering the engine builds for this plane:
    ``"shard_local"`` (shard_map, no data collectives) or ``"global_index"``."""
    return ("shard_local" if _shard_local_gather_ok(dataplane, config)
            else "global_index")


def _compile(dataplane: DataPlane, loss_fn: Callable, config: PipelineConfig):
    """(train_step, eval_loss) with the window gather fused over THIS data
    plane's resident series — rebuilt on every re-mesh.

    The series and ``loss_fn`` (with any arrays a ``Partial`` binds) are
    arguments of both jitted programs, bound by :class:`BoundStep`; their
    shardings come from the placed arrays (the loss's are replicated)."""
    gather = resolve_gather(config.gather)
    spec = dataplane.spec
    # halo=False + aligned feeds: provably-local gathers lower as a
    # shard_map — zero data collectives.  Eval stays on the global-index
    # gather: val/test pools are drawn globally, not shard-aligned.
    train_gather = (_shard_local_gather(gather, dataplane)
                    if _shard_local_gather_ok(dataplane, config) else gather)
    # Train batches are sharded like their starts whatever the gather's
    # lowering: GSPMD replicates a Pallas kernel's output, and the model
    # would then run on the whole batch on every device.
    pin = dataplane.batch_sharding

    def train_loss(params, starts, series, loss):
        with jax.named_scope(trace_names.GATHER):
            x, y = train_gather(series, starts, input_len=spec.in_len,
                                horizon=spec.horizon)
            if pin is not None:
                x, y = jax.lax.with_sharding_constraint((x, y), pin)
        return loss(params, x, y)

    def eval_loss(params, starts, series, loss):
        with jax.named_scope(trace_names.GATHER):
            x, y = gather(series, starts, input_len=spec.in_len,
                          horizon=spec.horizon)
        return loss(params, x, y)

    schedule = config.schedule or (lambda s: config.adam.lr)
    loop = config.loop
    train_step = make_train_step(
        train_loss, config.adam, schedule,
        microbatches=loop.microbatches, grad_dtype=loop.grad_dtype,
        donate=loop.donate)
    loss = _as_partial(loss_fn)
    if dataplane.mesh.size > 1:
        # Replicate the loss's arrays over the mesh once, here, instead of
        # letting every call copy them from the device they were made on.
        loss = jax.device_put(jax.tree.map(np.asarray, loss),
                              NamedSharding(dataplane.mesh, P()))
    args = (dataplane.dataset.series, loss)
    return BoundStep(train_step, args), BoundStep(jax.jit(eval_loss), args)


def build_engine(
    raw: np.ndarray | None,
    spec: WindowSpec,
    mesh,
    loss_fn: Callable[[Any, jnp.ndarray, jnp.ndarray], tuple[jnp.ndarray, dict]],
    init_params: Any,
    config: PipelineConfig = PipelineConfig(),
    *,
    dataset: IndexDataset | None = None,
    elastic: ElasticConfig | None = None,
) -> Engine:
    """Assemble the full placement-aware trainer (DataPlane + Engine).

    ``loss_fn(params, x, y) -> (loss, metrics)`` is the only model-specific
    piece; the engine supplies (x, y) by fusing the selected window gather
    into the jitted step.  Make it a ``jax.tree_util.Partial`` over the
    model's constant arrays (``Partial(f, supports)`` with
    ``f(supports, params, x, y)``): they then enter the step as arguments
    instead of being embedded in the program.  Pass ``dataset=`` to reuse an already-built
    ``IndexDataset``; pass ``elastic=`` to survive worker loss mid-fit.
    """
    dataplane = build_dataplane(raw, spec, mesh, config, dataset=dataset)
    train_step, eval_loss = _compile(dataplane, loss_fn, config)
    return Engine(dataplane=dataplane, loss_fn=loss_fn,
                  init_params=init_params, train_step=train_step,
                  _eval_loss=eval_loss, elastic=elastic)
