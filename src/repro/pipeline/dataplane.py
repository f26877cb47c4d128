"""DataPlane — the per-rank half of the pipeline: placement → sampler → feeds.

The data plane owns everything that decides *which window ids reach which
worker*: dataset placement (``core/distributed.series_sharding``), the
matching sampler, and the deterministic per-process feed
``feed(rank, epoch) -> [steps, batch_per_rank]`` built on the samplers'
first-class feed contract.  ``epoch_global`` is kept only as the single-host
assembly of the per-rank feed columns (rank-major) — the lock-step SPMD
simulation the tests verify equal to ``concat([feed(r, e) ...], axis=1)``.

It deliberately knows nothing about the jitted step, checkpoints, or
topology changes — that is the :class:`repro.pipeline.engine.Engine`'s job.
A data plane is cheap to rebuild, which is exactly what the engine does on an
elastic re-mesh: same dataset, new mesh/world, new sampler.

==============  ==========================  =================================
Placement       series sharding             sampler
==============  ==========================  =================================
REPLICATED      ``P()`` (every device)      GlobalShuffleSampler
PARTITIONED     ``P(data axes)`` on time    ShardAlignedBatchSampler (per-rank
                                            partitions on the device shard
                                            boundaries; falls back to the
                                            contiguous count-split when the
                                            train split leaves ranks empty)
ONDEMAND        ``P(data axes)`` on time    GlobalShuffleSampler (global
                                            draws — the measured DDP baseline
                                            whose gathers cross shards)
==============  ==========================  =================================
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.distributed import (Placement, batch_sharding, dp_size,
                                    series_sharding)
from repro.core.index_dataset import IndexDataset
from repro.core.sampler import (GlobalShuffleSampler, LocalBatchShuffleSampler,
                                ShardInfo)
from repro.core.windows import WindowSpec
from repro.optim import AdamConfig
from repro.pipeline.samplers import ShardAlignedBatchSampler
from repro.train.loop import TrainLoopConfig


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Everything the pipeline decides beyond the data/model themselves."""

    batch_per_rank: int = 8
    placement: Placement = Placement.REPLICATED
    gather: str = "slice"  # slice | take | fused | pallas | auto | lm
    seed: int = 0
    # Worker count for the sampler.  None = the mesh's data-parallel size;
    # benchmarks override it to simulate w lock-step SPMD workers on a small
    # host mesh (the global batch is then world × batch_per_rank).
    world: int | None = None
    # PARTITIONED partitioning: "aligned" places each rank's windows on its
    # device's series-shard boundaries (local gathers; falls back to the
    # count-split when a rank's shard holds no train windows); "count" forces
    # the equal count-split (the paper's Table-5 local-batch-shuffling arm,
    # equal per-rank training budget, approximate locality only).
    partition: str = "aligned"
    # PARTITIONED window domain (core/distributed.local_window_ids): halo=True
    # lets a rank's windows spill span−1 steps into the next shard (full
    # coverage, bounded neighbour exchange); halo=False keeps windows strictly
    # interior — zero data communication, slightly fewer samples (the paper's
    # communication-free claim; see launch/dryrun.py --halo-evidence).
    halo: bool = True
    adam: AdamConfig = AdamConfig()
    schedule: Callable[[Any], Any] | None = None  # step -> lr; None = adam.lr
    loop: TrainLoopConfig = TrainLoopConfig()


def _make_sampler(config: PipelineConfig, ds: IndexDataset, world: int):
    shard = ShardInfo(0, world)
    if config.placement is Placement.PARTITIONED:
        if config.partition == "aligned":
            # Per-rank partitions aligned to the series time-shards, so each
            # rank's gathers stay inside the shard its device owns (§5.4).
            try:
                return ShardAlignedBatchSampler(
                    ds.entries, ds.spec, ds.train_windows,
                    config.batch_per_rank, world, seed=config.seed,
                    halo=config.halo)
            except ValueError as e:
                # A rank's shard holds no (or too few) train windows — e.g.
                # the 70/10/20 split leaves the val/test-tail ranks empty,
                # or stride > 1.  Fall back to the contiguous count-split,
                # whose boundaries only approximate the device shards (some
                # gathers cross shards) — widen the train fraction if strict
                # locality matters.
                warnings.warn(
                    f"PARTITIONED: no shard-aligned sampler over {world} "
                    f"time shards ({e}); falling back to the count-split "
                    f"LocalBatchShuffleSampler, whose gathers may cross "
                    f"shards (global-index lowering)", RuntimeWarning,
                    stacklevel=2)
        elif config.partition != "count":
            raise ValueError(f"unknown partition {config.partition!r}; "
                             "expected 'aligned' or 'count'")
        return LocalBatchShuffleSampler(ds.train_windows, config.batch_per_rank,
                                        shard, seed=config.seed)
    # REPLICATED: the paper's communication-free global shuffle.
    # ONDEMAND: same global draws over a time-sharded series — every gather
    # crosses shard boundaries; kept as the measured DDP baseline.
    return GlobalShuffleSampler(ds.train_windows, config.batch_per_rank, shard,
                                seed=config.seed)


@dataclasses.dataclass
class DataPlane:
    """A placed dataset + matching sampler + deterministic per-rank feeds."""

    config: PipelineConfig
    mesh: Mesh
    spec: WindowSpec
    dataset: IndexDataset
    sampler: Any
    series_sharding: NamedSharding
    world: int
    batch_sharding: NamedSharding | None
    # split -> (tail_len, replicated device batch | None): the ragged eval
    # tail is identical every evaluate call, so its device row is built once
    # per data plane (a re-mesh builds a fresh plane, naturally invalidating
    # the cache).  See :meth:`eval_tail_batch`.
    _eval_tail_cache: dict = dataclasses.field(default_factory=dict,
                                               repr=False, compare=False)

    # ------------------------------------------------------------- accessors
    @property
    def steps_per_epoch(self) -> int:
        return self.sampler.steps_per_epoch

    @property
    def global_batch(self) -> int:
        return self.config.batch_per_rank * self.world

    @property
    def process_ranks(self) -> list[int] | None:
        """Feed ranks this process owns under ``jax.distributed``; None when
        the run is single-process (lock-step simulation via ``epoch_global``).

        Assumes the standard mesh construction (devices ordered by process):
        process p owns the contiguous block of ``world / process_count`` feed
        ranks aligned with its addressable series/batch shards — one rank per
        process when every host drives a single data-parallel slot, several
        when a host's processes own multiple device shards.
        """
        pc = jax.process_count()
        if pc <= 1:
            return None
        if self.world % pc:
            raise NotImplementedError(
                f"world {self.world} is not divisible by the process count "
                f"{pc}; per-process feeds need world % processes == 0")
        per = self.world // pc
        p = jax.process_index()
        return list(range(p * per, (p + 1) * per))

    def describe(self) -> dict:
        """The placement contract this data plane instantiated (testable)."""
        return {
            "placement": self.config.placement,
            "sampler": type(self.sampler).__name__,
            "series_spec": tuple(self.series_sharding.spec),
            "gather": self.config.gather,
            "world": self.world,
            "global_batch": self.global_batch,
            "halo": self.config.halo,
        }

    # ----------------------------------------------------------------- feeds
    def feed(self, rank: int, epoch: int) -> np.ndarray:
        """[steps, batch_per_rank] window ids for ``rank`` — the per-process
        index feed, a pure function of (seed, epoch, rank)."""
        return self.sampler.feed(rank, epoch)

    def epoch_global(self, epoch: int) -> np.ndarray:
        """[steps, world*batch] — single-host assembly of the feed columns."""
        return self.sampler.epoch_global(epoch)

    def epoch_grid(self, epoch: int) -> np.ndarray:
        """What the train loop iterates this epoch: the full global grid in
        single-process mode, the concatenation of this process's own feed
        columns under multi-process SPMD (no process ever materialises the
        global index grid)."""
        ranks = self.process_ranks
        if ranks is None:
            return self.epoch_global(epoch)
        return np.concatenate([self.feed(r, epoch) for r in ranks], axis=1)

    def feed_stream(self, rank: int, epoch: int, *, start: int = 0,
                    chunk: int = 8):
        """Chunk-iterable ``feed(rank, epoch)`` (see
        :class:`repro.core.sampler.FeedStream`): yields ``[<=chunk, batch]``
        row blocks that concatenate exactly to the feed, beginning at row
        ``start``."""
        return self.sampler.feed_stream(rank, epoch, start=start, chunk=chunk)

    def grid_stream(self, epoch: int, *, start: int = 0, chunk: int = 8):
        """Chunk-iterable :meth:`epoch_grid`: ``[<=chunk, width]`` row blocks
        of what THIS process iterates, beginning at row ``start``.

        This is the host half of the prefetch pipeline's contract — pure
        numpy, safe to drain from a background thread.  Under multi-process
        SPMD each block is the concatenation of this process's per-rank
        ``feed_stream`` blocks (all streams share start/chunk, so the blocks
        are row-aligned); single-process it slices ``epoch_global`` directly.
        Either way the blocks reassemble exactly to ``epoch_grid(epoch)`` —
        the invariant test_feeds_property pins.
        """
        ranks = self.process_ranks
        if ranks is None:
            grid = self.epoch_global(epoch)
            for lo in range(start, grid.shape[0], chunk):
                yield grid[lo:lo + chunk]
            return
        streams = [self.sampler.feed_stream(r, epoch, start=start, chunk=chunk)
                   for r in ranks]
        for blocks in zip(*streams):
            yield np.concatenate(blocks, axis=1)

    # ------------------------------------------------------------ eval feeds
    def eval_pool(self, split: str = "val") -> np.ndarray:
        """The split's global window-id pool (``val_windows``/``test_windows``)."""
        return np.asarray(getattr(self.dataset, f"{split}_windows"))

    def eval_feed(self, rank: int, split: str = "val") -> np.ndarray:
        """[steps, batch_per_rank] eval window ids for ``rank`` — the eval
        mirror of :meth:`feed`: rank ``rank``'s column block of the split
        pool's full global chunks, in pool order (no shuffle, no epoch)."""
        return self.sampler.eval_feed(rank, self.eval_pool(split))

    def eval_tail(self, split: str = "val") -> np.ndarray:
        """The split's ragged remainder — global, identical on every rank."""
        return self.sampler.eval_tail(self.eval_pool(split))

    def eval_grid(self, split: str = "val") -> tuple[np.ndarray, np.ndarray]:
        """``(rows, tail)`` — what THIS process iterates when evaluating.

        ``rows`` is the full-chunk grid: global ``[steps, world*batch]`` in
        single-process mode, the concatenation of this process's own
        ``eval_feed`` columns under multi-process SPMD (each process
        materialises only its rank-block of every chunk).  ``tail`` is the
        global ragged remainder, scored once as a replicated small batch.
        """
        pool = self.eval_pool(split)
        tail = self.sampler.eval_tail(pool)
        ranks = self.process_ranks
        if ranks is None:
            return self.sampler.eval_global(pool), tail
        return np.concatenate(
            [self.sampler.eval_feed(r, pool) for r in ranks], axis=1), tail

    def eval_tail_batch(self, split: str = "val"):
        """``(tail_len, replicated device batch | None)`` for the split's
        ragged eval tail — built ONCE per data plane and cached.

        The tail is a pure function of the split pool (no epoch, no
        shuffle), so re-running ``batch_of_starts(tail, replicate=True)``
        every evaluate call only repeats the same host→device transfer; the
        cache keeps the replicated row resident instead.  A re-mesh rebuilds
        the whole plane, so the cache can never serve a stale topology.
        """
        hit = self._eval_tail_cache.get(split)
        if hit is None:
            tail = self.eval_tail(split)
            batch = (self.batch_of_starts(tail, replicate=True)
                     if len(tail) else None)
            hit = (len(tail), batch)
            self._eval_tail_cache[split] = hit
        return hit

    # --------------------------------------------------------- data plumbing
    def host_batch_of_starts(self, window_ids: np.ndarray) -> np.ndarray:
        """Window ids -> HOST array of start steps (the batch, uncommitted).

        The bounded-stale transfer mode (:meth:`prefetch_transfer`,
        staleness >= 1): batch construction happens here — on the prefetch
        thread, ahead of consumption — and the host→device commit rides the
        jitted step's own dispatch, which enqueues it into the async stream
        while the PREVIOUS step's computation is still in flight.  On this
        runtime the Python-side ``device_put`` of a small starts row costs
        an order of magnitude more caller time than committing the same row
        inside dispatch, so this is where the pipeline's measured step-time
        win comes from (benchmarks/smoke.py records it, trend.py gates it).
        Values are identical either way — same bytes reach the same
        compiled program.
        """
        return np.asarray(self.dataset.starts[np.asarray(window_ids)])

    def can_defer_transfer(self) -> bool:
        """Whether the step can commit a HOST batch during its own dispatch:
        single-process with no batch sharding (one device).  Sharded or
        multi-process batches need the explicit assembly in
        :meth:`batch_of_starts` (``make_array_from_process_local_data``) —
        handing jit a raw host row there would let it pick a placement
        instead of the data plane."""
        return jax.process_count() == 1 and self.batch_sharding is None

    def prefetch_transfer(self, staleness: int):
        """The transfer fn the :class:`~repro.pipeline.prefetch.FeedPrefetcher`
        should run for this staleness.

        ``staleness == 0`` — :meth:`batch_of_starts`, on the consumer
        thread: the synchronous path's exact op order (the provable
        bit-identity).  ``staleness >= 1`` — the deferred host-batch mode
        when the topology allows it, else still :meth:`batch_of_starts`
        (just moved onto the transfer thread).
        """
        if staleness >= 1 and self.can_defer_transfer():
            return self.host_batch_of_starts
        return self.batch_of_starts

    def batch_of_starts(self, window_ids: np.ndarray, *,
                        replicate: bool = False) -> jnp.ndarray:
        """Window ids (one epoch grid row) -> device array of start steps.

        Multi-process runs hand per-process rows (this rank's feed columns)
        and assemble the global sharded array from process-local data; the
        single-process path device_puts the already-global row.

        ``replicate=True`` is the ragged-eval-tail path: ``window_ids`` is a
        GLOBAL row every process derived identically, and the batch stays
        replicated in both single- and multi-process runs — same program,
        same reduction grouping, bit-identical tail metrics.
        """
        starts_np = np.asarray(self.dataset.starts[np.asarray(window_ids)])
        if replicate:
            if jax.process_count() > 1:
                shd = NamedSharding(self.mesh, PartitionSpec())
                return jax.make_array_from_callback(
                    starts_np.shape, shd, lambda idx: starts_np[idx])
            return jnp.asarray(starts_np)
        ranks = self.process_ranks
        if ranks is not None and self.batch_sharding is not None:
            local_width = len(ranks) * self.config.batch_per_rank
            if starts_np.shape[0] != local_width:
                # Only per-process feed rows have process-local semantics;
                # treating a GLOBAL row as local data would assemble a
                # duplicated wrong-shaped batch.  Eval chunks ride the
                # eval_grid feed columns; the ragged tail passes
                # replicate=True.
                raise NotImplementedError(
                    f"under jax.distributed, batch_of_starts expects this "
                    f"process's feed row of width {local_width}, got "
                    f"{starts_np.shape[0]}; hand global rows through "
                    f"replicate=True instead")
            return jax.make_array_from_process_local_data(
                self.batch_sharding, starts_np)
        starts = jnp.asarray(starts_np)
        # Ragged eval tails may not divide the data axis — leave those
        # replicated (jit re-shards as needed) rather than fail the put.
        if self.batch_sharding is not None \
                and starts.shape[0] % max(dp_size(self.mesh), 1) == 0:
            starts = jax.device_put(starts, self.batch_sharding)
        return starts

    # --------------------------------------------------------------- elastic
    def remesh(self, mesh: Mesh, *, world: int, batch_per_rank: int) -> "DataPlane":
        """Rebuild this data plane for a new topology (elastic shrink OR
        grow — the direction only changes the mesh/world handed in).

        Re-places the series via ``series_sharding`` on the new mesh and
        rebuilds the sampler for the new world size; the dataset's windows,
        splits and scaler are untouched so (seed, epoch) determinism holds.
        Single-host only: re-materialising the series needs every shard
        addressable (a real multi-process fleet relaunches instead —
        ``ElasticConfig(remesh="relaunch")`` — and the new gang re-places
        from storage).
        """
        config = dataclasses.replace(self.config, world=world,
                                     batch_per_rank=batch_per_rank)
        host_ds = dataclasses.replace(self.dataset,
                                      series=np.asarray(self.dataset.series))
        return build_dataplane(None, self.spec, mesh, config, dataset=host_ds)


def build_dataplane(
    raw: np.ndarray | None,
    spec: WindowSpec,
    mesh: Mesh,
    config: PipelineConfig = PipelineConfig(),
    *,
    dataset: IndexDataset | None = None,
) -> DataPlane:
    """Place the dataset and pair it with the placement's sampler.

    Pass ``dataset=`` to reuse an already-built ``IndexDataset`` (it will
    still be (re)placed for the chosen placement); otherwise ``raw`` is
    windowed/standardised into one.
    """
    world = config.world if config.world is not None else max(dp_size(mesh), 1)
    sharding = series_sharding(mesh, config.placement)
    ds = dataset if dataset is not None else IndexDataset.from_raw(raw, spec)
    ds = ds.to_device(sharding)
    sampler = _make_sampler(config, ds, world)
    batch_shd = batch_sharding(mesh) if mesh.size > 1 else None
    return DataPlane(config=config, mesh=mesh, spec=spec, dataset=ds,
                     sampler=sampler, series_sharding=sharding, world=world,
                     batch_sharding=batch_shd)
