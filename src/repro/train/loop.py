"""Training loop: index-batched steps, microbatch accumulation, checkpointing.

The step function is the paper's workflow fused into one jitted SPMD program:

    starts --(window gather from the RESIDENT series)--> (x, y) --> loss
           --> grads --(all-reduce inserted by the partitioner)--> Adam

i.e. distributed-index-batching: the host only ever ships int32 window starts
to the device; the series was placed once (GPU-index-batching) and every
worker gathers its own batch locally.

Microbatch gradient accumulation (``microbatches > 1``) scans over microbatch
slices; besides fitting memory this overlaps per-microbatch compute with the
final cross-pod gradient reduce.  ``grad_dtype="bfloat16"`` compresses the
gradient tree before the all-reduce (the cross-pod axis is the slow link) —
the distributed-optimization knobs the 1000-node posture calls for.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace_names
from repro.optim import AdamConfig, apply_updates, init_opt_state


class RestartSignal(Exception):
    """Raised by a ``health_cb`` to request an engine-level restart.

    ``run_training`` checkpoints the in-flight state (so no step is lost),
    annotates the signal with everything the engine needs to re-mesh and
    resume — ``state``, ``history``, ``epoch``, ``step`` — and re-raises.
    """

    def __init__(self, plan=None, reason: str = ""):
        super().__init__(reason or getattr(plan, "reason", "restart requested"))
        self.plan = plan
        self.state = None
        self.history: list[dict] = []
        self.epoch = 0
        self.step = 0
        # Set by Engine.fit before re-raising in relaunch mode: whether the
        # raising process is the current LEADER (the one whose checkpoint
        # coordinates are durable and who should emit the plan).  True by
        # default so non-engine raisers keep the old single-process behavior.
        self.leader = True


@dataclasses.dataclass(frozen=True)
class TrainLoopConfig:
    epochs: int = 1
    log_every: int = 50
    ckpt_every: int = 0  # steps; 0 = only at end
    ckpt_dir: str | None = None
    microbatches: int = 1
    grad_dtype: str | None = None  # "bfloat16" compresses grads pre-all-reduce
    donate: bool = True
    # Epoch-end eval cadence: run eval_fn after every N-th epoch (1 = every
    # epoch, the historical behavior; 0 = never, even with an eval_fn).
    # Epoch-indexed, not call-counted, so a relaunch-resume keeps the cadence.
    eval_every: int = 1
    # Async feed prefetch (repro.pipeline.prefetch).  prefetch_depth 0 keeps
    # the synchronous pull-per-step path; >= 1 streams batches through a
    # FeedPrefetcher that materializes feed rows `depth` chunks ahead on a
    # background thread.  staleness 0 transfers at consume on the caller
    # thread — bit-identical to the synchronous path; staleness s >= 1 lets
    # the host→device transfer for step k+s overlap step k's computation.
    prefetch_depth: int = 0
    staleness: int = 0
    prefetch_chunk: int = 8
    # Stop after this many global steps (0 = run every epoch to its end).
    max_steps: int = 0


def combine_weighted(pairs) -> float:
    """Reduce ``(metric, weight)`` pairs to their weighted mean.

    This is the psum-style combine the evaluation paths share: each full
    eval chunk contributes ``(chunk_loss, chunk_windows)`` and the ragged
    tail ``(tail_loss, tail_windows)``.  Accumulated in float64 in pair
    order, so the single-host reference and the distributed per-rank-feed
    path perform the exact same arithmetic — bit-identical results.
    """
    weighted_sum = np.float64(0.0)
    weight = np.float64(0.0)
    for value, w in pairs:
        weighted_sum += np.float64(value) * np.float64(w)
        weight += np.float64(w)
    return float(weighted_sum / weight) if weight else float("nan")


class JsonlHistorySink:
    """Crash-durable, resume-idempotent history sink (one JSON row per line).

    Drop-in for the plain-list ``history_sink``: every logged row is appended
    to ``path`` and flushed+fsynced as it lands, so rows survive hard crashes
    (a peer death surfaces as a collective error, not a clean return).  On
    construction it reloads the rows already durable from a previous
    incarnation and silently drops re-logged duplicates — an exit-75
    relaunch that restores a mid-epoch checkpoint re-RUNS the tail of the
    epoch (training needs the steps), but its step rows and the epoch
    summary (including eval metrics) carry the same ``(epoch, step)``
    coordinates and must not appear twice in the durable history.

    ``rows`` holds only the rows ACCEPTED this incarnation (what this
    process actually contributed); ``load()`` returns the full durable
    history across all incarnations.

    Dedup is FIRST-WINS on coordinates, which leans on the repo's
    deterministic-resume contract: a resume that re-runs (epoch, step)
    recomputes the identical row (samplers are pure in (seed, epoch) and
    the global batch is preserved across relaunches), so keeping the
    already-durable copy is exact.  A re-mesh that CHANGES the global batch
    (``keep_global_batch`` ceil on a non-dividing world) breaks that
    premise — re-run coordinates then carry different losses and the sink
    keeps the pre-crash values; the returned ``fit`` history is the
    authoritative trajectory in that case.
    """

    def __init__(self, path: str):
        self.path = path
        self.rows: list[dict] = []
        self._seen: set = set()
        rows, durable_end = self._scan(path)
        for row in rows:
            self._seen.add(self._key(row))
        if durable_end is not None:
            # Drop the torn tail a crash mid-write left behind: it was never
            # durable (the row will be re-logged on resume), and appending
            # after a partial line would corrupt the NEXT row too.
            with open(path, "r+") as f:
                f.truncate(durable_end)
        self._f = open(path, "a")

    @staticmethod
    def _key(row: dict) -> tuple:
        kind = "summary" if "epoch_time_s" in row else "step"
        return (kind, row.get("epoch"), row.get("step"))

    @staticmethod
    def _scan(path: str) -> tuple[list[dict], int | None]:
        """(durable rows, truncation offset): a row is durable only when its
        line parses AND is newline-terminated; the offset points past the
        last such line when anything torn follows, else None."""
        if not os.path.exists(path):
            return [], None
        with open(path, "rb") as f:
            data = f.read()
        rows, offset, pos = [], 0, 0
        for line in data.splitlines(keepends=True):
            pos += len(line)
            if not line.endswith(b"\n"):
                break
            text = line.decode("utf-8", "replace").strip()
            if not text:
                offset = pos
                continue
            try:
                rows.append(json.loads(text))
            except ValueError:
                break
            offset = pos
        return rows, (offset if offset < len(data) else None)

    def append(self, row: dict) -> bool:
        key = self._key(row)
        if key in self._seen:
            return False
        self._seen.add(key)
        self.rows.append(row)
        self._f.write(json.dumps(row) + "\n")
        self._f.flush()
        os.fsync(self._f.fileno())
        return True

    def load(self) -> list[dict]:
        """All durable rows, across every incarnation, in logged order."""
        return self._scan(self.path)[0]

    def close(self) -> None:
        self._f.close()


def zero_grads_like(params, grad_dtype: str | None):
    """Zero tree for microbatch gradient accumulation.

    Each leaf takes the dtype the gradients will actually have — the
    ``grad_dtype`` compression target when set, else the param leaf's own
    dtype.  (A float32 default would silently up-cast bf16/f16 gradient
    trees through ``jnp.add``'s promotion inside the scan.)
    """
    return jax.tree.map(
        lambda p: jnp.zeros(p.shape, grad_dtype or p.dtype), params)


def make_train_step(
    loss_fn: Callable[[Any, Any], tuple[jnp.ndarray, dict]],
    adam: AdamConfig,
    schedule: Callable[[jnp.ndarray], jnp.ndarray],
    *,
    microbatches: int = 1,
    grad_dtype: str | None = None,
    donate: bool = True,
    in_shardings=None,
    out_shardings=None,
):
    """Build the jitted train step.

    loss_fn(params, batch, *args) -> (loss, metrics).  ``batch`` is any
    pytree whose leaves have a leading per-step batch dim (divisible by
    ``microbatches``); ``args`` are whole arrays every microbatch sees (the
    resident series, the model's constants), passed to the step as jit
    arguments and never donated.  Returns
    step(state, batch, *args) -> (state, metrics).
    """

    def grads_of(params, batch, args):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, *args)
        if grad_dtype is not None:
            grads = jax.tree.map(lambda g: g.astype(grad_dtype), grads)
        return loss, metrics, grads

    def step(state, batch, *args):
        params, opt_state = state["params"], state["opt"]
        if microbatches == 1:
            loss, metrics, grads = grads_of(params, batch, args)
        else:
            def slice_mb(i):
                return jax.tree.map(
                    lambda x: x.reshape((microbatches, -1) + x.shape[1:])[i], batch)

            def acc_step(carry, i):
                loss_a, grads_a = carry
                loss, _, grads = grads_of(params, slice_mb(i), args)
                return (loss_a + loss,
                        jax.tree.map(jnp.add, grads_a, grads)), None

            zero_g = zero_grads_like(params, grad_dtype)
            (loss, grads), _ = jax.lax.scan(
                acc_step, (jnp.zeros(()), zero_g), jnp.arange(microbatches))
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = {}
        with jax.named_scope(trace_names.OPTIMIZER):
            lr = schedule(opt_state["step"])
            new_params, new_opt, gnorm = apply_updates(
                params, grads, opt_state, adam, lr)
        out_metrics = {"loss": loss, "lr": lr, **metrics}
        if gnorm is not None:
            out_metrics["grad_norm"] = gnorm
        return {"params": new_params, "opt": new_opt}, out_metrics

    kw = {}
    if in_shardings is not None:
        kw["in_shardings"] = in_shardings
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    return jax.jit(step, donate_argnums=(0,) if donate else (), **kw)


def init_train_state(params, adam: AdamConfig):
    return {"params": params, "opt": init_opt_state(params, adam)}


def run_training(
    *,
    state,
    train_step,
    sampler,
    batch_of_starts: Callable[[np.ndarray], Any],
    loop: TrainLoopConfig,
    eval_fn: Callable[[Any], dict] | None = None,
    checkpointer=None,
    start_epoch: int = 0,
    start_step: int = 0,
    start_done_in_epoch: int | None = None,
    health_cb: Callable[[int], None] | None = None,
    history_sink: list | None = None,
    batch_stream: Callable[[int, int], Any] | None = None,
    start_span=None,
) -> tuple[Any, list[dict]]:
    """Generic epoch loop.

    ``sampler.epoch_global(e)`` yields [steps, global_batch] window starts
    (``sampler.epoch_grid(e)`` is preferred when present — a DataPlane
    returns only this process's feed columns under multi-process SPMD);
    ``batch_of_starts`` maps one row to the step's batch pytree (typically a
    device_put of the starts with the batch sharding — the gather itself
    happens inside the jitted step, from the resident series).
    Deterministic (seed, epoch) sampling + step-granular checkpoints mean a
    restart resumes bit-identically mid-epoch.

    ``start_done_in_epoch`` decouples the resume position from the step
    numbering: when given, ``start_epoch`` resumes after that many completed
    steps (later epochs start at 0) and ``start_step`` is ONLY the monotonic
    step counter.  Elastic restarts need this — after a re-mesh changes
    ``steps_per_epoch``, deriving the position from ``start_step`` would
    renumber checkpoints non-monotonically and ``latest_step`` could later
    resurrect a stale pre-restart checkpoint.  When None (the default), the
    position is derived from ``start_step`` as before.

    ``health_cb(global_step)`` runs after every step; it may raise
    :class:`RestartSignal` (e.g. the elastic engine's heartbeat monitor
    flagging a dead worker), in which case the loop checkpoints the current
    state with its (epoch, done_in_epoch) coordinates, annotates the signal,
    and re-raises for the engine to re-mesh and resume.

    ``history_sink``: optional caller-owned list mirroring every history row
    as it is logged.  Unlike the returned history it survives NON-elastic
    failures (a collective erroring out when a peer process dies raises
    straight through), so an external launcher can still persist the rows
    logged before the crash.  Pass a :class:`JsonlHistorySink` to make the
    rows crash-durable AND idempotent across relaunch-resumes (duplicate
    ``(epoch, step)`` rows from a re-run epoch tail are suppressed).

    ``batch_stream(epoch, done) -> iterator`` decouples the step loop from
    feed assembly: when given, each epoch's remaining batches are pulled
    from the iterator it returns (typically a
    :class:`repro.pipeline.prefetch.FeedPrefetcher` over the data plane's
    ``grid_stream``) instead of ``batch_of_starts(grid[i])`` per step.  The
    iterator must yield exactly ``steps_per_epoch - done`` device-ready
    batches — the same values the synchronous path would build.  If it has
    a ``close()`` it is drained on every exit from the epoch, normal or
    not — in particular on :class:`RestartSignal`, so an elastic re-mesh
    never leaves stale in-flight batches behind.

    Host spans (``repro.trace_names``) mark the feed, the dispatch, the
    logging sync, checkpoints, health polls and evaluation; ``fit.end`` runs
    from the last step's bookkeeping to the return.  ``start_span``, an entered
    ``jax.profiler.TraceAnnotation``, is exited before the first step's feed.
    """
    span = jax.profiler.TraceAnnotation
    history: list[dict] = []
    end_span = None
    global_step = start_step
    grid_of_epoch = getattr(sampler, "epoch_grid", sampler.epoch_global)

    def log_row(row: dict) -> None:
        history.append(row)
        if history_sink is not None:
            history_sink.append(row)

    def epoch_meta(epoch: int, done: int, steps: int) -> dict:
        """Checkpoint coordinates, normalised so a COMPLETE epoch reads as
        the start of the next one — a resume into a topology whose
        steps_per_epoch grew must not re-enter (and re-summarise) an epoch
        that already finished."""
        if done >= steps:
            return {"epoch": epoch + 1, "done_in_epoch": 0}
        return {"epoch": epoch, "done_in_epoch": done}

    def check_health(done_now: int, steps: int) -> None:
        """Poll health_cb; on RestartSignal checkpoint-and-annotate."""
        if health_cb is None:
            return
        try:
            with span(trace_names.LOOP_HEALTH):
                health_cb(global_step)
        except RestartSignal as sig:
            if checkpointer is not None:
                with span(trace_names.LOOP_CKPT):
                    checkpointer.save(state, step=global_step,
                                      meta=epoch_meta(epoch, done_now, steps))
                    checkpointer.wait()
            sig.state, sig.history = state, history
            sig.epoch, sig.step = epoch, global_step
            raise

    # Where the run stops: the end of the last epoch, or the position of the
    # ``max_steps``-th step (None when resuming at or past that step, so the
    # checkpoint on disk already records the position).
    final_meta: dict | None = {"epoch": loop.epochs, "done_in_epoch": 0}
    capped = False
    for epoch in range(start_epoch, loop.epochs):
        if loop.max_steps and global_step >= loop.max_steps:
            final_meta = None
            break
        if batch_stream is None:
            grid = grid_of_epoch(epoch)
            steps = grid.shape[0]
        else:
            grid, steps = None, sampler.steps_per_epoch
        t0 = time.perf_counter()
        # Resume mid-epoch: skip steps already done.  Clamp to [0, steps] —
        # a start_step beyond this epoch (resume past a partially-logged
        # epoch with a stale start_epoch) must skip it wholesale, not index
        # with a done-count larger than the grid.
        if start_done_in_epoch is not None:
            done_in_epoch = (min(start_done_in_epoch, steps)
                             if epoch == start_epoch else 0)
        else:
            done_in_epoch = min(
                max(global_step - epoch * sampler.steps_per_epoch, 0), steps)
        metrics = None
        batches = (batch_stream(epoch, done_in_epoch)
                   if batch_stream is not None and done_in_epoch < steps
                   else None)
        try:
            for i in range(done_in_epoch, steps):
                if start_span is not None:
                    start_span.__exit__(None, None, None)
                    start_span = None
                with span(trace_names.FEED_NEXT):
                    if batches is not None:
                        batch = next(batches)
                    else:
                        with span(trace_names.FEED_TRANSFER):
                            batch = batch_of_starts(grid[i])
                with span(trace_names.STEP_DISPATCH):
                    state, metrics = train_step(state, batch)
                global_step += 1
                if loop.log_every and global_step % loop.log_every == 0:
                    with span(trace_names.LOOP_LOG_SYNC):
                        m = {k: float(v) for k, v in metrics.items()}
                    log_row({"step": global_step, "epoch": epoch, **m})
                if (checkpointer is not None and loop.ckpt_every
                        and global_step % loop.ckpt_every == 0):
                    with span(trace_names.LOOP_CKPT):
                        checkpointer.save(
                            state, step=global_step,
                            meta=epoch_meta(epoch, i + 1, steps))
                if loop.max_steps and global_step >= loop.max_steps:
                    final_meta, capped = epoch_meta(epoch, i + 1, steps), True
                if capped or (i == steps - 1 and epoch == loop.epochs - 1):
                    end_span = span(trace_names.FIT_END)
                    end_span.__enter__()
                if capped:
                    break
                if i < steps - 1:
                    check_health(i + 1, steps)
        finally:
            # Drain the stream on EVERY exit — epoch end, RestartSignal, or
            # a peer-death collective error — so no prefetch thread is left
            # pulling feeds for a topology about to be re-meshed.
            close = getattr(batches, "close", None)
            if close is not None:
                close()
        if metrics is None:
            continue  # every step was already done on resume: nothing to log
        epoch_metrics = {"epoch": epoch, "epoch_time_s": time.perf_counter() - t0,
                         "step": global_step,
                         "loss": float(metrics["loss"])}
        if eval_fn is not None and loop.eval_every \
                and (epoch + 1) % loop.eval_every == 0:
            with span(trace_names.LOOP_EVAL):
                epoch_metrics.update(eval_fn(state))
        log_row(epoch_metrics)
        if capped:
            break
        # The final step's health poll runs AFTER the epoch summary: a
        # restart landing exactly on the epoch boundary would otherwise
        # abort before the summary/eval row and the resumed run — which
        # starts at the next epoch — could never emit it.
        check_health(steps, steps)
    if checkpointer is not None and final_meta is not None:
        with span(trace_names.LOOP_CKPT):
            checkpointer.save(state, step=global_step, meta=final_meta)
            checkpointer.wait()
    for open_span in (start_span, end_span):
        if open_span is not None:
            open_span.__exit__(None, None, None)
    return state, history
