"""End-to-end training launcher (real compute, host-scale).

Runs the paper's full workflow — synthetic data gen → index-batching
preprocessing → GPU(accelerator)-index-batching placement → distributed-index-
batching training with global shuffling — on whatever devices exist.  On the
CPU container this trains the reduced configs for real; on a TPU slice the
same entry point trains the full ones.

Every arch runs through `repro.pipeline` (placement-aware: the sampler,
series sharding and fused gather/step come from one definition).  LM archs
use the pipeline's `lm` gather (token-stream windows, y = shift(x)).

Multi-host: call with `--init-distributed` under a jax.distributed-capable
launcher (env-configured coordinator) and each process trains from its own
per-rank index feed (`DataPlane.feed(jax.process_index(), epoch)`) — no host
ever materialises the global index grid.  Epoch-end evaluation rides the
same plane: each process scores only its own rank-block of the val pool
(`DataPlane.eval_feed`), `--eval-every` sets the cadence, and the eval rows
land in the crash-durable `--history-out` sink.  `--elastic` attaches the
heartbeat/re-mesh policy so worker loss shrinks the data axis and resumes
from the latest checkpoint instead of killing the run; when the worker
returns, the inverse GROW plan re-admits it with the per-worker batch scaled
back down.  `--heartbeat file:<dir>|tcp://a:p[,b:p,...]` replaces the
simulated all-healthy feed with a REAL transport: every process emits its
ranks' beats each step, and every collector-capable process runs the monitor
over them — but only the LEADER (lowest live rank, see
`repro.distributed.leader`) acts on a verdict.  A `tcp://` spec may be an
ordered failover list: address k is served by process k (beats peer-mirror
between collectors, emitters fail over down the list), so when host 0 dies
the successor's collector is already primed and it takes over plan emission,
checkpoint writing and the durable history sink.

Single-process runs re-mesh in place.  A real fleet cannot (a dead peer's
shards are gone and its collectives would hang), so under
`--init-distributed` use `--elastic-remesh relaunch`: on a re-mesh plan the
process checkpoints, writes the plan to `--plan-out`, and exits with code 75
(EX_TEMPFAIL) — the external launcher (e.g. tests/multihost.py's driver)
tears the gang down and relaunches into the planned topology with the SAME
--batch (the global batch is preserved; per-rank batches re-divide).

Examples:
  python -m repro.launch.train --arch pgt-dcrnn-pems-all-la --nodes 200 \
      --entries 2000 --epochs 3 --batch 32
  python -m repro.launch.train --arch qwen1.5-4b --smoke --steps 100
  python -m repro.launch.train --arch dcrnn-pems --placement partitioned \
      --elastic --ckpt-dir /tmp/ck ...
  python -m repro.launch.train --arch dcrnn-pems --init-distributed \
      --elastic --elastic-remesh relaunch --heartbeat file:/shared/hb \
      --ckpt-dir /shared/ck --plan-out /shared/plan.json ...
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core import IndexDataset, Placement, WindowSpec
from repro.data import (gaussian_adjacency, make_token_stream, make_traffic_series,
                        random_sensor_coords, transition_matrices)
from repro.distributed import (LeaderHistorySink, LeaderTracker, latest_step,
                               make_transport)
from repro.distributed.transport import tcp_addresses
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import dcrnn, pgt_dcrnn
from repro.models.lm import model as lm
from repro.optim import AdamConfig, warmup_cosine
from repro.pipeline import ElasticConfig, PipelineConfig, build_pipeline
from repro.train.loop import RestartSignal, TrainLoopConfig


def stgnn_problem(arch, args):
    """The host-side problem of an ST-GNN arch at the launcher's sizes:
    ``(model config, series [entries, nodes, features], supports)``, all
    generated from ``--seed``."""
    mcfg = arch.model
    if args.nodes:
        mcfg = dataclasses.replace(mcfg, num_nodes=args.nodes)
    coords = random_sensor_coords(mcfg.num_nodes, seed=args.seed)
    adj = gaussian_adjacency(coords)
    supports = tuple(jnp.asarray(s) for s in transition_matrices(adj))
    series = make_traffic_series(args.entries, mcfg.num_nodes,
                                 mcfg.in_features, seed=args.seed, adjacency=adj)
    return mcfg, series, supports


def build_stgnn(arch, args, problem=None):
    """The ST-GNN engine the launcher trains: placement-aware sampler,
    series sharding and fused step over ``problem`` (default: built from
    ``args`` by :func:`stgnn_problem`)."""
    adam, sched, loop = train_config(args)
    mcfg, series, supports = problem or stgnn_problem(arch, args)
    spec = WindowSpec(horizon=mcfg.horizon, input_len=mcfg.input_len)

    mod = dcrnn if isinstance(mcfg, dcrnn.DCRNNConfig) else pgt_dcrnn
    params = mod.init(jax.random.PRNGKey(args.seed), mcfg)

    def loss_fn(supports, p, x, y):
        return mod.loss_fn(p, mcfg, supports, x, y), {}

    mesh = make_host_mesh()
    # --batch is the GLOBAL batch; the pipeline takes a per-rank size
    from repro.core.distributed import dp_size
    dp = max(dp_size(mesh), 1)
    if args.batch % dp:
        raise SystemExit(f"--batch {args.batch} not divisible by "
                         f"data-parallel size {dp}")
    return build_pipeline(
        series, spec, mesh, jax.tree_util.Partial(loss_fn, supports), params,
        PipelineConfig(batch_per_rank=args.batch // dp,
                       placement=Placement(args.placement),
                       gather=args.gather, halo=not args.no_halo,
                       seed=args.seed, adam=adam,
                       schedule=sched, loop=loop),
        elastic=_elastic_config(args))


def _train_stgnn(arch, args, sink: list | None = None):
    """Full pipeline path: placement-aware sampler/sharding/fused step."""
    pipe = build_stgnn(arch, args)
    loop = pipe.config.loop
    if args.resume and loop.ckpt_dir:
        step = latest_step(loop.ckpt_dir)
        if step is not None:
            print(f"resuming from step {step}")
    transport = _wire_heartbeat(pipe, args, sink)
    try:
        return pipe.fit(resume=args.resume, history_sink=sink)
    finally:
        if transport is not None:
            transport.close()


def _train_lm(arch, args, sink: list | None = None):
    """Token-stream windows (nodes==1 case) through the same pipeline: the
    ``lm`` gather entry reconstructs (tokens, shifted labels) on-device."""
    adam, sched, loop = train_config(args)
    cfg = arch.smoke_config() if args.smoke else arch.lm
    stream = np.asarray(make_token_stream(args.entries, cfg.vocab, seed=args.seed))
    spec = WindowSpec(horizon=1, input_len=args.seq_len)
    ds = IndexDataset.from_raw(stream, spec, scale_feature=None)
    ds = dataclasses.replace(ds, series=stream)  # tokens: no standardisation
    params = lm.init(jax.random.PRNGKey(args.seed), cfg)

    def loss_fn(p, toks, labels):
        return lm.loss_fn(p, cfg, toks, labels)

    mesh = make_host_mesh()
    from repro.core.distributed import dp_size
    dp = max(dp_size(mesh), 1)
    if args.batch % dp:
        raise SystemExit(f"--batch {args.batch} not divisible by "
                         f"data-parallel size {dp}")
    # --shuffle selects the sampler through the placement contract: global
    # draws over a replicated stream, or the fixed count-split partitions
    # (local batch shuffling) over a time-sharded stream.
    placement = (Placement.REPLICATED if args.shuffle == "global"
                 else Placement.PARTITIONED)
    pipe = build_pipeline(
        stream, spec, mesh, loss_fn, params,
        PipelineConfig(batch_per_rank=args.batch // dp, placement=placement,
                       partition="count", gather="lm", seed=args.seed,
                       adam=adam, schedule=sched, loop=loop),
        dataset=ds, elastic=_elastic_config(args))
    if args.resume and loop.ckpt_dir:
        step = latest_step(loop.ckpt_dir)
        if step is not None:
            print(f"resuming from step {step}")

    # Held-out LM evaluation through the SAME distributed eval feeds the
    # ST-GNN path rides (ISSUE 5 satellite, ex-ROADMAP item): the `lm`
    # gather reconstructs (tokens, shifted labels) for the val pool's
    # window ids, Engine.evaluate window-weights full chunks + the ragged
    # tail, and the launcher reports both the mean token cross-entropy and
    # its perplexity.  Same epoch-end cadence knob (--eval-every) as the
    # ST-GNN path; bit-identical across process counts for the same
    # reasons (every process derives the same chunk plan).
    if len(ds.val_windows) > 0:
        def eval_fn(st):
            val_loss = pipe.evaluate(st["params"], split="val")
            return {"val_loss": val_loss,
                    "val_ppl": float(np.exp(np.minimum(val_loss, 30.0)))}
    else:
        eval_fn = None
    transport = _wire_heartbeat(pipe, args, sink)
    try:
        return pipe.fit(resume=args.resume, eval_fn=eval_fn,
                        history_sink=sink)
    finally:
        if transport is not None:
            transport.close()


#: Exit code for "re-mesh requested" in relaunch mode (EX_TEMPFAIL: the run
#: is not broken, it wants to be relaunched into the planned topology).
EX_REMESH = 75


def _elastic_config(args) -> ElasticConfig | None:
    if not args.elastic:
        return None
    return ElasticConfig(heartbeat_timeout=args.heartbeat_timeout,
                         remesh=args.elastic_remesh,
                         target_world=args.target_world or None)


def _wire_heartbeat(pipe, args, sink=None):
    """Attach a real transport to an elastic pipeline: every process emits
    beats for the feed ranks it owns; every process that CAN collect polls
    them, but only the current LEADER — lowest live rank, tracked by a
    ``LeaderTracker`` over the same beat stream — acts on a verdict.  One
    decider at a time (no split-brain races on plans or checkpoint
    coordinates), yet the decider role survives the death of process 0:
    the successor's monitor state is already primed when it takes over.
    Returns the transport (caller closes it) or None."""
    if not args.heartbeat or pipe.elastic is None:
        return None
    idx = jax.process_index()
    addrs = tcp_addresses(args.heartbeat)
    if addrs is not None:
        # Address k of the failover list is served by process k; processes
        # beyond the list emit only.  The list length therefore bounds the
        # succession depth — ship one address per host that may ever lead.
        serve = idx < len(addrs)
        transport = make_transport(args.heartbeat, serve=serve,
                                   serve_index=idx)
    else:
        serve = True  # the file transport is symmetric: every process polls
        transport = make_transport(args.heartbeat)

    def emitter(step: int) -> None:
        # Re-read the topology every step: an in-process re-mesh changes the
        # world mid-fit, and beating for a rank outside the current world
        # would read as a returned worker.
        ranks = pipe.dataplane.process_ranks
        for r in (ranks if ranks is not None else range(pipe.world)):
            transport.emit(r, step)

    tracker = None
    if serve:
        # Only collector-capable processes can become the leader (a
        # non-polling process would decide plans off the simulated
        # all-healthy feed).  The rest keep leader=None, i.e. the fixed
        # process-0 gate — false for them by construction — and never
        # standby-buffer history rows they could never flush.
        tracker = LeaderTracker(pipe.world,
                                timeout=args.heartbeat_timeout)
        ranks = pipe.dataplane.process_ranks
        tracker.bind(ranks if ranks is not None else range(pipe.world))
        if isinstance(sink, LeaderHistorySink):
            sink.bind(tracker.is_leader, buffer_standby=True)
    pipe.elastic = dataclasses.replace(
        pipe.elastic, emitter=emitter, leader=tracker,
        step_feed=(transport.step_feed
                   if serve and hasattr(transport, "step_feed")
                   else pipe.elastic.step_feed))
    return transport


def _write_plan(args, sig) -> None:
    """Relaunch mode: persist the re-mesh plan for the external launcher.

    The LEADER only (the engine stamps ``sig.leader`` before re-raising:
    it is the decider and the checkpoint writer, so its (epoch, step)
    coordinates are the ones that match the durable checkpoint — process 0
    classically, the succession winner after a leader death), written
    atomically so the launcher can never read a torn plan."""
    if not getattr(sig, "leader", jax.process_index() == 0):
        return
    plan = sig.plan
    out = {
        "kind": plan.kind if plan is not None else "unknown",
        "reason": str(plan.reason) if plan is not None else str(sig),
        "dropped_workers": list(plan.dropped_workers) if plan else [],
        "readmitted_workers": list(plan.readmitted_workers) if plan else [],
        "mesh_shape": list(plan.mesh_shape) if plan else [],
        "decided_by": getattr(plan, "decided_by", None) if plan else None,
        "epoch": sig.epoch, "step": sig.step,
    }
    payload = json.dumps(out, indent=1)
    if args.plan_out:
        import os
        import tempfile
        fd, tmp = tempfile.mkstemp(
            prefix=".plan-", dir=os.path.dirname(args.plan_out) or ".")
        with os.fdopen(fd, "w") as f:
            f.write(payload)
        os.replace(tmp, args.plan_out)
    print(f"re-mesh requested (exit {EX_REMESH}): {payload}")


def train_config(args) -> tuple[AdamConfig, Callable, TrainLoopConfig]:
    """``(adam, lr schedule, loop config)`` from the launcher's flags."""
    adam = AdamConfig(lr=args.lr)
    total = max(args.steps, 100)
    sched = lambda s: warmup_cosine(s, base_lr=args.lr, warmup_steps=total // 10,
                                    total_steps=total)
    loop = TrainLoopConfig(epochs=args.epochs, log_every=args.log_every,
                           ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
                           eval_every=args.eval_every,
                           prefetch_depth=args.prefetch_depth,
                           staleness=args.staleness,
                           prefetch_chunk=args.prefetch_chunk,
                           max_steps=args.steps)
    return adam, sched, loop


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--entries", type=int, default=2_000)
    ap.add_argument("--nodes", type=int, default=0, help="override graph nodes")
    ap.add_argument("--seq-len", type=int, default=128, help="LM window")
    ap.add_argument("--batch", type=int, default=32, help="global batch")
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--steps", type=int, default=0,
                    help="stop after this many steps (0 = run --epochs)")
    ap.add_argument("--log-every", type=int, default=10,
                    help="log the step metrics every N steps")
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true", help="reduced LM config")
    ap.add_argument("--placement", default="replicated",
                    choices=[p.value for p in Placement],
                    help="ST-GNN dataset placement (pipeline)")
    ap.add_argument("--gather", default="slice",
                    choices=["slice", "take", "fused", "pallas", "auto"],
                    help="window-gather lowering fused into the train step; "
                         "'auto' dispatches per (backend, shape-bucket) "
                         "through the measured tuning cache (see --autotune)")
    ap.add_argument("--autotune", default="load",
                    choices=["off", "load", "tune"],
                    help="kernel autotune policy for backend='auto' dispatch: "
                         "'off' = static per-backend defaults, 'load' = use "
                         "results/TUNING_<backend>.json when a verdict covers "
                         "the shape bucket (never measures), 'tune' = measure "
                         "candidates on a cache miss and persist the verdict")
    ap.add_argument("--tuning-dir", default="results",
                    help="directory holding TUNING_<backend>.json")
    ap.add_argument("--shuffle", default="global", choices=["global", "local-batch"],
                    help="LM sampler (ST-GNN samplers follow --placement)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--eval-every", type=int, default=1,
                    help="epoch-end eval cadence: score the val split through "
                         "the distributed eval feeds after every N-th epoch "
                         "(0 disables eval).  Works under --init-distributed: "
                         "each process scores only its own rank-block of the "
                         "eval pool and the window-weighted metric is "
                         "bit-identical to the single-host value")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="async feed pipeline: materialize feed rows this "
                         "many chunks ahead on a background thread (0 = the "
                         "synchronous pull-per-step path).  At --staleness 0 "
                         "the pipelined run is bit-identical to synchronous")
    ap.add_argument("--staleness", type=int, default=0,
                    help="bounded-stale transfer overlap: 0 keeps lockstep "
                         "semantics (host->device transfer at consume, on "
                         "the step thread — provably bit-identical); s >= 1 "
                         "lets the transfer for step k+s run on a background "
                         "thread while step k computes (values unchanged — "
                         "feeds are pure in (seed, epoch, rank) — only the "
                         "overlap changes)")
    ap.add_argument("--prefetch-chunk", type=int, default=8,
                    help="feed rows per prefetched block")
    ap.add_argument("--no-halo", action="store_true",
                    help="PARTITIONED: keep windows strictly interior to each "
                         "rank's series shard (communication-free; see "
                         "launch/dryrun.py --halo-evidence)")
    ap.add_argument("--elastic", action="store_true",
                    help="attach the heartbeat->plan_remesh->re-mesh-and-"
                         "resume policy (needs --ckpt-dir).  Without "
                         "--heartbeat the transport simulates an all-healthy "
                         "fleet; pass a real transport to detect actual "
                         "worker loss and return")
    ap.add_argument("--heartbeat", default=None,
                    help="real heartbeat transport: file:<shared-dir> "
                         "(same-host multi-process; symmetric — every "
                         "process polls) or tcp://a:p[,b:p,...] — an "
                         "ordered FAILOVER list in leader-succession "
                         "order: process k binds address k and collectors "
                         "peer-mirror accepted beats, emitters fail over "
                         "down the list, so the heartbeat decider survives "
                         "the death of host 0 (list length = succession "
                         "depth)")
    ap.add_argument("--heartbeat-timeout", type=float, default=60.0)
    ap.add_argument("--elastic-remesh", default="inprocess",
                    choices=["inprocess", "relaunch"],
                    help="who executes a re-mesh plan: this process "
                         "(single-host only) or an external launcher — the "
                         "process then checkpoints, writes --plan-out and "
                         f"exits {EX_REMESH}")
    ap.add_argument("--target-world", type=int, default=0,
                    help="grow ceiling: re-admit returned workers up to this "
                         "world size.  0 = the world THIS process started "
                         "with — after a relaunch that is the SHRUNK world, "
                         "so a relaunching controller must pass the original "
                         "fleet size explicitly or the fleet never grows "
                         "back (see tests/multihost.py)")
    ap.add_argument("--plan-out", default=None,
                    help="relaunch mode: path for the re-mesh plan JSON")
    ap.add_argument("--init-distributed", action="store_true",
                    help="call jax.distributed.initialize() (env-configured "
                         "coordinator); each process then trains from its "
                         "own per-rank feed via jax.process_index()")
    ap.add_argument("--history-out", default=None,
                    help="crash-durable history: every logged row (train "
                         "steps AND epoch-end eval rows) is appended to this "
                         "file as one JSON object per line and fsynced as it "
                         "lands, so a crash or exit-75 relaunch loses "
                         "nothing; duplicate (epoch, step) rows from a "
                         "relaunch re-running an epoch tail are suppressed "
                         "(idempotent resume).  Process 0 writes it")
    return ap.parse_args(argv)


def main() -> None:
    args = parse_args()
    print(f"compile cache: {enable_compile_cache()}")
    # Set the autotune policy before anything builds a pipeline: 'auto'
    # dispatch resolves per call, so this only configures WHERE verdicts come
    # from — it never touches the backend (jax.distributed.initialize() below
    # must still run first against an untouched client).
    from repro.kernels.autotune import set_autotune
    set_autotune(mode=args.autotune, cache_dir=args.tuning_dir)
    if args.heartbeat and not args.elastic:
        # Silently ignoring the transport would leave the operator believing
        # health monitoring is active when nothing emits or collects beats.
        raise SystemExit("--heartbeat requires --elastic: the transport only "
                         "feeds the elastic heartbeat monitor")
    if args.elastic and args.elastic_remesh == "relaunch" \
            and not args.target_world:
        print("warning: --elastic-remesh relaunch without --target-world — "
              "growth is capped at this process's starting world; a "
              "relaunching controller should pass the original fleet size")
    if args.init_distributed and args.elastic \
            and args.elastic_remesh != "relaunch":
        # The in-process re-mesh path re-materialises the series on the host
        # (DataPlane.remesh), which needs every shard addressable — true on
        # one process, not on a real fleet.
        raise SystemExit("--elastic with --init-distributed needs "
                         "--elastic-remesh relaunch: a fleet re-meshes by "
                         "relaunching into the planned topology")
    if args.init_distributed:
        # CPU fleets need gloo for cross-process collectives: the default
        # CPU client ships NO collectives implementation, so psums would
        # fail outright once the mesh spans processes.  Must be set before
        # the backend is first touched; harmless on accelerator fleets.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize()
        print(f"jax.distributed: process {jax.process_index()} of "
              f"{jax.process_count()} (per-rank feed selection active)")

    arch = get_arch(args.arch)

    t0 = time.perf_counter()
    # The sink mirrors every logged row AS IT LANDS, so the rows survive the
    # crash paths too — a peer death surfaces as a plain collective error,
    # not a RestartSignal.  With --history-out the sink is crash-durable
    # (JSONL, fsynced per row) and idempotent across exit-75 relaunches, so
    # there is nothing to dump on any exit path: the file is always current.
    # EVERY process carries the leader-gated sink: the current leader's rows
    # land durably, standbys buffer — so history-writer duty survives the
    # leader's death.  Buffering starts OFF (without a succession tracker a
    # non-leader could never flush, so holding every row would be pure
    # waste); _wire_heartbeat turns it on when it binds a LeaderTracker to
    # a collector-capable process.
    sink: list | LeaderHistorySink = \
        (LeaderHistorySink(args.history_out,
                           lambda: jax.process_index() == 0,
                           buffer_standby=False)
         if args.history_out else [])
    try:
        if arch.family == "stgnn":
            state, history = _train_stgnn(arch, args, sink)
        else:
            state, history = _train_lm(arch, args, sink)
    except RestartSignal as sig:
        # relaunch-mode elastic: the state is already checkpointed with its
        # (epoch, done_in_epoch) coordinates; hand the plan to the launcher.
        _write_plan(args, sig)
        raise SystemExit(EX_REMESH)
    wall = time.perf_counter() - t0
    final = [h for h in history if "loss" in h]
    if final:
        print(f"done: {len(final)} logs, wall {wall:.1f}s, "
              f"loss {final[0]['loss']:.4f} -> {final[-1]['loss']:.4f}")
    else:
        print(f"done: nothing to train (resumed past requested epochs), "
              f"wall {wall:.1f}s")
    if isinstance(sink, LeaderHistorySink):
        sink.close()


if __name__ == "__main__":
    main()
