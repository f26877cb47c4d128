"""Serving launcher: replay an arrival trace through the serving stack.

Drives the sharded serving engine (``repro.serve``) on a reduced config —
a plane fleet over the host mesh, batched prefill, per-request deadlines —
and prints what it served.  ``--trace batch`` submits everything up front
(the PR-4 demo behaviour); ``--trace poisson`` replays independent arrivals
at ``--rate`` req/s against the wall clock, so backpressure and deadline
expiry actually fire.

``--block-size`` switches the KV cache to PAGED mode: cache lines come from
a shared pool of fixed-size blocks (``--pool-blocks`` usable blocks; default
= contiguous capacity at block granularity, so size it DOWN to expected live
tokens to realise the memory win) and admission accounts blocks, raising
clean backpressure instead of OOM-ing when the pool is exhausted.

``--role`` picks the process's job in an ELASTIC FLEET (PR 9):

- ``engine`` (default) — everything in one process, as before;
- ``fleet``  — coordinator: spawns ``--planes`` per-host worker processes
  (re-invoking this module with ``--role worker``; on a TPU host each
  worker is given a chip of its own, and more planes than chips is
  refused), assigns requests over
  file mailboxes, tracks liveness via heartbeats, and re-prefills a dead
  worker's in-flight requests on survivors;
- ``worker`` — one serving host: a single-plane engine pumping the file
  mailboxes under ``--fleet-dir`` and beating ``hb_<id>.json``.

  python -m repro.launch.serve --arch qwen1.5-4b --requests 8 --slots 4
  python -m repro.launch.serve --trace poisson --rate 30 --deadline 2.0
  python -m repro.launch.serve --block-size 16 --pool-blocks 24
  python -m repro.launch.serve --role fleet --planes 2 --requests 8
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.lm import model as lm
from repro.serve import (Backpressure, FileMailbox, FleetEngine, ServeConfig,
                         ServeEngine, ServeWorker)


def _serve_config(args: argparse.Namespace) -> ServeConfig:
    # 0 is the argv-safe "off" sentinel for the filters (workers are
    # re-spawned with string argv, so None can't ride through)
    return ServeConfig(slots=args.slots, max_len=args.max_len,
                       max_new_tokens=args.max_new_tokens,
                       temperature=args.temperature,
                       sample_seed=args.sample_seed,
                       top_k=args.top_k or None,
                       top_p=args.top_p or None,
                       block_size=args.block_size or None,
                       pool_blocks=args.pool_blocks or None)


def _prompts(args: argparse.Namespace, vocab: int) -> list:
    rng = np.random.default_rng(args.seed)
    return [rng.integers(0, vocab, size=int(rng.integers(4, 17)))
            for _ in range(args.requests)]


def _report(done: dict, out: dict, wall: float, rejects: int, extra: str) -> None:
    ok = [r for r in done.values() if r.status == "ok"]
    timed_out = sum(1 for r in done.values() if r.status == "timeout")
    truncated = sum(1 for r in done.values() if r.status == "truncated")
    toks = sum(len(r.out) for r in done.values() if r.status != "timeout")
    print(f"served {len(ok)}/{len(done)} requests "
          f"({timed_out} timeout, {truncated} truncated, "
          f"{rejects} backpressure-shed), "
          f"{toks} tokens in {wall:.2f}s ({toks / wall:.1f} tok/s, {extra})")
    for rid in sorted(out):
        tag = "" if done[rid].status == "ok" else f" [{done[rid].status}]"
        print(f"  req {rid}{tag}: {out[rid][:8]}"
              f"{'...' if len(out[rid]) > 8 else ''}")


# ------------------------------------------------------------ single process
def _run_engine(args: argparse.Namespace) -> None:
    arch = get_arch(args.arch)
    if arch.lm is None:
        raise SystemExit(f"{args.arch} is not an LM arch")
    cfg = arch.smoke_config()
    params = lm.init(jax.random.PRNGKey(args.seed), cfg)
    engine = ServeEngine(params, cfg, _serve_config(args),
                         planes=args.planes, seed=args.seed)
    prompts = _prompts(args, cfg.vocab)

    rejects = 0
    t0 = time.perf_counter()
    if args.trace == "batch":
        for p in prompts:
            engine.submit(p, deadline_s=args.deadline)
        out = engine.run()
    else:
        rng = np.random.default_rng(args.seed)
        arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
        i = 0
        while i < len(arrivals) or engine.active_lanes() or len(engine.router.queue):
            now = time.perf_counter() - t0
            while i < len(arrivals) and arrivals[i] <= now:
                try:
                    engine.submit(prompts[i], deadline_s=args.deadline)
                    i += 1
                except Backpressure:
                    rejects += 1  # shed; retried on the next tick
                    break
            if not engine.step() and i < len(arrivals):
                time.sleep(0.001)
        out = engine.router.results()
    wall = time.perf_counter() - t0

    mesh = engine.planes[0].mesh
    extra = (f"planes={args.planes} slots={args.slots} "
             f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")
    if args.block_size:
        pool = engine.planes[0].pool
        extra += (f" paged[bs={args.block_size} blocks={pool.num_blocks} "
                  f"cache={engine.planes[0].cache_bytes() / 1e6:.1f}MB]")
    _report(engine.router.done, out, wall, rejects, extra)


# ------------------------------------------------------------------- worker
def _run_worker(args: argparse.Namespace) -> None:
    """One serving host of an elastic fleet (see ``ServeWorker``)."""
    from repro.distributed.transport import FileHeartbeatTransport

    arch = get_arch(args.arch)
    cfg = arch.smoke_config()
    params = lm.init(jax.random.PRNGKey(args.seed), cfg)
    spool = os.path.join(args.fleet_dir, f"w{args.worker_id}_a{args.attempt}")
    worker = ServeWorker(
        params, cfg, _serve_config(args),
        worker_id=args.worker_id, attempt=args.attempt,
        inbox=FileMailbox(os.path.join(spool, "in")),
        outbox=FileMailbox(os.path.join(spool, "out")),
        heartbeat=FileHeartbeatTransport(os.path.join(args.fleet_dir, "hb")))
    worker.run()


# -------------------------------------------------------------- coordinator
def _probe_backend() -> tuple[str, int]:
    """``(backend, local device count)`` as a worker would see them, asked
    of a short-lived child process: the coordinator itself never touches
    JAX, so it holds no chip, and the child releases the chip on exit."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.default_backend(), jax.local_device_count())"],
        capture_output=True, text=True, check=True, timeout=300)
    backend, count = out.stdout.split()[-2:]
    return backend, int(count)


def _worker_env(backend: str, wid: int) -> dict | None:
    """The environment of worker ``wid``: on a TPU host each worker process
    sees only chip ``wid`` (a chip belongs to one process at a time)."""
    if backend != "tpu":
        return None
    return {**os.environ, "TPU_VISIBLE_CHIPS": str(wid),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(8476 + wid)}


def _run_fleet(args: argparse.Namespace) -> None:
    """Coordinator: spawn per-host workers, drive the fleet, shut it down."""
    from repro.distributed.transport import FileHeartbeatTransport

    arch = get_arch(args.arch)
    if arch.lm is None:
        raise SystemExit(f"{args.arch} is not an LM arch")
    backend, chips = _probe_backend()
    if backend == "tpu" and args.planes > chips:
        raise SystemExit(f"--planes {args.planes} needs one chip per worker; "
                         f"this host has {chips} TPU chips")
    cfg = arch.smoke_config()
    fleet_dir = args.fleet_dir or tempfile.mkdtemp(prefix="serve-fleet-")
    hb = FileHeartbeatTransport(os.path.join(fleet_dir, "hb"))
    fleet = FleetEngine(_serve_config(args), world=args.planes,
                        hb_timeout=args.hb_timeout,
                        step_feed=lambda: hb.step_feed(0, args.planes))

    procs = []
    for wid in range(args.planes):
        spool = os.path.join(fleet_dir, f"w{wid}_a0")
        fleet.attach(wid, attempt=0,
                     send=FileMailbox(os.path.join(spool, "in")),
                     recv=FileMailbox(os.path.join(spool, "out")))
        argv = [sys.executable, "-m", "repro.launch.serve", "--role", "worker",
                "--fleet-dir", fleet_dir, "--worker-id", str(wid),
                "--arch", args.arch, "--slots", str(args.slots),
                "--max-len", str(args.max_len),
                "--max-new-tokens", str(args.max_new_tokens),
                "--temperature", str(args.temperature),
                "--sample-seed", str(args.sample_seed),
                "--top-k", str(args.top_k),
                "--top-p", str(args.top_p),
                "--block-size", str(args.block_size),
                "--pool-blocks", str(args.pool_blocks),
                "--seed", str(args.seed)]
        procs.append(subprocess.Popen(argv, env=_worker_env(backend, wid)))
    print(f"# fleet: {args.planes} {backend} workers, mailboxes under "
          f"{fleet_dir}")

    prompts = _prompts(args, cfg.vocab)
    t0 = time.perf_counter()
    for p in prompts:
        fleet.submit(p, deadline_s=args.deadline)
    try:
        while fleet.pending():
            fleet.tick()
            time.sleep(0.02)
    finally:
        fleet.stop_workers()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
    wall = time.perf_counter() - t0
    served = {wid: w.served for wid, w in fleet.workers.items()}
    _report(fleet.router.done, fleet.results(), wall, 0,
            f"workers={args.planes} slots/worker={args.slots} "
            f"served-per-worker={served}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--role", choices=("engine", "fleet", "worker"),
                    default="engine",
                    help="engine: in-process fleet (default); fleet: spawn "
                         "per-host worker processes and coordinate them; "
                         "worker: one serving host (spawned by --role fleet)")
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode lanes per plane")
    ap.add_argument("--planes", type=int, default=1,
                    help="inference planes (engine: in-process slot pools; "
                         "fleet: worker PROCESSES, one plane each)")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="default sampling temperature (0 = greedy); draws "
                         "are request-keyed, so output is identical across "
                         "--planes counts for the same seeds")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="default per-request base sampling seed")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k largest logits before sampling "
                         "(0 = off)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass in (0, 1] (0 = off)")
    ap.add_argument("--block-size", type=int, default=0,
                    help="paged-KV block size in tokens (0 = contiguous "
                         "per-slot cache lines)")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="usable blocks in the paged pool (0 = contiguous "
                         "capacity, slots*ceil(max_len/block_size); size it "
                         "to expected LIVE tokens for the memory win)")
    ap.add_argument("--trace", choices=("batch", "poisson"), default="batch",
                    help="batch: submit all up front; poisson: timed arrivals")
    ap.add_argument("--rate", type=float, default=30.0,
                    help="poisson arrival rate, requests/second")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds (default: none)")
    ap.add_argument("--fleet-dir", default=None,
                    help="shared mailbox/heartbeat dir for --role "
                         "fleet/worker (fleet default: a fresh tempdir)")
    ap.add_argument("--worker-id", type=int, default=0)
    ap.add_argument("--attempt", type=int, default=0,
                    help="worker mailbox incarnation (bumped on relaunch)")
    ap.add_argument("--hb-timeout", type=float, default=10.0,
                    help="seconds of beat silence before a worker is "
                         "declared dead and its work re-prefilled")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.role == "worker":
        if args.fleet_dir is None:
            raise SystemExit("--role worker requires --fleet-dir")
        _run_worker(args)
    elif args.role == "fleet":
        _run_fleet(args)
    else:
        _run_engine(args)


if __name__ == "__main__":
    main()
