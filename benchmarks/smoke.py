"""CI bench-smoke harness — the perf trajectory's recorded points.

Runs the fig7 (distributed-index scaling) and table3 (index vs standard
batching) benchmarks in ``--smoke`` mode (tiny synthetic data, same code
paths) plus a window-gather microbench (jitted dense jnp vs Pallas interpret
vs the measured ``auto`` dispatch), and serialises everything to
``BENCH_smoke.json``:

- ``headline``: the few numbers a trend line wants — tokens/s through the
  fused gather/step, gather microseconds for the ``dense``,
  ``pallas``-interpret and autotuned ``auto`` lowerings, the
  async-feed-pipeline overlap
  (``step_overlap_pct`` / ``prefetch_step_us``, with the staleness-0
  bit-identity asserted on every run), peak RSS of the whole run;
- ``rows``: every ``name,value,unit,detail`` record the suites printed, so
  nothing the CSV stream shows is lost from the artifact.

CPU wall times are NOT accelerator performance (Pallas runs interpret mode
on CPU) — the point of this harness is (a) the benchmarks EXECUTE, end to
end, on every push, and (b) successive artifacts give the hot paths a
recorded history, so a regression in the gather/step machinery shows up as
a trend break instead of going unnoticed (MSPipe's untracked-stage lesson).

Usage: PYTHONPATH=src python -m benchmarks.smoke [--out results/BENCH_smoke.json]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import fig7_scaling, table3_index_vs_base
from benchmarks.common import peak_rss_bytes, recording, row, timed
from repro.kernels import window_gather, window_gather_ref


def _gather_microbench() -> None:
    """Window gather at a reduced PeMS-like shape: the hot path of
    index-batching.  All three arms are JITTED before timing — eager wall
    time is dominated by per-op Python dispatch and says nothing about the
    lowering (the pallas arm used to be timed eagerly, which buried the
    comparison under interpreter overhead):

    - ``dense``  — jit of the pure-jnp reference;
    - ``pallas`` — jit of the scalar-prefetch kernel (interpret mode on
      CPU; not TPU perf);
    - ``auto``   — jit of the measured dispatcher (kernels/autotune):
      dispatch fires at TRACE time exactly like the fused train step, so
      the steady state runs the tuned winner with zero dispatch overhead.
    """
    import functools
    import statistics

    from repro.kernels import verdict_for

    rng = np.random.default_rng(0)
    series = jnp.asarray(rng.standard_normal((512, 64)).astype(np.float32))
    starts = jnp.asarray(rng.integers(0, 480, 16).astype(np.int32))
    dense = jax.jit(window_gather_ref, static_argnames=("span",))
    pallas = jax.jit(functools.partial(window_gather, use_pallas=True),
                     static_argnames=("span",))
    auto = jax.jit(functools.partial(window_gather, impl="auto"),
                   static_argnames=("span",))
    # dense and auto often lower to the SAME graph (the tuner picks ref);
    # at the ~10µs scale an A-then-B comparison is pure scheduler jitter,
    # so the two arms are interleaved and compared by round medians.
    rounds, dense_ts, auto_ts = 5, [], []
    for _ in range(rounds):
        dense_ts.append(timed(lambda: dense(series, starts, span=24),
                              iters=5))
        auto_ts.append(timed(lambda: auto(series, starts, span=24), iters=5))
    t_dense = statistics.median(dense_ts)
    t_auto = statistics.median(auto_ts)
    row("smoke/gather_dense_us", f"{1e6 * t_dense:.1f}", "us",
        "[512,64] b=16 span=24, jit of the jnp dense lowering, median of "
        f"{rounds} interleaved rounds")
    t_pallas = timed(lambda: pallas(series, starts, span=24))
    row("smoke/gather_pallas_interpret_us", f"{1e6 * t_pallas:.1f}", "us",
        "same shape, jit of the Pallas kernel in interpret mode (CPU; "
        "not TPU perf)")
    v = verdict_for("window_gather", np.asarray(series), np.asarray(starts),
                    span=24)
    row("smoke/gather_auto_us", f"{1e6 * t_auto:.1f}", "us",
        f"same shape, autotuned dispatch -> {v.variant} ({v.source}), "
        f"median of {rounds} interleaved rounds")
    ok_pallas = np.array_equal(np.asarray(pallas(series, starts, span=24)),
                               np.asarray(dense(series, starts, span=24)))
    ok_auto = np.array_equal(np.asarray(auto(series, starts, span=24)),
                             np.asarray(dense(series, starts, span=24)))
    row("smoke/gather_pallas_matches_dense", int(ok_pallas), "bool", "")
    row("smoke/gather_auto_matches_dense", int(ok_auto), "bool",
        f"variant={v.variant}")
    if not ok_pallas:
        raise SystemExit("pallas gather diverged from the dense lowering")
    if not ok_auto:
        raise SystemExit("autotuned gather diverged from the dense lowering")


def _prefetch_bench(staleness: int) -> None:
    """Measured overlap of the async feed pipeline (ISSUE 6) — three arms of
    the same smoke-scale pgt_dcrnn fit:

    1. synchronous (prefetch_depth=0): the baseline step time AND the
       reference loss trajectory;
    2. pipelined at staleness 0: must be BIT-IDENTICAL to (1) — the
       refactor's correctness evidence, asserted here on every bench run;
    3. pipelined at ``staleness``: the timed arm — host feed assembly and
       the host→device transfer move off the step thread, so the step-time
       delta vs (1) is the measured overlap (not asserted into existence).

    The shape is deliberately host-bound (tiny model, modest batch): the
    caller-thread feed path — host row assembly + the Python-side
    ``device_put`` — is the overhead the pipeline hides, and this is where
    it is visible.  Arms are INTERLEAVED (sync/stale alternating rounds)
    and compared by median so machine noise hits both the same way; a
    single-shot A-then-B comparison on a shared CI core is pure jitter.
    """
    import statistics

    from repro.core import Placement, WindowSpec
    from repro.data import (gaussian_adjacency, make_traffic_series,
                            random_sensor_coords, transition_matrices)
    from repro.launch.mesh import make_host_mesh
    from repro.models import pgt_dcrnn
    from repro.pipeline import PipelineConfig, build_pipeline
    from repro.train import TrainLoopConfig

    n, entries = 8, 900
    spec = WindowSpec(horizon=2, input_len=2)
    series = make_traffic_series(entries, n)
    adj = gaussian_adjacency(random_sensor_coords(n))
    sup = tuple(jnp.asarray(s) for s in transition_matrices(adj))
    cfg = pgt_dcrnn.PGTDCRNNConfig(num_nodes=n, hidden=8, input_len=2,
                                   horizon=2)
    params = pgt_dcrnn.init(jax.random.PRNGKey(0), cfg)

    def loss(sup, p, x, y):
        return pgt_dcrnn.loss_fn(p, cfg, sup, x, y), {}

    loss_fn = jax.tree_util.Partial(loss, sup)

    mesh = make_host_mesh()

    def run(depth: int, stale: int, *, log_every: int):
        """(loss rows, steady-state step µs): a fresh 2-epoch fit; epoch 0
        absorbs the jit compile, epoch 1 is the timed steady state."""
        loop = TrainLoopConfig(epochs=2, log_every=log_every, eval_every=0,
                               prefetch_depth=depth, staleness=stale)
        pipe = build_pipeline(
            series, spec, mesh, loss_fn, params,
            PipelineConfig(batch_per_rank=16, placement=Placement.REPLICATED,
                           world=1, seed=0, loop=loop))
        _, hist = pipe.fit(eval_fn=None)
        losses = [h["loss"] for h in hist if "epoch_time_s" not in h]
        steady = [h["epoch_time_s"] for h in hist
                  if "epoch_time_s" in h and h["epoch"] == 1][0]
        return losses, 1e6 * steady / pipe.steps_per_epoch

    # Correctness arms: full per-step loss trajectories, compared exactly.
    sync_losses, _ = run(0, 0, log_every=1)
    id_losses, _ = run(2, 0, log_every=1)
    bit_identical = sync_losses == id_losses
    stale_losses = (run(2, staleness, log_every=1)[0] if staleness >= 1
                    else id_losses)
    # Timing arms: per-step logging off (each logged row is a host sync
    # that would mask the overlap), interleaved rounds, medians.
    rounds, sync_ts, stale_ts = 3, [], []
    for _ in range(rounds):
        sync_ts.append(run(0, 0, log_every=0)[1])
        stale_ts.append(run(2, staleness, log_every=0)[1])
    sync_us = statistics.median(sync_ts)
    stale_us = statistics.median(stale_ts)
    overlap_pct = 100.0 * (1.0 - stale_us / sync_us)
    steps = len(sync_losses)
    row("prefetch/sync_step_us", f"{sync_us:.1f}", "us",
        f"synchronous pull-per-step baseline, median of {rounds} "
        f"interleaved rounds")
    row("prefetch/prefetch_step_us", f"{stale_us:.1f}", "us",
        f"pipelined, depth=2 staleness={staleness}")
    row("prefetch/step_overlap_pct", f"{overlap_pct:.1f}", "%",
        "100*(1 - pipelined/sync) median steady-state step time")
    row("prefetch/bit_identical_at_0", int(bit_identical), "bool",
        f"staleness-0 loss trajectory ({steps} steps) vs synchronous")
    row("prefetch/final_loss_sync", f"{sync_losses[-1]:.10g}", "loss", "")
    row("prefetch/final_loss_stale", f"{stale_losses[-1]:.10g}", "loss",
        f"staleness={staleness}")
    if not bit_identical:
        raise SystemExit("staleness-0 pipelined losses diverged from the "
                         "synchronous path — the prefetch identity is broken")


def _pick(records: list[dict], name: str) -> float:
    vals = [float(r["value"]) for r in records if r["name"] == name]
    if not vals:
        raise SystemExit(f"bench-smoke produced no '{name}' record")
    return vals[0]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="results/BENCH_smoke.json")
    ap.add_argument("--staleness", type=int, default=1,
                    help="staleness of the TIMED prefetch arm (the "
                         "staleness-0 bit-identity arm always runs)")
    ap.add_argument("--autotune", choices=("off", "load", "tune"),
                    default="load",
                    help="kernel autotune policy for the 'auto' arms: off = "
                         "static defaults, load = use the committed "
                         "TUNING_<backend>.json, tune = measure and persist "
                         "fresh verdicts")
    ap.add_argument("--tuning-dir", default="results",
                    help="directory holding TUNING_<backend>.json")
    args = ap.parse_args(argv)

    from repro.kernels import set_autotune
    set_autotune(mode=args.autotune, cache_dir=args.tuning_dir)

    t0 = time.perf_counter()
    print("name,value,unit,detail")
    with recording() as records:
        fig7_scaling.main(smoke=True)
        table3_index_vs_base.main(smoke=True)
        _gather_microbench()
        _prefetch_bench(args.staleness)
    wall = time.perf_counter() - t0

    tokens = max(float(r["value"]) for r in records
                 if r["name"].startswith("fig7/tokens_per_s_"))
    payload = {
        "schema": 1,
        "kind": "bench-smoke",
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "autotune": args.autotune,
        "wall_s": round(wall, 2),
        "headline": {
            "tokens_per_s": tokens,
            "gather_dense_us": _pick(records, "smoke/gather_dense_us"),
            "gather_pallas_interpret_us": _pick(
                records, "smoke/gather_pallas_interpret_us"),
            "gather_auto_us": _pick(records, "smoke/gather_auto_us"),
            "step_overhead_vs_base_pct": round(
                100 * (_pick(records, "table3/step_index")
                       / _pick(records, "table3/step_base") - 1), 1),
            "step_overlap_pct": _pick(records, "prefetch/step_overlap_pct"),
            "prefetch_step_us": _pick(records, "prefetch/prefetch_step_us"),
            "peak_rss_bytes": peak_rss_bytes(),
        },
        "prefetch": {
            "staleness": args.staleness,
            "bit_identical_at_0": bool(
                _pick(records, "prefetch/bit_identical_at_0")),
            "sync_step_us": _pick(records, "prefetch/sync_step_us"),
            "prefetch_step_us": _pick(records, "prefetch/prefetch_step_us"),
            "step_overlap_pct": _pick(records, "prefetch/step_overlap_pct"),
            "final_loss_sync": _pick(records, "prefetch/final_loss_sync"),
            "final_loss_stale": _pick(records, "prefetch/final_loss_stale"),
        },
        "rows": records,
    }
    out_dir = os.path.dirname(args.out) or "."
    os.makedirs(out_dir, exist_ok=True)
    fd, tmp = tempfile.mkstemp(prefix=".bench-", dir=out_dir)
    with os.fdopen(fd, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, args.out)
    print(f"# bench-smoke done in {wall:.1f}s -> {args.out}")
    print(json.dumps(payload["headline"], indent=1))


if __name__ == "__main__":
    main()
