"""Paper Fig 7 + §5.3: distributed-index-batching vs baseline DDP scaling.

Two views:
1. HOST-SIMULATED strong scaling: fixed dataset, growing worker count; each
   "worker"'s step runs sequentially on this CPU (lock-step SPMD semantics),
   so reported speedup = T(1)/T(w) with perfect overlap — an upper bound that
   isolates ALGORITHMIC communication cost (which we account analytically
   from batch bytes moved).  The per-worker step is the `repro.pipeline`
   fused gather+grad+Adam program under the REPLICATED placement.
2. DRY-RUN collective bytes at production scale, read from
   results/dryrun_full.json when present: replicated vs partitioned vs
   ondemand — the Fig-7/Fig-9 contrast measured from compiled HLO.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from benchmarks.common import row, timed
from repro.core import Placement, WindowSpec
from repro.data import (gaussian_adjacency, make_traffic_series,
                        random_sensor_coords, transition_matrices)
from repro.launch.mesh import make_host_mesh
from repro.models import pgt_dcrnn
from repro.pipeline import PipelineConfig, build_pipeline
from repro.train import TrainLoopConfig
from repro.train.loop import init_train_state

N, ENTRIES, B_PER = 32, 600, 8


def main(smoke: bool = False) -> None:
    """``smoke=True``: tiny synthetic sizes + fewer worker points, for the CI
    bench-smoke leg (seconds, not minutes; same code path)."""
    n, entries, b_per = (8, 150, 4) if smoke else (N, ENTRIES, B_PER)
    worlds = (1, 2) if smoke else (1, 2, 4, 8)
    spec = WindowSpec(horizon=6, input_len=6)
    series = make_traffic_series(entries, n)
    adj = gaussian_adjacency(random_sensor_coords(n))
    sup = tuple(jnp.asarray(s) for s in transition_matrices(adj))
    cfg = pgt_dcrnn.PGTDCRNNConfig(num_nodes=n, hidden=16, input_len=6, horizon=6)
    params = pgt_dcrnn.init(jax.random.PRNGKey(0), cfg)

    def loss(sup, p, x, y):
        return pgt_dcrnn.loss_fn(p, cfg, sup, x, y), {}

    loss_fn = jax.tree_util.Partial(loss, sup)

    span = spec.in_len + spec.horizon
    window_bytes = span * n * 2 * 4  # one (x,y) span in f32
    mesh = make_host_mesh()

    for w in worlds:
        pipe = build_pipeline(
            series, spec, mesh, loss_fn, params,
            PipelineConfig(batch_per_rank=b_per, placement=Placement.REPLICATED,
                           world=w, seed=0,
                           loop=TrainLoopConfig(donate=False)))
        # one worker's slice of the first global batch (lock-step semantics)
        rank0 = pipe.sampler.epoch(0)[0]
        starts0 = pipe.batch_of_starts(rank0)
        state = init_train_state(jax.tree.map(jnp.copy, params),
                                 pipe.config.adam)
        t = timed(lambda: pipe.train_step(state, starts0)[1]["loss"],
                  iters=1 if smoke else 3)
        # distributed-index: zero data bytes; DDP ships every window to its worker
        ddp_bytes = b_per * w * window_bytes
        glob = b_per * w
        row(f"fig7/steps_per_epoch_w{w}", pipe.steps_per_epoch, "steps", "")
        row(f"fig7/index_step_w{w}", f"{1e3 * t:.2f}", "ms",
            "per-worker fused step; data comms = 0 B")
        # throughput with perfect lock-step overlap of the w workers — the
        # same upper-bound semantics as the speedup view above; "tokens" are
        # window ELEMENTS (batch x span x nodes x features) through the step
        row(f"fig7/windows_per_s_w{w}", f"{glob / t:.1f}", "windows/s",
            "global batch / per-worker step, simulated w-worker overlap")
        row(f"fig7/tokens_per_s_w{w}", f"{glob * span * n * 2 / t:.0f}",
            "tok/s", "window elements through the fused gather/step")
        row(f"fig7/ddp_data_bytes_w{w}", ddp_bytes, "B",
            "on-demand batch shipping per step")

    # production-scale collective contrast from the dry-run
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun_full.json")
    if os.path.exists(path):
        with open(path) as f:
            recs = json.load(f)
        for r in recs:
            if r.get("arch") == "dcrnn-pems" and r.get("status") == "ok" \
                    and not r.get("multi_pod"):
                pl = r["meta"].get("placement", "replicated")
                row(f"fig7/dryrun_coll_{pl}",
                    f"{r['collectives']['total'] / 2**20:.1f}", "MiB/step",
                    f"peak={r['memory']['peak_bytes'] / 2**30:.2f}GiB")


if __name__ == "__main__":
    main()
