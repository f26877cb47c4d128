"""End-to-end driver: train a ~100M-weight DCRNN on a PeMS-scaled synthetic
graph for a few hundred steps, with checkpoints, restart, and validation.

This is the full production path through `repro.pipeline`: index-batching +
device-resident series + global shuffling + async atomic checkpoints +
deterministic mid-epoch resume — the pipeline owns the sampler/placement/step
wiring the old driver glued by hand.

Run:  PYTHONPATH=src python examples/train_dcrnn_pems.py [--steps 200]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import WindowSpec
from repro.data import (gaussian_adjacency, make_traffic_series,
                        random_sensor_coords, transition_matrices)
from repro.distributed import latest_step
from repro.launch.mesh import make_host_mesh
from repro.models import dcrnn
from repro.optim import AdamConfig, warmup_cosine
from repro.pipeline import PipelineConfig, build_pipeline
from repro.train import TrainLoopConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=400)
    ap.add_argument("--hidden", type=int, default=96)
    ap.add_argument("--entries", type=int, default=4_000)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--gather", default="slice",
                    choices=["slice", "take", "fused", "pallas"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_dcrnn_ckpt")
    args = ap.parse_args()

    cfg = dcrnn.DCRNNConfig(num_nodes=args.nodes, hidden=args.hidden, layers=2,
                            max_diffusion_step=2, input_len=12, horizon=12,
                            remat=True)
    # weight count scales with hidden^2; report it like a real driver would
    params = dcrnn.init(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"DCRNN params: {n_params / 1e6:.2f}M  nodes={args.nodes}")

    adj = gaussian_adjacency(random_sensor_coords(args.nodes))
    supports = tuple(jnp.asarray(s) for s in transition_matrices(adj))
    series = make_traffic_series(args.entries, args.nodes, adjacency=adj)

    def loss_fn(supports, p, x, y):
        return dcrnn.loss_fn(p, cfg, supports, x, y), {}

    pipe = build_pipeline(
        series, WindowSpec(horizon=12), make_host_mesh(),
        jax.tree_util.Partial(loss_fn, supports), params,
        PipelineConfig(
            batch_per_rank=args.batch, gather=args.gather,
            adam=AdamConfig(lr=1e-2),
            schedule=lambda s: warmup_cosine(s, base_lr=1e-2, warmup_steps=20,
                                             total_steps=args.steps),
            loop=TrainLoopConfig(log_every=20, ckpt_every=50,
                                 ckpt_dir=args.ckpt_dir)))
    ds = pipe.dataset
    print(f"series resident: {ds.nbytes_index() / 2**20:.1f} MiB "
          f"(materialized would be {ds.nbytes_materialized() / 2**30:.2f} GiB)")
    resumed = latest_step(args.ckpt_dir)
    if resumed is not None:
        print(f"resuming from step {resumed}")

    t0 = time.perf_counter()
    epochs = max(1, -(-args.steps // pipe.steps_per_epoch))
    state, history = pipe.fit(epochs=epochs)
    # step logs when log_every fired, else fall back to epoch summaries
    logs = ([h for h in history if "loss" in h and "epoch_time_s" not in h]
            or [h for h in history if "loss" in h])
    vals = [h for h in history if "val_mae" in h]
    if not logs:  # history empty: resume already covered every step
        print(f"nothing to train: checkpoint already at step {resumed}")
        return
    print(f"wall {time.perf_counter() - t0:.1f}s  "
          f"train {logs[0]['loss']:.4f}->{logs[-1]['loss']:.4f}  "
          f"val {vals[-1]['val_mae']:.4f}")


if __name__ == "__main__":
    main()
