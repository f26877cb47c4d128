"""Paper Table 5: global shuffling vs local batch shuffling — validation MAE.

Trains the same model under both placements' samplers at several simulated
worker counts and reports the optimal validation MAE of each (paper finds
parity).  Both arms run through `repro.pipeline`: REPLICATED selects the
global shuffle, PARTITIONED the fixed-partition local batch shuffle; the
lock-step SPMD simulation is the pipeline's own epoch_global assembly
(every rank's batch concatenated into one jitted step).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import row
from repro.core import Placement, WindowSpec
from repro.data import (gaussian_adjacency, make_traffic_series,
                        random_sensor_coords, transition_matrices)
from repro.launch.mesh import make_host_mesh
from repro.models import pgt_dcrnn
from repro.optim import AdamConfig
from repro.pipeline import PipelineConfig, build_pipeline
from repro.train import TrainLoopConfig

N, ENTRIES, B = 24, 500, 8
EPOCHS = 6

ARMS = (("global", Placement.REPLICATED),
        ("local-batch", Placement.PARTITIONED))


def main() -> None:
    spec = WindowSpec(horizon=4, input_len=4)
    series = make_traffic_series(ENTRIES, N, seed=3)
    adj = gaussian_adjacency(random_sensor_coords(N, seed=3))
    sup = tuple(jnp.asarray(s) for s in transition_matrices(adj))
    cfg = pgt_dcrnn.PGTDCRNNConfig(num_nodes=N, hidden=16, input_len=4, horizon=4)
    params0 = pgt_dcrnn.init(jax.random.PRNGKey(0), cfg)
    mesh = make_host_mesh()

    def loss(sup, p, x, y):
        return pgt_dcrnn.loss_fn(p, cfg, sup, x, y), {}

    loss_fn = jax.tree_util.Partial(loss, sup)

    for world in (2, 4):
        for name, placement in ARMS:
            # partition="count": the paper's Table-5 local-batch arm uses
            # EQUAL per-rank partitions (same training budget as the global
            # arm) — the comparison is about shuffling granularity, not the
            # uneven time-shard ownership of the aligned partitioner.
            pipe = build_pipeline(
                series, spec, mesh, loss_fn, params0,
                PipelineConfig(batch_per_rank=B, placement=placement,
                               world=world, seed=7, partition="count",
                               adam=AdamConfig(lr=5e-3),
                               loop=TrainLoopConfig(epochs=EPOCHS, log_every=0)))
            _, history = pipe.fit()
            best = min(h["val_mae"] for h in history if "val_mae" in h)
            row(f"table5/{name}_w{world}", f"{best:.4f}", "val-mae", "")


if __name__ == "__main__":
    main()
