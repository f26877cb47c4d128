"""Quickstart: the paper's workflow in ~30 lines via `repro.pipeline`.

1. Build a PeMS-shaped synthetic series + sensor graph.
2. `build_pipeline` does the rest — index-batching preprocessing (ONE
   standardized series + int32 starts), device placement for the chosen
   `Placement`, the matching sampler, and a jitted train step with the
   window gather fused in.  Batches are reconstructed on-device from
   indices; no snapshot array ever exists.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import WindowSpec
from repro.data import (gaussian_adjacency, make_traffic_series,
                        random_sensor_coords, transition_matrices)
from repro.launch.mesh import make_host_mesh
from repro.models import pgt_dcrnn
from repro.optim import AdamConfig
from repro.pipeline import PipelineConfig, build_pipeline
from repro.train import TrainLoopConfig

NODES, ENTRIES, HORIZON, BATCH = 48, 1_000, 6, 16

# 1. data + graph
series = make_traffic_series(ENTRIES, NODES)
adj = gaussian_adjacency(random_sensor_coords(NODES))
supports = tuple(jnp.asarray(s) for s in transition_matrices(adj))

# 2. model loss on gathered (x, y) windows — the only model-specific piece
cfg = pgt_dcrnn.PGTDCRNNConfig(num_nodes=NODES, hidden=16,
                               input_len=HORIZON, horizon=HORIZON)
params = pgt_dcrnn.init(jax.random.PRNGKey(0), cfg)


def loss_fn(supports, p, x, y):
    return pgt_dcrnn.loss_fn(p, cfg, supports, x, y), {}


# 3. the pipeline: placement + sampler + fused gather/step in one call; the
#    supports ride into the step as arguments through the Partial
pipe = build_pipeline(
    series, WindowSpec(horizon=HORIZON), make_host_mesh(),
    jax.tree_util.Partial(loss_fn, supports), params,
    PipelineConfig(batch_per_rank=BATCH, adam=AdamConfig(lr=5e-3),
                   loop=TrainLoopConfig(epochs=3, log_every=10)))
ds = pipe.dataset
print(f"windows={ds.n_windows}  compact={ds.nbytes_index() / 2**20:.2f} MiB  "
      f"materialized-would-be={ds.nbytes_materialized() / 2**20:.2f} MiB")

state, history = pipe.fit()
logs = [h for h in history if "loss" in h and "epoch_time_s" not in h]
print(f"loss {logs[0]['loss']:.4f} -> {logs[-1]['loss']:.4f} over {len(logs)} logs")
