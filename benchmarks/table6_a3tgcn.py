"""Paper Table 6 / §5.5: index-batching generalises to A3T-GCN (and ST-LLM).

Trains A3T-GCN with base vs index batching (identical window ids) and
reports runtime + memory + final MSE parity; runs one ST-LLM step with
index-batched windows to cover the Fig-10 model family.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timed
from repro.core import (GlobalShuffleSampler, IndexDataset, ShardInfo,
                        WindowSpec, gather_batch, materialize_windows)
from repro.data import (gaussian_adjacency, make_traffic_series,
                        random_sensor_coords, sym_norm_adjacency)
from repro.models import a3tgcn, stllm
from repro.optim import AdamConfig
from repro.train.loop import init_train_state, make_train_step

N, ENTRIES, B = 24, 500, 8


def main() -> None:
    spec = WindowSpec(horizon=4, input_len=4)
    ds = IndexDataset.from_raw(make_traffic_series(ENTRIES, N, seed=5), spec)
    a_hat = jnp.asarray(sym_norm_adjacency(
        gaussian_adjacency(random_sensor_coords(N, seed=5))))
    cfg = a3tgcn.A3TGCNConfig(num_nodes=N, hidden=16, input_len=4, horizon=4)
    params = a3tgcn.init(jax.random.PRNGKey(0), cfg)
    adam = AdamConfig(lr=5e-3)
    series = jnp.asarray(ds.series)
    starts = jnp.asarray(ds.starts)

    xs, ys = materialize_windows(np.asarray(ds.series), ds.starts, 4, 4)
    xs_d, ys_d = jnp.asarray(xs), jnp.asarray(ys)
    row("table6/mem_base", f"{(xs.nbytes + ys.nbytes) / 2**20:.2f}", "MiB", "")
    row("table6/mem_index", f"{ds.nbytes_index() / 2**20:.2f}", "MiB",
        f"reduction={100 * (1 - ds.nbytes_index() / (xs.nbytes + ys.nbytes)):.1f}%"
        " (paper: 49.2%)")

    def loss_base(p, ids):
        return a3tgcn.loss_fn(p, cfg, a_hat, xs_d[ids], ys_d[ids]), {}

    def loss_index(p, ids):
        x, y = gather_batch(series, starts[ids], input_len=4, horizon=4)
        return a3tgcn.loss_fn(p, cfg, a_hat, x, y), {}

    sampler = GlobalShuffleSampler(ds.train_windows, B, ShardInfo(0, 1), seed=2)
    results = {}
    for name, lf in (("base", loss_base), ("index", loss_index)):
        step = make_train_step(lf, adam, lambda s: 5e-3, donate=False)
        state = init_train_state(params, adam)
        for epoch in range(4):
            for ids in sampler.epoch_global(epoch):
                state, m = step(state, jnp.asarray(ids))
        tval, _ = lf(state["params"], jnp.asarray(ds.test_windows[:64]))
        results[name] = float(tval)
        t = timed(lambda: step(init_train_state(params, adam),
                               jnp.asarray(sampler.epoch_global(0)[0])))
        row(f"table6/{name}_step", f"{1e3 * t:.2f}", "ms", "")
        row(f"table6/{name}_test_mse", f"{float(tval):.5f}", "mse", "")
    row("table6/mse_delta", f"{abs(results['base'] - results['index']):.2e}",
        "mse", "identical batches -> identical trajectory")

    # ---- ST-LLM (Fig 10 family): one index-batched train step
    scfg = stllm.STLLMConfig(num_nodes=N, input_len=4, horizon=4, smoke=True,
                             embed_width=16)
    sparams = stllm.init(jax.random.PRNGKey(1), scfg)

    def loss_stllm(p, ids):
        x, y = gather_batch(series, starts[ids], input_len=4, horizon=4)
        return stllm.loss_fn(p, scfg, None, x, y), {}

    step = make_train_step(loss_stllm, adam, lambda s: 1e-3, donate=False)
    t = timed(lambda: step(init_train_state(sparams, adam),
                           jnp.asarray(sampler.epoch_global(0)[0])))
    row("fig10/stllm_index_step", f"{1e3 * t:.2f}", "ms",
        "ST-LLM over index-batched windows")


if __name__ == "__main__":
    main()
