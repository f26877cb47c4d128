"""Compile the main path's kernels and train step for a described TPU v5e chip.

Interpret mode runs a Pallas kernel body in Python and cannot see what
Mosaic refuses (unaligned blocks, unsupported shape casts) or whether a step
fits the chip's 16 GiB.  These tests compile for one chip of a ``v5e:2x2``
topology that is described, not attached — at the widths of the
``pgt-dcrnn-pems-all-la`` cell (2,716 nodes, a 105,120-step series) — so no
chip time is spent finding such faults.  Nothing runs; only the compiler's
verdict and ``memory_analysis()`` are checked.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test collection happens in
every worker.
"""
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_arch
from repro.core.distributed import Placement, series_sharding
from repro.core.index_dataset import IndexDataset
from repro.core.windows import WindowSpec
from repro.kernels.diffusion_conv import diffusion_conv
from repro.kernels.flash_attention import flash_attention
from repro.kernels.linear_scan import linear_scan
from repro.kernels.window_gather import window_gather
from repro.models import dcrnn, pgt_dcrnn
from repro.optim import AdamConfig
from repro.pipeline.dataplane import DataPlane, PipelineConfig
from repro.pipeline.engine import _compile
from repro.train.loop import init_train_state

ENTRIES = 105_120           # Table 1: PeMS-All-LA, 5-minute steps
NODES, FEATURES = 2_716, 2
HBM_BYTES = 16 * 2**30      # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_for(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_window_gather_compiles(one_chip):
    series = _sds((ENTRIES, NODES * FEATURES), jnp.float32, one_chip)
    starts = _sds((64,), jnp.int32, one_chip)
    compiled = _compile_for(
        lambda s, st: window_gather(s, st, span=24, use_pallas=True,
                                    backend="tpu"), series, starts)
    assert "tpu_custom_call" in compiled.as_text()


def test_hop_project_compiles(one_chip):
    cfg = get_arch("pgt-dcrnn-pems-all-la").model
    c = cfg.in_features + cfg.hidden
    h = 2 * cfg.hidden
    x = _sds((64, NODES, c), jnp.float32, one_chip)
    sup = (_sds((NODES, NODES), jnp.float32, one_chip),) * 2
    w = _sds((cfg.n_matrices * c, h), jnp.float32, one_chip)
    b = _sds((h,), jnp.float32, one_chip)
    compiled = _compile_for(
        lambda x, sup, w, b: diffusion_conv(
            x, sup, w, b, k_hops=cfg.max_diffusion_step, use_pallas=True,
            backend="tpu"), x, sup, w, b)
    assert "tpu_custom_call" in compiled.as_text()


def test_linear_scan_compiles(one_chip):
    a = _sds((8, 2048, 2560), jnp.float32, one_chip)
    compiled = _compile_for(
        lambda a, b: linear_scan(a, b, use_pallas=True, backend="tpu"), a, a)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    q = _sds((1, 2048, 20, 128), jnp.bfloat16, one_chip)
    compiled = _compile_for(
        lambda q, k, v: flash_attention(q, k, v, causal=True, use_pallas=True,
                                        backend="tpu"), q, q, q)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def train_step(topo, one_chip):
    """``compiled(model, batch, gather)``: the engine's train step, built by
    ``pipeline.engine._compile``, compiled for one described chip over the
    resident Table-1 series at PeMS-All-LA width; each case compiled once."""
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"))

    @functools.cache
    def compiled(model, batch, gather):
        return _compile_train_step(mesh, one_chip, model, batch, gather)

    return compiled


def _compile_train_step(mesh, one_chip, model, batch, gather):
    module, cfg = _MODELS[model]()
    spec = WindowSpec(horizon=cfg.horizon, input_len=cfg.input_len)
    series = _sds((ENTRIES, NODES, FEATURES), jnp.float32, one_chip)
    sup = (_sds((NODES, NODES), jnp.float32, one_chip),) * 2
    config = PipelineConfig(batch_per_rank=batch, gather=gather,
                            adam=AdamConfig())
    dataset = IndexDataset(series=series, starts=np.zeros(1, np.int32),
                           spec=spec, scaler=None, train_windows=None,
                           val_windows=None, test_windows=None)
    plane = DataPlane(config=config, mesh=mesh, spec=spec, dataset=dataset,
                      sampler=None,
                      series_sharding=series_sharding(mesh,
                                                      Placement.REPLICATED),
                      world=1, batch_sharding=None)

    def loss(supports, p, x, y):
        return module.loss_fn(p, cfg, supports, x, y), {}

    step, _ = _compile(plane, jax.tree_util.Partial(loss, sup), config)
    state = jax.eval_shape(lambda: init_train_state(
        module.init(jax.random.PRNGKey(0), cfg), config.adam))
    state = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), state)
    return step.lower(state, _sds((batch,), jnp.int32, one_chip)).compile()


_MODELS = {
    "pgt_dcrnn": lambda: (pgt_dcrnn, get_arch("pgt-dcrnn-pems-all-la").model),
    # dcrnn-pems's encoder-decoder with remat, as its benchmark cell runs it,
    # at PeMS-All-LA width so the compile stays short.
    "dcrnn": lambda: (dcrnn, dataclasses.replace(
        get_arch("dcrnn-pems").model, num_nodes=NODES, remat=True)),
}


def test_pgt_dcrnn_train_step_fits_one_chip(train_step):
    """The engine's train step at batch 64 over the resident Table-1 series:
    arguments (series, supports, state) plus temporaries fit 16 GiB."""
    mem = train_step("pgt_dcrnn", 64, "slice").memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes >= ENTRIES * NODES * FEATURES * 4
    assert used < HBM_BYTES, (mem.argument_size_in_bytes,
                              mem.temp_size_in_bytes)


@pytest.mark.parametrize("model,batch,gather", [
    ("pgt_dcrnn", 64, "slice"),
    ("pgt_dcrnn", 64, "fused"),
    ("pgt_dcrnn", 64, "take"),
    ("dcrnn", 8, "slice"),
])
def test_train_step_keeps_no_bf16_series_copy(train_step, model, batch,
                                              gather):
    """The matmuls round their inputs to bf16; the compiled step converts the
    gathered windows, never the whole resident series once per step."""
    series_elems = ENTRIES * NODES * FEATURES
    copies = [m.group(0) for m in re.finditer(
        r"bf16\[([0-9,]+)\]", train_step(model, batch, gather).as_text())
        if math.prod(map(int, m.group(1).split(","))) == series_elems]
    assert not copies, copies
