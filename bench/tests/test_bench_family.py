"""A model family that is not DCGRU-shaped goes through a whole run from files
alone: a configuration dict, a program module and a reference module, with no
change to the harness, and ``step.mfu`` reads the family's own FLOP count."""
import copy
import sys
import time
from unittest import mock

import pytest

from bench import flops, harness, spec
from bench.tests import small, tiny_gcn_reference

CELL = "tiny-gcn.b8"
PEAK = 1e12


def _config() -> dict:
    base = spec.config("pgt-dcrnn-all-la")
    return {"name": "tiny-gcn", "family": "tiny_gcn",
            "program": {"module": "bench.tests.tiny_gcn_program",
                        "config": "TinyGCNConfig"},
            "model": {"num_nodes": small.NODES, "in_features": 2,
                      "out_features": 1, "input_len": 12, "horizon": 12,
                      "channels": 16},
            "series": {"entries": small.ENTRIES, "features": 2,
                       "chunk": small.CHUNK},
            "optimizer": copy.deepcopy(base["optimizer"]),
            "precision": copy.deepcopy(base["precision"])}


def _traced(pipe, recorder, steps, keep):
    """What the trace reduction gives, for a CPU that records no device
    plane."""
    return {"devices": 1, "window_s": 1.0, "busy_s": 0.5, "idle_share": 0.5,
            "collective_s_per_step": 0.0, "has_collectives": False,
            "device_ops": [], "idle_gaps": []}


def test_a_family_of_files_alone_runs_and_counts_its_own_flops(monkeypatch):
    cfg = _config()
    assert not {"hidden", "max_diffusion_step"} & set(cfg["model"])
    monkeypatch.setitem(sys.modules, "bench.reference.tiny_gcn",
                        tiny_gcn_reference)
    counted, count = [], tiny_gcn_reference.window_flops

    def window_flops(m, nnz, *, dense=False):
        counted.append((dict(m), list(nnz), dense))
        return count(m, nnz, dense=dense)

    monkeypatch.setattr(tiny_gcn_reference, "window_flops", window_flops)
    monkeypatch.setattr(harness, "_traced_fit", _traced)
    monkeypatch.setattr(flops, "peak_flops", lambda kind: PEAK)
    contexts = {}
    reader = spec.reader

    def spying_reader(name, *a):
        read = reader(name, *a)

        def spy(ctx):
            contexts[name] = ctx
            return read(ctx)
        return spy

    monkeypatch.setattr(spec, "reader", spying_reader)
    workload = {"name": CELL, "config": "tiny-gcn", "traffic": "b8", "chips": 1}
    with small.jax_config_kept(), mock.patch(
            "repro.launch.compile_cache.enable_compile_cache",
            lambda: "off in the tests"):
        result = harness.run(CELL, 2**31 + 5, 0.2, True,
                             t_start=time.perf_counter(), require_tpu=False,
                             workload=workload, cfg=cfg,
                             traffic=dict(spec.traffic("b64"), global_batch=8),
                             checks=spec.checks("pgt-dcrnn-all-la.b64"))
    assert result["correct"] is True, result["checks"]
    assert [d for _, _, d in counted] == [False, True]
    m, nnz, _ = counted[0]
    assert m == cfg["model"] and len(nnz) == 2 and all(nnz)
    ctx = contexts["step.mfu"]
    assert ctx["useful_flops"] == count(m, nnz)
    assert ctx["dense_flops"] > ctx["useful_flops"]
    mfu = result["metrics"]["step.mfu"]["value"]
    assert mfu == pytest.approx(100 * ctx["useful_flops"] * ctx["windows_per_s"]
                                / (ctx["chips"] * PEAK))
    assert set(result["metrics"]) == {"setup.compile_s", "step.mfu",
                                      "device.idle_share"}
