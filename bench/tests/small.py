"""Cells cut to a size the CPU runs in seconds, for the tests."""
from __future__ import annotations

import contextlib
import copy
import time

import jax

from bench import harness, spec

NODES, ENTRIES, CHUNK = 40, 2400, 150


#: Distributed-index-batching on four devices: the configuration of the
#: one-chip PeMS-All-LA cell, time-sharded (PARTITIONED, no halo), 8 windows
#: per device.  Not a cell of ``BENCHMARK.json`` yet.
FOUR = {"name": "pgt-dcrnn-all-la.part4", "config": "pgt-dcrnn-all-la",
        "traffic": None, "chips": 4, "checks": "pgt-dcrnn-all-la.b64",
        "traffic_data": {"global_batch": 32, "placement": "partitioned",
                         "gather": "slice", "halo": False}}


def _workload(cell: str) -> dict:
    return FOUR if cell == FOUR["name"] else spec.workload(cell)


def config(cell: str) -> dict:
    cfg = copy.deepcopy(spec.config(_workload(cell)["config"]))
    cfg["model"]["num_nodes"] = NODES
    cfg["series"].update(entries=ENTRIES, chunk=CHUNK)
    return cfg


def traffic(cell: str) -> dict:
    w = _workload(cell)
    if w is FOUR:
        return dict(FOUR["traffic_data"])
    return dict(spec.traffic(w["traffic"]), global_batch=8 * w["chips"])


@contextlib.contextmanager
def jax_config_kept():
    """Undo the compile-cache settings a run makes, so that tests that run
    later in the same process see JAX as they would have."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        yield
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()


def run(cell: str, seed: int = 3_000_000_017, *, trace: bool = False) -> dict:
    """One whole run of ``cell`` at the small size, the chip check skipped
    and JAX's persistent compile cache left off."""
    from unittest import mock
    with jax_config_kept(), mock.patch(
            "repro.launch.compile_cache.enable_compile_cache",
            lambda: "off in the tests"):
        w = _workload(cell)
        return harness.run(cell, seed, 0.2, trace, t_start=time.perf_counter(),
                           require_tpu=False, workload=w, cfg=config(cell),
                           traffic=traffic(cell),
                           checks=spec.checks(w.get("checks", cell)))
