"""Cells cut to a size the CPU runs in seconds, for the tests."""
from __future__ import annotations

import contextlib
import copy
import time

import jax

from bench import harness, spec

NODES, ENTRIES, CHUNK = 40, 2400, 150


def config(cell: str) -> dict:
    cfg = copy.deepcopy(spec.config(spec.workload(cell)["config"]))
    cfg["model"]["num_nodes"] = NODES
    cfg["series"].update(entries=ENTRIES, chunk=CHUNK)
    return cfg


def traffic(cell: str) -> dict:
    w = spec.workload(cell)
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
        return harness.run(cell, seed, 0.2, trace, t_start=time.perf_counter(),
                           require_tpu=False, cfg=config(cell),
                           traffic=traffic(cell))
