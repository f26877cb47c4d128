"""Finds a cell's pieces by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; each is a file of its own under ``bench/``.

==========================  ==================================================
``bench/configs/<c>.json``  a configuration: sizes, source, precision, the
                            program's model and the reference's family
``bench/traffic/<t>.json``  a traffic mix: batch, placement, gather, feed
``bench/checks/<cell>.json`` the limits of the comparison that decides
                            ``correct``, with the readings they were set from
``bench/metrics/<m>.py``    a per-layer metric's reader: ``read(ctx)``
``bench/reference/<f>.py``  a model family's plain reference
==========================  ==================================================

Adding a configuration, a traffic mix or a metric adds files and entries in
``BENCHMARK.json``; nothing here or in the harness changes.  So does adding a
model family: a configuration names its program (``program.module``, whose
class ``program.config`` takes the ``model`` dict) and its ``family``, and

- the program module has ``init(key, cfg)`` and
  ``loss_fn(params, cfg, supports, x, y)``;
- the reference module ``bench/reference/<family>.py`` has ``init(key, m)``
  and ``loss(params, m, supports, x, y)``, drawing the program's weights from
  the same key with no code of the program, and
  ``window_flops(m, nnz, *, dense) -> float``, the useful FLOPs of one
  training window, forward and backward, with the supports at ``nnz``
  nonzeros (``dense``: every entry), which the harness hands the metrics'
  readers.

``m`` is the configuration's ``model`` dict; ``x`` and ``y`` are
``[B, T, N, F]`` windows of the series and ``supports`` the graph's.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def workload(name: str, root: str = ROOT) -> dict:
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "bench", "configs", f"{name}.json"))


def traffic(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "bench", "traffic", f"{name}.json"))


def checks(cell: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "bench", "checks", f"{cell}.json"))


def metrics_for(cell: str, kind: str, root: str = ROOT) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it, and those that list no cells."""
    return [m for m in benchmark(root)[kind]
            if cell in m.get("workloads", [cell])]


def reader(metric: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
