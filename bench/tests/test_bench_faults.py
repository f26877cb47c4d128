"""A whole run, the chip check skipped, with the timed path broken
underneath: ``correct`` has to come out false for each fault a training cell
can have, and true with nothing broken."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness, spec
from bench.tests import small

CELL = "pgt-dcrnn-all-la.b64"


def _model_module(cell):
    import importlib
    cfg = small.config(cell)
    return importlib.import_module(cfg["program"]["module"])


def state_unchanged(monkeypatch, cell):
    """The step computes its loss and gradients and returns its state as it
    came in."""
    import repro.train.loop as loop
    monkeypatch.setattr(loop, "apply_updates",
                        lambda params, grads, opt, adam, lr: (params, opt, None))


def rows_left_out(monkeypatch, cell, keep=2):
    """The loss is the mean over the first ``1/keep`` of the batch only."""
    mod = _model_module(cell)
    real = mod.loss_fn

    def loss_fn(p, cfg, supports, x, y):
        n = x.shape[0] // keep
        return real(p, cfg, supports, x[:n], y[:n])

    monkeypatch.setattr(mod, "loss_fn", loss_fn)


def late_window(monkeypatch, cell):
    """The gather reads every window one step late: an answer altered where
    it is produced."""
    import repro.pipeline.engine as engine
    real = engine.resolve_gather

    def resolve(name):
        gather = real(name)
        return lambda series, starts, **kw: gather(series, starts + 1, **kw)

    monkeypatch.setattr(engine, "resolve_gather", resolve)


def nan_after_warmup(monkeypatch, cell):
    """From the first call after the warm-up's on, the step's weights come
    out NaN: a step broken inside the window only, where the warm-up's
    comparison cannot see it."""
    import jax
    import jax.numpy as jnp
    import repro.pipeline.engine as engine
    real = engine.make_train_step

    def make_train_step(*args, **kw):
        step = real(*args, **kw)
        calls = []

        def broken(state, *rest):
            state, metrics = step(state, *rest)
            calls.append(1)
            if len(calls) > harness.WARMUP_STEPS:
                state = dict(state, params=jax.tree.map(
                    lambda p: p * jnp.nan, state["params"]))
            return state, metrics

        broken.lower = step.lower
        return broken

    monkeypatch.setattr(engine, "make_train_step", make_train_step)


@pytest.mark.parametrize("fault", [state_unchanged, rows_left_out, late_window,
                                   nan_after_warmup])
@pytest.mark.parametrize("cell", [CELL, "dcrnn-pems.b8"])
def test_a_broken_step_is_not_correct(monkeypatch, fault, cell):
    fault(monkeypatch, cell)
    result = small.run(cell)
    assert result["correct"] is False, result["checks"]
    failed = [k for k, v in result["checks"].items() if v["value"] > v["limit"]]
    assert failed


FOUR_DEVICES = """
import json, sys
sys.path[:0] = {paths!r}
from bench.tests import small, test_bench_faults as faults
class Patch:
    def setattr(self, obj, name, value):
        setattr(obj, name, value)
if {fault!r}:
    # What the first chip applies when the gradient all-reduce is left out:
    # the gradient of its own quarter of the batch.
    faults.rows_left_out(Patch(), {cell!r}, keep=4)
print(json.dumps(small.run({cell!r})))
"""


@pytest.mark.parametrize("fault", [False, True])
def test_four_chip_cell_on_four_cpu_devices(fault):
    """PARTITIONED on four CPU devices: its time-sharded series and
    global-index gather are correct, and the exchange left out is not."""
    cell = "pgt-dcrnn-all-la.part4.b256"
    root = spec.ROOT
    code = FOUR_DEVICES.format(paths=[root, os.path.join(root, "src")],
                               fault=fault, cell=cell)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is (not fault), result["checks"]
