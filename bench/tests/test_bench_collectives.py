"""The two readers of the collectives layer: ``collective.ms_per_step`` against
a brute-force union on a trace recorded on four chips at the small size
(``bench/tools/layer_split.py --small``), and ``collective.bytes_per_step``
against ``hlo.collective_bytes`` of the compiled step on four CPU devices."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import spec
from bench import trace_reduce as T

DATA = os.path.join(spec.BENCH_DIR, "testdata")
FOUR_CHIP = os.path.join(DATA, "four_chip.xplane.pb")
ONE_CHIP = os.path.join(DATA, "one_chip.xplane.pb")
CELL = "pgt-dcrnn-all-la.part4.b256"


def _covered(pd, device, match):
    """ns of the window in which ``device`` ran a matching op, on either line,
    from a boolean timeline."""
    (w0, w1), = [(s, e) for _, s, e in T._host_events(pd, {T.WINDOW_SPAN})]
    w0, w1 = int(w0), int(w1)
    covered = np.zeros(w1 - w0, bool)
    by_line = T._device_ops(pd)[device]
    for line in (T.OPS_LINE, T.ASYNC_LINE):
        for n, s, e in by_line.get(line, []):
            if match(n):
                covered[max(int(s), w0) - w0:max(min(int(e), w1) - w0, 0)] = True
    return int(covered.sum())


def test_collective_ms_per_step_on_a_four_chip_trace():
    steps = json.load(open(FOUR_CHIP.replace(".xplane.pb", ".json")))["steps"]
    pd = T.load(FOUR_CHIP)
    r = T.reduce(pd, steps=steps)
    assert r["devices"] == 4 and r["has_collectives"]
    first = sorted(T._device_ops(pd))[0]
    want = _covered(pd, first, lambda n: bool(T.COLLECTIVE_RE.match(n)))
    got = spec.reader("collective.ms_per_step")({"trace": r})
    assert got == pytest.approx(want / 1e6 / steps, rel=1e-9)
    assert 0 < got * steps / 1e3 < r["window_s"]


def test_readers_return_nothing_without_a_collective():
    ms = spec.reader("collective.ms_per_step")
    by = spec.reader("collective.bytes_per_step")
    one_chip = T.reduce(T.load(ONE_CHIP), steps=3)
    assert not one_chip["has_collectives"]
    assert ms({"trace": one_chip}) is None
    assert ms({"trace": None}) is None
    assert by({"collectives": {"total": 0, "counts": {}}}) is None
    assert by({"collectives": {"total": 3 * 2**29}}) == 1.5


BYTES_ON_FOUR_DEVICES = """
import json, sys
sys.path[:0] = {paths!r}
from bench import harness, hlo
from bench.tests import small
real = harness._compiled_step
seen = {{}}
def compiled_step(pipe, state, batch):
    text = pipe.train_step.lower(state, batch).compile().as_text()
    seen["hlo"] = hlo.collective_bytes(text)
    return real(pipe, state, batch)
harness._compiled_step = compiled_step
harness._traced_fit = lambda pipe, rec, steps, keep: {{
    "devices": 4, "window_s": 1.0, "busy_s": 0.5, "idle_share": 0.5,
    "collective_s_per_step": 0.0, "has_collectives": False,
    "device_ops": [], "idle_gaps": []}}
import bench.flops
bench.flops.peak_flops = lambda kind: 1e12
result = small.run({cell!r}, trace=True)
print(json.dumps({{"metrics": result["metrics"], "hlo": seen["hlo"],
                  "count": result["device"]["count"]}}))
"""


def test_collective_bytes_per_step_on_four_cpu_devices():
    root = spec.ROOT
    code = BYTES_ON_FOUR_DEVICES.format(paths=[root, os.path.join(root, "src")],
                                        cell=CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["count"] == 4
    want = got["hlo"]
    assert want["counts"]["all-reduce"] > 0 and want["total"] > 0
    value = got["metrics"]["collective.bytes_per_step"]["value"]
    assert value == want["total"] / 2**30
    assert "collective.ms_per_step" not in got["metrics"]
