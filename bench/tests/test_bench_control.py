"""The lower-precision control: the plain reference put in the program's
place and computed in bfloat16 has to fail one of the cell's numbers.  On the
chip ``bench/control.py`` reads it at the cell's own size; here the same
comparison runs at a size the CPU holds."""
import gc
from unittest import mock

import pytest

from bench import harness, spec
from bench.tests import small


@pytest.mark.parametrize("cell", ["pgt-dcrnn-all-la.b64", "dcrnn-pems.b8"])
def test_bfloat16_control_is_not_correct(cell):
    cfg, traffic = small.config(cell), small.traffic(cell)
    limits = spec.checks(cell)["limits"]
    with small.jax_config_kept(), mock.patch(
            "repro.launch.compile_cache.enable_compile_cache", lambda: "off"):
        s = harness.setup(cfg, traffic, 2**31 + 99, cell)
        batches = harness.reference_batches(s.pipe.dataset.series,
                                            s.recorder.starts, cfg["model"])
        prog, parts = s.prog, s.parts
        del s
        gc.collect()
        ref = harness.follow_reference(cfg, parts, batches, block=8)
        sound = harness.readings(cfg, prog, ref)
        low = harness.follow_reference(cfg, parts, batches, block=8,
                                       dtype="bfloat16", precision="default")
        control = harness.readings(cfg, {"losses": low["losses"],
                                         "grad1": low["grad1"],
                                         "p0": low["params0"],
                                         "p_end": low["params"]}, ref)
    numbers = [k for k in limits if k in sound]
    assert all(sound[k] <= limits[k] for k in numbers), sound
    assert any(control[k] > limits[k] for k in numbers), control
