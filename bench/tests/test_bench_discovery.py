"""The harness finds a cell's pieces by name, from files, and names none of
them itself; ``BENCHMARK.json`` keeps to its contract."""
import json
import os
import re
import shutil

import pytest

from bench import spec

BENCH = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _names():
    return ([c["name"] for c in BENCH["configs"]]
            + [w["name"] for w in BENCH["workloads"]]
            + [w["traffic"] for w in BENCH["workloads"]]
            + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])


@pytest.mark.parametrize("module", ["run.py", "harness.py", "spec.py"])
def test_harness_code_names_no_cell_configuration_or_metric(module):
    text = open(os.path.join(spec.BENCH_DIR, module)).read()
    names = set(_names()) - {"windows_per_s", "hbm_peak_gib", "setup_s"}
    if module == "run.py":
        names |= {"windows_per_s", "hbm_peak_gib", "setup_s"}
    found = [n for n in names if re.search(rf"(?<![\w.-]){re.escape(n)}(?![\w-])", text)]
    assert not found, f"{module} names {found}"


def test_a_new_configuration_traffic_and_metric_are_found_from_files(tmp_path):
    root = tmp_path
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny-gcn", "source": "x",
                             "file": "bench/configs/tiny-gcn.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny-gcn.b2", "config": "tiny-gcn",
                               "traffic": "b2", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "feed.host_ms", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "feeds", "moves": "windows_per_s",
                               "workloads": ["tiny-gcn.b2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench/configs/tiny-gcn.json").write_text(json.dumps(
        {"name": "tiny-gcn", "model": {"num_nodes": 3}}))
    (root / "bench/traffic/b2.json").write_text(json.dumps({"global_batch": 2}))
    (root / "bench/metrics/feed.host_ms.py").write_text(
        "def read(ctx):\n    return ctx.get('feed_ms')\n")
    r = str(root)
    w = spec.workload("tiny-gcn.b2", r)
    assert spec.config(w["config"], r)["model"]["num_nodes"] == 3
    assert spec.traffic(w["traffic"], r)["global_batch"] == 2
    layer = [m["name"] for m in spec.metrics_for("tiny-gcn.b2", "per_layer", r)]
    assert "feed.host_ms" in layer and "collective.ms_per_step" not in layer
    assert spec.reader("feed.host_ms", r)({"feed_ms": 1.5}) == 1.5
    assert spec.reader("feed.host_ms", r)({}) is None
    old = [m["name"] for m in spec.metrics_for("pgt-dcrnn-all-la.b64",
                                               "per_layer", r)]
    assert "feed.host_ms" not in old
    with pytest.raises(KeyError):
        spec.workload("no-such.cell", r)


def test_every_named_file_exists_and_every_metric_has_a_reader():
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        assert spec.config(c["name"])["name"] == c["name"]
    for w in BENCH["workloads"]:
        spec.traffic(w["traffic"])
        spec.checks(w["name"])
    for m in BENCH["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_benchmark_json_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(NAME_RE.match(n) for n in _names())
    for group in (BENCH["configs"], BENCH["workloads"],
                  BENCH["end_to_end"] + BENCH["per_layer"]):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert not any(k.endswith(("_dim", "_rank")) or k == "hidden"
                       for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert len(w["why"]) <= 200


def _run(cwd, env_extra=None):
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pgt-dcrnn-all-la.b64",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_fails_without_a_chip_and_prints_no_result():
    out = _run(spec.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not TPUs" in out.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
