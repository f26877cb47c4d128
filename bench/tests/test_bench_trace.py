"""The trace reduction against numbers worked out by hand and by brute force
on a small trace recorded on the chip (``bench/tools/record_trace.py``)."""
import os

import numpy as np
import pytest

from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "testdata")
ONE_CHIP = os.path.join(DATA, "one_chip.xplane.pb")


def test_union_and_self_time_by_hand():
    assert T.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    nested = [("while.1", 0, 100), ("fusion.2", 10, 30), ("fusion.3", 40, 50),
              ("copy.4", 45, 48), ("fusion.2", 120, 125)]
    assert T.self_times(nested) == {"while.1": 70, "fusion.2": 25,
                                    "fusion.3": 7, "copy.4": 3}
    assert T.op_name("%all-gather.3 = f32[8]{0} all-gather(f32[2]{0} %p)") \
        == "all-gather.3"


def _brute(pd, lines, match=lambda name: True):
    """Per device: ns covered by matching ops inside the window, and the
    window, from a boolean timeline."""
    (w0, w1), = [(s, e) for _, s, e in T._host_events(pd, {T.WINDOW_SPAN})]
    w0, w1 = int(w0), int(w1)
    out = {}
    for name, by_line in T._device_ops(pd).items():
        covered = np.zeros(w1 - w0, bool)
        for line in lines:
            for n, s, e in by_line.get(line, []):
                if match(n):
                    covered[max(int(s), w0) - w0:max(min(int(e), w1) - w0, 0)] = True
        out[name] = covered
    return w0, w1, out


def _spans(pd):
    return list(T._host_events(pd, {T.WINDOW_SPAN, *T.HOST_SPANS}))


def test_one_chip_trace():
    pd = T.load(ONE_CHIP)
    r = T.reduce(pd, steps=3)
    w0, w1, covered = _brute(pd, [T.OPS_LINE])
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    busy = covered["/device:TPU:0"]
    assert r["busy_s"] == pytest.approx(busy.sum() / 1e9, rel=1e-6)
    assert not r["has_collectives"] and r["collective_s_per_step"] == 0
    # The longest gap: the longest run of False, named by the innermost
    # harness span open at its midpoint.
    edges = np.flatnonzero(np.diff(np.r_[1, busy.astype(np.int8), 1]))
    runs = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)]
    s, e = max(runs, key=lambda g: g[1] - g[0])
    name, secs = r["idle_gaps"][0]
    assert secs == pytest.approx((e - s) / 1e9, rel=1e-6)
    mid = w0 + (s + e) / 2
    holding = [(b - a, n) for n, a, b in _spans(pd) if a <= mid <= b]
    assert name == min(holding)[1]
    names = [n for n, _ in r["device_ops"]]
    assert all("=" not in n and not n.startswith("%") for n in names)
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] + 1e-9


def test_collective_attribution(monkeypatch):
    """The collective union and its per-step share, on the one-chip trace
    with the bf16 stacks (``concatenate``) standing in for collectives."""
    monkeypatch.setattr(T, "COLLECTIVE_RE", __import__("re").compile(r"^concatenate"))
    pd = T.load(ONE_CHIP)
    r = T.reduce(pd, steps=3)
    assert r["has_collectives"]
    _, _, covered = _brute(pd, [T.OPS_LINE, T.ASYNC_LINE],
                           lambda n: bool(T.COLLECTIVE_RE.match(n)))
    want = covered["/device:TPU:0"].sum() / 1e9 / 3
    assert r["collective_s_per_step"] == pytest.approx(want, rel=1e-6)
    assert 0 < r["collective_s_per_step"] * 3 < r["busy_s"]
