"""The per-layer split: the program's scopes in the compiled train step, the
benchmark's copy of the program's names, the attribution of instructions to
layers, and the hops' least work."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, hop_roofline
from bench import trace_layers as L
from bench import trace_reduce as T
from bench.reference import train as ref_train
from bench.tests import small
from repro import trace_names
from repro.train.loop import init_train_state

MODEL_SCOPES = ("rnn.scan", "dcgru", "dconv.hop")


def test_the_benchmark_names_are_the_programs():
    assert set(L.LAYERS) == set(trace_names.DEVICE_SCOPES)
    assert L.HOST_SPANS == trace_names.HOST_SPANS
    assert L.FEED_SPAN == trace_names.FEED_NEXT


def test_layer_of_by_hand():
    path = ("jit(step)/jit(main)/transpose(jvp(jit(loss_fn)))/rnn.scan/while/"
            "body/closed_call/checkpoint/rematted_computation/dcgru/dconv.hop/"
            "mn,bnc->bmc/dot_general")
    assert L.layer_of(path) == "dconv.hop"
    assert L.layer_of("jit(step)/transpose(jvp(rnn.scan))/while") == "rnn.scan"
    assert L.layer_of("jit(step)/jvp(dcgru)/concatenate") == "dcgru"
    assert L.layer_of("jit(step)/jit(main)/vmap(jit(slice))/gather") == L.OTHER
    assert L.layer_of("jit(step)/gather/jit(slice)/gather") == "gather"
    assert L.layer_of("jit(step)/optimizer/mul") == "optimizer"
    assert L.layer_of("args_0") == L.OTHER


def _compiled_text(cell: str, remat: bool) -> str:
    cfg = small.config(cell)
    cfg["model"]["remat"] = remat
    pipe, _ = harness.build(cfg, small.traffic(cell), 3_000_000_017, cell)
    state = init_train_state(jax.tree.map(jnp.copy, pipe.init_params),
                             pipe.config.adam)
    batch = pipe.dataplane.batch_of_starts(pipe.dataplane.epoch_grid(0)[0])
    return pipe.train_step.lower(state, batch).compile().as_text()


def _innermost_scope(path: str) -> str:
    """Brute force: the rightmost scope token among the path's parts but the
    last, searched as a plain substring with its wrappers' parentheses."""
    best, at = L.OTHER, -1
    head = path.rsplit("/", 1)[0] if "/" in path else ""
    for scope in L.LAYERS:
        for m in re.finditer(rf"(?:^|[/(]){re.escape(scope)}(?=$|[/)])", head):
            if m.end() > at:
                best, at = scope, m.end()
    return best


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("cell", ["pgt-dcrnn-all-la.b64", "dcrnn-pems.b8"])
def test_scopes_reach_every_part_of_the_compiled_step(cell, remat):
    text = _compiled_text(cell, remat)
    names = L.op_names(text)
    assert len(names) == sum(1 for line in text.splitlines()
                             if re.match(r"^\s*(ROOT\s+)?%?[\w.\-]+ = ", line)
                             and 'op_name="' in line)
    paths = list(names.values())
    for scope in MODEL_SCOPES:
        fwd = [p for p in paths if scope in p and "jvp(" in p
               and "transpose(" not in p]
        bwd = [p for p in paths if scope in p and "transpose(" in p]
        assert fwd and bwd, scope
    for scope in ("gather", "optimizer"):
        assert any(L.layer_of(p) == scope for p in paths), scope
    if remat:
        recomputed = [p for p in paths if "rematted_computation" in p
                      and "while/body" in p]
        assert {L.layer_of(p) for p in recomputed} >= {"dcgru", "dconv.hop"}
    layers = {n: L.layer_of(p) for n, p in names.items()}
    assert set(layers.values()) == {*L.LAYERS, L.OTHER}
    assert all(layers[n] == _innermost_scope(p) for n, p in names.items())


@pytest.mark.parametrize("family,cell", [("pgt_dcrnn", "pgt-dcrnn-all-la.b64"),
                                         ("dcrnn", "dcrnn-pems.b8")])
def test_hop_flops_are_the_hop_part_of_the_window_count(family, cell):
    m = small.config(cell)["model"]
    mod = ref_train.model(family)
    cells = mod.cells(m)
    nnz = [500, 700]
    hops = mod.window_flops(m, nnz) - mod.window_flops(m, [0, 0])
    assert hop_roofline.hop_flops(m, cells, nnz, 3) == pytest.approx(3 * hops)


def test_hop_bytes_and_the_least_time_by_hand():
    m = {"num_nodes": 10, "max_diffusion_step": 2}
    cells = [(4, 3)]                 # C = 4, three calls per window
    hops = 2 * 3 * 2                 # two convolutions, K = 2 each
    want = 2 * hops * ((8 * 30 + 8 * 5 * 10 * 4) + (8 * 20 + 8 * 5 * 10 * 4))
    assert hop_roofline.hop_bytes(m, cells, [30, 20], 5) == want
    least = hop_roofline.least_ms(m, cells, [30, 20], 5, "TPU v5 lite")
    assert least["bytes_ms"] == pytest.approx(1e3 * want / 819e9)
    assert least["least_ms"] == max(least["flops_ms"], least["bytes_ms"])
    assert least["bound"] == "bytes"
    with pytest.raises(KeyError, match="no peak"):
        hop_roofline.peak_hbm_bytes_per_s("TPU v9 imaginary")


SCOPED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "testdata", "one_chip_scoped.xplane.pb")


def _scoped():
    with open(SCOPED.replace(".xplane.pb", ".json")) as f:
        meta = json.load(f)
    return T.load(SCOPED), meta["steps"], meta["op_names"]


def _paint(ops, runs, w0, w1):
    """Brute force on the window's elementary segments (between any two event
    edges): the layer of the innermost op over each, -1 where no step op
    runs; and the segments' edges."""
    edges = np.unique(np.array([w0, w1] + [x for _, s, e in ops for x in (s, e)]
                               + [x for r in runs for x in r], np.float64))
    edges = edges[(edges >= w0) & (edges <= w1)]
    paint = np.full(len(edges) - 1, -1)
    busy = np.zeros(len(edges) - 1, bool)
    codes = {n: i for i, n in enumerate((*L.LAYERS, L.OTHER))}
    # Parents first, so that a nested op paints over its parent.
    for layer, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        a, b = np.searchsorted(edges, [s, e])
        busy[a:b] = True
        if any(r0 <= s and e <= r1 for r0, r1 in runs):
            paint[a:b] = codes[layer]
    return edges, paint, busy


def test_scoped_trace_split_against_brute_force():
    pd, steps, names = _scoped()
    r = L.reduce(pd, steps=steps, op_names=names)
    spans = L._window_thread(pd)
    (w0, w1), = [(s, e) for n, s, e in spans if n == T.WINDOW_SPAN]
    plane, = [p for p in pd.planes if T._DEVICE_RE.match(p.name)]
    lines = {line.name: list(line.events) for line in plane.lines}
    ops = [(L.layer_of(names.get(T.op_name(ev.name), "")), ev.start_ns,
            ev.start_ns + ev.duration_ns) for ev in lines[T.OPS_LINE]]
    runs = [(ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in lines[L.MODULES_LINE] if ev.name.startswith(L.STEP_MODULE)]
    edges, paint, busy = _paint(ops, runs, w0, w1)
    width = np.diff(edges)
    to_ms = 1e6 * steps
    assert r["step_module_ms_per_step"] == pytest.approx(
        width[paint >= 0].sum() / to_ms, rel=1e-6)
    assert sum(r["layers_ms_per_step"].values()) == pytest.approx(
        r["step_module_ms_per_step"], rel=1e-6)
    for i, layer in enumerate((*L.LAYERS, L.OTHER)):
        want = width[paint == i].sum() / to_ms
        assert r["layers_ms_per_step"].get(layer, 0.0) == pytest.approx(
            want, rel=1e-6, abs=1e-9), layer
    assert set(L.LAYERS) <= set(r["layers_ms_per_step"])
    # The longest gap, named by the innermost span open on the window's
    # thread at its midpoint: a program span.
    idle = np.flatnonzero(np.diff(np.r_[0, (~busy).astype(np.int8), 0]))
    gaps = [(edges[a], edges[b]) for a, b in zip(idle[::2], idle[1::2])]
    s, e = max(gaps, key=lambda g: g[1] - g[0])
    name, secs = r["idle_gaps"][0]
    assert secs == pytest.approx((e - s) / 1e9, rel=1e-6)
    mid = (s + e) / 2
    assert name == min((b - a, n) for n, a, b in spans if a <= mid <= b)[1]
    assert name in L.HOST_SPANS
    assert all(re.fullmatch(r"[\w.\-]+ \[[\w.]+\]", n) for n, _ in r["device_ops"])
    feed = [b - a for n, a, b in spans if n == L.FEED_SPAN]
    assert len(feed) == steps
    assert r["feed_ms_per_step"] == pytest.approx(sum(feed) / 1e6 / steps)
