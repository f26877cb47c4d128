"""The program's host spans, as the profiler records them around ``Engine.fit``.

Three steps of a tiny pipeline run under ``jax.profiler.trace`` on the CPU,
with the prefetcher (depth 2) and with the synchronous feed; the host plane's
events are counted by name and checked for nesting on the fitting thread.
"""
import glob

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import trace_names as N
from repro.core import Placement, WindowSpec
from repro.data import make_traffic_series
from repro.launch.mesh import make_host_mesh
from repro.optim import AdamConfig
from repro.pipeline import PipelineConfig, build_pipeline
from repro.train import TrainLoopConfig

NODES, ENTRIES, B, STEPS = 3, 120, 2, 3
SPEC = WindowSpec(horizon=2, input_len=2)


def _loss_fn(p, x, y):
    return jnp.mean((x[:, -1] * p["w"] - y[:, 0]) ** 2), {}


def _host_spans(path):
    """``[[(name, start, end), ...] per thread]`` of the program's spans."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.append([(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events if ev.name in N.HOST_SPANS])
    return out


def _fit_spans(tmp_path, *, depth, log_every=1, eval_fn=None, **loop):
    """The program's spans on each host thread of a traced 3-step ``fit``,
    the fitting thread's first."""
    pipe = build_pipeline(
        make_traffic_series(ENTRIES, NODES), SPEC, make_host_mesh(),
        _loss_fn, {"w": jnp.full((NODES, 2), 0.1, jnp.float32)},
        PipelineConfig(batch_per_rank=B, placement=Placement.REPLICATED,
                       world=1, seed=7, adam=AdamConfig(lr=1e-2),
                       loop=TrainLoopConfig(log_every=log_every,
                                            prefetch_depth=depth,
                                            max_steps=STEPS, **loop)))
    pipe.fit(eval_fn=eval_fn, resume=False)   # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path / "trace"), profiler_options=opts):
        pipe.fit(eval_fn=eval_fn, resume=False)
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    threads = _host_spans(path)
    main, = [t for t in threads if any(n == N.FIT_START for n, _, _ in t)]
    return [main] + [t for t in threads if t is not main]


def _count(spans):
    return {n: sum(1 for m, _, _ in spans if m == n) for n in N.HOST_SPANS}


@pytest.mark.parametrize("depth", [2, 0], ids=["prefetch", "sync"])
@pytest.mark.parametrize("log_every", [1, 2])
def test_fit_host_spans(tmp_path, depth, log_every):
    spans, *others = _fit_spans(tmp_path, depth=depth, log_every=log_every,
                                eval_every=0)
    count = _count(spans)
    assert count[N.FIT_START] == count[N.FIT_END] == 1
    assert count[N.STEP_DISPATCH] == count[N.FEED_NEXT] == STEPS
    assert count[N.LOOP_LOG_SYNC] == STEPS // log_every
    assert count[N.FEED_TRANSFER] == STEPS
    assert count[N.LOOP_CKPT] == count[N.LOOP_EVAL] == 0
    feeds = [(s, e) for n, s, e in spans if n == N.FEED_NEXT]
    for n, s, e in spans:
        if n in (N.FEED_WAIT, N.FEED_TRANSFER):
            assert any(fs <= s and e <= fe for fs, fe in feeds), n
    (_, s0, e0), = [x for x in spans if x[0] == N.FIT_START]
    (_, s1, e1), = [x for x in spans if x[0] == N.FIT_END]
    steps = [(s, e) for n, s, e in spans if n == N.STEP_DISPATCH]
    assert e0 <= min(s for s, _ in feeds) and max(e for _, e in steps) <= s1
    built = [n for t in others for n, _, _ in t]
    if depth:
        assert count[N.FEED_WAIT] >= 1 and N.FEED_BUILD in built
    else:
        assert count[N.FEED_WAIT] == 0 and not built


def test_checkpoint_and_eval_spans_inside_the_fit(tmp_path):
    spans, *_ = _fit_spans(tmp_path, depth=2, ckpt_every=2,
                           ckpt_dir=str(tmp_path / "ckpt"),
                           eval_fn=lambda state: {"val_mae": 0.0})
    count = _count(spans)
    assert count[N.LOOP_CKPT] == 2      # step 2, and the final save
    assert count[N.LOOP_EVAL] == 1
    (_, s1, e1), = [x for x in spans if x[0] == N.FIT_END]
    final = max((x for x in spans if x[0] == N.LOOP_CKPT), key=lambda x: x[1])
    ev, = [x for x in spans if x[0] == N.LOOP_EVAL]
    assert all(s1 <= x[1] and x[2] <= e1 for x in (final, ev))
