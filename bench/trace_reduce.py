"""Reduces a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData``.  A device is a plane named
``/device:<kind>:<n>`` (the host's own CPU plane excepted); its operations
are the events of its ``XLA Ops`` line, named by the HLO instruction they
run (``%fusion.12 = f32[...] fusion(...)``: the name is ``fusion.12``), and
nested where an instruction (a ``while``) runs others.  Asynchronous
operations (``all-gather-start`` to its ``-done``) are the events of its
``Async XLA Ops`` line.  The harness's host spans
(``jax.profiler.TraceAnnotation``) are events of the host plane on the same
clock.

- busy: the union of a device's ``XLA Ops`` intervals inside the traced
  window (the harness's ``bench.fit`` span), averaged over devices; idle
  share = 1 - busy / window;
- device operations: each operation name's self time (its duration less
  that of the operations nested in it), averaged over devices, longest first;
- collectives: on the first device, the union of the intervals, on either
  line, of operations whose name begins with a collective's HLO opcode, per
  step;
- idle gaps: on the first device, each interval of the window in which it
  ran nothing, named by the innermost harness span open on the host at its
  midpoint.
"""
from __future__ import annotations

import re

WINDOW_SPAN = "bench.fit"
HOST_SPANS = ("bench.feed", "bench.dispatch", "bench.loop", "bench.log_sync")
COLLECTIVE_RE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_DEVICE_RE = re.compile(r"^/device:(?!CPU)[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union(intervals):
    """Merge ``[(start, end), ...]`` into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _host_events(pd, names):
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _device_ops(pd):
    """{device plane name: {line name: [(op name, start, end), ...]}}."""
    out = {}
    for plane in pd.planes:
        if not _DEVICE_RE.match(plane.name):
            continue
        out[plane.name] = {
            line.name: [(op_name(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in line.events]
            for line in plane.lines if line.name in (OPS_LINE, ASYNC_LINE)}
    return out


def self_times(ops):
    """{op name: total self time} of possibly nested ``(name, start, end)``."""
    out, stack = {}, []   # open ops: [name, start, end, time of children]
    def close(item):
        name, s, e, child = item
        out[name] = out.get(name, 0) + (e - s) - child
        if stack:
            stack[-1][3] += e - s
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return out


def _label(t, spans):
    """The innermost (shortest) host span that holds time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host.other"


def reduce(pd, *, steps: int, top: int = 10) -> dict:
    """The numbers of one traced window of ``steps`` training steps."""
    windows = [(s, e) for _, s, e in _host_events(pd, {WINDOW_SPAN})]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = windows[0]
    spans = list(_host_events(pd, {WINDOW_SPAN, *HOST_SPANS}))
    devices = _device_ops(pd)
    if not devices or not any(d.get(OPS_LINE) for d in devices.values()):
        raise ValueError("no device plane with an 'XLA Ops' line in the trace")

    def clip(ops):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                if e > w0 and s < w1]

    busy_ns, coll_ns, per_op, gaps = 0, 0, {}, None
    for name in sorted(devices):
        lines = devices[name]
        ops = clip(lines.get(OPS_LINE, []))
        merged = union([(s, e) for _, s, e in ops])
        busy_ns += sum(e - s for s, e in merged)
        for n, t in self_times(ops).items():
            per_op[n] = per_op.get(n, 0) + t
        if gaps is None:
            coll = [(s, e) for n, s, e in ops + clip(lines.get(ASYNC_LINE, []))
                    if COLLECTIVE_RE.match(n)]
            coll_ns = sum(e - s for s, e in union(coll))
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    window_s = (w1 - w0) / 1e9
    busy_s = busy_ns / n_dev / 1e9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "devices": n_dev,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "collective_s_per_step": coll_ns / 1e9 / steps,
        "has_collectives": coll_ns > 0,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in ops],
        "idle_gaps": [[_label((s + e) / 2, spans), (e - s) / 1e9]
                      for s, e in gaps],
    }
