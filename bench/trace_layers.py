"""Per-layer device time of the train step, and the program's host spans,
read from a profiler trace (``.xplane.pb``).

The profiler's ``XLA Ops`` events carry only the instruction they run.  The
compiled step's HLO text carries each instruction's ``op_name``: the path of
JAX transforms and ``jax.named_scope`` s it was traced under (``jvp(...)``
forward, ``transpose(jvp(...))`` backward, ``while/body`` inside a scan; a
fusion carries its root's).  Joining the two gives every device operation of
the train step a layer:

- layers: the self time of each operation that runs inside a train-step
  module event (``XLA Modules`` line, ``jit_step(<fingerprint>)``) within the
  window span, given to the innermost of ``LAYERS`` in its instruction's
  ``op_name``, else to ``OTHER``; ms per step, averaged over devices, with the
  step module's whole self time, which they sum to;
- device operations: ``trace_reduce``'s list, each name followed by its
  layer (``concatenate.42 [dcgru]``);
- idle gaps: ``trace_reduce``'s, named by the innermost span open at the
  midpoint on the host thread that holds the window span, among the window
  span and the program's ``HOST_SPANS``;
- feed: the host's ``feed.next`` time per step.

The program's names (``repro/trace_names.py``) are kept here as literals, so
that this reads a program without them: its operations all go to ``OTHER``
and ``layers`` holds no layer.  A test holds the two lists equal.
"""
from __future__ import annotations

import re

from bench import trace_reduce as T

HOST_SPANS = ("fit.start", "fit.end", "step.dispatch", "feed.next",
              "feed.wait", "feed.transfer", "feed.build", "loop.log_sync",
              "loop.ckpt", "loop.health", "loop.eval")
LAYERS = ("gather", "rnn.scan", "dcgru", "dconv.hop", "optimizer")
OTHER = "other"
FEED_SPAN = "feed.next"
STEP_MODULE = "jit_step("
MODULES_LINE = "XLA Modules"

_OP_NAME_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*?op_name="([^"]*)"')
_WRAPPED_RE = re.compile(r"^[\w-]+\((.*)\)$")


def op_names(hlo_text: str) -> dict[str, str]:
    """``{instruction name: op_name}`` of every instruction of a compiled
    program's text whose metadata has an ``op_name``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME_RE.match(line)
        if m is not None:
            out[m.group(1)] = m.group(2)
    return out


def layer_of(path: str) -> str:
    """The innermost of ``LAYERS`` among the scopes of an ``op_name`` path,
    else ``OTHER``.  The path's last part is the primitive (``gather`` is one
    too); a scope may appear wrapped in transforms:
    ``transpose(jvp(rnn.scan))``."""
    for part in reversed(path.split("/")[:-1]):
        while (m := _WRAPPED_RE.match(part)) is not None:
            part = m.group(1)
        if part in LAYERS:
            return part
    return OTHER


def _window_thread(pd):
    """``[(name, start, end), ...]`` of the window span and the program's
    spans on the host thread that holds the window span."""
    names = {T.WINDOW_SPAN, *HOST_SPANS}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in line.events if ev.name in names]
            if any(n == T.WINDOW_SPAN for n, _, _ in spans):
                return spans
    raise ValueError(f"no {T.WINDOW_SPAN!r} span in the trace")


def _step_runs(pd, w0: int, w1: int):
    """{device plane name: ``(ops, runs)``}: its ``XLA Ops`` and its train-step
    module runs, clipped to the window."""
    out = {}
    for plane in pd.planes:
        if not T._DEVICE_RE.match(plane.name):
            continue
        ops, runs = [], []
        for line in plane.lines:
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                if line.name == T.OPS_LINE:
                    ops.append((T.op_name(ev.name), s, e))
                elif line.name == MODULES_LINE and ev.name.startswith(STEP_MODULE):
                    runs.append((s, e))
        out[plane.name] = (ops, runs)
    return out


def in_runs(ops, runs):
    """The ``(name, start, end)`` ops that lie inside one of the ``(start,
    end)`` runs."""
    out, runs, i = [], sorted(runs), 0
    for op in sorted(ops, key=lambda o: o[1]):
        while i < len(runs) and runs[i][1] < op[1]:
            i += 1
        if i < len(runs) and runs[i][0] <= op[1] and op[2] <= runs[i][1]:
            out.append(op)
    return out


def reduce(pd, *, steps: int, op_names: dict[str, str], top: int = 10) -> dict:
    """The per-layer numbers of one traced window of ``steps`` train steps,
    given the step's ``{instruction: op_name}``."""
    base = T.reduce(pd, steps=steps, top=top)
    spans = _window_thread(pd)
    (w0, w1), = [(s, e) for n, s, e in spans if n == T.WINDOW_SPAN]
    devices = _step_runs(pd, w0, w1)
    per_op = {}
    for ops, runs in devices.values():
        for n, t in T.self_times(in_runs(ops, runs)).items():
            per_op[n] = per_op.get(n, 0) + t
    layer = lambda n: layer_of(op_names[n]) if n in op_names else OTHER
    to_ms = 1e6 * len(devices) * steps   # ns over devices -> ms per step
    layers = {}
    for n, t in per_op.items():
        layers[layer(n)] = layers.get(layer(n), 0) + t / to_ms
    feed = [e - s for n, s, e in spans if n == FEED_SPAN and w0 <= s < w1]
    gaps = _gaps(pd, w0, w1)
    return {
        "layers_ms_per_step": layers,
        "step_module_ms_per_step": sum(per_op.values()) / to_ms,
        "device_ops": [[f"{n} [{layer(n)}]", t] for n, t in base["device_ops"]],
        "idle_gaps": [[T._label((s + e) / 2, spans), (e - s) / 1e9]
                      for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]],
        "feed_ms_per_step": sum(feed) / 1e6 / steps if feed else None,
    }


def _gaps(pd, w0: int, w1: int):
    """The first device's idle intervals inside the window, as
    ``trace_reduce`` finds them."""
    devices = T._device_ops(pd)
    ops = [(max(s, w0), min(e, w1))
           for _, s, e in devices[min(devices)].get(T.OPS_LINE, [])
           if e > w0 and s < w1]
    edges = [w0] + [x for iv in T.union(ops) for x in iv] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
