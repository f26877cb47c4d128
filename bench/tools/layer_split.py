"""Per-layer device time of one cell's train step, on the chip.

    python3 bench/tools/layer_split.py --workload <cell> [--seed <n>]
        [--steps <n>] [--small] [--out <file.xplane.pb>] [--text-out <file>]

Builds the cell and warms it up as the benchmark does (``harness.setup``),
then runs ``fit`` for ``--steps`` steps twice: without the profiler, then
under it inside the window span.  Compiles the step again (from the cache)
for its instruction names, and prints one JSON line: windows/s of both
``fit`` s, the step's ``memory_analysis()``, a digest of its compiled text
without metadata, ``trace_reduce``'s numbers, ``trace_layers``' per-layer
split and the hops' least time (``hop_roofline``).

``--small`` shrinks the configuration as ``record_trace.py`` does (64
sensors, 2,920 steps, 8 windows per chip); ``--out`` keeps the raw trace and
writes the step's ``{instruction: op_name}`` beside it (``.json``);
``--text-out`` writes the compiled text without metadata.
"""
import argparse
import copy
import hashlib
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_TABLES_RE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*", re.M)


def strip_metadata(hlo_text: str) -> str:
    """A compiled program's text without what names and locates its source:
    each instruction's ``metadata={...}`` and the tables of files, functions
    and stack frames after the module's header."""
    return re.sub(r", metadata=\{[^}]*\}", "", _TABLES_RE.sub("", hlo_text))


def _traced_fit(pipe, steps: int, keep: str | None):
    """``(profile, seconds)`` of ``steps`` steps of ``fit`` under the
    profiler, inside the window span."""
    import jax
    from jax.profiler import ProfileData

    from bench import harness, trace_reduce
    out_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(out_dir, profiler_options=opts):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                t0 = time.perf_counter()
                state, _ = harness._fit(pipe, steps)
                jax.block_until_ready(state)
                seconds = time.perf_counter() - t0
        path, = [os.path.join(d, f) for d, _, fs in os.walk(out_dir)
                 for f in fs if f.endswith(".xplane.pb")]
        if keep:
            shutil.copy(path, keep)
        with open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read()), seconds
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def split(cell: str, cfg: dict, traffic: dict, seed: int, *,
          steps: int | None = None, chips: int = 1, keep: str | None = None,
          text_out: str | None = None) -> dict:
    """One cell's per-layer split; ``keep`` and ``text_out`` as ``--out``
    and ``--text-out``."""
    import jax

    from bench import harness, hop_roofline, trace_layers, trace_reduce
    from bench.reference import train as ref_train
    s = harness.setup(cfg, traffic, seed, cell)
    pipe, gb = s.pipe, traffic["global_batch"]
    steps = steps or min(max(3, math.ceil(harness.TRACE_SECONDS / s.step_s)), 64)
    t0 = time.perf_counter()
    state, _ = harness._fit(pipe, steps)
    jax.block_until_ready(state)
    plain_s = time.perf_counter() - t0
    profile, traced_s = _traced_fit(pipe, steps, keep)
    batch = pipe.dataplane.batch_of_starts(pipe.dataplane.epoch_grid(0)[0])
    compiled = pipe.train_step.lower(state, batch).compile()
    text = compiled.as_text()
    names = trace_layers.op_names(text)
    if keep:
        with open(re.sub(r"\.xplane\.pb$", "", keep) + ".json", "w") as f:
            json.dump({"steps": steps, "op_names": names}, f)
    bare = strip_metadata(text)
    if text_out:
        with open(text_out, "w") as f:
            f.write(bare)
    mem = compiled.memory_analysis()
    kind = jax.devices()[0].device_kind
    m = cfg["model"]
    cells = ref_train.model(cfg["family"]).cells(m)
    return {
        "cell": cell, "seed": seed, "steps": steps, "device": kind,
        "windows_per_s": {"untraced": steps * gb / plain_s,
                          "traced": steps * gb / traced_s},
        "memory_analysis": {k: int(getattr(mem, f"{k}_size_in_bytes"))
                            for k in ("argument", "output", "alias", "temp",
                                      "generated_code")},
        "text_sha256": hashlib.sha256(bare.encode()).hexdigest(),
        "trace": trace_reduce.reduce(profile, steps=steps),
        "layers": trace_layers.reduce(profile, steps=steps, op_names=names),
        "hops": hop_roofline.least_ms(m, cells, s.parts["nnz"], gb // chips,
                                      kind),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_718_281_828)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--text-out", default=None)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, spec
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    w = spec.workload(args.workload)
    harness._require_chips(w["chips"])
    cfg = copy.deepcopy(spec.config(w["config"]))
    traffic = spec.traffic(w["traffic"])
    if args.small:
        cfg["model"]["num_nodes"] = 64
        cfg["series"].update(entries=2920, chunk=730)
        traffic = dict(traffic, global_batch=8 * w["chips"])
    out = split(args.workload, cfg, traffic, args.seed, steps=args.steps,
                chips=w["chips"], keep=args.out and os.path.abspath(args.out),
                text_out=args.text_out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
