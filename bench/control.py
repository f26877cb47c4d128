"""Readings the limits of ``bench/checks/<cell>.json`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3,... \
        [--control-seeds 1,2,3]

For every seed it builds the cell at its own size, runs the warm-up steps
through ``Engine.fit`` exactly as a benchmark run does, and compares them with
the plain reference, at the block and matmul precision the cell's checks file
gives (``harness.reference_args``): the program's readings (the lower end of
each limit).
For the control seeds it also reads, against the same reference:

- ``control``: the reference put in the program's place, computed in
  bfloat16 (the precision below the configuration's float32);
- ``half_batch``: the reference on the first half of each batch;
- ``one_shard``: on four chips, the reference on the first chip's share of
  each batch, what a step that left out the gradient all-reduce would apply;
- ``late_window``: the reference on windows one step late, an answer
  altered where it is produced (the gather).

A state left unchanged reads 1 on the gradient and change numbers by their
definition and needs no run.  One JSON line per seed and reading; the
benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _as_program(ref: dict) -> dict:
    return {"losses": ref["losses"], "grad1": ref["grad1"],
            "p0": ref["params0"], "p_end": ref["params"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import gc
    import jax
    from bench import harness, spec
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    w = spec.workload(args.workload)
    harness._require_chips(w["chips"])
    cfg, traffic = spec.config(w["config"]), spec.traffic(w["traffic"])
    checks = spec.checks(args.workload)
    ref_args = harness.reference_args(checks)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        s = harness.setup(cfg, traffic, seed, args.workload)
        starts = s.recorder.starts
        series = s.pipe.dataset.series
        batches = harness.reference_batches(series, starts, cfg["model"])
        late = (harness.reference_batches(series, [x + 1 for x in starts],
                                          cfg["model"])
                if seed in controls else None)
        prog, parts = s.prog, s.parts
        del s, series
        gc.collect()
        ref = harness.follow_reference(cfg, parts, batches, **ref_args)
        out = {"seed": seed, "reading": "program",
               **harness.readings(cfg, prog, ref),
               "losses": prog["losses"], "ref_losses": ref["losses"]}
        print(json.dumps(out), flush=True)
        if seed in controls:
            b = traffic["global_batch"]
            variants = {
                "control": dict(dtype="bfloat16", precision="default"),
                "half_batch": dict(rows=slice(0, b // 2)),
                "late_window": dict(batches=late),
            }
            if w["chips"] > 1:
                variants["one_shard"] = dict(rows=slice(0, b // w["chips"]))
            for name, kw in variants.items():
                got = harness.follow_reference(
                    cfg, parts, kw.pop("batches", batches), **{**ref_args, **kw})
                print(json.dumps({"seed": seed, "reading": name,
                                  **harness.readings(cfg, _as_program(got), ref)}),
                      flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s", file=sys.stderr,
              flush=True)
        del ref, batches, late, parts, prog
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
