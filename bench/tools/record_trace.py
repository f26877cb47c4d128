"""Records the small profiler trace ``bench/tests`` reduce, on the chip.

    python3 bench/tools/record_trace.py --workload <cell> --out <file.xplane.pb>

Runs the cell's configuration shrunk to 64 sensors, 2,920 steps and 8
windows per chip, with ``--trace 1``, and keeps the raw trace.  Prints the
run's result line.
"""
import argparse
import copy
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, spec
    w = spec.workload(args.workload)
    cfg = copy.deepcopy(spec.config(w["config"]))
    cfg["model"]["num_nodes"] = 64
    cfg["series"].update(entries=2920, chunk=730)
    traffic = dict(spec.traffic(w["traffic"]), global_batch=8 * w["chips"])
    result = harness.run(args.workload, args.seed, 0.5, True, t_start=T_START,
                         cfg=cfg, traffic=traffic,
                         keep_trace=os.path.abspath(args.out))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
