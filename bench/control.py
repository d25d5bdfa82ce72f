"""Readings that set a cell's correctness limit, many seeds in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--seconds 10]

For each seed it runs the cell as ``bench/run.py`` does (the cell's mix at
its rate) and prints every number the check reads: with the program as the
configuration states it (a number's lower reading is its largest over
these seeds), then with the control, the program's own lower-precision
path switched on (Tensorizer W8A8 weights and activations; the upper
reading is the smallest over its seeds). One line of JSON per run,
prefixed ``[control]``, with the cell's end-to-end metrics (``setup_s``
counts from the process start, so only the first run's is a set-up) and
every scored token's gap and router margin, request by request (a
request's first row is its prefill's token). Needs the chip the cell asks
for.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    from bench import common, run
    w = common.workload(args.workload)
    c = common.config(w["config"])
    run.enable_cache()
    run.chips_or_exit(w["chips"])
    from bench import harness
    mix, cell = common.mix(w["traffic"]), common.cell(w["name"])
    metrics = [m for m in common.benchmark()["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
    plan = [("program", "off", s) for s in args.seeds.split(",") if s] + \
        [("control", "serve", s) for s in args.control_seeds.split(",") if s]
    for kind, quantize, seed in plan:
        t0 = time.monotonic()
        res = harness.run_cell(c, mix, cell, int(seed), args.seconds, False,
                               metrics, t_process=T_PROCESS,
                               quantize=quantize, keep_scored=True)
        print("[control] " + json.dumps(run.finite({
            "workload": w["name"], "kind": kind, "seed": int(seed),
            "correct": res["correct"], "failed": res["failed"],
            "attempted": res["attempted"], "readings": res["readings"],
            "checks": res["checks"], "metrics": res["metrics"],
            "scored": res["scored"],
            "wall_s": time.monotonic() - t0})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
