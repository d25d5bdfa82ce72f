"""The benchmark's command.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on and prints one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, ``readings`` (every number the correctness
check read) and last ``checks``: each number it compared, with its limit
(also the last lines of standard error). It exits non-zero, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for. JAX's compilation cache is kept in
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set.
"""

import time

T_PROCESS = time.monotonic()        # setup_s starts here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scored-out", default=None,
                    help="also write every scored token's reference gap and "
                         "router margin, request by request, to this JSON "
                         "file (for setting limits; not part of a result)")
    return ap.parse_args(argv)


def enable_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def chips_or_exit(n: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        sys.exit(f"bench: needs {n} TPU chip(s), JAX found {len(devs)} "
                 f"{devs[0].platform} device(s) ({devs[0].device_kind})")


def finite(x):
    """JSON has no infinity: a non-finite number prints as null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    return x


def main(argv=None) -> int:
    args = parse(argv)
    from bench import common
    bm = common.benchmark()
    w = common.workload(args.workload)
    c = common.config(w["config"])
    enable_cache()
    chips_or_exit(w["chips"])
    from bench import harness
    trace = bool(args.trace)
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bm[kind]
               if args.workload in m.get("workloads", [args.workload])]
    result = harness.run_cell(
        c, common.mix(w["traffic"]), common.cell(w["name"]),
        args.seed, args.seconds, trace, metrics, t_process=T_PROCESS,
        trace_dir=str(ROOT / ".bench_trace" / w["name"]),
        keep_scored=args.scored_out is not None)
    if args.scored_out is not None:
        with open(args.scored_out, "w") as f:
            json.dump(finite({"workload": w["name"], "seed": args.seed,
                              "readings": result["readings"],
                              "scored": result.pop("scored")}), f)
    for name, value in result["readings"].items():
        limit = result["checks"].get(name, {}).get("limit", "none: reported only")
        print(f"[bench] check {name}: {value} (limit {limit})",
              file=sys.stderr, flush=True)
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
