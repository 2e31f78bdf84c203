"""Readings that the limits of ``correct`` are set from: for each seed,
one short window of a cell at its own size, then each compared number as
the program reads it and as the control reads it (the plain reference
in the program's place, one precision step below the configuration's).
All seeds run in this one process, so the programs compile once.

    python3 benchmarks/chip/calibrate.py --workload sr1.batch_b256 \
        --seeds 1,2,3 --seconds 5

Each seed prints one JSON line; the last line sums up, per number, the
program's largest reading and the control's smallest. Not part of a
benchmark run. Needs a TPU, like run.py.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    worst: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        got = harness.readings(cell, seed, args.seconds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "seconds": time.perf_counter() - t0,
                          "readings": got}), flush=True)
        for name, (prog, ctl) in got.items():
            lo, hi = worst.get(name, (0.0, float("inf")))
            worst[name] = (max(lo, prog), min(hi, ctl))
    print(json.dumps({"workload": cell.name, "program_max_control_min":
                      worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
