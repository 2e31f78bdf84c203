"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload sr1.stream_b8 --seed 7 \
        --seconds 30 --trace 0

The cell, its configuration and its traffic mix are looked up by name in
``BENCHMARK.json`` at the root of the checkout; everything that belongs to
one configuration, mix or metric is a file of its own under this
directory (see ``harness.py``). The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` a ``breakdown``), then ``checks``:
each number compared with the plain reference, beside its limit. The same
comparisons end standard error.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero before any work and prints no result.
"""
import time

T_START = time.perf_counter()   # set-up is timed from process start

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
