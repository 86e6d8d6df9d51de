"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's system on the chips JAX finds (exits non-zero, printing
no result, without a TPU or with fewer chips than the cell needs), warms
it, measures an open-loop window of ``--seconds`` and checks the served
tokens against the float32 reference. The last line of standard output is
the result object; the last lines of standard error give each number
compared beside its limit. ``--trace 1`` reports the per-layer metrics
from a profiler trace of the window instead of the end-to-end ones.
``--control 1`` also reads the float8 control on the same tokens and
decides it by the same limits, as ``control.correct`` (for setting the
limits; the benchmark's own runs leave it off).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="arrival rate in requests/s in place of the traffic "
                    "file's (for finding the knee; never in a cell's run)")
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    from bench import harness

    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START,
                         control=bool(args.control),
                         rate=args.rate)
    harness.report(result)


if __name__ == "__main__":
    main()
