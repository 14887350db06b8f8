"""Run one cell of BENCHMARK.json once, in this process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    (or: python3 -m benchmark.run ...)

Loads the cell's files, warms every shape it will use (set-up), measures
for `--seconds`, compares what the timed path produced with the plain
reference, and prints one JSON object as the last line of standard output.
It fails, and does not fall back, when jax finds no TPU or another number
of chips than the cell asks for: exit code 2 and no result line.
"""
import time

T_START = time.monotonic()      # set-up counts from the process's start

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness
    harness.place_caches()          # before jax is imported
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"benchmark: {e}; nothing was run", file=sys.stderr)
        return 2
    except harness.Unsteady as e:
        print(f"benchmark: {e}; no result", file=sys.stderr)
        return 3
    harness.emit(out["result"], out["checks"], out["notes"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
