"""Read, on the chip, what the limits of `correct` are set from: for each
seed the numbers a sound run of the program gives against the reference
(the lower readings), what the control gives — the reference in the
program's place, computed in the nearest precision below the one the
configuration states — and, for a training cell, what each planted fault
gives (the upper readings). One process, many seeds:

    python3 benchmark/readings.py --workload <name> --seeds 1,2,3 --seconds 8 [--faults 3]

Prints one JSON line per seed and a summary line; sets nothing. The
benchmark's own runs never call this.
"""
import time

T_START = time.monotonic()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--faults", type=int, default=3,
                    help="plant the faults on this many of the seeds")
    args = ap.parse_args(argv)

    from benchmark import harness
    harness.place_caches()
    bench = harness.load_benchmark()
    cell = harness.load_cell(bench, args.workload)
    try:
        harness.require_chip(cell["chips"])
    except harness.NoChip as e:
        print(f"readings: {e}; nothing was run", file=sys.stderr)
        return 2
    driver = harness.driver_for(cell["config"]["kind"])
    summary = {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        session = driver.setup(cell, seed)
        window = driver.measure(session, args.seconds, None)
        driver.release(session)
        got = driver.readings(session, window, faults=i < args.faults)
        for group, numbers in got.items():
            for name, value in numbers.items():
                summary.setdefault(group, {}).setdefault(name, []).append(
                    value)
        print(json.dumps({"seed": seed, "seconds": time.monotonic() - t,
                          "end_to_end": window["end_to_end"], **got}),
              flush=True)
    spread = {g: {n: {"min": min(v), "max": max(v), "n": len(v)}
                  for n, v in numbers.items()}
              for g, numbers in summary.items()}
    print(json.dumps({"summary": spread}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
