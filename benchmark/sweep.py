"""Find the knee of an open-loop serving cell once, on the chip: one
process, one deployment, the cell's own mix at each of a few fixed rates.

    python3 benchmark/sweep.py --workload <name> --vary rate_per_s --values 1,2,3 --seconds 15 --seed 1 [--trace 1]

(`--vary clients` sweeps a closed loop's callers the same way.) Prints one
JSON line per value: arrivals and completions per second in the
window, the tails, and the engine's queue and active slots as the window
closed. The knee is the highest rate at which completions keep up with
arrivals and the queue does not grow. Sets nothing: the rate chosen goes
into the traffic file by hand, with this table in PERF.md.
"""
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
    ap.add_argument("--vary", default="rate_per_s")
    ap.add_argument("--values", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the end of each window and print the "
                         "programs' median device times")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from benchmark import harness
    harness.place_caches()
    cell = harness.load_cell(harness.load_benchmark(), args.workload)
    try:
        harness.require_chip(cell["chips"])
    except harness.NoChip as e:
        print(f"sweep: {e}; nothing was run", file=sys.stderr)
        return 2
    driver = harness.driver_for(cell["config"]["kind"])
    session = driver.setup(cell, args.seed)
    from benchmark.stats import stat
    for i, raw in enumerate(args.values.split(",")):
        value = float(raw) if "." in raw else int(raw)
        session["cell"] = dict(cell, traffic=dict(cell["traffic"],
                                                  **{args.vary: value}))
        session["seed"] = args.seed + i
        profile = harness.Profile(args.workload) if args.trace else None
        w = driver.measure(session, args.seconds, profile)
        n = w["notes"]
        traced = {}
        if profile is not None:
            r = profile.reduce()
            traced = {"busy_share": r["busy_s"] / max(r["window_s"], 1e-9),
                      "programs_ms": {k: [len(v), 1e3 * stat(v, "p50"),
                                          1e3 * sum(v)]
                                      for k, v in r["programs"].items()},
                      "device_ops": r["device_ops"][:6],
                      "idle_gaps": r["idle_gaps"][:4]}
        print(json.dumps({
            args.vary: value, **traced,
            "arrivals_per_s": n["requests_in_window"] / args.seconds,
            "completions_per_s": n["completed_in_window"] / args.seconds,
            "failed": n["failed"], "unfinished": n["unfinished"],
            "serve_tokens_per_s": w["end_to_end"]["serve_tokens_per_s"],
            "ttft_p50_ms": n["ttft_p50_ms"],
            "ttft_p95_ms": w["end_to_end"]["ttft_p95_ms"],
            "itl_p50_ms": n["itl_p50_ms"],
            "itl_p95_ms": w["end_to_end"]["itl_p95_ms"],
            "queued_at_close": w["gauges"].get("engine.queued"),
            "active_at_close": w["gauges"].get("engine.active_slots"),
            "loadgen_late_p95_ms": n["loadgen_late_p95_ms"]}), flush=True)
    driver.release(session)
    return 0


if __name__ == "__main__":
    sys.exit(main())
