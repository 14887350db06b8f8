"""One traced run of a cell with the device time named: the run that
`run.py --trace 1` makes, plus the scope table (`scope_reduce.py`) and the
program's host spans, read from the trace before it is deleted.

    python3 benchmark/scope_table.py --workload <name> --seed <n> --seconds <s>
        [--benchmark <file like BENCHMARK.json>] [--set key=value ...]

`harness.run_cell` hands its readers only the reduced trace and deletes the
trace directory first, so the per-layer metrics that read scopes
(`scope_time_ms`, `scope_share_pct`, `host_span_ms_per`) find nothing to
read there. This tool drives the same driver through the same steps with a
`Profile` that also reduces scopes, and prints one JSON line: every metric
file under `layer_metrics/` that finds something to read (those of
BENCHMARK.json and those that no cell reports yet), the scope table per
program in ms per execution, the idle gaps by span, and the seconds the
scope reduction took. `--set` overrides keys of the traffic file for this
run (`--set clients=8`), as `sweep.py --vary` does. A tool for the chip,
like `sweep.py`: runs of the benchmark never call it.
"""
import argparse
import glob
import importlib
import json
import os
import sys
import time

T_START = time.monotonic()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def scoped_profile(harness):
    from benchmark import scope_reduce, trace_reduce

    class ScopedProfile(harness.Profile):
        """`Profile.reduce` with two more keys: `scopes` (scope_reduce's
        table) and `host_spans` ({name: [count, seconds]})."""

        def reduce(self):
            path = trace_reduce.find_xplane(self.dir)
            size = os.path.getsize(path)
            t0 = time.monotonic()
            scopes = scope_reduce.reduce_scopes(
                scope_reduce.read_xplane_scoped(path))
            took = time.monotonic() - t0
            spans = {}
            for name, _, dur in trace_reduce.read_xplane(path)["host"]:
                n, s = spans.get(name, (0, 0.0))
                spans[name] = [n + 1, s + dur]
            out = super().reduce()
            out.update(scopes=scopes, host_spans=spans,
                       scope_reduce_s=took, xplane_bytes=size)
            return out

    return ScopedProfile


def per_execution(scopes: dict) -> dict:
    """The table in ms per execution, with each scope's share of the
    program's operation time and its achieved FLOP/s and bytes/s."""
    out = {}
    for prog, p in scopes["programs"].items():
        n = max(p["executions"], 1)
        rows = {}
        for scope, r in sorted(p["scopes"].items(),
                               key=lambda kv: -kv[1]["device_s"]):
            s = r["device_s"]
            rows[scope] = {
                "ms": 1e3 * s / n, "share_pct": 100.0 * s / p["op_s"]
                if p["op_s"] else None,
                "forward_ms": 1e3 * r["forward_s"] / n,
                "backward_ms": 1e3 * r["backward_s"] / n,
                "tflop_per_s": r["flops"] / s / 1e12 if s else None,
                "gbyte_per_s": r["bytes"] / s / 1e9 if s else None,
                "events": r["events"] / n}
        out[prog] = {"executions": p["executions"],
                     "op_ms": 1e3 * p["op_s"] / n, "scopes": rows,
                     "uncovered_ms": {k: 1e3 * v / n for k, v in sorted(
                         p["uncovered"].items(), key=lambda kv: -kv[1])},
                     "uncovered_ops_ms": [[k, 1e3 * v / n]
                                          for k, v in p["uncovered_ops"]]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--benchmark", default=None,
                    help="a file like BENCHMARK.json (default: the root's)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="key=value", help="override a traffic key")
    args = ap.parse_args(argv)

    from benchmark import harness
    harness.place_caches()
    bench = (harness.load_json(args.benchmark) if args.benchmark
             else harness.load_benchmark())
    cell = harness.load_cell(bench, args.workload)
    for kv in args.set:
        k, v = kv.split("=", 1)
        cell["traffic"][k] = json.loads(v)
    try:
        device = harness.require_chip(cell["chips"])
    except harness.NoChip as e:
        print(f"scope_table: {e}; nothing was run", file=sys.stderr)
        return 2
    driver = harness.driver_for(cell["config"]["kind"])
    profile = scoped_profile(harness)(args.workload)
    session = driver.setup(cell, args.seed)
    compiles0 = harness.compile_count()
    ready_at = time.monotonic()
    window = driver.measure(session, args.seconds, profile)
    setup_s = session.get("window_starts_at", ready_at) - T_START
    profile.stop()
    compiled = harness.compile_count() - compiles0
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    driver.release(session)
    reduced = profile.reduce()
    e2e = dict(window["end_to_end"], setup_s=setup_s)
    ctx = {"cell": cell, "seconds": args.seconds, "end_to_end": e2e,
           "trace": reduced, "window": window,
           "peak": harness.peak_for(device["kind"]), "chips": cell["chips"]}
    metrics = {}
    for path in sorted(glob.glob(os.path.join(
            harness.HERE, "layer_metrics", "*.json"))):
        spec = harness.load_json(path)
        reader = importlib.import_module(
            "benchmark.layer_metrics." + spec["reader"])
        try:
            value = reader.read(ctx, spec.get("params", {}))
        except (KeyError, TypeError):   # a reader of another kind of cell
            value = None
        if value is not None:
            metrics[spec["name"]] = float(value)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": device,
        "end_to_end": e2e, "compiles_in_window": compiled,
        "attempted": window["attempted"], "failed": window["failed"],
        "metrics": metrics, "busy_s": reduced["busy_s"],
        "window_s": reduced["window_s"],
        "programs_ms": {k: [len(v), 1e3 * sum(v) / len(v)]
                        for k, v in reduced["programs"].items()},
        "scopes": per_execution(reduced["scopes"]),
        "host_spans": reduced["host_spans"],
        "idle_gaps": reduced["idle_gaps"],
        "scope_reduce_s": reduced["scope_reduce_s"],
        "xplane_bytes": reduced["xplane_bytes"],
        "notes": window.get("notes", {})}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
