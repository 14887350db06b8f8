"""What every cell's run shares: finding the cell's files by name, the
look for the chip, the profiler window, per-layer readers, and the one
result line. Nothing here knows a cell, a configuration or a metric by
name: those are files under `configs/`, `traffic/` and `layer_metrics/`,
found from the cell's entry in `BENCHMARK.json`.
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time
from typing import List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TMP_DIR = os.path.join(ROOT, ".bench_tmp")


class NoChip(RuntimeError):
    """The machine does not hold what the cell asks for."""


class Unsteady(RuntimeError):
    """Something compiled inside the measured window."""


def place_caches():
    """Before jax is imported: its persistent cache at a fixed path inside
    the checkout (the path is part of the cache key), whatever the machine
    had set, and every compile cached however short. The program's
    executable store goes beside it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ["DL4J_TPU_CACHE_DIR"] = os.path.join(CACHE_DIR, "dl4j-store")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def load_cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration and traffic files read:
    `{"name", "chips", "config": {...}, "traffic": {...}}`."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = dict(cells[workload])
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["config_name"] = cell["config"]
    cell["config"] = load_json(root, conf["file"])
    cell["traffic_name"] = cell["traffic"]
    # the tests' rehearsal file keeps its tiny mixes in a directory of its own
    traffic_dir = bench.get("_traffic_dir", bench["paths"][0] + "/traffic")
    cell["traffic"] = load_json(root, traffic_dir,
                                cell["traffic_name"] + ".json")
    return cell


def cell_metrics(bench: dict, workload: str, group: str) -> List[dict]:
    """The entries of `end_to_end` or `per_layer` that this cell reports:
    those that list it under `workloads`, or list nothing."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def driver_for(kind: str):
    return importlib.import_module(f"benchmark.drivers.{kind}")


def require_chip(chips: int) -> dict:
    """The device as jax reports it; raises NoChip off the TPU or on
    another count than the cell's."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, jax found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) != chips:
        raise NoChip(f"the cell asks for {chips} chip(s), jax sees "
                     f"{len(devs)}")
    return device_info()


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """`peak_bytes_in_use` of the fullest chip (0 where the backend
    reports none, as the CPU does)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def peak_for(kind: str) -> dict:
    peaks = load_json(HERE, "peaks.json")
    if kind not in peaks or kind.startswith("_"):
        raise KeyError(f"no peaks for device_kind {kind!r} in peaks.json")
    return peaks[kind]


def seed_words(seed: int) -> Tuple[int, int]:
    """A seed of any size as two non-negative int32 words."""
    seed = int(seed)
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def compile_count() -> int:
    from deeplearning4j_tpu.common.environment import environment
    return environment().compile_count()


class Profile:
    """One profiler session into a fixed directory under the checkout,
    with the Python tracer off (host annotations stay on)."""

    def __init__(self, name: str):
        self.dir = os.path.join(TMP_DIR, "trace-" + name)
        self.on = False

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True

    def stop(self) -> None:
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def reduce(self) -> dict:
        from benchmark import trace_reduce
        out = trace_reduce.reduce_trace(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def read_layer_metrics(bench: dict, workload: str, ctx: dict,
                       root: str = ROOT) -> dict:
    """Each per-layer metric of the cell through its own reader:
    `layer_metrics/<name>.json` names a reader module (a file beside it)
    and its parameters; a reader that finds nothing returns None and the
    metric is left out."""
    out = {}
    base = os.path.join(root, bench["paths"][0], "layer_metrics")
    for m in cell_metrics(bench, workload, "per_layer"):
        spec = load_json(base, m["name"] + ".json")
        reader = importlib.import_module(
            "benchmark.layer_metrics." + spec["reader"])
        value = reader.read(ctx, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def checks_pass(checks: List[dict]) -> bool:
    """`checks`: [{"name", "value", "limit"}]; a value that is missing or
    not a number fails."""
    ok = bool(checks)
    for c in checks:
        v = c["value"]
        if v is None or v != v or v > c["limit"]:
            ok = False
    return ok


def emit(result: dict, checks: List[dict], notes: Optional[dict] = None):
    """The earlier lines (notes, on stdout), the numbers compared beside
    their limits as the last lines of stderr, and the result as the last
    line of stdout with the same numbers under its last key."""
    if notes:
        print(json.dumps({"notes": notes}), flush=True)
    compared = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                for c in checks}
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["compared"] = compared
    print(json.dumps(result), flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, need_chip: bool = True,
             bench: Optional[dict] = None, root: str = ROOT) -> dict:
    """One run of one cell: set up, measure, read the memory peak, free
    the program, compare with the reference. Returns the result (without
    printing). `need_chip=False` is for the tests, which drive the rest
    of a run on the CPU."""
    bench = bench or load_benchmark(root)
    cell = load_cell(bench, workload, root)
    device = require_chip(cell["chips"]) if need_chip else device_info()
    driver = driver_for(cell["config"]["kind"])
    profile = Profile(workload) if trace else None

    session = driver.setup(cell, seed)
    compiles0 = compile_count()
    ready_at = time.monotonic()
    window = driver.measure(session, seconds, profile)
    # set-up runs to the opening of the window: a driver whose traffic has
    # a lead-in says when that was
    setup_s = session.get("window_starts_at", ready_at) - t_start
    if profile is not None:
        profile.stop()
    compiled = compile_count() - compiles0
    device["memory_peak_bytes"] = memory_peak_bytes()
    driver.release(session)
    checks = driver.check(session, window)
    if compiled:
        raise Unsteady(f"{compiled} compile(s) inside the measured window")

    e2e = dict(window["end_to_end"], setup_s=setup_s)
    if trace:
        reduced = profile.reduce()
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        ctx = {"cell": cell, "seconds": seconds, "end_to_end": e2e,
               "trace": reduced, "window": window,
               "peak": peak_for(device["kind"]) if need_chip else None,
               "chips": cell["chips"]}
        metrics = read_layer_metrics(bench, workload, ctx, root)
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell_metrics(bench, workload, "end_to_end")}
    result = {"correct": checks_pass(checks),
              "attempted": int(window["attempted"]),
              "failed": int(window["failed"]),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    notes = dict(window.get("notes", {}), setup_s=setup_s, workload=workload,
                 seed=seed, seconds=seconds)
    if trace:
        notes["trace_lines"] = reduced["lines"]
        notes["trace_programs"] = {k: len(v) for k, v in
                                   reduced["programs"].items()}
    return {"result": result, "checks": checks, "notes": notes}
