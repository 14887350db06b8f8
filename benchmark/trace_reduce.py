"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

Reads the trace with `jax.profiler.ProfileData` and nothing else. A device
plane is one named `/device:TPU:<n>`; on it the line `XLA Ops` holds one
event per executed operation and `XLA Modules` one per executed program
(`jit_<fn>(<fingerprint>)`). Host planes (`/host:CPU`) hold one line per
thread; `jax.profiler.TraceAnnotation` spans appear there by name, and
only names of the form `<layer>/<what>` are taken for spans.

- busy: the union of the device's operation intervals, clipped to the
  window; averaged over the device planes. idle share = 1 - busy/window.
- programs: per program name, the durations of its executions.
- device_ops: operations by total time, the ten largest.
- idle_gaps: every gap of the union, attributed to the host annotation
  that overlaps it most ("no annotation" where none does), summed by
  name, the ten largest.

`reduce_events` does the arithmetic on plain tuples, so tests can feed it
by hand; `read_xplane` only converts the file into those tuples.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]          # name, start_s, duration_s

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# a span of the program or of the benchmark is named `<layer>/<what>`
# (`serving/request`, `generation/decode`, `bench/window`); the runtime's
# own host events (`PJRT_...`, `tpu::System::Execute=>Done`) are not
_SPAN_NAME = re.compile(r"^[A-Za-z_][\w.-]*(/[\w.-]+)+$")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_xplane(path: str) -> dict:
    """`{"devices": {plane: {"ops": [Event], "modules": [Event]}},
    "host": [Event], "lines": {plane: [line names]}}`, times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        names = []
        for line in plane.lines:
            names.append(line.name)
            if plane.name.startswith("/device:TPU:"):
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dev = out["devices"].setdefault(
                    plane.name, {"ops": [], "modules": []})
                key = "ops" if line.name == OPS_LINE else "modules"
                dev[key] = [(op_name(e.name), e.start_ns * 1e-9,
                             e.duration_ns * 1e-9) for e in line.events]
            elif plane.name.startswith("/host:"):
                out["host"].extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events
                    if e.duration_ns > 0 and _SPAN_NAME.match(e.name))
        out["lines"][plane.name] = names
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Sorted, merged `[start, end]` intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def op_name(event_name: str) -> str:
    """An operation's event carries its whole HLO text
    (`%fusion.3 = bf16[...] fusion(...)`): keep the name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def program_name(event_name: str) -> str:
    """`jit_decode_fn(123456)` -> `jit_decode_fn`."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


class _Host:
    """Host annotation spans, for attributing device gaps."""

    def __init__(self, host: List[Event]):
        self.names = [n for n, _, _ in host]
        self.start = np.array([s for _, s, _ in host], float)
        self.end = np.array([s + d for _, s, d in host], float)

    def attribute(self, gs: float, ge: float) -> str:
        """The shortest span that covers at least half of [gs, ge] (the
        innermost says most about the gap); where none covers half, the
        span that overlaps it most."""
        if not self.names:
            return "no annotation"
        ov = np.minimum(ge, self.end) - np.maximum(gs, self.start)
        if float(ov.max()) <= 0:
            return "no annotation"
        half = np.nonzero(ov >= 0.5 * (ge - gs))[0]
        if half.size:
            i = half[np.argmin(self.end[half] - self.start[half])]
        else:
            i = int(np.argmax(ov))
        return self.names[int(i)]


def reduce_events(devices: Dict[str, dict], host: List[Event],
                  window: Optional[Tuple[float, float]] = None,
                  top: int = 10) -> dict:
    """See the module docstring. `window` (start_s, end_s) defaults to the
    span from the first to the last device operation of any plane."""
    all_ops = [ev for d in devices.values() for ev in d["ops"]]
    if not all_ops:
        return {"busy_s": 0.0, "window_s": 0.0, "programs": {},
                "device_ops": [], "idle_gaps": [], "chips": len(devices)}
    if window is None:
        window = (min(s for _, s, _ in all_ops),
                  max(s + d for _, s, d in all_ops))
    w0, w1 = window
    busy, gaps_by_name, op_time, programs = [], {}, {}, {}
    spans = _Host(host)
    for dev in devices.values():
        merged = union((max(s, w0), min(s + d, w1))
                       for _, s, d in dev["ops"]
                       if s + d > w0 and s < w1)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge - gs > 0:
                name = spans.attribute(gs, ge)
                gaps_by_name[name] = gaps_by_name.get(name, 0.0) + (ge - gs)
        for name, s, d in dev["ops"]:
            if s + d > w0 and s < w1:
                op_time[name] = op_time.get(name, 0.0) + d
        for name, s, d in dev["modules"]:
            if w0 <= s + d / 2 <= w1:     # an execution belongs where its middle lies
                programs.setdefault(program_name(name), []).append(d)
    n = len(devices)
    rank = lambda d: [[k, v / n] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy) / n, "window_s": w1 - w0,
            "programs": programs, "device_ops": rank(op_time),
            "idle_gaps": rank(gaps_by_name), "chips": n}


def reduce_trace(trace_dir: str, top: int = 10) -> dict:
    raw = read_xplane(find_xplane(trace_dir))
    out = reduce_events(raw["devices"], raw["host"], top=top)
    out["lines"] = raw["lines"]
    return out
