"""From a profiler trace (`.xplane.pb`) to device time per model scope.

The program names its model components with `jax.named_scope("dl4j.<x>")`
(`deeplearning4j_tpu.common.tracing.model_scope`). XLA keeps the scope path
of every operation as its `op_name`, and the TPU's profiler writes it on the
operation's *event metadata* as the stat `tf_op`
(`jit(step)/transpose(jvp(dl4j.attn))/dl4j.attn_core/mul`), beside `flops`,
`bytes_accessed` and `program_id`. `jax.profiler.ProfileData` shows an
event's own stats only, so this module reads the protobuf's wire format
itself: `XSpace.planes[] -> XPlane{name, lines[], event_metadata{},
stat_metadata{}}`, `XLine{name, timestamp_ns, events[]}`,
`XEvent{metadata_id, offset_ps, duration_ps}`, `XEventMetadata{id, name,
stats[]}`, `XStat{metadata_id, value}`. Nothing but the standard library is
imported, and only device planes are decoded.

- leaf scope: the last `dl4j.<name>` of the path, whatever wraps it
  (`jvp(...)`, `transpose(...)`, `checkpoint`, `remat`); `transpose(`
  anywhere in the path marks the operation backward. An operation of the
  program with no scope in its path is `unscoped`; one with no `tf_op` at
  all is `compiler`: what the compiler put in itself (the asynchronous
  copies and slices that prefetch operands, `copy-start` ... `slice-done`),
  which no scope in the program can name. Both are rows of the table,
  reported and never hidden, with their time by `hlo_category` beside.
- a fusion is one operation and has one `tf_op`: where XLA fuses across
  scopes (a weight's Adam update into the matmul that makes its gradient)
  the whole fusion goes where XLA's metadata puts it.
- program: the operation's `program_id` is the number in the name of its
  program's event on the `XLA Modules` line (`jit_step(<id>)`).
- time: an operation's *self* time, its duration less that of operations
  nested in it on the line (a `while` holds its body's operations), so that
  per program Σ scopes + `unscoped` + `compiler` = the union of its
  operations' intervals: the program's share of trace_reduce's `busy_s`.

`reduce_scopes` does the arithmetic on plain tuples, so tests can feed it
by hand; `read_xplane_scoped` only converts the file into those tuples.
"""
from __future__ import annotations

import re
import struct
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark.trace_reduce import (MODULES_LINE, OPS_LINE, find_xplane,
                                    program_name)

# the program's `common.tracing.MODEL_SCOPE_PREFIX`, repeated here because
# the benchmark also runs over a checkout of the program that has no scopes
SCOPE_PREFIX = "dl4j."
UNSCOPED = "unscoped"
COMPILER = "compiler"
_SCOPE = re.compile(re.escape(SCOPE_PREFIX) + r"([A-Za-z0-9_]+)")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")

OpEvent = Tuple[int, float, float]       # metadata id, start_s, duration_s


# -- protobuf wire format -------------------------------------------------

def _varint(buf, pos: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) over one message: an int for a
    varint, a memoryview for a length-delimited field, the raw 8 or 4
    bytes for a fixed one."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value = buf[pos:pos + n]
            pos += n
        elif wire == 1:
            value = buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            value = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf) -> Tuple[int, object]:
    """An XStat as (stat metadata id, value); a reference to another
    stat's name comes back as `("ref", id)`."""
    sid, value = 0, None
    for no, wire, v in _fields(buf):
        if no == 1:
            sid = v
        elif no == 2:
            value = struct.unpack("<d", v)[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif no == 7:
            value = ("ref", v)
    return sid, value


def _map_entry(buf):
    """(key, value bytes) of one `map<int64, Message>` entry."""
    key, value = 0, b""
    for no, _, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _event_metadata(buf) -> Tuple[str, List[Tuple[int, object]]]:
    name, stats = "", []
    for no, _, v in _fields(buf):
        if no == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif no == 5:
            stats.append(_stat(v))
    return name, stats


def _line(buf) -> Tuple[str, int, list]:
    """(name, timestamp_ns, [event bytes])."""
    name, t0, events = "", 0, []
    for no, _, v in _fields(buf):
        if no == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif no == 3:
            t0 = _signed(v)
        elif no == 4:
            events.append(v)
    return name, t0, events


def _event(buf) -> Tuple[int, int, int]:
    """(metadata id, offset_ps, duration_ps) of one XEvent."""
    mid = off = dur = 0
    for no, wire, v in _fields(buf):
        if wire == 0:
            if no == 1:
                mid = v
            elif no == 2:
                off = v
            elif no == 3:
                dur = v
    return mid, off, dur


def read_xplane_scoped(path: str) -> Dict[str, dict]:
    """`{plane: {"ops": [OpEvent], "modules": [(name, start_s, dur_s)],
    "meta": {metadata id: {"name", "tf_op", "program_id", "category",
    "flops", "bytes"}}}}` for every `/device:TPU:<n>` plane, times in seconds."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for no, wire, plane in _fields(space):
        if no != 1 or wire != 2:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for pno, _, v in _fields(plane):
            if pno == 2:
                name = bytes(v).decode("utf-8", "replace")
                if not name.startswith("/device:TPU:"):
                    break
            elif pno == 3:
                lines.append(v)
            elif pno == 4:
                metas.append(v)
            elif pno == 5:
                key, sm = _map_entry(v)
                for sno, _, sv in _fields(sm):
                    if sno == 2:
                        stat_names[key] = bytes(sv).decode("utf-8",
                                                           "replace")
        if not name.startswith("/device:TPU:"):
            continue
        dev = {"ops": [], "modules": [], "meta": {}}
        names = {}
        for entry in metas:
            key, md = _map_entry(entry)
            mname, stats = _event_metadata(md)
            names[key] = mname
            got = {stat_names.get(sid): val for sid, val in stats}
            tf_op = got.get("tf_op")
            if isinstance(tf_op, tuple):          # a reference to a name
                tf_op = stat_names.get(tf_op[1])
            dev["meta"][key] = {
                "name": mname, "tf_op": tf_op,
                "program_id": got.get("program_id"),
                "category": got.get("hlo_category"),
                "flops": got.get("flops") or 0,
                "bytes": got.get("bytes_accessed") or 0}
        for lbuf in lines:
            lname, t0_ns, events = _line(lbuf)
            if lname == OPS_LINE:
                dev["ops"] = [
                    (mid, t0_ns * 1e-9 + off * 1e-12, dur * 1e-12)
                    for mid, off, dur in map(_event, events)]
            elif lname == MODULES_LINE:
                dev["modules"] = [
                    (names.get(mid, ""), t0_ns * 1e-9 + off * 1e-12,
                     dur * 1e-12)
                    for mid, off, dur in map(_event, events)]
        out[name] = dev
    return out


# -- the reduction --------------------------------------------------------

def leaf_scope(tf_op: Optional[str]) -> Tuple[str, bool]:
    """(`<name>` of the last `dl4j.<name>` in the path, `unscoped` where
    the path has none, `compiler` where there is no path; whether the
    path marks the operation backward)."""
    if not tf_op:
        return COMPILER, False
    found = _SCOPE.findall(tf_op)
    return (found[-1] if found else UNSCOPED), "transpose(" in tf_op


def self_times(ops: List[OpEvent]) -> List[Tuple[int, float]]:
    """(metadata id, self seconds) per event: its duration less the
    events that lie inside it on the same line."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [d for _, _, d in ops]
    stack: List[int] = []                 # indices of the open events
    for i in order:
        _, s, d = ops[i]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return [(ops[i][0], max(own[i], 0.0)) for i in range(len(ops))]


def _empty_row() -> dict:
    return {"device_s": 0.0, "forward_s": 0.0, "backward_s": 0.0,
            "flops": 0, "bytes": 0, "events": 0}


def _empty_program() -> dict:
    return {"executions": 0, "op_s": 0.0, "scopes": {}, "uncovered": {},
            "uncovered_ops": {}}


def reduce_scopes(devices: Dict[str, dict],
                  window: Optional[Tuple[float, float]] = None,
                  top: int = 5) -> dict:
    """`{"programs": {program: {"executions", "op_s", "scopes": {scope:
    {"device_s", "forward_s", "backward_s", "flops", "bytes",
    "events"}}, "uncovered": {hlo category: seconds of the `unscoped` and
    `compiler` rows}, "uncovered_ops": [[HLO text, seconds]] (their `top`
    largest operations)}}, "chips"}`. `window` (start_s, end_s) defaults to the span
    of all device operations, as trace_reduce's does; an operation counts
    where its start lies, an execution where its middle lies. Seconds,
    FLOPs and bytes are sums over the window and over the chips."""
    all_ops = [ev for d in devices.values() for ev in d["ops"]]
    programs: Dict[str, dict] = {}
    if not all_ops:
        return {"programs": programs, "chips": len(devices)}
    if window is None:
        window = (min(s for _, s, _ in all_ops),
                  max(s + d for _, s, d in all_ops))
    w0, w1 = window
    for dev in devices.values():
        by_id = {}
        for name, s, d in dev["modules"]:
            m = _PROGRAM_ID.search(name)
            if m:
                by_id[int(m.group(1))] = program_name(name)
            if w0 <= s + d / 2 <= w1:
                programs.setdefault(program_name(name),
                                    _empty_program())["executions"] += 1
        per_op: Dict[int, list] = {}     # metadata id -> [seconds, events]
        for (mid, own), (_, start, _) in zip(self_times(dev["ops"]),
                                             dev["ops"]):
            if w0 <= start < w1:
                acc = per_op.setdefault(mid, [0.0, 0])
                acc[0] += own
                acc[1] += 1
        # everything else about an operation is its metadata's
        for mid, (own, n) in per_op.items():
            meta = dev["meta"].get(mid, {})
            prog = programs.setdefault(
                by_id.get(meta.get("program_id"), "no_program"),
                _empty_program())
            scope, backward = leaf_scope(meta.get("tf_op"))
            if scope in (UNSCOPED, COMPILER):
                cat = meta.get("category") or "none"
                prog["uncovered"][cat] = prog["uncovered"].get(cat, 0.0) + own
                text = meta.get("name", "")[:160]
                prog["uncovered_ops"][text] = \
                    prog["uncovered_ops"].get(text, 0.0) + own
            row = prog["scopes"].setdefault(scope, _empty_row())
            row["device_s"] += own
            row["backward_s" if backward else "forward_s"] += own
            row["flops"] += n * meta.get("flops", 0)
            row["bytes"] += n * meta.get("bytes", 0)
            row["events"] += n
            prog["op_s"] += own
    for prog in programs.values():
        prog["uncovered_ops"] = [list(kv) for kv in sorted(
            prog["uncovered_ops"].items(), key=lambda kv: -kv[1])[:top]]
    return {"programs": programs, "chips": len(devices)}


def scope_table(trace_dir: str) -> dict:
    return reduce_scopes(read_xplane_scoped(find_xplane(trace_dir)))
