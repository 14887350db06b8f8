"""Host-side tracing: request-scoped span trees in a chrome-trace ring.

Reference: the `ProfilingListener` half of the reference observability
stack (chrome trace-format JSON that `common/profile_analyzer.py` loads
and compares) grown into a Dapper/Canopy-style request tracer: a
contextvar ``TraceContext`` (trace_id / span_id / parent) propagates
through every layer, so nested ``span()`` calls form a *tree* that can be
reassembled per request (``span_tree``), fetched by trace id
(``tracer().events_for``), and linked from metric exemplars.

Primitives:

- ``span(name, **attrs)`` — context manager recording one complete ("X")
  event per exit into a bounded ring buffer (``DL4J_TPU_TRACE_BUFFER``
  events, oldest dropped first). When a trace context is active the span
  allocates a child span_id and pushes itself as the new parent, so
  nested spans — across admission wait, micro-batch coalesce, padded
  dispatch — share the request's trace_id. A span that exits with an
  exception records ``args["error"]`` and counts
  ``dl4j_span_errors_total{name}`` so failing requests are
  distinguishable in traces.
- ``use_context(ctx)`` / ``current_context()`` — bind/read the active
  ``TraceContext`` (contextvar: thread- and task-local).
- ``parse_traceparent`` / ``format_traceparent`` — W3C trace-context
  interop for the HTTP edge.
- ``tracer().record(name, t0, t1, context=...)`` — append a completed
  span on behalf of another thread (the micro-batcher emits per-rider
  spans this way; contextvars do not cross threads).
- ``capture_profile(seconds)`` — on-demand ``jax.profiler`` device
  capture for the ``/debug/profile`` endpoint.
- ``model_scope(name)`` — the device-side counterpart of ``span()``:
  ``jax.named_scope("dl4j.<name>")`` around a model component inside a
  traced function, so every device operation of a jitted step carries
  the component it belongs to in its ``op_name`` (the profiler's
  ``tf_op``). Trace-time only: the compiled program is unchanged.
- ``build_span(kind)`` — one executable build (``counted_jit``'s first
  call of a signature): a span ``compile/<kind>`` whose children are
  jax's own compile phases, heard through ``jax.monitoring``
  (``watch_compiles``) as spans ``jax/trace``, ``jax/lower`` and
  ``jax/compile`` (``fun_name``, and ``cache`` = hit / miss where jax's
  persistent cache answered). A build feeds
  ``dl4j_compile_phase_seconds_total{kind,phase}`` with each phase's self
  time and ``dl4j_jax_cache_requests_total{kind,outcome}``; phases heard
  outside a build (a plain ``jax.jit``, once a first build registered the
  listeners) are root spans with no ``kind`` and feed no counter.

Export (``tracer().export(path)``) writes exactly the format
`load_trace`/`aggregate` consume — atomically (tmp + rename, parent dirs
created), so a run can be diffed against a previous one with
`profile_analyzer.compare` and a crash never leaves a truncated file.

One clock: ``ts`` is microseconds of the wall clock (``time.time()``),
the clock the profiler stamps its host lines with (an ``.xplane.pb``'s
``profile_start_time`` plus an event's offset) and jax stamps its compile
phases with. Callers keep passing ``time.perf_counter`` seconds; the
tracer adds one offset taken when it is made.

When a jax device profile is active (`jax.profiler.start_trace`), each
span additionally enters a `jax.profiler.TraceAnnotation` so the host
span shows up on the device timeline too.

Cost model: enabled-ness is ONE cached flag (the metrics registry's,
resolved from ``DL4J_TPU_METRICS``); a disabled `span()` returns a shared
no-op context manager — no event dict, no buffer append, no lock. An
enabled span with no active trace context pays one contextvar read over
the previous flat-span cost.
"""
from __future__ import annotations

import contextvars
import gzip
import heapq
import json
import os
import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager
from typing import Dict, List, NamedTuple, Optional

from .locks import ordered_lock
from .metrics import registry

# device-profile-active probe; resolved lazily so importing tracing never
# forces a jax import (False = not yet resolved / unavailable)
_JAX_PROFILE_STATE = None


def _device_profile_active() -> bool:
    global _JAX_PROFILE_STATE
    if _JAX_PROFILE_STATE is None:
        import sys
        if "jax" not in sys.modules:  # no jax yet -> no profile either
            return False
        try:
            from jax._src.profiler import _profile_state
            _JAX_PROFILE_STATE = _profile_state
        except Exception:  # pragma: no cover - older/newer jax layouts
            _JAX_PROFILE_STATE = False
    return (_JAX_PROFILE_STATE is not False
            and getattr(_JAX_PROFILE_STATE, "profile_session", None)
            is not None)


# ---------------------------------------------------------------------------
# trace context (contextvar: per-thread, per-task)
# ---------------------------------------------------------------------------

class TraceContext(NamedTuple):
    """The active position in a request's span tree.

    ``span_id`` is the id of the currently open span — children created
    under this context take it as their parent. An empty ``span_id``
    marks a root context (children become tree roots)."""
    trace_id: str
    span_id: str = ""
    parent_id: Optional[str] = None


_CTX: contextvars.ContextVar[Optional[TraceContext]] = \
    contextvars.ContextVar("dl4j_tpu_trace_ctx", default=None)


def new_trace_id() -> str:
    return os.urandom(16).hex()


def new_span_id() -> str:
    return os.urandom(8).hex()


def current_context() -> Optional[TraceContext]:
    """The TraceContext bound to this thread/task, or None."""
    return _CTX.get()


@contextmanager
def use_context(ctx: Optional[TraceContext]):
    """Bind ``ctx`` as the active trace context for the with-block."""
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(token)


def parse_traceparent(header: Optional[str]) -> Optional[TraceContext]:
    """W3C `traceparent` -> TraceContext, or None when absent/malformed.
    Format: ``<2hex version>-<32hex trace-id>-<16hex parent-id>-<2hex
    flags>``; all-zero ids are invalid per the spec."""
    if not header:
        return None
    parts = header.strip().lower().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, parent_id = parts[0], parts[1], parts[2]
    if (len(version) != 2 or len(trace_id) != 32 or len(parent_id) != 16
            or version == "ff"):
        return None
    try:
        int(trace_id, 16), int(parent_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or parent_id == "0" * 16:
        return None
    return TraceContext(trace_id, parent_id, None)


def format_traceparent(ctx: TraceContext) -> str:
    return f"00-{ctx.trace_id}-{ctx.span_id or '0' * 16}-01"


def context_from_traceparent(header: Optional[str]) -> TraceContext:
    """The entry context for one inbound request: the remote caller's
    (trace_id, span_id) when a valid ``traceparent`` arrives — locally
    created spans then parent under the remote span — else a fresh root
    trace."""
    ctx = parse_traceparent(header)
    return ctx if ctx is not None else TraceContext(new_trace_id())


#: every model scope is ``dl4j.<component>``: the fixed prefix is how a
#: reader finds the scopes in an operation's ``op_name`` path
#: (``jit(step)/transpose(jvp(dl4j.attn))/dl4j.attn_core/mul``) without a
#: list of components kept in two places
MODEL_SCOPE_PREFIX = "dl4j."


def model_scope(name: str):
    """``with model_scope("attn_core"): ...`` inside traced model code:
    the operations traced in the block are named ``dl4j.attn_core`` in
    the compiled program's metadata, forward and (under autodiff)
    backward. Nothing runs at step time and there is nothing to switch
    off; the innermost scope is the one an operation is counted under."""
    import jax
    return jax.named_scope(MODEL_SCOPE_PREFIX + name)


class _NullSpan:
    """Shared no-op context manager returned when tracing is disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass

    def count_phases(self):
        pass


_NULL_SPAN = _NullSpan()


def _count_span_error(name: str):
    try:
        registry().counter(
            "dl4j_span_errors_total",
            "Spans that exited with an exception, by span name",
            labels=("name",)).labels(name=name).inc()
    except Exception:
        pass  # observability must never break the failing path further


class _Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "_annotation", "_ctx",
                 "_token")

    def __init__(self, tracer: "Tracer", name: str, args: Dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0.0
        self._annotation = None
        self._ctx: Optional[TraceContext] = None
        self._token = None

    def __enter__(self):
        parent = _CTX.get()
        if parent is not None:
            self._ctx = TraceContext(parent.trace_id, new_span_id(),
                                     parent.span_id or None)
            self._token = _CTX.set(self._ctx)
        if _device_profile_active():
            try:
                import jax.profiler
                self._annotation = jax.profiler.TraceAnnotation(self.name)
                self._annotation.__enter__()
            except Exception:
                self._annotation = None
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs):
        """Add args to the span before it closes."""
        self.args.update(attrs)

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self._annotation is not None:
            try:
                self._annotation.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        if self._token is not None:
            _CTX.reset(self._token)
        ev = {"name": self.name, "ph": "X",
              "ts": (self._t0 + self._tracer.epoch) * 1e6,
              "dur": (t1 - self._t0) * 1e6,
              "pid": self._tracer.pid, "tid": threading.get_ident()}
        args = self.args
        if exc_type is not None:
            args = dict(args) if args else {}
            args["error"] = exc_type.__name__
            _count_span_error(self.name)
        if self._ctx is not None:
            args = dict(args) if args else {}
            args["trace_id"] = self._ctx.trace_id
            args["span_id"] = self._ctx.span_id
            if self._ctx.parent_id:
                args["parent_span_id"] = self._ctx.parent_id
        if args:
            ev["args"] = args
        self._tracer._events.append(ev)  # deque append: thread-safe
        return False


class Tracer:
    """Ring buffer of span events (capacity = DL4J_TPU_TRACE_BUFFER)."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            # layered resolution (DL102): programmatic
            # set_property(TRACE_BUFFER) > DL4J_TPU_TRACE_BUFFER > default
            from .environment import environment
            capacity = environment().trace_buffer()
        self.capacity = max(int(capacity), 1)
        self.pid = os.getpid()
        self._events: deque = deque(maxlen=self.capacity)
        # perf_counter seconds + epoch = wall-clock seconds (module doc)
        self.epoch = time.time() - time.perf_counter()

    def span(self, name: str, **attrs):
        """Context manager timing one region; a no-op singleton when
        telemetry is disabled."""
        if not registry().enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def record(self, name: str, t0: float, t1: float,
               context: Optional[TraceContext] = None,
               span_id: Optional[str] = None,
               **attrs) -> Optional[dict]:
        """Append one completed span on behalf of a request whose context
        lives on another thread (``t0``/``t1`` in ``time.perf_counter``
        seconds). With ``context``, the span enters that request's tree
        as a child of ``context.span_id``. ``span_id`` pins the recorded
        span's own id instead of minting one — a caller that already
        *announced* an id (the fleet router forwards each attempt's span
        id downstream in ``traceparent``, so the replica's server-side
        spans parent under it) records the matching span here. An
        ``error=...`` attr counts ``dl4j_span_errors_total`` exactly
        like a failing ``span()``."""
        if not registry().enabled:
            return None
        ev = {"name": name, "ph": "X", "ts": (t0 + self.epoch) * 1e6,
              "dur": max(t1 - t0, 0.0) * 1e6, "pid": self.pid,
              "tid": threading.get_ident()}
        args = dict(attrs)
        if context is not None:
            args["trace_id"] = context.trace_id
            args["span_id"] = span_id or new_span_id()
            if context.span_id:
                args["parent_span_id"] = context.span_id
        if args.get("error"):
            _count_span_error(name)
        if args:
            ev["args"] = args
        self._events.append(ev)
        return ev

    def events(self) -> List[dict]:
        return list(self._events)

    def events_for(self, trace_id: str) -> List[dict]:
        """Every buffered event tagged with ``trace_id``, oldest first
        (a linear scan of the ring — debug/flight-recorder use, not the
        request hot path). The scan runs over a copy: other threads keep
        appending (jax's compile phases among them)."""
        return [e for e in self.events()
                if e.get("args", {}).get("trace_id") == trace_id]

    def clear(self):
        self._events.clear()
        return self

    def export(self, path: str) -> int:
        """Write the buffer as a chrome trace JSON file (gzipped when the
        path ends in .gz) that `profile_analyzer.load_trace` reads back.
        Parent directories are created; the write is atomic (tmp +
        rename) so a crash mid-export never leaves a truncated file.
        Returns the number of events written."""
        events = self.events()
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        opener = gzip.open if path.endswith(".gz") else open
        tmp = f"{path}.tmp{os.getpid()}"
        try:
            with opener(tmp, "wt") as f:
                json.dump({"traceEvents": events,
                           "displayTimeUnit": "ms"}, f)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
        return len(events)


# ---------------------------------------------------------------------------
# span-tree reconstruction (the /debug/requests view)
# ---------------------------------------------------------------------------

def span_tree(events: List[dict]) -> List[dict]:
    """Nest a flat event list (``events_for`` output) into span trees by
    span_id/parent_span_id; roots (and orphans whose parent fell off the
    ring) sort by start time. Context-free events pass through as
    roots."""
    nodes, order = {}, []
    for e in events:
        args = e.get("args", {})
        node = {"name": e.get("name"), "ts": e.get("ts"),
                "dur": e.get("dur"),
                "args": {k: v for k, v in args.items()
                         if k not in ("trace_id", "span_id",
                                      "parent_span_id")},
                "span_id": args.get("span_id"),
                "parent_span_id": args.get("parent_span_id"),
                "children": []}
        order.append(node)
        if node["span_id"]:
            nodes[node["span_id"]] = node
    roots = []
    for node in order:
        parent = nodes.get(node["parent_span_id"]) \
            if node["parent_span_id"] else None
        if parent is not None and parent is not node:
            parent["children"].append(node)
        else:
            roots.append(node)
    for node in order:
        node["children"].sort(key=lambda n: n["ts"] or 0)
    roots.sort(key=lambda n: n["ts"] or 0)
    return roots


# ---------------------------------------------------------------------------
# on-demand device profiling (the /debug/profile endpoint)
# ---------------------------------------------------------------------------

_PROFILE_CAPTURE_LOCK = ordered_lock("tracing.profile_capture")


class ProfileBusyError(RuntimeError):
    """A device-profile capture is already running (jax allows one)."""


def capture_profile(seconds: float, log_dir: Optional[str] = None) -> dict:
    """Run a blocking ``jax.profiler`` capture for ``seconds`` and return
    ``{"path", "seconds", "files": [{"file", "bytes"}, ...]}`` — the
    ``files`` list includes the ``.xplane.pb`` capture TensorBoard /
    XProf load. One capture at a time (``ProfileBusyError`` otherwise);
    captures land under ``log_dir`` (default
    ``Environment.profile_dir()``), one timestamped subdir each."""
    import jax

    from .environment import environment

    seconds = min(max(float(seconds), 0.01), 120.0)
    base = log_dir or environment().profile_dir()
    path = os.path.join(
        base, time.strftime("%Y%m%d-%H%M%S") + f"-{os.getpid()}")
    if not _PROFILE_CAPTURE_LOCK.acquire(blocking=False):
        raise ProfileBusyError(
            "a profiler capture is already running; retry when it ends")
    try:
        os.makedirs(path, exist_ok=True)
        jax.profiler.start_trace(path)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
    finally:
        _PROFILE_CAPTURE_LOCK.release()
    files = []
    for root, _, names in os.walk(path):
        for name in names:
            p = os.path.join(root, name)
            try:
                files.append({"file": os.path.relpath(p, path),
                              "bytes": os.path.getsize(p)})
            except OSError:
                pass
    return {"path": path, "seconds": seconds,
            "files": sorted(files, key=lambda f: f["file"])}


# ---------------------------------------------------------------------------
# per-trace failure dispositions (resilience post-mortems)
# ---------------------------------------------------------------------------
# The engines record WHAT the resilience machinery did to a request
# (``retried`` — rescued by an isolated re-dispatch; ``quarantined`` —
# designated poison; ``engine_restart`` — failed by a crashed worker
# dispatch; the serving layer adds ``breaker_open``). The HTTP server
# pops the disposition into the request ring / flight recorder, so a
# post-mortem can distinguish shed load from faulted load by trace id.
# Bounded dict, oldest-first eviction; keyed by trace_id.

_DISPOSITIONS: "OrderedDict[str, str]" = OrderedDict()
_DISPOSITIONS_LOCK = ordered_lock("tracing.dispositions")
_DISPOSITIONS_CAP = 4096


def record_disposition(trace_id: Optional[str], disposition: str):
    """Stamp a failure disposition on ``trace_id`` (no-op without one)."""
    if not trace_id:
        return
    with _DISPOSITIONS_LOCK:
        _DISPOSITIONS[trace_id] = disposition
        _DISPOSITIONS.move_to_end(trace_id)
        while len(_DISPOSITIONS) > _DISPOSITIONS_CAP:
            _DISPOSITIONS.popitem(last=False)


def pop_disposition(trace_id: Optional[str]) -> Optional[str]:
    """Consume the disposition recorded for ``trace_id``, if any."""
    if not trace_id:
        return None
    with _DISPOSITIONS_LOCK:
        return _DISPOSITIONS.pop(trace_id, None)


_TRACER: Optional[Tracer] = None
_TRACER_LOCK = ordered_lock("tracing.singleton")


def tracer() -> Tracer:
    global _TRACER
    if _TRACER is None:
        with _TRACER_LOCK:
            if _TRACER is None:
                _TRACER = Tracer()
    return _TRACER


def span(name: str, **attrs):
    """`with span("train/step", epoch=3): ...` on the process tracer."""
    return tracer().span(name, **attrs)


def export(path: str) -> int:
    """Module-level convenience: `tracing.export(path)`."""
    return tracer().export(path)


# ---------------------------------------------------------------------------
# executable builds: jax's compile phases as spans and counters
# ---------------------------------------------------------------------------

#: jax.monitoring's time-span events -> the phase each times. jax stamps
#: them with ``time.time()``; ``backend_compile`` covers a persistent-cache
#: read as well as a real XLA compile
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: jax.monitoring's persistent-cache events -> outcome
CACHE_OUTCOMES = {"/jax/compilation_cache/cache_hits": "hit",
                  "/jax/compilation_cache/cache_misses": "miss"}

_BUILD: "contextvars.ContextVar[Optional[_Build]]" = \
    contextvars.ContextVar("dl4j_tpu_build", default=None)
# the cache outcome jax announced for the compile span about to close on
# this thread (both events fire inside ``backend_compile``'s span)
_CACHE_OUTCOME = threading.local()
_WATCHING = False
_WATCH_LOCK = threading.Lock()


def phase_self_seconds(spans, lo: float, hi: float) -> Dict[str, float]:
    """``[(phase, t0, t1)]`` -> ``{phase: seconds}``, each instant of
    ``[lo, hi]`` counted once, for the innermost span over it (the latest
    start). jax emits a phase for every nested ``jit`` — an inner pass
    traced inside the step's trace, an eager ``jit`` compiled at trace
    time — so a phase's seconds are the union of its spans less what
    spans of another phase nested inside them cover, and the phases
    together never exceed ``hi - lo``."""
    out = {p: 0.0 for p in COMPILE_PHASES.values()}
    order = sorted((max(a, lo), min(b, hi), p) for p, a, b in spans
                   if min(b, hi) > max(a, lo))
    cuts = sorted({t for a, b, _ in order for t in (a, b)})
    active, i = [], 0
    for x0, x1 in zip(cuts, cuts[1:]):
        while i < len(order) and order[i][0] <= x0:
            a, b, p = order[i]
            heapq.heappush(active, (-a, b, p))   # latest start on top
            i += 1
        while active and active[0][1] <= x0:
            heapq.heappop(active)
        if active:
            out[active[0][2]] += x1 - x0
    return out


class _Build:
    """One executable build in progress: its span, and the phase spans
    jax reported while it ran."""
    __slots__ = ("kind", "spans", "_span", "_t0", "_t1", "_tokens")

    def __init__(self, kind: str):
        self.kind = kind
        self.spans: List[tuple] = []
        self._t0 = self._t1 = 0.0

    def __enter__(self):
        # the span needs a trace context for its children to name it as
        # their parent: the caller's, else a tree of its own
        parent = _CTX.get()
        self._tokens = (_CTX.set(parent or TraceContext(new_trace_id())),
                        _BUILD.set(self))
        self._span = tracer().span("compile/" + self.kind).__enter__()
        self._t0 = time.time()
        return self

    def __exit__(self, *exc):
        self._t1 = time.time()
        try:
            return self._span.__exit__(*exc)
        finally:
            _BUILD.reset(self._tokens[1])
            _CTX.reset(self._tokens[0])

    def set(self, **attrs):
        self._span.set(**attrs)

    def count_phases(self):
        """Feed ``dl4j_compile_phase_seconds_total`` with this build's
        self time a phase (every phase, 0 included). The caller does so
        where it observes the build's ``dl4j_compile_seconds``, so the
        phases of a kind never exceed that histogram's sum."""
        try:
            fam = registry().counter(
                "dl4j_compile_phase_seconds_total",
                "Self seconds of jax's trace, lower and compile phases in "
                "executable builds", labels=("kind", "phase"))
            for phase, s in phase_self_seconds(self.spans, self._t0,
                                               self._t1).items():
                fam.labels(kind=self.kind, phase=phase).inc(s)
        except Exception:
            pass  # observability must never break the dispatch path


def build_span(kind: str):
    """``with build_span(kind) as b: ...`` around one executable build:
    the span ``compile/<kind>`` (``b.set(cache=...)`` adds args), jax's
    phases as its children, ``b.count_phases()`` for the counter. A no-op
    while telemetry is off."""
    if not registry().enabled:
        return _NULL_SPAN
    watch_compiles()
    return _Build(kind)


def watch_compiles() -> bool:
    """Register the jax.monitoring listeners, once per process and only
    while the registry is enabled; True once they are registered."""
    global _WATCHING
    if _WATCHING or not registry().enabled:
        return _WATCHING
    with _WATCH_LOCK:
        if not _WATCHING:
            import jax.monitoring
            jax.monitoring.register_event_time_span_listener(
                _on_compile_phase)
            jax.monitoring.register_event_listener(_on_cache_event)
            _WATCHING = True
    return True


def _on_compile_phase(event, start_time, end_time, **kwargs):
    """A phase of a compile, in ``time.time()`` seconds: a span in the
    ring (a child of the build in progress, or a root), and the build's
    own record. Never raises into jax."""
    try:
        phase = COMPILE_PHASES.get(event)
        if phase is None or not registry().enabled:
            return
        attrs = {"fun_name": kwargs.get("fun_name", "")}
        if phase == "compile":
            cache = getattr(_CACHE_OUTCOME, "outcome", None)
            if cache:
                attrs["cache"] = cache
                _CACHE_OUTCOME.outcome = None
        build = _BUILD.get()
        if build is not None:
            build.spans.append((phase, start_time, end_time))
            attrs["kind"] = build.kind
        t = tracer()
        t.record("jax/" + phase, start_time - t.epoch, end_time - t.epoch,
                 context=_CTX.get() if build is not None else None, **attrs)
    except Exception:
        pass


def _on_cache_event(event, **kwargs):
    """jax's persistent cache answered a compile: counted for the build
    in progress, and named on the compile span that follows."""
    try:
        outcome = CACHE_OUTCOMES.get(event)
        if outcome is None or not registry().enabled:
            return
        _CACHE_OUTCOME.outcome = outcome
        build = _BUILD.get()
        if build is not None:
            registry().counter(
                "dl4j_jax_cache_requests_total",
                "jax persistent-cache answers in executable builds",
                labels=("kind", "outcome")).labels(
                    kind=build.kind, outcome=outcome).inc()
    except Exception:
        pass
