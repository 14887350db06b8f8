"""Shape-bucketed compiled inference engine with dynamic micro-batching.

Reference: `org/deeplearning4j/parallelism/ParallelInference.java` (worker
threads + `batchLimit`/`queueLimit` request coalescing) and the Clipper/
Orca-style adaptive-batching serving literature.

The TPU problem it solves: every executable frontend here jits on exact
input shapes, so a serving stream with mixed batch sizes (1, 3, 7, 17, ...)
spends its time retracing/recompiling in XLA instead of on the MXU. The fix
is the standard serving recipe:

- **bucket ladder** — incoming batches are zero-padded up the batch dim to
  the next bucket (default: powers of two up to ``max_batch``), so at most
  ``ceil(log2(max_batch)) + 1`` executables ever compile; padded rows are
  sliced off the result. Row-independent inference (every layer-API forward
  at ``training=False``) makes the sliced rows value-identical to an
  exact-shape run.
- **warmup** — pre-compiles the bucket set before traffic arrives.
- **dynamic micro-batching** — ``submit()`` returns a Future; a background
  thread coalesces concurrent requests within a ``max_delay_ms`` /
  ``max_batch`` window into ONE padded device dispatch and resolves each
  future with its unpadded slice.

The same bucketing is wired into the direct ``output()``/``predict()``
paths of MultiLayerNetwork / ComputationGraph / SameDiff via
``maybe_pad_tree`` (gated by ``Environment.inference_bucketing``, on by
default); every jitted inference entry routes through ``counted_jit`` so
``Environment.compile_count()`` observes one event per newly compiled
input signature.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..common import faults
from ..common.environment import environment
from ..common.locks import ordered_condition, ordered_lock
from ..common.metrics import linear_buckets, registry
from ..common.tracing import (build_span, current_context,
                              record_disposition, span, tracer, use_context)


# ---------------------------------------------------------------------------
# bucket ladder + padding primitives
# ---------------------------------------------------------------------------

def bucket_ladder(max_batch: int,
                  buckets: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """The sorted bucket set: explicit `buckets` if given, else powers of
    two up to (and always including) `max_batch`."""
    if buckets:
        out = sorted({int(b) for b in buckets if int(b) > 0})
        if not out:
            raise ValueError("bucket ladder is empty")
        return tuple(out)
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(int(max_batch))
    return tuple(out)


def bucket_for(n: int, ladder: Sequence[int]) -> Optional[int]:
    """Smallest bucket >= n, or None when n exceeds the ladder."""
    for b in ladder:
        if b >= n:
            return b
    return None


def pad_batch(x, target: int):
    """Zero-pad the leading (batch) dim of `x` up to `target` rows."""
    n = x.shape[0]
    if n == target:
        return x
    widths = [(0, target - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths)


def _leading_dim(tree) -> Optional[int]:
    """Shared leading dim of every array leaf, or None if leaves disagree /
    any leaf is unbatched (scalar) / there are no leaves."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return None
    n = None
    for leaf in leaves:
        if getattr(leaf, "ndim", 0) < 1:
            return None
        if n is None:
            n = leaf.shape[0]
        elif leaf.shape[0] != n:
            return None
    return n


def maybe_pad_tree(tree, *, training: bool = False, mesh=None):
    """Environment-gated bucket padding for the direct output() paths.

    Returns (padded_tree, (n, bucket)) when bucketing applies, else
    (tree, None): disabled flag, training mode (padded rows would enter
    batch statistics), sharded batches, mismatched/absent leading dims,
    batch already on a bucket, or batch above the ladder (exact-shape
    fallback in all cases).
    """
    env = environment()
    if training or mesh is not None or not env.inference_bucketing():
        return tree, None
    n = _leading_dim(tree)
    if n is None or n == 0:
        return tree, None
    b = bucket_for(n, bucket_ladder(env.inference_max_batch()))
    if b is None or b == n:
        return tree, None
    return jax.tree_util.tree_map(lambda l: pad_batch(l, b), tree), (n, b)


def slice_batch(outputs: Sequence[Any], n: int, bucket: int) -> List[Any]:
    """Drop padded rows: slice every output whose leading dim is the bucket
    (batch-shaped); leave scalars / non-batch outputs untouched."""
    return [o[:n] if getattr(o, "ndim", 0) >= 1 and o.shape[0] == bucket
            else o for o in outputs]


# ---------------------------------------------------------------------------
# compile-counted jit
# ---------------------------------------------------------------------------

def counted_jit(fn: Callable, tag: str, **jit_kwargs) -> Callable:
    """``jax.jit(fn, **jit_kwargs)`` wrapped with recompile observability
    AND the AOT compile cache: each new input signature records one
    compile event and resolves its executable through
    ``runtime.compile_cache.aot_entry`` — a persistent-store hit
    deserializes the executable and skips XLA, a miss compiles via
    ``lower().compile()`` and serializes back, and ineligible entries
    (donation, shardings, caching disabled) dispatch through the live jit
    exactly as before. Used by every jitted inference entry AND the fit
    fast path's train/epoch steps (donate_argnums passes through).

    The signature is computed from ``args[1:]`` — by convention the first
    argument is the parameter pytree, whose shapes only change on
    re-init/distribute (which rebuild the wrapper anyway); skipping it
    keeps the per-call overhead off the hot path. Python-scalar leaves
    (e.g. the iteration counter) hash by type, matching jit's behavior of
    tracing them as abstract values — a changing int must not count as a
    recompile. Array leaves include weak_type so an AOT executable is
    never fed an aval it was not built for; and if a resolved entry still
    fails to accept a call (e.g. the param tree was re-initialized with
    new shapes under an unchanged data signature), the entry permanently
    falls back to the live jit for that signature — cache problems may
    cost a compile, never an exception; each such fallback is logged at
    warning and observed as ``cache=bypass:call-error``.

    A first call is one build: the span ``compile/<kind>`` with jax's
    ``jax/trace``, ``jax/lower`` and ``jax/compile`` spans as its children,
    and their self seconds in ``dl4j_compile_phase_seconds_total{kind,
    phase}`` beside ``dl4j_compile_seconds`` (``tracing.build_span``).
    """
    from . import compile_cache

    jfn = jax.jit(fn, **jit_kwargs)
    entries: Dict[Any, Callable] = {}
    kind = tag.split(":")[0]

    def wrapped(*args):
        data = args[1:]
        sig = (jax.tree_util.tree_structure(data),
               tuple((tuple(l.shape), str(l.dtype),
                      bool(getattr(l, "weak_type", False)))
                     if hasattr(l, "shape") else f"py:{type(l).__name__}"
                     for l in jax.tree_util.tree_leaves(data)))
        call = entries.get(sig)
        if call is None:
            t0 = time.perf_counter()
            # jax's trace / lower / compile of this build are the span's
            # children and its phase counters (common/tracing.py)
            with build_span(kind) as build:
                call, label = compile_cache.aot_entry(jfn, tag, args,
                                                      jit_kwargs)
                build.set(cache=label)
                # dl4j_compiles_total keeps the base label; the reasoned
                # form ("bypass:donation", ...) lands on dl4j_compile_seconds
                environment().record_compile((tag,) + sig,
                                             cache=label.partition(":")[0])
                if call is jfn:
                    out = jfn(*args)  # first call compiles via the live jit
                else:
                    try:
                        out = call(*args)
                    except Exception as e:
                        return call_failed(sig, e, args)
            compile_cache.observe_compile(kind, label,
                                          time.perf_counter() - t0)
            build.count_phases()
            entries[sig] = call
            return out
        if call is jfn:
            return jfn(*args)
        try:
            return call(*args)
        except Exception as e:
            return call_failed(sig, e, args)

    def call_failed(sig, e, args):
        # the resolved executable refused the call: this signature rides
        # the live jit from here on, and the event is counted under its
        # own label so nothing that watches the cache mistakes it for a hit
        logging.getLogger(__name__).warning(
            "AOT executable for %s refused a call (%s: %s); live jit",
            tag, type(e).__name__, e)
        compile_cache.observe_compile(kind, "bypass:call-error", 0.0)
        entries[sig] = jfn
        return jfn(*args)

    wrapped._jit = jfn
    wrapped.lower = jfn.lower
    return wrapped


# ---------------------------------------------------------------------------
# frontend adapters
# ---------------------------------------------------------------------------

def _unwrap(x):
    if hasattr(x, "jax"):  # NDArray without importing ndarray (cycle-free)
        return x.jax()
    return jnp.asarray(x)


class _MultiLayerAdapter:
    """MultiLayerNetwork: one input array -> one output NDArray."""

    def __init__(self, model):
        self.model = model

    def inputs_of(self, request) -> List[jax.Array]:
        return [_unwrap(request)]

    def run(self, inputs: List[jax.Array]) -> List[jax.Array]:
        return [self.model._output_jit(False)(self.model._params, inputs[0])]

    def package(self, outputs: List[jax.Array]):
        from ..ndarray.ndarray import NDArray
        return NDArray(outputs[0])

    def shard(self, mesh, spec):
        from ..common.mesh import shard_params
        self.model._params = shard_params(mesh, self.model._params, spec)


class _GraphAdapter:
    """ComputationGraph: array/list/dict request -> list of NDArrays,
    ordered as conf.outputs."""

    def __init__(self, model):
        self.model = model
        self.input_names = list(model.conf.inputs)

    def inputs_of(self, request) -> List[jax.Array]:
        if isinstance(request, dict):
            return [_unwrap(request[n]) for n in self.input_names]
        if not isinstance(request, (list, tuple)):
            request = [request]
        if len(request) != len(self.input_names):
            raise ValueError(f"graph expects {len(self.input_names)} inputs, "
                             f"got {len(request)}")
        return [_unwrap(x) for x in request]

    def run(self, inputs: List[jax.Array]) -> List[jax.Array]:
        ind = {n: x for n, x in zip(self.input_names, inputs)}
        return list(self.model._output_jit(False)(self.model._params, ind))

    def package(self, outputs: List[jax.Array]):
        from ..ndarray.ndarray import NDArray
        return [NDArray(o) for o in outputs]

    def shard(self, mesh, spec):
        from ..common.mesh import shard_params
        self.model._params = shard_params(mesh, self.model._params, spec)


class _SameDiffAdapter:
    """SameDiff: placeholder dict -> {name: NDArray} for `outputs`."""

    def __init__(self, model, outputs: Sequence[Any]):
        if not outputs:
            raise ValueError("wrapping a SameDiff requires outputs=[...] "
                             "(the variable names to serve)")
        self.model = model
        self.out_names = [o.name if hasattr(o, "name") else o for o in outputs]
        self.ph_names: Optional[List[str]] = None

    def inputs_of(self, request) -> List[jax.Array]:
        if not isinstance(request, dict):
            raise TypeError("SameDiff requests must be placeholder dicts")
        if self.ph_names is None:
            self.ph_names = sorted(request)
        if sorted(request) != self.ph_names:
            raise ValueError(f"placeholder keys {sorted(request)} != "
                             f"{self.ph_names} of the first request")
        return [_unwrap(request[n]) for n in self.ph_names]

    def run(self, inputs: List[jax.Array]) -> List[jax.Array]:
        sd = self.model
        ph = {n: x for n, x in zip(self.ph_names, inputs)}
        if any(op.needs_key for op in sd._ops.values()):
            fn = sd.make_function(self.out_names, tuple(self.ph_names),
                                  with_rng=True)
            sd._rng_calls = getattr(sd, "_rng_calls", 0) + 1
            return list(fn(sd._arrays, ph,
                           jax.random.key(sd._rng_seed + sd._rng_calls)))
        fn = sd.make_function(self.out_names, tuple(self.ph_names))
        return list(fn(sd._arrays, ph))

    def package(self, outputs: List[jax.Array]):
        from ..ndarray.ndarray import NDArray
        return {n: NDArray(o) for n, o in zip(self.out_names, outputs)}

    def shard(self, mesh, spec):
        from ..common.mesh import shard_params
        self.model._arrays = shard_params(mesh, self.model._arrays, spec)


def _make_adapter(model, outputs):
    # duck-typed so runtime never imports nn/autodiff at module load
    if hasattr(model, "make_function") and hasattr(model, "_vars"):
        return _SameDiffAdapter(model, outputs or [])
    if hasattr(model, "conf") and hasattr(getattr(model.conf, "outputs", None),
                                          "__iter__") and hasattr(
                                              model, "_order"):
        return _GraphAdapter(model)
    if hasattr(model, "layers") and hasattr(model, "_output_jit"):
        return _MultiLayerAdapter(model)
    raise TypeError(f"cannot serve a {type(model).__name__}; expected "
                    "MultiLayerNetwork, ComputationGraph, or SameDiff")


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class EngineClosedError(RuntimeError):
    """Raised by ``submit()``/``infer()`` once the engine is draining or
    closed: late requests must fail fast with a clear signal the caller
    can act on (the serving registry retries them against the engine that
    replaced this one; everyone else surfaces the error)."""


class PoisonRequestError(RuntimeError):
    """A request that failed its coalesced dispatch AND its one isolated
    re-dispatch: the failure follows the request, not the batch, so it is
    quarantined (HTTP 422 with trace id) instead of re-killing every
    micro-batch it rides in. Carries the underlying dispatch error as
    ``__cause__``-style ``cause``."""

    def __init__(self, message: str, cause: Optional[BaseException] = None):
        super().__init__(message)
        self.cause = cause


class _Request:
    __slots__ = ("inputs", "n", "sig", "future", "deadline", "ctx",
                 "t_submit")

    def __init__(self, inputs, sig, future, deadline=None, ctx=None):
        self.inputs = inputs
        self.n = inputs[0].shape[0]
        self.sig = sig
        self.future = future
        self.deadline = deadline  # monotonic instant, or None
        # the submitter's trace context: the batcher thread emits this
        # request's spans under it (contextvars don't cross threads)
        self.ctx = ctx
        self.t_submit = time.perf_counter()

    def expired(self) -> bool:
        return self.deadline is not None and time.monotonic() >= self.deadline


class InferenceEngine:
    """Serving front-end over any executable frontend.

    - ``infer(request)`` — synchronous bucketed inference (pads to the
      bucket, slices padded rows off; batches above ``max_batch`` are
      chunked so the compile bound still holds).
    - ``warmup(example[, batch_sizes])`` — pre-compile buckets.
    - ``submit(request) -> Future`` — enqueue for the dynamic micro-batcher:
      a background thread coalesces concurrent requests within the
      ``max_delay_ms`` / ``max_batch`` window into one padded dispatch.

    Knob mapping from the reference ParallelInference: ``batchLimit`` ->
    ``max_batch``; ``InferenceMode.BATCHED`` -> ``submit()``; ``queueLimit``
    has no analog (the queue is unbounded, ``max_delay_ms`` bounds latency);
    worker replicas are subsumed by XLA running one executable per bucket.
    """

    def __init__(self, model, *, max_batch: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 max_delay_ms: float = 2.0,
                 outputs: Optional[Sequence[Any]] = None,
                 manifest_path: Optional[str] = None,
                 mesh=None, param_spec=None):
        self.model = model
        self._adapter = _make_adapter(model, outputs)
        # tensor-parallel serving: params are committed into their sharded
        # layout once at construction (model axis; replicated fallback per
        # leaf) and every dispatch's padded batch is committed over the
        # data axis — jit propagates the shardings and XLA inserts the
        # collectives (SNIPPETS [2] GSPMD idiom). mesh=None is the
        # single-device path, byte-for-byte unchanged.
        self.mesh = mesh
        self.param_spec = param_spec
        self._batch_sharding = None
        self._data_size = 1
        if mesh is not None:
            from ..common.mesh import DATA, data_sharding, validate_mesh
            validate_mesh(mesh, required=(DATA,))
            self._batch_sharding = data_sharding(mesh)
            self._replicated = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            self._data_size = int(mesh.shape[DATA])
            self._adapter.shard(mesh, param_spec)
        self.max_batch = int(max_batch if max_batch is not None
                             else environment().inference_max_batch())
        self.ladder = bucket_ladder(self.max_batch, buckets)
        self.max_batch = self.ladder[-1]
        self.max_delay_ms = float(max_delay_ms)
        # warmup guard + traffic-shape manifest: _warmed holds
        # (bucket, input-sig) keys already compiled by warmup, _warming the
        # in-flight ones (concurrent/repeated warmups wait instead of
        # double-compiling); _observed accumulates the shapes live traffic
        # actually dispatched, auto-persisted when manifest_path is set so
        # a restarted server can replay yesterday's buckets before taking
        # traffic.
        # DL105: tracked locks — names are the class-level ordering
        # identity the runtime lock-order tracker (common.locks) and the
        # static pass both reason about
        self._warm_lock = ordered_lock("inference.warm")
        self._warmed: set = set()
        self._warming: Dict[Any, threading.Event] = {}
        self.manifest_path = manifest_path
        self._observed: Dict[Tuple, set] = {}
        # micro-batcher state
        self._cv = ordered_condition("inference.batcher")
        self._pending: List[_Request] = []
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        # lifecycle: draining refuses new requests but is reversible via
        # start() (the registry parks retired versions this way so a
        # rollback re-admits without recompiling); closed is permanent
        self._draining = False
        self._closed = False
        self._inflight = 0  # synchronous infer() calls currently running
        # resilience: the supervised batcher's restart budget state and
        # the watchdog-readable in-flight dispatch timestamp
        self._worker_dead = False
        self._dispatch_started_at: Optional[float] = None
        # stats
        self._lock = ordered_lock("inference.stats")
        self._stats = {"requests": 0, "dispatches": 0, "rows_real": 0,
                       "rows_padded": 0, "coalesced": 0,
                       "bucket_dispatches": {}}
        # telemetry: registry families created once, per-bucket children
        # cached so the dispatch path pays one dict lookup + observe
        self._reg = registry()
        lat = self._reg.histogram(
            "dl4j_inference_latency_seconds",
            "Per-bucket dispatch latency of the inference engine",
            labels=("bucket",))
        pad = self._reg.histogram(
            "dl4j_inference_padding_ratio",
            "Fraction of dispatched rows that were bucket padding",
            labels=("bucket",), buckets=linear_buckets(0.0, 0.05, 20))
        self._m_latency = {b: lat.labels(bucket=b) for b in self.ladder}
        self._m_padding = {b: pad.labels(bucket=b) for b in self.ladder}
        self._m_requests = self._reg.counter(
            "dl4j_inference_requests_total",
            "Requests accepted by infer()/submit()")
        self._m_queue = self._reg.gauge(
            "dl4j_inference_queue_depth",
            "Requests waiting in the submit() micro-batcher queue")
        self._m_coalesce = self._reg.histogram(
            "dl4j_inference_coalesce_size",
            "Requests coalesced into one micro-batched dispatch",
            buckets=[float(1 << i) for i in range(11)])
        self._m_expired = self._reg.counter(
            "dl4j_inference_deadline_expired_total",
            "submit() requests whose deadline expired before dispatch")
        self._m_restarts = self._reg.counter(
            "dl4j_engine_restarts_total",
            "Supervised engine worker-thread restarts after a crash",
            labels=("engine",)).labels(engine="inference")
        self._m_quarantined = self._reg.counter(
            "dl4j_quarantined_requests_total",
            "Poison requests quarantined after a failed isolated retry")
        self._m_isolated = self._reg.counter(
            "dl4j_inference_isolated_retries_total",
            "Riders of a failed coalesced dispatch re-dispatched "
            "individually, by outcome", labels=("outcome",))

    # -- core dispatch ---------------------------------------------------
    def _dispatch(self, inputs: List[jax.Array], n: int,
                  span_attrs: Optional[Dict[str, Any]] = None
                  ) -> List[jax.Array]:
        """Pad `inputs` (shared leading dim n <= max_batch) to the bucket,
        run, slice the padded rows back off. The dispatch span inherits
        any active trace context; ``span_attrs`` lets the micro-batcher
        stamp the coalesced riders' trace_ids onto it."""
        b = bucket_for(n, self.ladder)
        if faults.active():
            faults.check("engine.dispatch", inputs=inputs, rows=n, bucket=b)
        padded = [pad_batch(x, b) for x in inputs]
        if self._batch_sharding is not None:
            # commit the bucket over the data axis (replicated when the
            # bucket does not divide) so jit sees the sharded aval
            sh = (self._batch_sharding if b % self._data_size == 0
                  else self._replicated)
            padded = [jax.device_put(x, sh) for x in padded]
        self._dispatch_started_at = time.monotonic()  # watchdog-readable
        try:
            if self._reg.enabled:
                ctx = current_context()
                t0 = time.perf_counter()
                with span("inference/dispatch", bucket=b, rows=n,
                          **(span_attrs or {})):
                    outs = self._adapter.run(padded)
                lat = self._m_latency.get(b)
                if lat is not None:
                    # tail observations carry the request's trace_id as an
                    # exemplar, linking the histogram back to /debug/trace
                    lat.observe(time.perf_counter() - t0,
                                exemplar=ctx.trace_id if ctx else None)
                    self._m_padding[b].observe((b - n) / b)
            else:
                outs = self._adapter.run(padded)
        finally:
            self._dispatch_started_at = None
        with self._lock:
            s = self._stats
            s["dispatches"] += 1
            s["rows_real"] += n
            s["rows_padded"] += b - n
            s["bucket_dispatches"][b] = s["bucket_dispatches"].get(b, 0) + 1
        self._record_observed(inputs, b)
        return slice_batch(outs, n, b)

    def _dispatch_chunked(self, inputs: List[jax.Array],
                          n: int) -> List[jax.Array]:
        if n <= self.max_batch:
            return self._dispatch(inputs, n)
        pieces = []
        for lo in range(0, n, self.max_batch):
            hi = min(lo + self.max_batch, n)
            pieces.append(self._dispatch([x[lo:hi] for x in inputs], hi - lo))
        out = []
        for idx, parts in enumerate(zip(*pieces)):
            # outputs that carried the batch dim were per-chunk sliced;
            # concatenate those, keep non-batch outputs from the last chunk
            # (all chunks agree on them only for row-independent nets, which
            # is the contract of this engine)
            sliced = all(getattr(p, "ndim", 0) >= 1
                         and p.shape[0] == min(self.max_batch,
                                               n - i * self.max_batch)
                         for i, p in enumerate(parts))
            out.append(jnp.concatenate(parts, axis=0) if sliced
                       else parts[-1])
        return out

    def infer(self, request):
        """Synchronous bucketed inference for one request."""
        with self._cv:
            if self._draining or self._closed or self._worker_dead:
                raise EngineClosedError(
                    "InferenceEngine is "
                    + ("closed" if self._closed else
                       "draining" if self._draining else
                       "dead (worker restart budget exhausted)")
                    + "; it no longer accepts requests")
            self._inflight += 1
        try:
            inputs = self._adapter.inputs_of(request)
            n = _leading_dim(inputs)
            if n is None:
                raise ValueError(
                    "request inputs must share a leading batch dim")
            with self._lock:
                self._stats["requests"] += 1
            self._m_requests.inc()
            return self._adapter.package(self._dispatch_chunked(inputs, n))
        finally:
            with self._cv:
                self._inflight -= 1
                self._cv.notify_all()

    __call__ = infer

    # -- warmup + manifest -------------------------------------------------
    @staticmethod
    def _input_sig(inputs: Sequence[Any]) -> Tuple:
        """Trailing (feature) shapes + dtypes — what identifies a traffic
        shape independent of its batch bucket."""
        return tuple((tuple(int(d) for d in x.shape[1:]), str(x.dtype))
                     for x in inputs)

    def _record_observed(self, inputs: Sequence[Any], bucket: int):
        """Remember that live traffic exercised (sig, bucket); persist to
        the manifest file when one is configured (new keys only — the hot
        path pays a set lookup per dispatch)."""
        sig = self._input_sig(inputs)
        with self._warm_lock:
            buckets = self._observed.setdefault(sig, set())
            if bucket in buckets:
                return
            buckets.add(bucket)
        if self.manifest_path:
            try:
                self.save_manifest(self.manifest_path)
            except OSError as e:
                logging.getLogger(__name__).warning(
                    "warmup manifest write to %s failed (%s)",
                    self.manifest_path, e)

    def save_manifest(self, path: Optional[str] = None) -> str:
        """Write the observed bucket/shape/dtype keys as JSON (atomic).
        A restarted server hands the file to ``warmup()`` to replay
        yesterday's shapes before taking traffic."""
        path = path or self.manifest_path
        if not path:
            raise ValueError("no manifest path given or configured")
        with self._warm_lock:
            entries = [{"inputs": [{"shape": list(s), "dtype": d}
                                   for s, d in sig],
                        "buckets": sorted(int(b) for b in buckets)}
                       for sig, buckets in sorted(self._observed.items())]
        doc = {"version": 1, "max_batch": self.max_batch,
               "entries": entries}
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, path)
        return path

    @staticmethod
    def load_manifest(path: str) -> List[dict]:
        """Parse a warmup manifest; malformed files return [] with a
        warning (a stale manifest must never block serving startup)."""
        try:
            with open(path, "r") as f:
                doc = json.load(f)
            entries = []
            for e in doc.get("entries", []):
                inputs = [(tuple(int(d) for d in i["shape"]), str(i["dtype"]))
                          for i in e["inputs"]]
                buckets = [int(b) for b in e["buckets"]]
                if buckets:
                    entries.append({"inputs": inputs, "buckets": buckets})
            return entries
        except Exception as e:
            logging.getLogger(__name__).warning(
                "warmup manifest %s unreadable (%s: %s); skipping replay",
                path, type(e).__name__, e)
            return []

    def observed_entries(self) -> List[dict]:
        """The live-traffic manifest in ``load_manifest`` format, without
        touching disk — the in-process handoff a serving registry uses to
        warm an incoming model version with the shapes the outgoing
        version actually served."""
        with self._warm_lock:
            return [{"inputs": [(tuple(int(d) for d in s), str(dt))
                                for s, dt in sig],
                     "buckets": sorted(int(b) for b in buckets)}
                    for sig, buckets in sorted(self._observed.items())]

    def warmup(self, example=None,
               batch_sizes: Optional[Sequence[int]] = None,
               manifest: Optional[str] = None,
               workers: Optional[int] = None,
               entries: Optional[List[dict]] = None) -> List[int]:
        """Pre-compile bucket executables before traffic arrives,
        concurrently (XLA compilation releases the GIL, so the ladder
        compiles on a thread pool — wall clock ~ the slowest bucket, not
        the sum).

        `example` is any valid request (its batch size is irrelevant; only
        the trailing feature shapes/dtypes matter). With `batch_sizes`,
        only the buckets those sizes map to are compiled; default is the
        whole ladder. With ``example=None``, shapes are replayed from
        ``entries`` (``load_manifest``/``observed_entries`` format — the
        hot-swap handoff from a live predecessor engine) or from
        ``manifest`` (or the engine's configured ``manifest_path``) — the
        restart flow. Returns the sorted buckets warmed.

        Idempotent and re-entrant: a (bucket, shape) pair already warmed —
        or being warmed by a concurrent call — is never compiled twice;
        late callers wait for the in-flight compile instead.

        With a shared artifact store configured (``DL4J_TPU_REMOTE_CACHE``,
        or a ``runtime.warm_image`` pre-baked artifact dir), each warmup
        compile resolves through the tiered store first — on a fleet
        joiner or freshly booted CI image the whole ladder typically
        loads as store hits and never reaches XLA.
        """
        jobs: List[Tuple[int, Tuple]] = []  # (bucket, input-sig)
        if example is not None:
            sig = self._input_sig(self._adapter.inputs_of(example))
            if batch_sizes is not None:
                todo = sorted({bucket_for(min(int(s), self.max_batch),
                                          self.ladder)
                               for s in batch_sizes})
            else:
                todo = list(self.ladder)
            jobs = [(b, sig) for b in todo]
        else:
            if entries is None:
                path = manifest or self.manifest_path
                if not path or not os.path.exists(path):
                    return []
                entries = self.load_manifest(path)
            for e in entries:
                sig = tuple((tuple(int(d) for d in s), str(dt))
                            for s, dt in e["inputs"])
                for b in e["buckets"]:
                    b = bucket_for(min(int(b), self.max_batch), self.ladder)
                    jobs.append((b, sig))
            jobs = sorted(set(jobs))
        if not jobs:
            return []

        claimed: List[Tuple[int, Tuple, threading.Event]] = []
        wait_for: List[threading.Event] = []
        with self._warm_lock:
            for b, sig in jobs:
                key = (b, sig)
                if key in self._warmed:
                    continue
                ev = self._warming.get(key)
                if ev is not None:
                    wait_for.append(ev)
                    continue
                ev = threading.Event()
                self._warming[key] = ev
                claimed.append((b, sig, ev))

        def compile_one(b, sig, ev):
            try:
                self._dispatch([jnp.zeros((b,) + shape, dtype)
                                for shape, dtype in sig], b)
                with self._warm_lock:
                    self._warmed.add((b, sig))
            finally:
                ev.set()
                with self._warm_lock:
                    self._warming.pop((b, sig), None)

        if claimed:
            n_workers = workers or environment().warmup_threads() \
                or min(len(claimed), os.cpu_count() or 1, 8)
            if n_workers <= 1 or len(claimed) == 1:
                for b, sig, ev in claimed:
                    compile_one(b, sig, ev)
            else:
                with ThreadPoolExecutor(
                        max_workers=min(int(n_workers), len(claimed)),
                        thread_name_prefix="dl4j-tpu-warmup") as pool:
                    futs = [pool.submit(compile_one, b, sig, ev)
                            for b, sig, ev in claimed]
                    for f in futs:
                        f.result()  # surface the first compile error
        for ev in wait_for:
            ev.wait(timeout=600)
        return sorted({b for b, _ in jobs})

    # -- dynamic micro-batcher -------------------------------------------
    def submit(self, request, timeout_s: Optional[float] = None) -> Future:
        """Enqueue one request; the returned Future resolves to the same
        value infer(request) would produce.

        With ``timeout_s``, the request carries a deadline budget: if it
        is still queued when the budget expires, the micro-batcher
        resolves its Future with ``TimeoutError`` instead of padding it
        into a batch slot nobody is waiting for (deadline propagation —
        expired work is shed before dispatch, not after)."""
        inputs = self._adapter.inputs_of(request)
        n = _leading_dim(inputs)
        if n is None:
            raise ValueError("request inputs must share a leading batch dim")
        if n > self.max_batch:
            raise ValueError(f"submit() batch {n} exceeds max_batch "
                             f"{self.max_batch}; use infer() (it chunks)")
        sig = tuple((x.shape[1:], str(x.dtype)) for x in inputs)
        fut: Future = Future()
        deadline = (time.monotonic() + timeout_s
                    if timeout_s is not None else None)
        with self._cv:
            if self._draining or self._closed or self._worker_dead:
                raise EngineClosedError(
                    "InferenceEngine is "
                    + ("closed" if self._closed else
                       "draining" if self._draining else
                       "dead (worker restart budget exhausted)")
                    + "; it no longer accepts requests")
            self._pending.append(_Request(inputs, sig, fut, deadline,
                                          ctx=current_context()))
            depth = len(self._pending)
            self._cv.notify_all()
        with self._lock:
            self._stats["requests"] += 1
        self._m_requests.inc()
        self._m_queue.set(depth)
        self._ensure_thread()
        return fut

    def _ensure_thread(self):
        with self._cv:
            if self._draining or self._closed or self._worker_dead:
                return  # a drain in progress must never be un-stopped
            if self._thread is None or not self._thread.is_alive():
                self._stopping = False
                self._thread = threading.Thread(
                    target=self._batcher_main,
                    name="dl4j-tpu-inference-batcher", daemon=True)
                self._thread.start()

    @property
    def worker_dead(self) -> bool:
        """True once the supervised batcher exhausted its restart budget
        (the watchdog reports this engine unhealthy; submits fail fast)."""
        return self._worker_dead

    def _batcher_main(self):
        """Supervised batcher: a crash anywhere in the loop fails at most
        the dispatch it was running (``_run_group`` already fails only
        its riders), is counted, and the loop resumes after exponential
        backoff with jitter — one uncaught exception must never silently
        kill the dispatch path for every subsequent request. A crash
        *burst* past ``DL4J_TPU_ENGINE_MAX_RESTARTS`` declares the
        worker dead: queued requests fail fast with ``EngineClosedError``
        and the watchdog flips ``/readyz``."""
        policy = faults.RetryPolicy(
            max_restarts=environment().engine_max_restarts(),
            base_s=0.01, max_s=2.0, seed=0)
        while True:
            try:
                self._batcher_loop()
                return  # normal stop (drain / idle exit)
            except Exception:
                logging.getLogger(__name__).exception(
                    "inference batcher crashed; restarting the loop")
                policy.note_failure()
                self._m_restarts.inc()
                if policy.exhausted():
                    self._worker_died()
                    return
                time.sleep(policy.backoff.next_delay())

    def _worker_died(self):
        """Restart budget exhausted: fail everything queued, refuse new
        work, leave the process alive (the registry / operator decides
        what happens next — rollback, redeploy, or drain)."""
        with self._cv:
            self._worker_dead = True
            leftovers, self._pending = self._pending, []
            if self._thread is threading.current_thread():
                self._thread = None
            self._cv.notify_all()
        logging.getLogger(__name__).error(
            "inference batcher exceeded its restart budget; engine "
            "refuses new work (worker_dead)")
        for r in leftovers:
            if not r.future.done():
                r.future.set_exception(EngineClosedError(
                    "InferenceEngine worker thread permanently failed "
                    "(restart budget exhausted)"))

    def start(self):
        """(Re)open the engine for requests: reverses drain() — a parked
        previous version resumes without recompiling — and starts the
        micro-batcher thread. Raises once close() has run."""
        with self._cv:
            if self._closed:
                raise EngineClosedError(
                    "InferenceEngine is closed; it cannot be restarted")
            self._draining = False
        self._ensure_thread()
        return self

    def stop(self):
        """Drain pending requests, then stop the batcher thread (the
        engine stays open: a later submit() restarts it)."""
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=30)
        return self

    # -- graceful drain / close ------------------------------------------
    @property
    def draining(self) -> bool:
        return self._draining and not self._closed

    @property
    def closed(self) -> bool:
        return self._closed

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Stop admitting, flush every queued request through the
        micro-batcher, wait for in-flight infer() calls, and stop the
        batcher thread. Idempotent; reversible via start() (a rollback
        re-admits a parked version). Late submit()/infer() calls raise
        ``EngineClosedError``. Returns True when fully drained within
        ``timeout_s``."""
        deadline = time.monotonic() + timeout_s
        with self._cv:
            self._draining = True
            self._stopping = True
            self._cv.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        # a submit that raced the drain may have left requests behind a
        # dead batcher: fail them explicitly rather than strand futures
        with self._cv:
            leftovers, self._pending = self._pending, []
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cv.wait(remaining)
            drained = self._inflight == 0 and (t is None or not t.is_alive())
        for r in leftovers:
            if not r.future.done():
                r.future.set_exception(EngineClosedError(
                    "InferenceEngine drained before this request was "
                    "dispatched"))
        return drained

    def close(self, timeout_s: float = 30.0) -> bool:
        """Permanent drain: like drain(), but the engine can never be
        restarted. Idempotent. Returns True when fully drained."""
        self._closed = True
        return self.drain(timeout_s)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def _expire(self, req: _Request) -> bool:
        """Resolve an expired request's Future with TimeoutError; True if
        it was expired (and must not occupy a batch slot)."""
        if not req.expired():
            return False
        if not req.future.done():
            req.future.set_exception(TimeoutError(
                "request deadline expired before dispatch"))
        self._m_expired.inc()
        if req.ctx is not None and self._reg.enabled:
            # the expired wait shows up in the request's trace with error
            # status — a shed request's timeline stays reconstructable
            tracer().record("inference/queue_expired", req.t_submit,
                            time.perf_counter(), context=req.ctx,
                            rows=req.n, error="TimeoutError")
        return True

    def _batcher_loop(self):
        while True:
            # the crash site sits BEFORE any request is popped, so an
            # injected batcher crash loses no queued work — the
            # supervisor restarts the loop and the queue survives
            if faults.active():
                faults.check("engine.batcher")
            with self._cv:
                while not self._pending and not self._stopping:
                    self._cv.wait()
                if not self._pending:  # stopping and drained
                    if self._thread is threading.current_thread():
                        # a submit() racing this exit sees _thread None and
                        # reliably starts a fresh batcher for its request
                        self._thread = None
                    return
                first = self._pending.pop(0)
            if self._expire(first):
                continue
            group, total = [first], first.n
            deadline = time.monotonic() + self.max_delay_ms / 1000.0
            while total < self.max_batch:
                with self._cv:
                    timeout = deadline - time.monotonic()
                    while (not self._pending and timeout > 0
                           and not self._stopping):
                        self._cv.wait(timeout)
                        timeout = deadline - time.monotonic()
                    if not self._pending:
                        break
                    nxt = self._pending[0]
                    if nxt.sig != first.sig or total + nxt.n > self.max_batch:
                        break
                    self._pending.pop(0)
                if self._expire(nxt):
                    continue
                group.append(nxt)
                total += nxt.n
            if self._reg.enabled:
                with self._cv:
                    self._m_queue.set(len(self._pending))
            self._run_group(group, total)

    def _run_group(self, group: List[_Request], total: int):
        self._m_coalesce.observe(len(group))
        # the dispatch span runs under the first traced rider's context
        # and lists every rider's trace_id, so each request's timeline
        # survives coalescing: its own trace keeps an inference/ride
        # span, and the shared dispatch names all trace_ids that rode
        lead_ctx = next((r.ctx for r in group if r.ctx is not None), None)
        attrs: Dict[str, Any] = {}
        if lead_ctx is not None:
            riders = [r.ctx.trace_id for r in group if r.ctx is not None]
            attrs["trace_ids"] = riders
            if len(group) > 1:
                attrs["coalesced"] = len(group)
        t_dispatch = time.perf_counter()
        try:
            if len(group) == 1:
                inputs = group[0].inputs
            else:
                with self._lock:
                    self._stats["coalesced"] += len(group)
                inputs = [jnp.concatenate(parts, axis=0)
                          for parts in zip(*(r.inputs for r in group))]
            if lead_ctx is not None:
                with use_context(lead_ctx):
                    outs = self._dispatch(inputs, total, span_attrs=attrs)
            else:
                outs = self._dispatch(inputs, total, span_attrs=attrs)
            lo = 0
            for r in group:
                hi = lo + r.n
                r.future.set_result(self._adapter.package(
                    [o[lo:hi] if getattr(o, "ndim", 0) >= 1
                     and o.shape[0] == total else o for o in outs]))
                lo = hi
            self._record_rides(group, t_dispatch)
        except Exception as e:
            self._rescue_group(group, e, t_dispatch)

    def _rescue_group(self, group: List[_Request], exc: Exception,
                      t_dispatch: float):
        """Poison isolation: a failed coalesced dispatch re-dispatches
        each rider individually ONCE, so the one request actually
        carrying the fault is quarantined (``PoisonRequestError`` → 4xx
        with trace id) while its innocent riders succeed — instead of
        the poison re-killing every batch it rides in. An
        ``EngineClosedError`` (drain race) is not a model fault and
        fails the group as before so the registry's swap retry fires."""
        if isinstance(exc, EngineClosedError):
            for r in group:
                if not r.future.done():
                    r.future.set_exception(exc)
            self._record_rides(group, t_dispatch,
                               error=type(exc).__name__)
            return
        for r in group:
            if r.future.done():
                continue
            trace_id = r.ctx.trace_id if r.ctx is not None else None
            try:
                outs = self._dispatch(r.inputs, r.n,
                                      span_attrs={"isolated_retry": True})
            except Exception as e2:
                self._m_isolated.labels(outcome="quarantined").inc()
                self._m_quarantined.inc()
                record_disposition(trace_id, "quarantined")
                if r.ctx is not None and self._reg.enabled:
                    tracer().record(
                        "inference/quarantine", t_dispatch,
                        time.perf_counter(), context=r.ctx, rows=r.n,
                        error=type(e2).__name__)
                r.future.set_exception(PoisonRequestError(
                    f"request quarantined: dispatch failed coalesced "
                    f"({type(exc).__name__}: {exc}) and again isolated "
                    f"({type(e2).__name__}: {e2})", cause=e2))
            else:
                self._m_isolated.labels(outcome="ok").inc()
                record_disposition(trace_id, "retried")
                r.future.set_result(self._adapter.package(outs))
        self._record_rides(group, t_dispatch,
                           error=type(exc).__name__)

    def _record_rides(self, group: List[_Request], t_dispatch: float,
                      error: Optional[str] = None):
        """Per-rider micro-batcher spans: each traced request gets an
        ``inference/ride`` span in its OWN trace covering queue wait +
        dispatch, so its timeline reads end-to-end even when another
        request's trace holds the shared dispatch span."""
        if not self._reg.enabled:
            return
        t1 = time.perf_counter()
        for r in group:
            if r.ctx is None:
                continue
            attrs = {"rows": r.n, "coalesced": len(group),
                     "queue_s": round(t_dispatch - r.t_submit, 6)}
            if error is not None:
                attrs["error"] = error
            tracer().record("inference/ride", r.t_submit, t1,
                            context=r.ctx, **attrs)

    # -- observability ---------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            s = {k: (dict(v) if isinstance(v, dict) else v)
                 for k, v in self._stats.items()}
        real, padded = s["rows_real"], s["rows_padded"]
        s["padding_overhead"] = padded / max(real + padded, 1)
        s["compile_count"] = environment().compile_count()
        s["buckets"] = list(self.ladder)
        if self.mesh is not None:
            from ..common.mesh import mesh_shape, spec_desc
            s["mesh_shape"] = mesh_shape(self.mesh)
            s["param_spec"] = spec_desc(self.param_spec)
        return s
