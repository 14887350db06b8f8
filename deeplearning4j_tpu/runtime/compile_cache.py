"""Ahead-of-time compilation pipeline: persistent executable cache.

The north-star deployment restarts constantly (autoscaling, rollouts,
preemption), and before this module every restart paid a full re-trace +
XLA re-compile for every inference bucket, train step, and SameDiff graph.
Production serving systems treat compiled executables as cacheable
artifacts (ORCA's amortized engine builds; JAX's persistent compilation
cache); here the same idea is wired through ``counted_jit``, the single
choke point every jitted entry in this codebase dispatches through.

Three layers, safest-first:

1. **On-disk executable store** (``DL4J_TPU_CACHE_DIR``, on by default at
   ``~/.cache/deeplearning4j_tpu``): for *serving-shaped* entries (no
   donation, no explicit sharding kwargs, plain array args — including
   mesh-sharded arrays committed via ``NamedSharding``) the first call
   per input signature runs ``jit(...).lower(...)`` and consults the
   store. A hit deserializes the XLA executable (``PjRtClient.
   deserialize_executable``) and skips XLA compilation entirely; a miss
   compiles via ``lowered.compile()`` and serializes the result back,
   for multi-device programs together with the mesh + in/out
   PartitionSpecs needed to place inputs and reassemble sharded outputs
   into global arrays on reload. The cache key is a sha256 over
   everything that feeds a trace: the lowered StableHLO module (which
   captures shapes, dtypes, batch bucket, donation/sharding attributes,
   and every conf knob that changes the traced program), the jit kwargs,
   the device assignment + input shardings of the concrete call,
   jax/jaxlib versions, backend platform + device kind + device count,
   and the trace-relevant ``DL4J_TPU_*`` flags.
2. **jax persistent-compilation-cache backstop**: when the store is
   enabled on an accelerator backend, ``jax_compilation_cache_dir`` is
   pointed at ``<dir>/xla`` so every compile this process runs —
   including donated train steps and mesh-sharded programs our own store
   refuses to wrap — still loads from disk on restart instead of
   re-running XLA. Gated by ``DL4J_TPU_XLA_CACHE`` (auto|on|off;
   "auto" keeps it off on the CPU backend, where deserialized donated
   executables proved unstable under churn and the store already covers
   the serving path).
3. **Fallback, never crash**: corrupt/truncated/version-mismatched
   entries are deleted and recompiled with a one-time warning; any error
   while lowering, loading, serializing, or calling an AOT entry falls
   back to the live ``jax.jit`` dispatch that predates this module.

Observability: ``dl4j_compiles_total`` is labeled
``cache=hit|miss|bypass``; the ``dl4j_compile_seconds`` histogram carries
the reasoned form — ``hit``, ``miss``, or ``bypass:<reason>`` (e.g.
``bypass:donation`` for the donated-KV decode steps that remain
store-ineligible by design, ``bypass:disabled`` when the store is off).
Disable everything with ``DL4J_TPU_CACHE_DIR=""``.

**Donated-KV-cache decode steps are store-ineligible by design.** The
generative fast path (``runtime.generation.DecodeEngine``) donates its
preallocated KV cache into every prefill/decode step so the cache updates
in place; a raw stored executable bypasses jax's donation bookkeeping, so
``_ineligible_reason`` refuses these entries and they dispatch through
the live jit. They are NOT silently missing from telemetry:
``counted_jit`` still records one compile event per signature with
``cache=bypass`` on ``dl4j_compiles_total`` and ``cache=bypass:donation``
on the ``dl4j_compile_seconds`` histogram (asserted in
tests/test_generation.py). On accelerator backends the
``jax_compilation_cache_dir`` backstop at ``<dir>/xla`` still shortens
their restart compiles; on CPU the backstop stays off (see
``_backstop_wanted``) and decode steps recompile on restart — bounded at
one prefill per prompt bucket plus one decode executable.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import logging
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..common import faults
from ..common.environment import environment
from ..common.locks import ordered_lock

log = logging.getLogger(__name__)

#: bump to invalidate every existing on-disk entry (layout change)
FORMAT_VERSION = 3

_PAYLOAD_EXT = ".bin"
_META_EXT = ".json"


# ---------------------------------------------------------------------------
# environment fingerprint + cache key
# ---------------------------------------------------------------------------

def env_fingerprint() -> str:
    """JSON of everything outside the traced program that can change what
    an executable computes or how it was compiled: versions, topology, and
    the DL4J_TPU_* flags that feed traces. Part of every cache key."""
    import jax
    import jaxlib

    env = environment()
    dev = jax.devices()[0]
    return json.dumps({
        "format": FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "num_devices": jax.device_count(),
        "dtype": env.default_float_dtype(),
        "matmul_precision": env.matmul_precision(),
        "remat": env.training_remat(),
        "grad_accum": env.training_grad_accum(),
        "zero1": env.training_zero1(),
        "bucketing": env.inference_bucketing(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }, sort_keys=True)


def _jit_kwargs_repr(jit_kwargs: Dict[str, Any]) -> str:
    """Stable repr of the jit kwargs for key composition. Donation and
    shardings must key entries apart even when they do not change the
    lowered text (e.g. donation XLA judged unusable)."""
    return repr(sorted((k, repr(v)) for k, v in jit_kwargs.items()))


def _placement_fingerprint(args) -> str:
    """Device assignment + input shardings of the call's args. The
    StableHLO text carries the *logical* sharding attributes, but not the
    physical device assignment — two processes with the same program on
    different device orderings (or one sharded vs one replicated over a
    different mesh) must not share a raw executable."""
    if args is None:
        return ""
    import jax
    from jax.sharding import NamedSharding

    parts = []
    try:
        for leaf in jax.tree_util.tree_leaves(args):
            sh = getattr(leaf, "sharding", None)
            if sh is None:
                parts.append("host")
            elif isinstance(sh, NamedSharding):
                mesh = sh.mesh
                parts.append("named:%s:%s:%s:%s" % (
                    ",".join(mesh.axis_names),
                    "x".join(str(s) for s in mesh.devices.shape),
                    ",".join(str(d.id) for d in mesh.devices.flat),
                    sh.spec))
            else:
                ids = sorted(d.id for d in getattr(sh, "device_set", ()))
                parts.append("%s:%s" % (type(sh).__name__, ids))
    except Exception:
        parts.append("unknown")
    return ";".join(parts)


def cache_key(lowered, jit_kwargs: Optional[Dict[str, Any]] = None,
              args=None) -> str:
    """sha256 hex key for a ``jax.stages.Lowered``: the StableHLO text
    captures shapes/dtypes/buckets/mesh attributes and every conf knob
    that alters the traced program; the fingerprint adds versions,
    topology, and env flags; the placement fingerprint adds the device
    assignment + input shardings of the concrete call."""
    h = hashlib.sha256()
    h.update(env_fingerprint().encode())
    h.update(b"\x00")
    h.update(_jit_kwargs_repr(jit_kwargs or {}).encode())
    h.update(b"\x00")
    h.update(_placement_fingerprint(args).encode())
    h.update(b"\x00")
    h.update(lowered.as_text().encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# pluggable raw artifact stores
# ---------------------------------------------------------------------------

class CorruptEntryError(Exception):
    """A stored entry failed validation (format/size/digest) and was
    deleted by the store before raising. The cache layer turns this into
    a one-time warning + a miss — never an exception to the caller."""

    def __init__(self, why: str):
        super().__init__(why)
        self.why = why


_TMP_COUNTER = itertools.count()


def _tmp_suffix() -> str:
    """Unique-per-writer tmp suffix: two replicas (or two threads of one
    replica) pushing the same key must never collide on the tmp file —
    each writes its own and the last ``os.replace`` wins atomically."""
    return ".tmp%d-%d-%d" % (os.getpid(), threading.get_ident(),
                             next(_TMP_COUNTER))


def _stamp_meta(payload: bytes, meta: dict) -> dict:
    """Copy of ``meta`` stamped with the integrity fields every store
    validates on read."""
    meta = dict(meta)
    meta["format"] = FORMAT_VERSION
    meta["payload_bytes"] = len(payload)
    meta["payload_sha"] = hashlib.sha256(payload).hexdigest()
    return meta


def _validate_entry(payload: bytes, meta: dict):
    """Raise ValueError when (payload, meta) fail the integrity check."""
    if meta.get("format") != FORMAT_VERSION:
        raise ValueError(f"format {meta.get('format')} != {FORMAT_VERSION}")
    if len(payload) != meta.get("payload_bytes"):
        raise ValueError("payload truncated")
    if hashlib.sha256(payload).hexdigest() != meta.get("payload_sha"):
        raise ValueError("payload checksum mismatch")


class _FilesystemStore:
    """Shared machinery of the filesystem-rooted stores: an entry is
    ``<key>.bin`` + ``<key>.json`` under ``_entry_dir(key)``, written via
    a unique tmp file + ``os.replace`` (atomic on POSIX, so concurrent
    writers of the same key cannot interleave partial content) and
    digest-verified on every read (a failed check deletes the entry and
    raises :class:`CorruptEntryError`)."""

    tier = "local"

    def _entry_dir(self, key: str, create: bool = False) -> str:
        raise NotImplementedError

    def _paths(self, key: str, create: bool = False) -> Tuple[str, str]:
        d = self._entry_dir(key, create=create)
        return (os.path.join(d, key + _PAYLOAD_EXT),
                os.path.join(d, key + _META_EXT))

    def contains(self, key: str) -> bool:
        return os.path.exists(self._paths(key)[1])

    def get(self, key: str) -> Optional[Tuple[bytes, dict]]:
        payload_p, meta_p = self._paths(key)
        if not os.path.exists(meta_p):
            return None
        try:
            with open(meta_p, "r") as f:
                meta = json.load(f)
            with open(payload_p, "rb") as f:
                payload = f.read()
            _validate_entry(payload, meta)
        except Exception as e:
            self.delete(key)
            raise CorruptEntryError(f"{type(e).__name__}: {e}") from e
        self.touch(key)
        return payload, meta

    def put(self, key: str, payload: bytes, meta: dict) -> bool:
        """``meta`` must already be stamped (``_stamp_meta``)."""
        try:
            payload_p, meta_p = self._paths(key, create=True)
            for path, data, mode in ((payload_p, payload, "wb"),
                                     (meta_p, json.dumps(meta), "w")):
                tmp = path + _tmp_suffix()
                with open(tmp, mode) as f:
                    f.write(data)
                os.replace(tmp, path)
        except OSError as e:
            log.warning("artifact store write failed (%s); continuing "
                        "uncached", e)
            return False
        return True

    def delete(self, key: str):
        for p in self._paths(key):
            try:
                os.remove(p)
            except OSError:
                pass

    def touch(self, key: str):
        """LRU recency hint; overridden to a no-op where mtime churn is
        unwanted (the shared remote)."""
        now = time.time()
        try:
            os.utime(self._paths(key)[0], (now, now))
        except OSError:
            pass

    def entry_meta(self, key: str) -> Optional[dict]:
        try:
            with open(self._paths(key)[1], "r") as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def last_used(self, key: str) -> Optional[float]:
        try:
            return os.stat(self._paths(key)[0]).st_mtime
        except OSError:
            return None

    def _iter_dirs(self):
        raise NotImplementedError

    def keys(self) -> List[str]:
        out = []
        for d in self._iter_dirs():
            try:
                names = os.listdir(d)
            except OSError:
                continue
            out.extend(n[:-len(_META_EXT)] for n in names
                       if n.endswith(_META_EXT))
        return out

    def stat(self) -> Dict[str, int]:
        """{"entries", "bytes"} of the tier, by payload files."""
        entries = 0
        total = 0
        for d in self._iter_dirs():
            try:
                names = os.listdir(d)
            except OSError:
                continue
            for n in names:
                if n.endswith(_META_EXT):
                    entries += 1
                elif n.endswith(_PAYLOAD_EXT):
                    try:
                        total += os.stat(os.path.join(d, n)).st_size
                    except OSError:
                        pass
        return {"entries": entries, "bytes": total}

    def clear(self):
        for d in self._iter_dirs():
            try:
                names = os.listdir(d)
            except OSError:
                continue
            for n in names:
                try:
                    os.remove(os.path.join(d, n))
                except OSError:
                    pass
        return self

    def tiers(self) -> List["_FilesystemStore"]:
        return [self]

    def enforce_cap(self, max_bytes: int) -> int:
        """Evict LRU entries beyond ``max_bytes``; returns evicted count.
        Only the local tier caps — see the overrides."""
        return 0


class LocalDirStore(_FilesystemStore):
    """Today's per-machine layout: flat ``<base_dir>/aot/<key>.bin|.json``
    with mtime-LRU eviction. The default store — behavior-identical to
    the pre-ArtifactStore cache when no remote is configured."""

    tier = "local"

    def __init__(self, base_dir: str):
        self.base_dir = base_dir
        self.aot_dir = os.path.join(base_dir, "aot")
        os.makedirs(self.aot_dir, exist_ok=True)

    def _entry_dir(self, key: str, create: bool = False) -> str:
        return self.aot_dir

    def _iter_dirs(self):
        yield self.aot_dir

    def enforce_cap(self, max_bytes: int) -> int:
        if max_bytes <= 0:
            return 0
        evicted = 0
        try:
            entries = []
            total = 0
            for name in os.listdir(self.aot_dir):
                if not name.endswith(_PAYLOAD_EXT):
                    continue
                p = os.path.join(self.aot_dir, name)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                total += st.st_size
                entries.append((st.st_mtime, st.st_size,
                                name[:-len(_PAYLOAD_EXT)]))
            if total <= max_bytes:
                return 0
            entries.sort()  # oldest first
            for _, size, key in entries:
                if total <= max_bytes:
                    break
                self.delete(key)
                total -= size
                evicted += 1
        except OSError:
            pass  # capping is best-effort; never fail the compile path
        return evicted

    def describe(self) -> dict:
        return {"tier": self.tier, "backend": "local-dir",
                "path": self.aot_dir}


class RemoteStore(_FilesystemStore):
    """Content-addressed shared store the whole fleet reads and writes:
    sha256-keyed objects under ``<root>/objects/<key[:2]>/`` (the cache
    key is already a sha256; the two-hex fan-out keeps any one directory
    small at fleet scale). Writes are unique-tmp + ``os.replace`` and
    reads digest-verify, so N replicas pushing the same key concurrently
    converge on one valid entry and a torn write can never be served.

    This filesystem-rooted implementation is both the test double and a
    real deployment path (``DL4J_TPU_REMOTE_CACHE`` pointed at an NFS /
    FUSE-mounted bucket). An HTTP/object-store client is the documented
    extension point: subclass and override ``get``/``put``/``delete``/
    ``contains``/``keys``/``stat`` (and ``manifest_*``) with your
    transport — everything above the store (keying, validation fallback,
    tiering, pull metrics) is transport-agnostic. No LRU here: recency
    touches and byte caps are per-machine policies (``LocalDirStore``);
    a shared store is pruned by whoever owns the bucket."""

    tier = "remote"

    def __init__(self, root: str):
        self.root = root
        self.objects_dir = os.path.join(root, "objects")
        os.makedirs(self.objects_dir, exist_ok=True)

    def _entry_dir(self, key: str, create: bool = False) -> str:
        d = os.path.join(self.objects_dir, key[:2] or "_")
        if create:
            os.makedirs(d, exist_ok=True)
        return d

    def _iter_dirs(self):
        try:
            shards = sorted(os.listdir(self.objects_dir))
        except OSError:
            shards = []
        for s in shards:
            yield os.path.join(self.objects_dir, s)

    def touch(self, key: str):
        pass  # shared mtimes stay put: every replica would churn them

    def manifest_dir(self, create: bool = False) -> str:
        """Where pushed warmup manifests live (``<root>/manifests``) —
        the pull-on-boot counterpart of ``serving_manifest_dir``."""
        d = os.path.join(self.root, "manifests")
        if create:
            os.makedirs(d, exist_ok=True)
        return d

    def describe(self) -> dict:
        return {"tier": self.tier, "backend": "remote-fs",
                "path": self.objects_dir}


class TieredStore:
    """Read local-then-remote, write-populate both.

    A local miss falls through to the shared remote; a remote hit is
    written back into the local dir so the next restart never leaves the
    machine. A *corrupt* local entry is deleted and transparently
    refetched from the remote (``on_corrupt`` is still told, so the
    cache's corruption stats see it); a corrupt remote entry is deleted
    for the whole fleet and reported as a miss. Remote fetch latency
    lands on ``dl4j_cache_pull_seconds{outcome=hit|miss|error}``."""

    tier = "tiered"

    def __init__(self, local: LocalDirStore, remote: RemoteStore):
        self.local = local
        self.remote = remote
        #: set by the owning cache to route corruption into its
        #: warn-once + stats path
        self.on_corrupt: Optional[Callable[[str, str], None]] = None

    def _corrupt(self, key: str, why: str):
        if self.on_corrupt is not None:
            self.on_corrupt(key, why)
        else:
            log.warning("compile cache entry %s.. dropped (%s)",
                        key[:12], why)

    def contains(self, key: str) -> bool:
        return self.local.contains(key) or self.remote.contains(key)

    def get(self, key: str) -> Optional[Tuple[bytes, dict]]:
        local_why = None
        try:
            entry = self.local.get(key)
            if entry is not None:
                return entry
        except CorruptEntryError as e:
            local_why = e.why  # deleted; try to refetch from the remote
        t0 = time.perf_counter()
        try:
            entry = self.remote.get(key)
        except CorruptEntryError as e:
            observe_pull("error", time.perf_counter() - t0)
            self._corrupt(key, f"remote entry: {e.why}")
            return None
        if entry is None:
            observe_pull("miss", time.perf_counter() - t0)
            if local_why is not None:
                # nothing to refetch: surface the local corruption
                raise CorruptEntryError(local_why)
            return None
        observe_pull("hit", time.perf_counter() - t0)
        if local_why is not None:
            self._corrupt(key, f"{local_why}; refetched from remote store")
        self.local.put(key, entry[0], entry[1])
        return entry

    def put(self, key: str, payload: bytes, meta: dict) -> bool:
        local_ok = self.local.put(key, payload, meta)
        remote_ok = self.remote.put(key, payload, meta)
        return local_ok or remote_ok

    def delete(self, key: str):
        self.local.delete(key)
        self.remote.delete(key)

    def keys(self) -> List[str]:
        """Local-tier keys (what the inventory lists as resident)."""
        return self.local.keys()

    def entry_meta(self, key: str) -> Optional[dict]:
        return self.local.entry_meta(key) or self.remote.entry_meta(key)

    def last_used(self, key: str) -> Optional[float]:
        return self.local.last_used(key)

    def stat(self) -> Dict[str, int]:
        return self.local.stat()

    def clear(self):
        """Clears the *local* tier only: the shared remote outlives any
        one replica (use ``RemoteStore.clear()`` deliberately)."""
        self.local.clear()
        return self

    def tiers(self) -> List[Any]:
        return [self.local, self.remote]

    def enforce_cap(self, max_bytes: int) -> int:
        return self.local.enforce_cap(max_bytes)

    def describe(self) -> dict:
        return {"tier": self.tier, "backend": "tiered"}


def observe_pull(outcome: str, seconds: float):
    """Record one remote-store fetch on
    ``dl4j_cache_pull_seconds{outcome}`` (hit = object downloaded, miss =
    not in the remote, error = corrupt/unreadable remote entry) — the
    boot-time pull latency the fleet cold-start gate bounds."""
    try:
        from ..common.metrics import COMPILE_SECONDS_BUCKETS, registry
        registry().histogram(
            "dl4j_cache_pull_seconds",
            "Remote artifact-store fetch latency by outcome "
            "(hit|miss|error)", labels=("outcome",),
            buckets=COMPILE_SECONDS_BUCKETS).labels(
                outcome=outcome).observe(seconds)
    except Exception:
        pass  # observability must never break the load path


# ---------------------------------------------------------------------------
# the executable cache (policy layer over an ArtifactStore)
# ---------------------------------------------------------------------------

class AOTCompileCache:
    """Executable cache: validation stats, corruption warnings, and LRU
    policy over a pluggable raw store.

    Default store is :class:`LocalDirStore` — entry = ``<key>.bin``
    (serialized XLA executable) + ``<key>.json`` (integrity + reload
    metadata) under ``<dir>/aot``, LRU by file mtime, capped at
    ``max_bytes`` (``DL4J_TPU_CACHE_MAX_BYTES``). With
    ``DL4J_TPU_REMOTE_CACHE`` set the store is a :class:`TieredStore`
    (local + content-addressed shared remote). Every read validates
    format version, payload size, and payload sha256; anything off is
    deleted and reported as a miss — a corrupt cache can cost a compile,
    never an exception."""

    def __init__(self, base_dir: str, max_bytes: int, store=None):
        self.base_dir = base_dir
        self.store = store if store is not None else LocalDirStore(base_dir)
        local = next((t for t in self.store.tiers() if t.tier == "local"),
                     None)
        #: the local tier's flat entry dir (tests poke files here); None
        #: for a remote-only store
        self.aot_dir = local.aot_dir if local is not None else None
        self.max_bytes = int(max_bytes)
        self._lock = ordered_lock("cache.store")
        self._warned_keys: set = set()
        self.stats = {"hits": 0, "misses": 0, "puts": 0, "corrupt": 0,
                      "evictions": 0, "put_errors": 0}
        if isinstance(self.store, TieredStore):
            self.store.on_corrupt = self._warn_once
        self._refresh_store_gauges()

    def _drop(self, key: str):
        self.store.delete(key)

    def _warn_once(self, key: str, why: str):
        with self._lock:
            self.stats["corrupt"] += 1
            if key in self._warned_keys:
                return
            self._warned_keys.add(key)
        log.warning("compile cache entry %s.. dropped (%s); recompiling",
                    key[:12], why)

    def _refresh_store_gauges(self):
        """Per-tier size gauges, refreshed on every store mutation."""
        try:
            from ..common.metrics import registry
            reg = registry()
            g_bytes = reg.gauge(
                "dl4j_cache_store_bytes",
                "Payload bytes resident per artifact-store tier",
                labels=("tier",))
            g_entries = reg.gauge(
                "dl4j_cache_store_entries",
                "Executable entries resident per artifact-store tier",
                labels=("tier",))
            for t in self.store.tiers():
                st = t.stat()
                g_bytes.labels(tier=t.tier).set(st["bytes"])
                g_entries.labels(tier=t.tier).set(st["entries"])
        except Exception:
            pass  # observability must never break the compile path

    # -- read --------------------------------------------------------------
    def get(self, key: str) -> Optional[Tuple[bytes, dict]]:
        """(payload, meta) for a valid entry, else None. Corrupt entries
        are deleted with a one-time warning (a tiered store transparently
        refetches a locally corrupt entry from the remote first)."""
        entry = None
        mutated = False
        try:
            entry = self.store.get(key)
            if entry is not None and faults.active():
                # injected read fault: exercises the corrupt-entry
                # recovery path (drop + warn + recompile) on demand
                faults.check("cache.load", key=key)
        except CorruptEntryError as e:
            self._warn_once(key, e.why)
            entry = None
            mutated = True
        except Exception as e:
            self.store.delete(key)
            self._warn_once(key, f"{type(e).__name__}: {e}")
            entry = None
            mutated = True
        if mutated:
            self._refresh_store_gauges()
        if entry is None:
            with self._lock:
                self.stats["misses"] += 1
            return None
        with self._lock:
            self.stats["hits"] += 1
        return entry

    # -- write -------------------------------------------------------------
    def put(self, key: str, payload: bytes, meta: dict) -> bool:
        """Atomic write (unique tmp + rename), then LRU cap
        enforcement on the local tier."""
        meta = _stamp_meta(payload, meta)
        if not self.store.put(key, payload, meta):
            with self._lock:
                self.stats["put_errors"] += 1
            return False
        with self._lock:
            self.stats["puts"] += 1
        evicted = self.store.enforce_cap(self.max_bytes)
        if evicted:
            with self._lock:
                self.stats["evictions"] += evicted
        self._refresh_store_gauges()
        return True

    # -- maintenance -------------------------------------------------------
    def clear(self):
        self.store.clear()
        self._refresh_store_gauges()
        return self

    def entry_count(self) -> int:
        try:
            return len(self.store.keys())
        except OSError:
            return 0


# ---------------------------------------------------------------------------
# singleton resolution (env-driven, re-resolved when the dir changes)
# ---------------------------------------------------------------------------

_CACHE: Optional[AOTCompileCache] = None
_CACHE_CONF_USED: Optional[Tuple] = None
_CACHE_LOCK = ordered_lock("cache.global")
_BACKSTOP_DIR: Optional[str] = None


def _store_conf() -> Tuple[Optional[str], Optional[str], str]:
    """(cache_dir, remote_cache, cache_tier) — the env triple the
    singleton is keyed on."""
    env = environment()
    return (env.cache_dir(), env.remote_cache(), env.cache_tier())


def _build_store(cache_dir: str, remote: Optional[str], tier: str):
    """Store for the resolved conf: no remote (or tier=local) keeps
    today's LocalDirStore; tier=remote serves straight off the shared
    store; auto/tiered with a remote configured reads local-then-remote
    and write-populates both."""
    if tier == "local" or not remote:
        return LocalDirStore(cache_dir)
    if tier == "remote":
        return RemoteStore(remote)
    return TieredStore(LocalDirStore(cache_dir), RemoteStore(remote))


def cache() -> Optional[AOTCompileCache]:
    """The process-wide store, or None when caching is disabled
    (``DL4J_TPU_CACHE_DIR=""``). Re-resolves if the configured dir,
    remote root, or tier changed since the last call (tests,
    ``Environment.set_cache_dir``/``set_remote_cache``)."""
    global _CACHE, _CACHE_CONF_USED
    conf = _store_conf()
    if conf == _CACHE_CONF_USED:
        return _CACHE
    with _CACHE_LOCK:
        if conf != _CACHE_CONF_USED:
            d, remote, tier = conf
            if d:
                try:
                    _CACHE = AOTCompileCache(
                        d, environment().cache_max_bytes(),
                        store=_build_store(d, remote, tier))
                except OSError as e:
                    log.warning("compile cache dir %s unusable (%s); "
                                "caching disabled", d, e)
                    _CACHE = None
            else:
                _CACHE = None
            _CACHE_CONF_USED = conf
        if _CACHE is not None and _backstop_wanted():
            _configure_backstop(_CACHE.base_dir)
        else:
            _disable_backstop()
    return _CACHE


def reset_cache():
    """Drop the singleton and immediately re-resolve the store conf
    (DL4J_TPU_CACHE_DIR / _REMOTE_CACHE / _CACHE_TIER), re-pointing (or
    disabling) the jax backstop so no compile keeps writing into a stale
    — possibly deleted — directory."""
    global _CACHE, _CACHE_CONF_USED
    with _CACHE_LOCK:
        _CACHE = None
        _CACHE_CONF_USED = None
    cache()


def _backstop_wanted() -> bool:
    """Whether to wire ``jax_compilation_cache_dir`` at ``<dir>/xla``
    (``DL4J_TPU_XLA_CACHE``): "on"/"off" force it; "auto" (default)
    enables it only on accelerator backends. On the CPU backend the raw
    executable store already covers serving-shaped entries, and the
    programs only the backstop would cover (donated train steps) proved
    unstable when XLA:CPU deserializes them under churn — reproducible
    nondeterministic SIGABRTs / corrupted updates mid-train-step across
    full-suite runs, gone with the backstop off — so auto keeps CPU on
    the store alone.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the outside has placed
    jax's cache: it stays there, whatever the mode, and this module never
    touches ``jax_compilation_cache_dir``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return False
    mode = environment().xla_cache()
    if mode == "on":
        return True
    if mode == "off":
        return False
    try:
        import jax
        return jax.default_backend() != "cpu"
    except Exception:
        return False


def _configure_backstop(base_dir: str):
    """Point jax's persistent compilation cache at ``<dir>/xla`` so every
    compile — including the donated/sharded programs the store cannot wrap
    raw — is disk-backed across restarts. Backends without executable
    serialization simply no-op inside jax; this must never raise."""
    global _BACKSTOP_DIR
    xla_dir = os.path.join(base_dir, "xla")
    if _BACKSTOP_DIR == xla_dir:
        return
    try:
        import jax
        os.makedirs(xla_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", xla_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # jax latches its cache object at the first compile of the
        # process; (re)pointing the config only takes effect after an
        # explicit reset
        try:
            from jax._src import compilation_cache as _jcc
            _jcc.reset_cache()
        except Exception:
            pass
        _BACKSTOP_DIR = xla_dir
    except Exception as e:  # unsupported jax version/backend: store-only
        log.debug("persistent-compilation-cache backstop unavailable: %s", e)


def _disable_backstop():
    """Unset the jax compilation-cache dir (store disabled, or its old
    directory is going away)."""
    global _BACKSTOP_DIR
    if _BACKSTOP_DIR is None:
        return
    try:
        import jax
        jax.config.update("jax_compilation_cache_dir", None)
        try:
            from jax._src import compilation_cache as _jcc
            _jcc.reset_cache()
        except Exception:
            pass
        _BACKSTOP_DIR = None
    except Exception:
        pass


# ---------------------------------------------------------------------------
# serving warmup-manifest handoff
# ---------------------------------------------------------------------------

def serving_manifest_dir(create: bool = True) -> Optional[str]:
    """Directory where the serving registry persists per-model warmup
    manifests so the NEXT replica (or the incoming version of a hot swap)
    replays the shapes live traffic exercised before taking traffic.

    ``DL4J_TPU_SERVING_MANIFEST_DIR`` overrides; the default rides the
    executable cache at ``<DL4J_TPU_CACHE_DIR>/manifests`` — the same
    volume a deployment already ships between replicas for AOT
    executables. Returns None when both are disabled (manifests then live
    only in process memory: hot-swap handoff still works, restart replay
    does not)."""
    d = environment().serving_manifest_dir()
    if not d:
        base = environment().cache_dir()
        if not base:
            return None
        d = os.path.join(base, "manifests")
    if create:
        try:
            os.makedirs(d, exist_ok=True)
        except OSError as e:
            log.warning("serving manifest dir %s unusable (%s); manifests "
                        "stay in-memory", d, e)
            return None
    return d


# ---------------------------------------------------------------------------
# fleet handoff: push-on-drain / pull-on-boot over the shared store
# ---------------------------------------------------------------------------

def _tiered() -> Optional[TieredStore]:
    cc = cache()
    if cc is not None and isinstance(cc.store, TieredStore):
        return cc.store
    return None


def _copy_manifests(src: Optional[str], dst: Optional[str]) -> int:
    """Atomic-copy every ``*.warmup.json`` from src into dst; returns the
    count copied."""
    if not src or not dst or not os.path.isdir(src):
        return 0
    try:
        os.makedirs(dst, exist_ok=True)
        names = [n for n in os.listdir(src) if n.endswith(".warmup.json")]
    except OSError:
        return 0
    copied = 0
    for name in names:
        try:
            tmp = os.path.join(dst, name + _tmp_suffix())
            shutil.copyfile(os.path.join(src, name), tmp)
            os.replace(tmp, os.path.join(dst, name))
            copied += 1
        except OSError as e:
            log.warning("manifest copy %s failed (%s)", name, e)
    return copied


def push_to_remote() -> Dict[str, int]:
    """Publish this replica's warm state to the shared store: every local
    executable the remote doesn't have yet, plus the serving warmup
    manifests. Called by ``GracefulLifecycle.drain`` so a draining
    replica's compiles outlive it; safe under concurrent pushers (unique
    tmp + atomic rename per object). No-op without a tiered store."""
    store = _tiered()
    if store is None:
        return {"executables": 0, "manifests": 0}
    pushed = 0
    for key in store.local.keys():
        if store.remote.contains(key):
            continue
        try:
            entry = store.local.get(key)
        except CorruptEntryError:
            continue  # deleted by the read; nothing to publish
        if entry is not None and store.remote.put(key, entry[0], entry[1]):
            pushed += 1
    manifests = _copy_manifests(serving_manifest_dir(create=False),
                                store.remote.manifest_dir(create=True))
    cc = cache()
    if cc is not None:
        cc._refresh_store_gauges()
    if pushed or manifests:
        log.info("pushed %d executables, %d manifests to remote store",
                 pushed, manifests)
    return {"executables": pushed, "manifests": manifests}


def pull_manifests() -> int:
    """Copy the shared store's warmup manifests into the local serving
    manifest dir (overwriting), so ``registry.deploy`` replays the fleet's
    observed shapes instead of starting blind. No-op without a tiered
    store."""
    store = _tiered()
    if store is None:
        return 0
    return _copy_manifests(store.remote.manifest_dir(create=False),
                           serving_manifest_dir(create=True))


def pull_from_remote(keys: Optional[List[str]] = None) -> Dict[str, int]:
    """Boot-time warm restore: download manifests plus every remote
    executable not already local (or just ``keys``) into the local tier.
    Run this *before* ``/readyz`` flips — a replica advertised ready with
    a cold store would compile under live traffic, the exact spike this
    store exists to prevent. Each fetch lands on
    ``dl4j_cache_pull_seconds``. No-op without a tiered store."""
    store = _tiered()
    if store is None:
        return {"executables": 0, "manifests": 0}
    manifests = pull_manifests()
    pulled = 0
    for key in (keys if keys is not None else store.remote.keys()):
        if store.local.contains(key):
            continue
        try:
            if store.get(key) is not None:  # tiered get write-populates
                pulled += 1
        except CorruptEntryError:
            pass  # deleted from the fleet store; next compile republishes
    cc = cache()
    if cc is not None:
        cc._refresh_store_gauges()
    if pulled or manifests:
        log.info("pulled %d executables, %d manifests from remote store",
                 pulled, manifests)
    return {"executables": pulled, "manifests": manifests}


# ---------------------------------------------------------------------------
# AOT entry construction (the counted_jit integration point)
# ---------------------------------------------------------------------------

def _ineligible_reason(args, jit_kwargs: Dict[str, Any]) -> Optional[str]:
    """Why a call may NOT be wrapped as a raw executable (None = may).

    Raw executables bypass jax's arg handling, so refuse anything with
    donation (buffer invalidation — the DecodeEngine's donated-KV steps),
    explicit sharding kwargs / static args (closure semantics), or
    non-array leaves beyond plain python scalars (extended dtypes such as
    PRNG keys lower to internal layouts). Multi-device args ARE eligible:
    the key folds in the device assignment + shardings
    (``_placement_fingerprint``) and ``_load_executor`` reassembles
    sharded outputs into global arrays."""
    import jax

    for k in ("donate_argnums", "donate_argnames"):
        if jit_kwargs.get(k):
            return "donation"
    for k in ("static_argnums", "static_argnames"):
        if jit_kwargs.get(k):
            return "static-args"
    for k in ("in_shardings", "out_shardings"):
        if jit_kwargs.get(k):
            # explicit sharding kwargs ride the live jit (they only appear
            # on training paths, usually next to donation anyway); the
            # serving path shards via committed args, which we do wrap
            return "shardings-kwarg"
    try:
        for leaf in jax.tree_util.tree_leaves(args):
            if isinstance(leaf, (bool, int, float)):
                continue
            dt = getattr(leaf, "dtype", None)
            if dt is None or not hasattr(leaf, "shape"):
                return "non-array"
            if jax.dtypes.issubdtype(dt, jax.dtypes.extended):
                return "extended-dtype"
    except Exception:
        return "args-error"
    return None


def _eligible(args, jit_kwargs: Dict[str, Any]) -> bool:
    return _ineligible_reason(args, jit_kwargs) is None


def cost_analysis(compiled) -> Optional[dict]:
    """XLA cost/memory analysis of a ``jax.stages.Compiled`` as a small
    JSON-able dict — flops, bytes accessed, and the compiled buffer
    sizes. Best-effort: None when the backend exposes neither (the
    inventory then shows the entry without cost columns)."""
    out = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, dict):
            for key, name in (("flops", "flops"),
                              ("bytes accessed", "bytes_accessed")):
                if key in ca:
                    out[name] = float(ca[key])
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for attr, name in (("argument_size_in_bytes", "argument_bytes"),
                           ("output_size_in_bytes", "output_bytes"),
                           ("temp_size_in_bytes", "temp_bytes"),
                           ("generated_code_size_in_bytes", "code_bytes")):
            v = getattr(ma, attr, None)
            if v is not None:
                out[name] = int(v)
    except Exception:
        pass
    return out or None


def _spec_encode(spec) -> list:
    """PartitionSpec -> JSON list (None | axis name | [axis names])."""
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, (tuple, list)):
            out.append([str(n) for n in e])
        else:
            out.append(str(e))
    return out


def _spec_decode(enc):
    from jax.sharding import PartitionSpec as P
    return P(*[tuple(e) if isinstance(e, list) else e for e in enc])


def _sharding_meta(compiled) -> Optional[dict]:
    """mesh + flat in/out PartitionSpecs for a multi-device program (the
    reload recipe ``_load_executor`` uses to place inputs and reassemble
    outputs into global arrays). None for single-device programs. Raises
    on sharding flavors we cannot round-trip (e.g. GSPMDSharding without
    a named mesh) — the caller then treats the entry as bypass."""
    import jax
    from jax.sharding import NamedSharding

    in_leaves = jax.tree_util.tree_leaves(compiled.input_shardings[0])
    out_leaves = jax.tree_util.tree_leaves(compiled.output_shardings)
    if all(len(getattr(s, "device_set", ())) <= 1
           for s in in_leaves + out_leaves):
        return None
    mesh = None

    def desc(s):
        nonlocal mesh
        if not isinstance(s, NamedSharding):
            raise ValueError(
                f"cannot round-trip {type(s).__name__} shardings")
        if mesh is None:
            mesh = s.mesh
        elif s.mesh != mesh:
            raise ValueError("multiple meshes in one program")
        return _spec_encode(s.spec)

    return {"in_specs": [desc(s) for s in in_leaves],
            "out_specs": [desc(s) for s in out_leaves],
            "mesh": {"axes": list(mesh.axis_names),
                     "shape": [int(x) for x in mesh.devices.shape],
                     "device_ids": [int(d.id)
                                    for d in mesh.devices.flat]}}


def _serialize(compiled) -> Tuple[bytes, dict]:
    """(payload, meta) for a ``jax.stages.Compiled``. Raises when the
    backend does not support executable serialization, or when a
    multi-device program's shardings cannot be round-tripped (caller
    treats the entry as bypass; the jax backstop still covers it)."""
    import jax

    exe = compiled.runtime_executable()
    backend = jax.devices()[0].client
    payload = backend.serialize_executable(exe)
    kept = getattr(compiled._executable, "_kept_var_idx", None)
    if kept is None:
        raise ValueError("executable exposes no kept_var_idx")
    # the executable's device assignment, in its own order: deserialize
    # must be handed the same devices back
    meta = {"kept_var_idx": sorted(int(i) for i in kept),
            "device_ids": [int(d.id) for d in exe.local_devices()],
            "created": time.time()}
    sharded = _sharding_meta(compiled)
    if sharded:
        meta.update(sharded)
    cost = cost_analysis(compiled)
    if cost:
        meta["cost"] = cost
    return payload, meta


def _load_executor(payload: bytes, meta: dict, lowered) -> Optional[Callable]:
    """Rebuild a callable from a stored executable: deserialize, then per
    call flatten args in jit order, keep only the argument positions the
    compiled program kept, execute, and unflatten with the lowering's
    output treedef.

    Single-device programs take shard [0] of each result (there is only
    one). Multi-device programs carry their mesh + in/out PartitionSpecs
    in ``meta`` (``_sharding_meta``): inputs are committed to the stored
    input shardings and every result's shards are reassembled into a
    global array via ``jax.make_array_from_single_device_arrays`` (shards
    map by device, so executable device order is irrelevant). Non-donating
    programs only (enforced by ``_ineligible_reason`` before anything is
    stored)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    try:
        if faults.active():
            # injected deserialize fault: the caller must fall back to a
            # live recompile, never surface the failure to a request
            faults.check("cache.deserialize")
        from jax._src.lib import xla_client as xc
        backend = jax.devices()[0].client
        by_id = {d.id: d for d in jax.devices()}
        exe = backend.deserialize_executable(
            payload,
            xc.DeviceList(tuple(by_id[i] for i in meta["device_ids"])))
        kept = meta["kept_var_idx"]
        out_tree = lowered.out_tree
        in_sh = out_sh = out_avals = None
        mesh_meta = meta.get("mesh")
        if mesh_meta:
            devs = np.asarray(
                [by_id[i] for i in mesh_meta["device_ids"]],
                dtype=object).reshape(mesh_meta["shape"])
            mesh = Mesh(devs, tuple(mesh_meta["axes"]))
            in_sh = [NamedSharding(mesh, _spec_decode(s))
                     for s in meta["in_specs"]]
            out_sh = [NamedSharding(mesh, _spec_decode(s))
                      for s in meta["out_specs"]]
            out_avals = jax.tree_util.tree_leaves(lowered.out_info)
            if len(out_avals) != len(out_sh):
                raise ValueError("out_specs/out_info arity mismatch")
    except Exception as e:
        log.warning("compile cache deserialize failed (%s: %s); "
                    "recompiling", type(e).__name__, e)
        return None

    def call(*args):
        flat = jax.tree_util.tree_leaves(args)
        if in_sh is None:
            bufs = [flat[i] if isinstance(flat[i], jax.Array)
                    else jnp.asarray(flat[i]) for i in kept]
        else:
            bufs = [jax.device_put(flat[i], in_sh[i]) for i in kept]
        results = exe.execute_sharded(
            bufs).disassemble_into_single_device_arrays()
        if out_sh is None:
            return jax.tree_util.tree_unflatten(out_tree,
                                                [r[0] for r in results])
        outs = [jax.make_array_from_single_device_arrays(
                    tuple(av.shape), s, r)
                for av, s, r in zip(out_avals, out_sh, results)]
        return jax.tree_util.tree_unflatten(out_tree, outs)

    return call


def aot_entry(jfn, tag: str, args, jit_kwargs: Dict[str, Any]
              ) -> Tuple[Callable, str]:
    """Resolve the callable for one new input signature of ``jfn``.

    Returns ``(callable, label)`` with label in:

    - ``"hit"``    — executable loaded from the store, XLA never ran;
    - ``"miss"``   — lowered + compiled AOT, serialized into the store;
    - ``"bypass:<reason>"`` — caching disabled, entry ineligible for raw
      serialization (e.g. ``bypass:donation`` for the DecodeEngine's
      donated-KV steps), or a step failed (``bypass:lower-error``,
      ``:compile-error``, ``:serialize-error``, ``:store-error``; logged
      at warning): the live ``jax.jit`` dispatch is returned unchanged
      (the jax persistent-cache backstop still shortens its compile when
      enabled). ``dl4j_compiles_total`` records the base label; the
      reasoned form lands on ``dl4j_compile_seconds``, and a stored
      entry that failed to load is observed there as
      ``bypass:deserialize-error`` before it is recompiled.
    """
    cc = cache()
    if cc is None:
        return jfn, "bypass:disabled"
    why = _ineligible_reason(args, jit_kwargs)
    if why is not None:
        return jfn, "bypass:" + why
    try:
        lowered = jfn.lower(*args)
        key = cache_key(lowered, jit_kwargs, args)
    except Exception as e:
        log.warning("AOT lowering failed for %s (%s: %s); live jit", tag,
                    type(e).__name__, e)
        return jfn, "bypass:lower-error"
    entry = cc.get(key)
    if entry is not None:
        t0 = time.perf_counter()
        call = _load_executor(entry[0], entry[1], lowered)
        if call is not None:
            return call, "hit"
        # stale or foreign artifact: drop it, recompile below, and count
        # the drop under its own label — an entry this same
        # jax/jaxlib/backend wrote must load, so a non-zero count outside
        # fault injection is a defect, not a cache miss
        cc._drop(key)
        observe_compile(tag.split(":")[0], "bypass:deserialize-error",
                        time.perf_counter() - t0)
    try:
        compiled = lowered.compile()
    except Exception as e:
        log.warning("AOT compile failed for %s (%s: %s); live jit", tag,
                    type(e).__name__, e)
        return jfn, "bypass:compile-error"
    try:
        payload, meta = _serialize(compiled)
        meta["tag_kind"] = tag.split(":")[0]
        stored = cc.put(key, payload, meta)
    except Exception as e:
        log.warning("executable serialization failed for %s (%s: %s); "
                    "entry not stored", tag, type(e).__name__, e)
        return compiled, "bypass:serialize-error"
    return compiled, ("miss" if stored else "bypass:store-error")


def warm(jfn, args, jit_kwargs: Optional[Dict[str, Any]] = None,
         tag: str = "warm") -> str:
    """Pre-bake one entry without executing it: lower + compile + store
    (and populate the jax backstop) so a later process — or this one —
    starts warm. Unlike ``aot_entry``, ineligible entries (donated train
    steps, sharded programs) are still AOT-compiled here so the backstop
    gets their executable on disk — nothing runs, so donation never
    invalidates a live buffer. Returns the cache label. Used by
    ``FitFastPathMixin.warm_compile`` and CI cache-baking."""
    cc = cache()
    if cc is None:
        return "bypass"
    jit_kwargs = jit_kwargs or {}
    if _eligible(args, jit_kwargs):
        _, label = aot_entry(jfn, tag, args, jit_kwargs)
        return label.partition(":")[0]
    try:
        jfn.lower(*args).compile()
    except Exception as e:
        log.debug("warm compile failed for %s (%s: %s)", tag,
                  type(e).__name__, e)
    return "bypass"


# ---------------------------------------------------------------------------
# executable inventory (the /debug/compile_cache endpoint)
# ---------------------------------------------------------------------------

def inventory() -> dict:
    """The executable store as a JSON-able listing: per entry the cache
    key, tag kind, payload size, creation/last-use times, and the XLA
    cost analysis captured at compile time (flops, bytes accessed,
    buffer sizes); plus per-tier backend/entry-count/byte totals under
    ``"tiers"``. Entries (from the primary tier) sort most-recently-used
    first."""
    cc = cache()
    if cc is None:
        return {"enabled": False, "entries": [], "stats": {},
                "tiers": []}
    entries = []
    for key in cc.store.keys():
        meta = cc.store.entry_meta(key)
        if meta is None:
            continue
        entry = {"key": key, "tag_kind": meta.get("tag_kind"),
                 "payload_bytes": meta.get("payload_bytes"),
                 "created": meta.get("created"),
                 "last_used": cc.store.last_used(key)}
        if meta.get("cost"):
            entry["cost"] = meta["cost"]
        entries.append(entry)
    entries.sort(key=lambda e: e.get("last_used") or 0, reverse=True)
    tiers = []
    for t in cc.store.tiers():
        st = t.stat()
        tiers.append({**t.describe(), "entry_count": st["entries"],
                      "payload_bytes": st["bytes"]})
    with cc._lock:
        stats = dict(cc.stats)
    return {"enabled": True, "dir": cc.base_dir,
            "max_bytes": cc.max_bytes, "entry_count": len(entries),
            "total_payload_bytes": sum(e.get("payload_bytes") or 0
                                       for e in entries),
            "stats": stats, "tiers": tiers, "entries": entries}


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def observe_compile(kind: str, cache_label: str, seconds: float):
    """Record one executable materialization (build + first dispatch) in
    ``dl4j_compile_seconds{kind,cache}``."""
    try:
        from ..common.metrics import COMPILE_SECONDS_BUCKETS, registry
        registry().histogram(
            "dl4j_compile_seconds",
            "Wall time to materialize + first-run an executable, by cache "
            "outcome", labels=("kind", "cache"),
            buckets=COMPILE_SECONDS_BUCKETS).labels(
                kind=kind, cache=cache_label).observe(seconds)
    except Exception:
        pass  # observability must never break the dispatch path
