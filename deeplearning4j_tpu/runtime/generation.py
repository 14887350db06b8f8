"""Generative serving fast path: paged KV cache, batched prefill,
continuous batching, and speculative decoding.

The serving stack through PR 6 pads whole requests through a bucket ladder
and answers them one-shot — it cannot serve autoregressive traffic. This
module is the Orca (OSDI '22) per-iteration scheduling playbook plus the
vLLM/PagedAttention (SOSP '23) block-granular KV cache, plus Leviathan et
al. (2023) draft-model speculative decoding:

- **paged KV cache** — the cache is a block pool
  ``[num_blocks, layers, block_size, heads, head_dim]`` plus a per-slot
  block table, so a sequence only holds ``ceil(len/block_size)`` blocks
  instead of reserving ``max_ctx`` rows up front, and long/short requests
  share one memory budget. Admission is gated on free *blocks* (not just
  free slots), blocks are appended on demand as a sequence grows, and the
  block-table gather happens inside the jitted step so the executable set
  stays fixed. Block 0 is a scratch block: padding and inactive-slot
  writes land there and are masked out of every attention read. When the
  pool runs dry mid-decode the engine preempts the most recently admitted
  sequence (LIFO), returns its blocks, and requeues it at the head of the
  queue with its generated prefix — recompute-style preemption that keeps
  greedy output token-identical.
- **prefix-aware KV reuse** (RadixAttention, SGLang) — blocks are
  content-addressed by token prefix: the refcounted allocator plus a
  radix tree keyed on block-aligned token bytes let an admitted prompt
  attach the longest cached block run (refcount++) and prefill only the
  uncached tail (``paged_prefill``'s ``start_pos`` entry — same
  executables, zero steady-state recompiles). Completed/preempted
  requests *release* refs instead of freeing; their committed full blocks
  stay cached, so a shared system prompt is prefilled once per fleet
  replica and a multi-turn session's next turn re-attaches its whole
  history. Cold cached leaves are reclaimed LRU as the primary reclaim
  path (LIFO preemption stays the backstop); block-aligned sharing means
  a shared block is never written by an attacher — the copy-on-write
  fork is simply a fresh block at the divergence point. Greedy output is
  token-identical to cold prefill by construction. Gate with
  ``prefix_cache=`` / ``deploy(decode_prefix_cache=)`` /
  ``DL4J_TPU_PREFIX_CACHE``.
- **prefill/decode split with batched prefill** — queued prompts that pad
  to the same prompt bucket are coalesced into ONE fixed-shape jitted
  ``prefill`` dispatch (prompt padded up the bucket ladder, group padded
  up a batch ladder — the ``InferenceEngine`` micro-batcher pattern), so
  a burst of prompts costs one dispatch instead of one per prompt; every
  later token costs ONE jitted ``decode`` step shared by all active slots.
- **continuous batching** — requests join and leave the running decode
  batch *per token*: the loop admits pending requests into free slots
  between decode steps, so a short generation admitted after a long one
  finishes first instead of waiting behind it (no head-of-line blocking),
  and a finished slot is recycled immediately (its blocks return to the
  pool).
- **speculative decoding** — with a small draft model configured
  (``draft_model`` + ``spec_k``/``DL4J_TPU_SPEC_DRAFT_K``), each
  all-greedy decode iteration runs ONE jitted ``spec`` step: the draft
  proposes k tokens autoregressively, the target scores all k+1 positions
  in one cache-aware verify pass, and the accepted prefix (longest match
  against the target's own greedy choices, plus one free target token) is
  committed. Output is token-identical to non-speculative greedy by
  construction; sampling riders and near-context-full sequences fall back
  to the plain decode step.
- **sampling** — greedy (temperature 0), temperature, and top-k, all
  per-slot arrays inside the jitted step so mixed sampling configs share
  one executable; per-request ``max_tokens`` and EOS stop host-side.

All steps route through ``counted_jit`` with the cache(s) donated, so the
compile counter observes exactly ``len(prompt buckets) *
len(batch ladder) + 1 (+1 with speculation)`` executables after warmup
and steady-state decode performs **zero recompiles** — the acceptance
invariant of the ``generative_decode`` bench. Donated-cache entries are
store-ineligible by design (``runtime.compile_cache``): they record
``cache=bypass:donation`` on the compile-seconds histogram and rely on
the XLA backstop cache on accelerator backends.

Observability: ``dl4j_decode_requests_total``, ``dl4j_decode_tokens_total``,
``dl4j_decode_steps_total``, ``dl4j_decode_active_slots``,
``dl4j_decode_queue_depth``, ``dl4j_kv_blocks_free{model}``,
``dl4j_decode_preempted_total``, ``dl4j_spec_proposed_tokens_total`` /
``dl4j_spec_accepted_tokens_total``,
``dl4j_kv_prefix_{hits,misses,evictions}_total``,
``dl4j_kv_prefix_blocks{model}``, ``dl4j_decode_ttft_seconds{model}``
(exemplared with trace ids), ``dl4j_decode_itl_seconds{model}``
(inter-token latency), and the goodput split
``dl4j_tokens_total{model,slo=ok|violated}`` — a token is "good" when
its request's TTFT met the per-model latency objective
(``DL4J_TPU_SLO_LATENCY_MS``; with no objective set every token is ok). Each request's trace gains a
``generation/queue`` span (submit → its first prefill dispatch), a
``generation/prefill`` span (the prompt dispatch; with the queue, TTFT)
and a ``generation/decode`` span (first token → finish), and its result
carries a ``phases`` dict (``queue_s``/``prefill_s``/``decode_s``) so
``/debug/requests`` reconstructs — and attributes — a generation's
timeline end to end; ``/debug/decode`` dumps the live slot map and
block tables. The scheduler thread's own loop is live ``span()``s under
one trace id per thread: ``generation/idle`` (waiting for work),
``generation/admit`` (with ``generation/prefill_dispatch`` per prefill
group), ``generation/step`` (children ``generation/ensure_blocks``,
``generation/decode_dispatch``, ``generation/readback`` — the wait for
the device — and ``generation/emit``) and ``generation/reconcile``.
Under a device profile they are host events on the profiler's clock, so
a device gap is attributed to the phase that covered it. Inside the
jitted steps the device operations carry model scopes
(``common.tracing.model_scope``: ``dl4j.embed|attn|attn_core|kv_write|
kv_read|mlp|ln|head|sample``).
"""
from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common import faults
from ..common.environment import environment
from ..common.locks import (ordered_condition, ordered_lock,
                            ordered_rlock)
from ..common.metrics import exponential_buckets, registry
from ..common.tracing import (TraceContext, current_context, model_scope,
                              new_trace_id, record_disposition, span,
                              tracer, use_context)
from .inference import (EngineClosedError, bucket_for, bucket_ladder,
                        counted_jit)

log = logging.getLogger(__name__)


def is_generative_model(model) -> bool:
    """Duck-typed generative-model protocol (``models.causal_lm.CausalLM``):
    the paged-cache trio ``init_paged_kv_cache`` / ``paged_prefill`` /
    ``paged_decode`` (what ``DecodeEngine`` actually serves from), the
    legacy slab trio ``init_kv_cache`` / ``prefill`` / ``decode``, plus a
    params pytree."""
    return all(callable(getattr(model, m, None))
               for m in ("init_kv_cache", "prefill", "decode",
                         "init_paged_kv_cache", "paged_prefill",
                         "paged_decode")) \
        and hasattr(model, "params")


def _cdiv(a: int, b: int) -> int:
    return -(-int(a) // int(b))


# ---------------------------------------------------------------------------
# sampling (runs inside the jitted steps: per-slot arrays, one executable)
# ---------------------------------------------------------------------------

def sample_tokens(logits, temperature, top_k, key):
    """Next-token sampling over ``logits`` [S, V] (f32).

    ``temperature`` [S]: <= 0 means greedy argmax for that slot.
    ``top_k`` [S]: <= 0 disables the top-k filter for that slot.
    Sampling uses the Gumbel-max trick so greedy/temperature/top-k all
    stay one fused program with fixed shapes.
    """
    V = logits.shape[-1]
    with model_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        k = jnp.clip(jnp.where(top_k <= 0, V, top_k), 1, V)
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        thr = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
        masked = jnp.where(scaled >= thr, scaled, -jnp.inf)
        sampled = jnp.argmax(masked + jax.random.gumbel(key, logits.shape),
                             axis=-1).astype(jnp.int32)
        return jnp.where(temperature > 0, sampled, greedy)


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

class _GenRequest:
    __slots__ = ("prompt", "max_tokens", "temperature", "top_k", "eos",
                 "on_token", "future", "ctx", "deadline", "t_submit",
                 "t_first", "t_prefill0", "t_last", "tokens", "slot",
                 "prefix", "admit_seq", "reuse_nodes", "start")

    def __init__(self, prompt, max_tokens, temperature, top_k, eos,
                 on_token, deadline, ctx):
        self.prompt = prompt              # np.int32 [T]
        self.max_tokens = max_tokens
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos = eos                    # int or None
        self.on_token = on_token
        self.future: Future = Future()
        self.ctx = ctx                    # submitter's TraceContext
        self.deadline = deadline          # monotonic instant or None
        self.t_submit = time.perf_counter()
        self.t_first: Optional[float] = None
        # phase boundaries for per-request latency decomposition:
        # queue = [t_submit, t_prefill0), prefill = [t_prefill0,
        # t_first), decode = [t_first, finish). t_last is the previous
        # token's emit instant (the inter-token-latency basis).
        self.t_prefill0: Optional[float] = None
        self.t_last: Optional[float] = None
        self.tokens: List[int] = []
        self.slot: Optional[int] = None
        # the rows a prefill must (re)compute: the prompt, extended with
        # every generated token when the request is preempted/requeued
        self.prefix = prompt              # np.int32 [>=T]
        self.admit_seq = -1               # LIFO preemption order
        # prefix-cache attachment planned at admission: the radix nodes
        # whose blocks this request shares, covering rows [0, start)
        self.reuse_nodes: List = []
        self.start = 0

    def expired(self) -> bool:
        return self.deadline is not None and time.monotonic() >= self.deadline


class _BlockAllocator:
    """Refcounted free-list allocator over KV-pool block ids ``1..total``
    (block 0 is the scratch block and is never handed out). A block is
    freed only when its refcount reaches zero: a slot's block table holds
    one ref per appearance, and the radix prefix cache holds one per
    cached node — so a completed request *releases* shared blocks instead
    of freeing them. Callers hold the engine's scheduler lock around
    every operation."""

    def __init__(self, total: int):
        self.total = int(total)
        self._free = list(range(self.total, 0, -1))  # pop() yields 1 first
        self._refs: Dict[int, int] = {}

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._refs)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def ref(self, block: int) -> int:
        return self._refs.get(int(block), 0)

    def incref(self, ids) -> None:
        """Add one ref per id (attaching a cached block to another owner).
        Unknown ids are ignored — only live blocks can be shared."""
        for b in ids:
            b = int(b)
            if b in self._refs:
                self._refs[b] += 1

    def decref(self, ids) -> int:
        """Drop one ref per id; a block reaching zero returns to the
        pool. Unknown ids and id 0 are ignored (the reconcile pass
        repairs, it must never corrupt). Returns how many blocks were
        actually freed."""
        n = 0
        for b in ids:
            b = int(b)
            r = self._refs.get(b)
            if r is None:
                continue
            if r <= 1:
                del self._refs[b]
                self._free.append(b)
                n += 1
            else:
                self._refs[b] = r - 1
        return n

    # the historical name: releasing a plain (refcount-1) allocation is
    # exactly a decref
    free = decref

    def refcounts(self) -> Dict[int, int]:
        return dict(self._refs)

    def reset_to(self, expected) -> None:
        """Rebuild so exactly ``expected`` is outstanding
        (block-accounting repair): a ``{block: refcount}`` mapping, or a
        bare iterable of ids meaning refcount 1 each."""
        if not isinstance(expected, dict):
            expected = {int(b): 1 for b in expected}
        self._refs = {int(b): int(r) for b, r in expected.items()
                      if 0 < int(b) <= self.total and int(r) > 0}
        self._free = [b for b in range(self.total, 0, -1)
                      if b not in self._refs]


class _RadixNode:
    """One cached block: ``key`` is the block's exact token bytes,
    ``block`` the pool block id holding those rows' KV. ``refs`` counts
    the slots currently attached through this node (0 = evictable once
    it is a leaf); ``digest`` is the chained prefix hash shown by
    ``/debug/decode``."""
    __slots__ = ("key", "digest", "block", "parent", "children", "refs",
                 "last_used")

    def __init__(self, key: bytes, digest: str, block: int, parent):
        self.key = key
        self.digest = digest
        self.block = int(block)
        self.parent = parent
        self.children: Dict[bytes, "_RadixNode"] = {}
        self.refs = 0
        self.last_used = 0


class _RadixCache:
    """Radix tree over block-aligned token prefixes (RadixAttention,
    SGLang): depth ``d`` holds a sequence's ``d``-th full KV block, keyed
    by that block's exact token bytes — content-addressing by value, so
    two requests sharing a system prompt resolve to the same nodes and
    hash collisions are impossible (the sha1 ``digest`` chain is debug
    display only). The tree holds one allocator ref per cached block;
    attached slots add theirs on top. All mutations happen under the
    engine's scheduler lock."""

    def __init__(self, block_size: int):
        self.block_size = int(block_size)
        self.root = _RadixNode(b"", "", 0, None)
        self._nodes: set = set()
        self._clock = 0
        self.evictions = 0          # lifetime LRU evictions

    @property
    def size(self) -> int:
        return len(self._nodes)

    def nodes(self) -> List[_RadixNode]:
        return list(self._nodes)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match(self, tokens) -> List[_RadixNode]:
        """Longest run of cached full blocks prefixing ``tokens`` (walked
        from the root); bumps the run's LRU stamps."""
        out: List[_RadixNode] = []
        node = self.root
        bs = self.block_size
        n = int(len(tokens))
        i = 0
        while i + bs <= n:
            child = node.children.get(tokens[i:i + bs].tobytes())
            if child is None:
                break
            out.append(child)
            node = child
            i += bs
        t = self._tick()
        for nd in out:
            nd.last_used = t
        return out

    def insert(self, tokens, blocks) -> List[_RadixNode]:
        """Record ``blocks[j]`` as the cached KV for token rows
        ``[j*bs, (j+1)*bs)``. Existing nodes win — a duplicate block
        (two identical prompts prefilled cold in one group) stays owned
        by its slot and is freed on release. Returns the newly created
        nodes; the caller takes the tree's allocator ref on each."""
        import hashlib

        node = self.root
        bs = self.block_size
        created: List[_RadixNode] = []
        t = self._tick()
        for j, block in enumerate(blocks):
            if (j + 1) * bs > len(tokens):
                break
            key = tokens[j * bs:(j + 1) * bs].tobytes()
            child = node.children.get(key)
            if child is None:
                digest = hashlib.sha1(
                    node.digest.encode() + key).hexdigest()[:12]
                child = _RadixNode(key, digest, int(block), node)
                node.children[key] = child
                self._nodes.add(child)
                created.append(child)
            child.last_used = t
            node = child
        return created

    def lru_leaf(self) -> Optional[_RadixNode]:
        """Least-recently-used unattached leaf (the next LRU eviction
        victim), or None when nothing is evictable."""
        best = None
        for nd in self._nodes:
            if nd.children or nd.refs > 0:
                continue
            if best is None or nd.last_used < best.last_used:
                best = nd
        return best

    def remove(self, node: _RadixNode) -> None:
        node.parent.children.pop(node.key, None)
        self._nodes.discard(node)

    def reclaimable_count(self, exclude=(), ref_fn=None) -> int:
        """Blocks reclaimable by cascading leaf eviction: nodes whose
        entire subtree is unattached (and not in ``exclude`` — admission
        excludes the nodes a forming prefill group is about to attach).
        ``ref_fn(block)`` is the allocator refcount: a node whose block
        is still owned elsewhere (an active slot inserted it) can be
        *removed* but frees nothing, so it is not counted."""
        ex = set(exclude)

        def walk(nd):
            n, all_ok = 0, True
            for ch in nd.children.values():
                cn, ok = walk(ch)
                n += cn
                all_ok = all_ok and ok
            if all_ok and nd.refs == 0 and nd not in ex:
                frees = ref_fn is None or ref_fn(nd.block) <= 1
                return (n + 1 if frees else n), True
            return n, False

        return sum(walk(ch)[0] for ch in self.root.children.values())


def _shard_kv_pool(mesh, cache_tree):
    """Commit a paged KV pool over the mesh: the heads dim (axis 3 of the
    ``[blocks, layers, block_size, heads, head_dim]`` pool) shards over
    the ``model`` axis when divisible, everything else replicates —
    attention is head-parallel, so each device owns its heads' KV bytes
    end to end."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..common.mesh import MODEL

    size = int(mesh.shape[MODEL]) if MODEL in mesh.axis_names else 1

    def place(leaf):
        if (size > 1 and getattr(leaf, "ndim", 0) == 5
                and leaf.shape[3] % size == 0):
            spec = P(None, None, None, MODEL, None)
        else:
            spec = P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(place, cache_tree)


class DecodeEngine:
    """Continuous-batching autoregressive decode engine over one model,
    serving from a paged (block-granular) KV cache.

    - ``generate(prompt, ...) -> Future`` resolving to a result dict
      (``tokens``, ``finish_reason``, ``ttft_s``, token counts); an
      optional ``on_token`` callback streams tokens as they are sampled.
    - ``warmup()`` pre-compiles one prefill executable per (prompt bucket,
      batch rung) pair plus the decode-step executable (plus the
      speculative step when a draft model is configured).
    - ``drain()/close()/start()`` mirror ``InferenceEngine`` lifecycle so
      the serving registry hot-swaps/parks generative versions the same
      way it does predict engines.

    ``slots`` bounds concurrent sequences (``DL4J_TPU_DECODE_SLOTS``);
    ``max_ctx`` bounds prompt+generation length per sequence
    (``DL4J_TPU_DECODE_MAX_CTX``, capped by the model's position table);
    ``kv_block_size`` (``DL4J_TPU_KV_BLOCK_SIZE``) sets the block
    granularity — clamped to ``max_ctx``, so setting it >= max_ctx
    reproduces the legacy slab layout; ``kv_blocks`` sizes the pool
    (default: slab-equivalent, ``slots * ceil(max_ctx/block_size)``);
    ``prefill_batch`` caps how many same-bucket prompts share one prefill
    dispatch; ``draft_model`` + ``spec_k`` (``DL4J_TPU_SPEC_DRAFT_K``)
    enable greedy speculative decoding; ``prefix_cache``
    (``DL4J_TPU_PREFIX_CACHE``, default on) enables content-addressed
    KV-block reuse across requests and turns.
    """

    def __init__(self, model, *, slots: Optional[int] = None,
                 max_ctx: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 eos_token: Optional[int] = None, seed: int = 0,
                 kv_block_size: Optional[int] = None,
                 kv_blocks: Optional[int] = None,
                 prefill_batch: Optional[int] = None,
                 draft_model=None, spec_k: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 model_name: str = "default",
                 mesh=None, param_spec=None):
        if not is_generative_model(model):
            raise TypeError(
                f"cannot decode a {type(model).__name__}: expected the "
                "generative-model protocol (init_paged_kv_cache/"
                "paged_prefill/paged_decode)")
        env = environment()
        self.model = model
        self.model_name = str(model_name)
        self.slots = int(slots if slots is not None else env.decode_slots())
        max_ctx = int(max_ctx if max_ctx is not None
                      else env.decode_max_ctx())
        pos_cap = getattr(getattr(model, "config", None),
                          "max_position_embeddings", None)
        if pos_cap:
            max_ctx = min(max_ctx, int(pos_cap))
        self.max_ctx = max_ctx
        # prompt-length bucket ladder: one prefill executable per rung.
        # The top rung always covers max_ctx: a preempted rider re-enters
        # the queue with prompt+generated as its prefix, which can exceed
        # the largest explicit bucket (but never max_ctx), and must still
        # be admittable.
        self.ladder = bucket_ladder(self.max_ctx, prompt_buckets)
        if self.ladder[-1] < self.max_ctx:
            self.ladder = self.ladder + (self.max_ctx,)
        # paged-cache geometry: block size clamps to the context window
        # (block_size == max_ctx -> one block per sequence == slab layout)
        bs = int(kv_block_size if kv_block_size is not None
                 else env.kv_block_size())
        self.block_size = max(1, min(bs, self.max_ctx))
        self.max_blocks = _cdiv(self.max_ctx, self.block_size)  # per slot
        pool = int(kv_blocks if kv_blocks is not None
                   else self.slots * self.max_blocks)
        self.kv_blocks = max(1, pool)
        # batched prefill: group same-bucket prompts up a batch ladder
        pb = int(prefill_batch if prefill_batch is not None
                 else min(4, self.slots))
        self.prefill_batch = max(1, min(pb, self.slots))
        self.batch_ladder = bucket_ladder(self.prefill_batch)
        # speculative decoding: draft proposes spec_k tokens per step
        k = int(spec_k if spec_k is not None else env.spec_draft_k())
        self.spec_k = max(0, k)
        self.draft = draft_model
        if self.draft is not None and not is_generative_model(self.draft):
            raise TypeError(
                f"draft_model {type(self.draft).__name__} does not "
                "implement the generative-model protocol")
        self._spec_enabled = self.draft is not None and self.spec_k >= 1
        self.eos_token = eos_token
        self._seed = int(seed)
        self._params = model.params
        # +1: block 0 is the scratch block for padding/inactive writes
        self._cache = model.init_paged_kv_cache(self.kv_blocks + 1,
                                                self.block_size)
        self._dparams = self.draft.params if self._spec_enabled else None
        self._dcache = (self.draft.init_paged_kv_cache(
            self.kv_blocks + 1, self.block_size)
            if self._spec_enabled else None)
        # tensor-parallel decode: params shard over the model axis and the
        # paged KV pool shards over its heads dim (replicated fallback when
        # heads do not divide); jit propagates the committed shardings into
        # the donated prefill/decode steps. mesh=None: single-device path.
        self.mesh = mesh
        self.param_spec = param_spec
        if mesh is not None:
            from ..common.mesh import shard_params, validate_mesh
            validate_mesh(mesh)
            self._params = shard_params(mesh, self._params, param_spec)
            self._cache = _shard_kv_pool(mesh, self._cache)
            if self._spec_enabled:
                self._dparams = shard_params(mesh, self._dparams, param_spec)
                self._dcache = _shard_kv_pool(mesh, self._dcache)
        self._step = 0
        # per-slot host state (the loop thread owns it)
        S = self.slots
        self._tokens = np.zeros(S, np.int32)
        self._lengths = np.zeros(S, np.int32)
        self._temps = np.zeros(S, np.float32)
        self._topks = np.zeros(S, np.int32)
        self._tables = np.zeros((S, self.max_blocks), np.int32)
        self._nblocks = np.zeros(S, np.int32)
        self._alloc = _BlockAllocator(self.kv_blocks)
        # content-addressed prefix reuse over the block pool
        # (DL4J_TPU_PREFIX_CACHE / deploy(decode_prefix_cache=))
        pc = (prefix_cache if prefix_cache is not None
              else env.prefix_cache_enabled())
        self._prefix_cache = bool(pc)
        self._radix = _RadixCache(self.block_size)
        self._slot_nodes: List[List[_RadixNode]] = [[] for _ in range(S)]
        self._slot_req: List[Optional[_GenRequest]] = [None] * S
        self._active_n = 0
        self._admit_counter = 0
        # dispatch serialization: warmup and the loop both step the cache
        self._dispatch_lock = ordered_rlock("decode.dispatch")
        self._warmed: set = set()
        # scheduler state
        self._cv = ordered_condition("decode.scheduler")
        self._pending: List[_GenRequest] = []
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._draining = False
        self._closed = False
        # resilience: supervised-loop state + watchdog-readable dispatch
        # timestamp (serving/resilience.py polls these from outside)
        self._worker_dead = False
        self._dispatch_started_at: Optional[float] = None
        # registry-compat surface (manifest machinery is predict-only)
        self.max_batch = self.slots
        self.manifest_path = None
        self._stats_lock = ordered_lock("decode.stats")
        self._stats = {"requests": 0, "tokens": 0, "decode_steps": 0,
                       "prefills": 0, "prefill_dispatches": 0,
                       "prefill_rows": 0, "expired": 0, "preempted": 0,
                       "spec_steps": 0, "spec_proposed": 0,
                       "spec_accepted": 0, "prefix_hits": 0,
                       "prefix_misses": 0, "prefix_reused_rows": 0}
        self._build_steps()
        reg = registry()
        self._reg = reg
        self._m_requests = reg.counter(
            "dl4j_decode_requests_total",
            "Generation requests accepted by DecodeEngine.generate()")
        self._m_tokens = reg.counter(
            "dl4j_decode_tokens_total",
            "Tokens sampled across prefill + decode steps")
        self._m_steps = reg.counter(
            "dl4j_decode_steps_total",
            "Batched decode dispatches (plain single-token + speculative)")
        self._m_active = reg.gauge(
            "dl4j_decode_active_slots",
            "Sequences currently occupying a decode slot")
        self._m_queue = reg.gauge(
            "dl4j_decode_queue_depth",
            "Generation requests waiting for a free slot")
        self._m_blocks_free = reg.gauge(
            "dl4j_kv_blocks_free",
            "Free KV-cache blocks in the paged decode pool",
            labels=("model",)).labels(model=self.model_name)
        self._m_blocks_free.set(self._alloc.free_count)
        self._m_ttft = reg.histogram(
            "dl4j_decode_ttft_seconds",
            "Time from generate() to the first sampled token",
            labels=("model",),
            buckets=exponential_buckets(1e-3, 2.0, 18)).labels(
                model=self.model_name)
        self._m_itl = reg.histogram(
            "dl4j_decode_itl_seconds",
            "Inter-token latency: gap between consecutive sampled "
            "tokens of one request (the decode-phase tail a reader "
            "actually feels)",
            labels=("model",),
            buckets=exponential_buckets(1e-4, 2.0, 18)).labels(
                model=self.model_name)
        goodput = reg.counter(
            "dl4j_tokens_total",
            "Goodput: tokens emitted, split by whether the owning "
            "request's TTFT met the per-model latency objective "
            "(DL4J_TPU_SLO_LATENCY_MS; no objective -> every token ok)",
            labels=("model", "slo"))
        self._m_tok_ok = goodput.labels(model=self.model_name, slo="ok")
        self._m_tok_violated = goodput.labels(model=self.model_name,
                                              slo="violated")
        self._slo_latency_s = env.slo_latency_s()
        self._m_expired = reg.counter(
            "dl4j_decode_expired_total",
            "Generation requests whose deadline expired before a slot")
        self._m_restarts = reg.counter(
            "dl4j_engine_restarts_total",
            "Supervised engine worker-thread restarts after a crash",
            labels=("engine",)).labels(engine="decode")
        self._m_slot_leaks = reg.counter(
            "dl4j_decode_slot_leaks_total",
            "KV-cache slots found leaked (occupied without a live rider) "
            "and reclaimed by the per-iteration accounting check")
        self._m_block_leaks = reg.counter(
            "dl4j_kv_block_leaks_total",
            "KV-pool blocks whose allocator accounting drifted from the "
            "slot block tables and were repaired by the reconcile pass")
        self._m_cancelled = reg.counter(
            "dl4j_decode_cancelled_total",
            "Riders whose future was cancelled mid-decode; their slot is "
            "freed immediately")
        self._m_preempted = reg.counter(
            "dl4j_decode_preempted_total",
            "Sequences preempted (blocks reclaimed, requeued for "
            "recompute) because the KV block pool ran dry mid-decode")
        self._m_spec_proposed = reg.counter(
            "dl4j_spec_proposed_tokens_total",
            "Draft tokens proposed by speculative decode steps")
        self._m_spec_accepted = reg.counter(
            "dl4j_spec_accepted_tokens_total",
            "Draft tokens accepted (verified equal to the target model's "
            "greedy choice) by speculative decode steps")
        self._m_prefix_hits = reg.counter(
            "dl4j_kv_prefix_hits_total",
            "Admitted prompts that attached at least one cached KV block "
            "from the radix prefix cache (tail-only prefill)")
        self._m_prefix_misses = reg.counter(
            "dl4j_kv_prefix_misses_total",
            "Admitted prompts that found no cached KV prefix and "
            "prefilled cold")
        self._m_prefix_evictions = reg.counter(
            "dl4j_kv_prefix_evictions_total",
            "Cached KV blocks reclaimed from the radix prefix cache "
            "(LRU leaf eviction — the primary reclaim path)")
        self._m_prefix_blocks = reg.gauge(
            "dl4j_kv_prefix_blocks",
            "KV-pool blocks currently held by the radix prefix cache",
            labels=("model",)).labels(model=self.model_name)

    # -- jitted steps ------------------------------------------------------
    def _build_steps(self):
        model = self.model
        draft = self.draft if self._spec_enabled else None
        k = self.spec_k

        def prefill_fn(params, cache, ids, tables, lengths, starts, temps,
                       top_ks, seed, step):
            # starts [B]: rows already committed by attached prefix-cache
            # blocks — the dispatch prefills only the tail (all-zero for
            # a cold prefill; traced, so warm and cold tails share one
            # executable per (bucket, batch) rung)
            cache, logits = model.paged_prefill(params, cache, ids,
                                                tables, lengths, starts)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            toks = sample_tokens(logits, temps, top_ks, key)
            return cache, toks

        def prefill_draft_fn(params, dparams, cache, dcache, ids, tables,
                             lengths, starts, temps, top_ks, seed, step):
            # the draft cache must hold the same committed rows as the
            # target's, so the draft prefills inside the same dispatch
            cache, logits = model.paged_prefill(params, cache, ids,
                                                tables, lengths, starts)
            dcache, _ = draft.paged_prefill(dparams, dcache, ids, tables,
                                            lengths, starts)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            toks = sample_tokens(logits, temps, top_ks, key)
            return cache, dcache, toks

        def decode_fn(params, cache, tables, tokens, lengths, active,
                      temps, top_ks, seed, step):
            cache, logits = model.paged_decode(params, cache, tables,
                                               tokens[:, None], lengths)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            nxt = sample_tokens(logits[:, 0], temps, top_ks, key)
            return cache, jnp.where(active, nxt, tokens)

        def spec_fn(params, dparams, cache, dcache, tables, tokens,
                    lengths, active):
            # greedy-only speculative step (Leviathan et al., 2023):
            # draft proposes k tokens one at a time (k+1 steps — the last
            # is write-only so the draft cache covers every row the
            # target may commit), the target verifies all k+1 positions
            # in ONE cache-aware pass, and the longest drafted prefix
            # matching the target's own greedy choices is committed plus
            # one free target token. Rejected rows are overwritten by the
            # next dispatch's writes before any mask admits them.
            S = tokens.shape[0]
            prev = tokens
            drafted = []
            for j in range(k + 1):
                dcache, dlogits = draft.paged_decode(
                    dparams, dcache, tables, prev[:, None], lengths + j)
                if j < k:
                    prev = jnp.argmax(dlogits[:, 0, :],
                                      axis=-1).astype(jnp.int32)
                    drafted.append(prev)
            d = jnp.stack(drafted, axis=1)                      # [S, k]
            verify_in = jnp.concatenate([tokens[:, None], d], axis=1)
            cache, vlogits = model.paged_decode(params, cache, tables,
                                                verify_in, lengths)
            g = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)  # [S, k+1]
            match = (d == g[:, :k]).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [S]
            idx = jnp.arange(k + 1)[None, :]
            g_at = jnp.take_along_axis(g, n_acc[:, None], axis=1)
            pad_d = jnp.concatenate(
                [d, jnp.zeros((S, 1), jnp.int32)], axis=1)
            commit = jnp.where(idx < n_acc[:, None], pad_d,
                               jnp.where(idx == n_acc[:, None], g_at, 0))
            n_commit = jnp.where(active, n_acc + 1, 0).astype(jnp.int32)
            return cache, dcache, commit, n_commit

        # the KV cache(s) are donated: each step consumes the previous
        # buffers in place (on backends that honor donation) — these
        # entries are deliberately ineligible for the raw executable store
        # and show up as cache=bypass:donation on dl4j_compile_seconds
        # (see compile_cache docs)
        # a quantized twin (quant/transforms.quantize_model) carries
        # _precision — suffix the tag so its executables never collide with
        # the full-precision model's in the persistent store (the first tag
        # segment stays "prefill"/"decode"/"spec": it is the kind metric
        # label)
        prec = getattr(model, "_precision", None)
        suffix = f":{prec}" if prec else ""
        if self._spec_enabled:
            self._prefill = counted_jit(prefill_draft_fn,
                                        "prefill" + suffix,
                                        donate_argnums=(2, 3))
            self._spec = counted_jit(spec_fn, "spec" + suffix,
                                     donate_argnums=(2, 3))
        else:
            self._prefill = counted_jit(prefill_fn, "prefill" + suffix,
                                        donate_argnums=(1,))
            self._spec = None
        self._decode = counted_jit(decode_fn, "decode" + suffix,
                                   donate_argnums=(1,))

    def _run_prefill(self, ids, tables, lengths, starts, temps, top_ks):
        """One batched prefill dispatch: ``ids`` [B, Tb] padded prompt
        *tails*, ``tables`` [B, MB] the target slots' block tables,
        ``lengths`` [B] real total prompt lengths, ``starts`` [B] rows
        already committed by attached cached blocks (0 = cold). Returns
        the B first sampled tokens."""
        if faults.active():
            faults.check("decode.prefill", batch=ids.shape[0],
                         bucket=ids.shape[1])
        with self._dispatch_lock:
            self._dispatch_started_at = time.monotonic()
            try:
                args = (jnp.asarray(ids), jnp.asarray(tables),
                        jnp.asarray(lengths),
                        jnp.asarray(starts, jnp.int32),
                        jnp.asarray(temps, jnp.float32),
                        jnp.asarray(top_ks, jnp.int32),
                        jnp.asarray(self._seed, jnp.int32),
                        jnp.asarray(self._step, jnp.int32))
                if self._spec_enabled:
                    cache, dcache, toks = self._prefill(
                        self._params, self._dparams, self._cache,
                        self._dcache, *args)
                    self._dcache = dcache
                else:
                    cache, toks = self._prefill(self._params, self._cache,
                                                *args)
                self._cache = cache
                self._step += 1
            finally:
                self._dispatch_started_at = None
        return np.asarray(toks)

    def _run_decode(self, active):
        if faults.active():
            faults.check("decode.step", active=int(np.sum(active)))
        with self._dispatch_lock:
            self._dispatch_started_at = time.monotonic()
            try:
                with span("generation/decode_dispatch"):
                    cache, nxt = self._decode(
                        self._params, self._cache,
                        jnp.asarray(self._tables),
                        jnp.asarray(self._tokens),
                        jnp.asarray(self._lengths),
                        jnp.asarray(active), jnp.asarray(self._temps),
                        jnp.asarray(self._topks),
                        jnp.asarray(self._seed, jnp.int32),
                        jnp.asarray(self._step, jnp.int32))
                self._cache = cache
                self._step += 1
            finally:
                self._dispatch_started_at = None
        with span("generation/readback"):     # waits for the device
            return np.asarray(nxt)

    def _run_spec(self, active):
        if faults.active():
            faults.check("decode.step", active=int(np.sum(active)),
                         spec=True)
        with self._dispatch_lock:
            self._dispatch_started_at = time.monotonic()
            try:
                with span("generation/decode_dispatch", spec=True):
                    cache, dcache, commit, n_commit = self._spec(
                        self._params, self._dparams, self._cache,
                        self._dcache, jnp.asarray(self._tables),
                        jnp.asarray(self._tokens),
                        jnp.asarray(self._lengths), jnp.asarray(active))
                self._cache = cache
                self._dcache = dcache
                self._step += 1
            finally:
                self._dispatch_started_at = None
        with span("generation/readback"):     # waits for the device
            return np.asarray(commit), np.asarray(n_commit)

    # -- warmup ------------------------------------------------------------
    def warmup(self, example=None,
               batch_sizes: Optional[Sequence[int]] = None,
               **_ignored) -> List[int]:
        """Compile the ladder before traffic: one prefill executable per
        (prompt bucket, batch rung) pair + the decode-step executable
        (+ the speculative step when enabled). Idempotent. Warmup rows
        use the scratch block table (all zeros) so no live block is
        touched. (``example``/``batch_sizes`` are accepted for
        registry-warmup signature compatibility and ignored: the shapes
        are fixed by the engine's own configuration.)

        ``runtime.warm_image --generative`` runs exactly this warmup to
        pre-bake the ladder into a shared artifact dir; a fleet joiner
        with ``DL4J_TPU_REMOTE_CACHE`` set then pulls the prefill
        executables instead of compiling them. The donated-KV decode
        step is raw-store-ineligible (see ``compile_cache``): it loads
        from the baked ``xla/`` backstop on accelerators and recompiles
        on CPU — bounded at one executable."""
        with self._cv:
            if self._active_n > 0:
                raise RuntimeError(
                    "warmup() while sequences are active would overwrite "
                    "live KV rows; warm before taking traffic")
        warmed = []
        for b in self.ladder:
            for bb in self.batch_ladder:
                key = ("prefill", bb, b)
                if key not in self._warmed:
                    self._run_prefill(np.zeros((bb, b), np.int32),
                                      np.zeros((bb, self.max_blocks),
                                               np.int32),
                                      np.ones(bb, np.int32),
                                      np.zeros(bb, np.int32),
                                      np.zeros(bb, np.float32),
                                      np.zeros(bb, np.int32))
                    self._warmed.add(key)
            warmed.append(b)
        if "decode" not in self._warmed:
            self._run_decode(np.zeros(self.slots, bool))
            self._warmed.add("decode")
        if self._spec_enabled and "spec" not in self._warmed:
            self._run_spec(np.zeros(self.slots, bool))
            self._warmed.add("spec")
        return warmed

    # -- request intake ----------------------------------------------------
    def generate(self, prompt, *, max_tokens: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_token="default", on_token: Optional[Callable] = None,
                 timeout_s: Optional[float] = None) -> Future:
        """Enqueue one generation request; returns a Future resolving to
        ``{"tokens", "finish_reason", "ttft_s", "prompt_tokens",
        "completion_tokens", "tokens_per_sec", "phases"}`` — ``phases``
        decomposes the request's latency into
        ``queue_s``/``prefill_s``/``decode_s``.

        ``timeout_s`` bounds the wait for a decode *slot* (admission into
        the running batch), not the generation itself; an expired request
        fails with ``TimeoutError`` before any model work. ``on_token``
        is called from the decode loop with each sampled token id
        (streaming). ``eos_token="default"`` uses the engine's configured
        EOS; ``None`` disables the stop."""
        ids = np.asarray(prompt, np.int32).reshape(-1)
        if ids.size < 1:
            raise ValueError("prompt must contain at least one token")
        if ids.size >= self.max_ctx:
            raise ValueError(
                f"prompt length {ids.size} leaves no room to generate "
                f"within max_ctx {self.max_ctx}")
        cap = self.max_ctx - int(ids.size)
        if max_tokens is None:
            max_tokens = min(environment().decode_max_tokens(), cap)
        max_tokens = max(1, min(int(max_tokens), cap))
        worst = self._blocks_for(int(ids.size) + max_tokens)
        if worst > self._alloc.total:
            raise ValueError(
                f"request may need {worst} KV blocks "
                f"(prompt {ids.size} + max_tokens {max_tokens}, "
                f"block_size {self.block_size}) but the pool holds only "
                f"{self._alloc.total}; raise kv_blocks or lower "
                "max_tokens")
        eos = self.eos_token if eos_token == "default" else eos_token
        deadline = (time.monotonic() + timeout_s
                    if timeout_s is not None else None)
        req = _GenRequest(ids, max_tokens, temperature, top_k, eos,
                          on_token, deadline, current_context())
        with self._cv:
            if self._draining or self._closed or self._worker_dead:
                raise EngineClosedError(
                    "DecodeEngine is "
                    + ("closed" if self._closed else
                       "draining" if self._draining else
                       "dead (worker restart budget exhausted)")
                    + "; it no longer accepts requests")
            self._pending.append(req)
            depth = len(self._pending)
            self._cv.notify_all()
        with self._stats_lock:
            self._stats["requests"] += 1
        self._m_requests.inc()
        self._m_queue.set(depth)
        self._ensure_thread()
        return req.future

    def generate_sync(self, prompt, **kw) -> Dict[str, Any]:
        return self.generate(prompt, **kw).result()

    # -- the continuous-batching loop --------------------------------------
    def _ensure_thread(self):
        with self._cv:
            if self._draining or self._closed or self._worker_dead:
                return
            if self._thread is None or not self._thread.is_alive():
                self._stopping = False
                self._thread = threading.Thread(
                    target=self._loop_main, name="dl4j-tpu-decode-loop",
                    daemon=True)
                self._thread.start()

    @property
    def worker_dead(self) -> bool:
        """True once the supervised decode loop exhausted its restart
        budget (the watchdog reports this engine unhealthy)."""
        return self._worker_dead

    def _loop_main(self):
        """Supervised decode loop: a crash that escapes the per-iteration
        handler (scheduler-state corruption, not a dispatch fault) is
        counted and the loop restarts with exponential backoff + jitter
        instead of silently killing generation for every later request.
        A crash burst past ``DL4J_TPU_ENGINE_MAX_RESTARTS`` declares the
        worker dead and fails everything queued."""
        policy = faults.RetryPolicy(
            max_restarts=environment().engine_max_restarts(),
            base_s=0.01, max_s=2.0, seed=0)
        while True:
            try:
                # the scheduler's own trace: its loop spans
                # (generation/idle|admit|step|...) nest under one trace id
                # per thread, apart from every request's tree
                with use_context(TraceContext(new_trace_id())):
                    self._loop()
                return  # normal stop
            except Exception:
                log.exception("decode loop crashed; restarting")
                policy.note_failure()
                self._m_restarts.inc()
                if policy.exhausted():
                    self._worker_died()
                    return
                time.sleep(policy.backoff.next_delay())

    def _worker_died(self):
        with self._cv:
            self._worker_dead = True
            pending, self._pending = self._pending, []
            if self._thread is threading.current_thread():
                self._thread = None
            self._cv.notify_all()
        log.error("decode loop exceeded its restart budget; engine "
                  "refuses new work (worker_dead)")
        exc = EngineClosedError(
            "DecodeEngine worker thread permanently failed "
            "(restart budget exhausted)")
        for req in pending:
            if not req.future.done():
                req.future.set_exception(exc)
        self._fail_dispatch_riders(exc)

    def _loop(self):
        while True:
            # deliberate thread-crash site: raises OUTSIDE the
            # per-iteration handler so only the supervisor catches it
            if faults.active():
                faults.check("decode.loop")
            with self._cv:
                while (not self._pending and self._active_n == 0
                       and not self._stopping):
                    # no work: a device gap under this span is the
                    # traffic's, not the scheduler's
                    with span("generation/idle"):
                        self._cv.wait()
                if (self._stopping and not self._pending
                        and self._active_n == 0):
                    if self._thread is threading.current_thread():
                        self._thread = None
                    return
            try:
                with span("generation/admit"):
                    self._admit_pending()
                if self._active_n > 0:
                    with span("generation/step"):
                        self._decode_once()
            except Exception as e:  # a dispatch fault must not strand
                # futures — but it fails only THIS dispatch's riders
                # (the active slots); queued requests stay queued and
                # are admitted fresh on the next iteration
                log.exception("decode dispatch failed; failing its "
                              "riders only")
                self._fail_dispatch_riders(e)
            with span("generation/reconcile"):
                self._reconcile_slots()

    def _fail_dispatch_riders(self, exc: Exception):
        """Fail + release only the sequences that rode the failed
        dispatch (every active slot); pending requests survive."""
        for slot, req in enumerate(list(self._slot_req)):
            if req is not None:
                if not req.future.done():
                    req.future.set_exception(exc)
                if req.ctx is not None:
                    record_disposition(req.ctx.trace_id, "engine_restart")
                self._release_slot(slot)

    def _reconcile_slots(self):
        """Slot- and block-lifecycle assertion: every occupied slot must
        hold a rider whose future is still undelivered or just-finished,
        and the allocator's outstanding-block set must equal the union of
        the occupied slots' block tables — a cancelled/leaked rider or a
        drifted block is reclaimed here and counted, so a KV slot (or
        pool block) can never stay occupied forever (the regressions the
        ``dl4j_decode_slot_leaks_total`` / ``dl4j_kv_block_leaks_total``
        counters exist to catch)."""
        leaked = []
        with self._cv:
            occupied = sum(1 for r in self._slot_req if r is not None)
            if occupied != self._active_n:
                leaked.append(("accounting", occupied - self._active_n))
                self._active_n = occupied
        for slot, req in enumerate(list(self._slot_req)):
            if req is not None and req.future.cancelled():
                self._m_cancelled.inc()
                self._release_slot(slot)
        if leaked:
            self._m_slot_leaks.inc(abs(leaked[0][1]))
            log.warning("decode slot accounting drifted by %d; repaired",
                        leaked[0][1])
        block_drift = 0
        with self._cv:
            # a free slot must hold zero blocks and zero cache
            # attachments; a crashed/cancelled rider's blocks are
            # *decref'd* (not freed): a block shared with the radix cache
            # or another slot survives with its remaining refs
            for slot, req in enumerate(self._slot_req):
                nb = int(self._nblocks[slot])
                if req is None and (nb > 0 or self._slot_nodes[slot]):
                    block_drift += nb
                    self._alloc.decref(self._tables[slot, :nb])
                    for nd in self._slot_nodes[slot]:
                        nd.refs = max(0, nd.refs - 1)
                    self._slot_nodes[slot] = []
                    self._tables[slot, :] = 0
                    self._nblocks[slot] = 0
            # expected refcounts: one per appearance in an occupied
            # slot's table + one per radix-cache node
            expected: Dict[int, int] = {}
            for slot, req in enumerate(self._slot_req):
                if req is None:
                    continue
                for b in self._tables[slot, :int(self._nblocks[slot])]:
                    expected[int(b)] = expected.get(int(b), 0) + 1
            for nd in self._radix.nodes():
                expected[nd.block] = expected.get(nd.block, 0) + 1
            actual = self._alloc.refcounts()
            if expected != actual:
                block_drift += len(
                    {b for b in set(expected) | set(actual)
                     if expected.get(b, 0) != actual.get(b, 0)})
                self._alloc.reset_to(expected)
            # node attachment counts must mirror the slots' lists
            want_refs: Dict[int, int] = {}
            for slot, req in enumerate(self._slot_req):
                if req is None:
                    continue
                for nd in self._slot_nodes[slot]:
                    want_refs[id(nd)] = want_refs.get(id(nd), 0) + 1
            for nd in self._radix.nodes():
                want = want_refs.get(id(nd), 0)
                if nd.refs != want:
                    block_drift += 1
                    nd.refs = want
            free = self._alloc.free_count
            cached = self._radix.size
        self._m_blocks_free.set(free)
        self._m_prefix_blocks.set(cached)
        if block_drift:
            self._m_block_leaks.inc(block_drift)
            log.warning("KV block accounting drifted by %d blocks; "
                        "repaired", block_drift)

    # -- block accounting --------------------------------------------------
    def _blocks_for(self, rows: int) -> int:
        """Blocks a sequence needs to hold ``rows`` KV rows (capped at the
        per-slot maximum — a row index can never reach max_ctx)."""
        return _cdiv(min(int(rows), self.max_ctx), self.block_size)

    def _grow_slot(self, slot: int, rows: int) -> bool:
        """Extend ``slot``'s block table to cover ``rows`` rows, evicting
        LRU cached leaves when the free list alone cannot satisfy it;
        returns False when the pool cannot satisfy it at all. Caller
        holds ``_cv``."""
        need = self._blocks_for(rows)
        have = int(self._nblocks[slot])
        if need <= have:
            return True
        n = need - have
        if n > self._alloc.free_count:
            self._evict_for(n)
        got = self._alloc.alloc(n)
        if got is None:
            return False
        self._tables[slot, have:need] = got
        self._nblocks[slot] = need
        return True

    def _evict_for(self, n: int) -> int:
        """LRU-evict unattached radix leaves until ``n`` blocks are free
        (the primary reclaim path — LIFO preemption stays the backstop
        when the cache has nothing left to give). Removing a leaf can
        expose its parent as the next candidate, so whole cold chains
        unwind oldest-first. Caller holds ``_cv``."""
        evicted = 0
        while self._alloc.free_count < n:
            leaf = self._radix.lru_leaf()
            if leaf is None:
                break
            self._radix.remove(leaf)
            self._alloc.decref([leaf.block])
            evicted += 1
        if evicted:
            self._radix.evictions += evicted
            self._m_prefix_evictions.inc(evicted)
            self._m_prefix_blocks.set(self._radix.size)
        return evicted

    def _available_blocks(self, exclude=()) -> int:
        """Blocks obtainable without preempting anyone: the free list
        plus everything LRU eviction could actually free. Caller holds
        ``_cv``."""
        return self._alloc.free_count + self._radix.reclaimable_count(
            exclude, self._alloc.ref)

    def _match_prefix(self, req: _GenRequest):
        """Longest cached full-block run prefixing ``req.prefix``, capped
        so at least one tail token remains to prefill (the logits of the
        request's first generated token must come from a real dispatch).
        Returns ``(nodes, rows)``. Caller holds ``_cv``."""
        if not self._prefix_cache:
            return [], 0
        nodes = self._radix.match(req.prefix)
        max_rows = len(req.prefix) - 1
        while nodes and len(nodes) * self.block_size > max_rows:
            nodes.pop()
        return nodes, len(nodes) * self.block_size

    def _attach_nodes(self, slot: int, req: _GenRequest) -> None:
        """Share the matched cached blocks into ``slot``'s table:
        refcount++ on each block, attachment++ on each node (pinning it
        against eviction). The request then prefills only its tail — the
        shared blocks are never written (tail and decode rows land in
        blocks allocated at the divergence point: the copy-on-write
        fork). Caller holds ``_cv``."""
        k = len(req.reuse_nodes)
        if k == 0:
            return
        blocks = [nd.block for nd in req.reuse_nodes]
        self._tables[slot, :k] = blocks
        self._nblocks[slot] = k
        self._alloc.incref(blocks)
        for nd in req.reuse_nodes:
            nd.refs += 1
        self._slot_nodes[slot] = list(req.reuse_nodes)

    def _cache_slot_prefix(self, slot: int, req: _GenRequest) -> None:
        """Insert the slot's committed full blocks into the radix tree
        (tree takes one allocator ref per newly cached block) so a later
        request — or this rider itself after a preemption — can
        re-attach them instead of re-prefilling. Caller holds ``_cv``."""
        if not self._prefix_cache:
            return
        committed = int(self._lengths[slot])
        full = committed // self.block_size
        if full <= 0:
            return
        seq = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)]
        ).astype(np.int32)[:committed]
        blocks = [int(b) for b in self._tables[slot, :full]]
        for nd in self._radix.insert(seq, blocks):
            self._alloc.incref([nd.block])
        self._m_prefix_blocks.set(self._radix.size)

    def _blocks_deficit(self, horizon: int) -> int:
        """Additional pool blocks the active set needs so every rider can
        write ``horizon`` more rows. Caller holds ``_cv``."""
        deficit = 0
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            need = self._blocks_for(int(self._lengths[slot]) + horizon)
            deficit += max(0, need - int(self._nblocks[slot]))
        return deficit

    def _ensure_blocks(self, horizon: int):
        """Guarantee every active rider owns blocks for its next
        ``horizon`` rows, preempting the most recently admitted rider
        (LIFO recompute: blocks returned, request requeued at the queue
        head with its generated prefix) when the pool runs dry."""
        while True:
            victim = failed = None
            with self._cv:
                if self._blocks_deficit(horizon) <= self._available_blocks():
                    for slot, req in enumerate(self._slot_req):
                        if req is not None:
                            ok = self._grow_slot(
                                slot, int(self._lengths[slot]) + horizon)
                            assert ok, "deficit accounting went stale"
                    self._m_blocks_free.set(self._alloc.free_count)
                    return
                riders = [(req.admit_seq, slot, req)
                          for slot, req in enumerate(self._slot_req)
                          if req is not None]
                if len(riders) <= 1:
                    # nothing left to preempt: the pool genuinely cannot
                    # host this sequence (generate() validation makes
                    # this unreachable; keep the guard for drifted state)
                    failed = (riders[0][1], riders[0][2])
                else:
                    _, vslot, vreq = max(riders)
                    victim = (vslot, vreq)
            if failed is not None:
                slot, req = failed
                if not req.future.done():
                    req.future.set_exception(RuntimeError(
                        "KV block pool exhausted with no rider left "
                        "to preempt; raise kv_blocks"))
                self._release_slot(slot)
                return
            self._preempt(*victim)

    def _preempt(self, slot: int, req: _GenRequest):
        """Recompute-preemption: drop ``req`` from its slot, return its
        blocks, and requeue it at the queue head with prompt + generated
        tokens as the new prefill prefix (greedy output stays
        token-identical: a prefill over the full prefix yields the same
        next-token argmax the decode path would have). The victim's
        committed full blocks are first inserted into the radix cache, so
        on re-admit the regrown prefix re-attaches them (refcount++) and
        the re-prefill covers only the uncached tail — unless pool
        pressure LRU-evicted them meanwhile, in which case it recomputes
        from scratch exactly as before."""
        with self._cv:
            if self._slot_req[slot] is not req:
                return
            req.prefix = np.concatenate(
                [req.prompt,
                 np.asarray(req.tokens, np.int32)]).astype(np.int32)
            self._pending.insert(0, req)
            depth = len(self._pending)
            self._cache_slot_prefix(slot, req)
        self._release_slot(slot)
        req.slot = None
        with self._stats_lock:
            self._stats["preempted"] += 1
        self._m_preempted.inc()
        self._m_queue.set(depth)
        log.info("preempted slot %d (seq len %d) for KV blocks; requeued",
                 slot, len(req.prefix))

    # -- admission ---------------------------------------------------------
    def _admit_pending(self):
        """Fill free slots from the queue (the per-iteration join half of
        continuous batching: this runs between every decode step).
        Each queued prompt first walks the radix prefix cache: the
        longest cached block run is attached (refcount++) and only the
        uncached *tail* is prefilled, so requests are coalesced by TAIL
        bucket — a warm multi-turn prompt and a fresh short prompt can
        share one dispatch. Admission is capped by free slots, available
        blocks (free + LRU-evictable cached), and ``prefill_batch``; the
        queue head is always first in its group, so admission order
        cannot starve."""
        while True:
            expired: List[_GenRequest] = []
            group: List[_GenRequest] = []
            slots_for: List[int] = []
            bucket = None
            with self._cv:
                while self._pending and self._pending[0].expired():
                    expired.append(self._pending.pop(0))
                free_slots = [i for i, r in enumerate(self._slot_req)
                              if r is None]
                self._m_queue.set(len(self._pending))
                if self._pending and free_slots:
                    head = self._pending[0]
                    h_nodes, h_start = self._match_prefix(head)
                    bucket = bucket_for(len(head.prefix) - h_start,
                                        self.ladder)
                    # blocks promised to the group so far; matched nodes
                    # are pinned out of the evictable budget (attachment
                    # below makes the pin real before any eviction runs)
                    pinned: set = set()
                    committed = 0
                    need = (self._blocks_for(len(head.prefix) + 1)
                            - len(h_nodes))
                    if bucket is not None and need <= \
                            self._available_blocks(set(h_nodes)):
                        head.reuse_nodes, head.start = h_nodes, h_start
                        pinned.update(h_nodes)
                        committed += need
                        group.append(head)
                        cap = min(len(free_slots), self.prefill_batch)
                        for req in self._pending[1:]:
                            if len(group) >= cap:
                                break
                            if req.expired():
                                expired.append(req)
                                continue
                            r_nodes, r_start = self._match_prefix(req)
                            if bucket_for(len(req.prefix) - r_start,
                                          self.ladder) != bucket:
                                continue
                            need = (self._blocks_for(len(req.prefix) + 1)
                                    - len(r_nodes))
                            if committed + need > self._available_blocks(
                                    pinned | set(r_nodes)):
                                continue
                            req.reuse_nodes, req.start = r_nodes, r_start
                            pinned.update(r_nodes)
                            committed += need
                            group.append(req)
                        for req in group + expired:
                            if req in self._pending:
                                self._pending.remove(req)
                        slots_for = free_slots[:len(group)]
                        # attach every member's cached run BEFORE any
                        # grow: attachment pins the nodes, so one
                        # member's eviction can never free a block
                        # another member matched
                        for req, slot in zip(group, slots_for):
                            self._attach_nodes(slot, req)
                        for req, slot in zip(group, slots_for):
                            ok = self._grow_slot(slot,
                                                 len(req.prefix) + 1)
                            assert ok, "admission budget went stale"
                        self._m_blocks_free.set(self._alloc.free_count)
                        self._m_queue.set(len(self._pending))
            for req in expired:
                self._expire(req)
            if not group:
                return
            try:
                self._start_group(group, slots_for, bucket)
            except Exception as e:
                for req, slot in zip(group, slots_for):
                    if not req.future.done():
                        req.future.set_exception(e)
                    with self._cv:
                        blks = self._tables[slot,
                                            :int(self._nblocks[slot])]
                        self._alloc.decref(blks)
                        for nd in self._slot_nodes[slot]:
                            nd.refs = max(0, nd.refs - 1)
                        self._slot_nodes[slot] = []
                        self._tables[slot, :] = 0
                        self._nblocks[slot] = 0
                        self._m_blocks_free.set(self._alloc.free_count)
                    if self._slot_req[slot] is req:
                        self._release_slot(slot)
                return

    def _expire(self, req: _GenRequest):
        if not req.future.done():
            req.future.set_exception(TimeoutError(
                "generation deadline expired before a decode slot freed"))
        with self._stats_lock:
            self._stats["expired"] += 1
        self._m_expired.inc()
        if req.ctx is not None and self._reg.enabled:
            tracer().record("generation/queue_expired", req.t_submit,
                            time.perf_counter(), context=req.ctx,
                            prompt_tokens=int(req.prompt.size),
                            error="TimeoutError")

    def _start_group(self, group: List[_GenRequest], slots: List[int],
                     bucket: int):
        """Prefill a same-TAIL-bucket group of prompts in ONE dispatch
        (padded up the batch ladder; padding rows write the scratch
        block) and sample each request's first token (the TTFT-defining
        dispatch). A member with an attached cached prefix ships only its
        uncached tail — ``starts[r]`` rows are already committed in its
        shared blocks."""
        B = len(group)
        bb = bucket_for(B, self.batch_ladder)
        ids = np.zeros((bb, bucket), np.int32)
        tables = np.zeros((bb, self.max_blocks), np.int32)
        lengths = np.ones(bb, np.int32)
        starts = np.zeros(bb, np.int32)
        temps = np.zeros(bb, np.float32)
        topks = np.zeros(bb, np.int32)
        for r, (req, slot) in enumerate(zip(group, slots)):
            p = req.prefix
            s = int(req.start)
            tail = p[s:]
            ids[r, :tail.size] = tail
            tables[r] = self._tables[slot]
            lengths[r] = p.size
            starts[r] = s
            temps[r] = req.temperature
            topks[r] = req.top_k
        t0 = time.perf_counter()
        with span("generation/prefill_dispatch", bucket=bucket, batch=B):
            toks = self._run_prefill(ids, tables, lengths, starts, temps,
                                     topks)
        t_done = time.perf_counter()
        hits = sum(1 for req in group if req.start > 0)
        reused = int(sum(req.start for req in group))
        with self._stats_lock:
            self._stats["prefills"] += B
            self._stats["prefill_dispatches"] += 1
            self._stats["prefill_rows"] += int(
                sum(len(req.prefix) - req.start for req in group))
            if self._prefix_cache:
                self._stats["prefix_hits"] += hits
                self._stats["prefix_misses"] += B - hits
                self._stats["prefix_reused_rows"] += reused
        if self._prefix_cache:
            if hits:
                self._m_prefix_hits.inc(hits)
            if B - hits:
                self._m_prefix_misses.inc(B - hits)
        for r, (req, slot) in enumerate(zip(group, slots)):
            tok = int(toks[r])
            first = req.t_first is None
            if req.t_prefill0 is None:
                # first prefill dispatch closes the queue phase; a
                # preempted rider keeps its original boundary so queue
                # attribution stays honest across requeues
                req.t_prefill0 = t0
                if req.ctx is not None:
                    tracer().record("generation/queue", req.t_submit, t0,
                                    context=req.ctx)
            if first:
                req.t_first = t_done
            if self._reg.enabled:
                if first:
                    self._m_ttft.observe(
                        req.t_first - req.t_submit,
                        exemplar=req.ctx.trace_id if req.ctx else None)
                if req.ctx is not None:
                    tracer().record(
                        "generation/prefill", t0, t_done, context=req.ctx,
                        slot=slot, prompt_tokens=int(req.prefix.size),
                        cached_tokens=int(req.start),
                        bucket=bucket, batch=B,
                        queue_s=round(t0 - req.t_submit, 6))
            req.slot = slot
            with self._cv:
                self._admit_counter += 1
                req.admit_seq = self._admit_counter
                self._slot_req[slot] = req
                self._active_n += 1
            self._m_active.set(self._active_n)
            self._tokens[slot] = tok
            self._lengths[slot] = int(req.prefix.size)
            self._temps[slot] = req.temperature
            self._topks[slot] = req.top_k
            with self._cv:
                # publish the just-committed prompt blocks: a storm
                # follower sharing this prompt attaches them while this
                # rider is still decoding (decode writes land strictly
                # past the prefix, never inside a published block)
                self._cache_slot_prefix(slot, req)
            self._emit_token(req, tok)
            self._check_stop(req, slot, tok)

    # -- decode ------------------------------------------------------------
    def _spec_ready(self) -> bool:
        """True when this iteration can take the speculative step: every
        active rider is greedy and has k+1 rows of context headroom, and
        the pool can cover the k+1-row write horizon without preempting
        anyone (speculation is a throughput luxury — it must never evict
        a rider that plain decode could serve)."""
        if not self._spec_enabled:
            return False
        k = self.spec_k
        with self._cv:
            riders = [slot for slot, r in enumerate(self._slot_req)
                      if r is not None]
            if not riders:
                return False
            for slot in riders:
                if self._temps[slot] > 0:
                    return False
                if int(self._lengths[slot]) + k + 1 > self.max_ctx:
                    return False
            return self._blocks_deficit(k + 1) <= self._available_blocks()

    def _decode_once(self):
        with span("generation/ensure_blocks"):
            spec = self._spec_ready()
            self._ensure_blocks(self.spec_k + 1 if spec else 1)
        active = np.array([r is not None for r in self._slot_req])
        if not active.any():
            return
        if spec:
            self._spec_once(active)
        else:
            nxt = self._run_decode(active)
            with self._stats_lock:
                self._stats["decode_steps"] += 1
            self._m_steps.inc()
            with span("generation/emit"):
                for slot, req in enumerate(list(self._slot_req)):
                    if req is None:
                        continue
                    self._lengths[slot] += 1
                    tok = int(nxt[slot])
                    self._tokens[slot] = tok
                    self._emit_token(req, tok)
                    self._check_stop(req, slot, tok)

    def _spec_once(self, active):
        commit, n_commit = self._run_spec(active)
        k = self.spec_k
        n_active = int(np.sum(active))
        accepted = int(np.sum(np.maximum(n_commit[active] - 1, 0)))
        with self._stats_lock:
            self._stats["decode_steps"] += 1
            self._stats["spec_steps"] += 1
            self._stats["spec_proposed"] += k * n_active
            self._stats["spec_accepted"] += accepted
        self._m_steps.inc()
        self._m_spec_proposed.inc(k * n_active)
        self._m_spec_accepted.inc(accepted)
        with span("generation/emit"):
            for slot, req in enumerate(list(self._slot_req)):
                if req is None:
                    continue
                for j in range(int(n_commit[slot])):
                    tok = int(commit[slot, j])
                    self._lengths[slot] += 1
                    self._tokens[slot] = tok
                    self._emit_token(req, tok)
                    self._check_stop(req, slot, tok)
                    if self._slot_req[slot] is not req:
                        break  # finished mid-prefix: drop the rest

    def _emit_token(self, req: _GenRequest, tok: int):
        req.tokens.append(tok)
        with self._stats_lock:
            self._stats["tokens"] += 1
        self._m_tokens.inc()
        if self._reg.enabled:
            now = time.perf_counter()
            if req.t_last is not None:
                self._m_itl.observe(now - req.t_last)
            req.t_last = now
            # goodput: every token of a request whose TTFT met the
            # latency objective counts as slo=ok; a late first token
            # taints the whole request's tokens. No configured
            # objective (slo_latency_s() -> None) means nothing can
            # violate — mirrors SLOTracker.
            obj = self._slo_latency_s
            ttft = (req.t_first - req.t_submit) \
                if req.t_first is not None else None
            (self._m_tok_ok if obj is None
             or (ttft is not None and ttft <= obj)
             else self._m_tok_violated).inc()
        if req.on_token is not None:
            try:
                req.on_token(tok)
            except Exception:
                log.exception("on_token callback raised; token dropped "
                              "from the stream")

    def _check_stop(self, req: _GenRequest, slot: int, tok: int):
        reason = None
        if req.eos is not None and tok == req.eos:
            reason = "eos"
        elif len(req.tokens) >= req.max_tokens:
            reason = "length"
        elif int(self._lengths[slot]) >= self.max_ctx:
            reason = "length"   # context full: no cache row left to write
        if reason is not None:
            self._finish(req, slot, reason)

    def _finish(self, req: _GenRequest, slot: int, reason: str):
        t_done = time.perf_counter()
        if req.ctx is not None and self._reg.enabled:
            tracer().record("generation/decode", req.t_first or t_done,
                            t_done, context=req.ctx, slot=slot,
                            tokens=len(req.tokens), finish_reason=reason)
        with self._cv:
            # cache prompt + generated full blocks for the session's next
            # turn (the client re-sends its history: the warm turn
            # attaches these and prefills only the new user tail)
            self._cache_slot_prefix(slot, req)
        self._release_slot(slot)
        ttft = ((req.t_first - req.t_submit)
                if req.t_first is not None else None)
        gen_s = t_done - (req.t_first or req.t_submit)
        phases = {
            "queue_s": round(req.t_prefill0 - req.t_submit, 6)
            if req.t_prefill0 is not None else None,
            "prefill_s": round(req.t_first - req.t_prefill0, 6)
            if req.t_first is not None and req.t_prefill0 is not None
            else None,
            "decode_s": round(t_done - req.t_first, 6)
            if req.t_first is not None else None,
        }
        if not req.future.done():
            req.future.set_result({
                "tokens": list(req.tokens),
                "finish_reason": reason,
                "prompt_tokens": int(req.prompt.size),
                "completion_tokens": len(req.tokens),
                "ttft_s": round(ttft, 6) if ttft is not None else None,
                "tokens_per_sec": round(len(req.tokens) / gen_s, 3)
                if gen_s > 0 else None,
                "phases": phases,
            })

    def _release_slot(self, slot: int):
        with self._cv:
            if self._slot_req[slot] is not None:
                self._slot_req[slot] = None
                self._active_n -= 1
            # the slot RELEASES its blocks (refcount--): a block cached
            # in the radix tree or shared with another slot survives
            # with its remaining refs, the rest return to the pool.
            # Stale KV rows stay in freed blocks but lengths=0 + a
            # zeroed table masks them out of every future attention
            # (poison-value test)
            nb = int(self._nblocks[slot])
            if nb > 0:
                self._alloc.decref(self._tables[slot, :nb])
                self._tables[slot, :] = 0
                self._nblocks[slot] = 0
            for nd in self._slot_nodes[slot]:
                nd.refs = max(0, nd.refs - 1)
            self._slot_nodes[slot] = []
            self._lengths[slot] = 0
            self._tokens[slot] = 0
            free = self._alloc.free_count
            self._cv.notify_all()
        self._m_active.set(self._active_n)
        self._m_blocks_free.set(free)

    # -- lifecycle (registry-compatible) -----------------------------------
    @property
    def draining(self) -> bool:
        return self._draining and not self._closed

    @property
    def closed(self) -> bool:
        return self._closed

    def start(self):
        with self._cv:
            if self._closed:
                raise EngineClosedError(
                    "DecodeEngine is closed; it cannot be restarted")
            self._draining = False
        self._ensure_thread()
        return self

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Stop admitting, finish queued + in-flight generations, stop the
        loop. Reversible via ``start()`` (the registry parks retired
        generative versions warm, same as predict engines)."""
        deadline = time.monotonic() + timeout_s
        with self._cv:
            self._draining = True
            self._stopping = True
            self._cv.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        with self._cv:
            leftovers, self._pending = self._pending, []
            drained = (self._active_n == 0
                       and (t is None or not t.is_alive()))
        for req in leftovers:
            if not req.future.done():
                req.future.set_exception(EngineClosedError(
                    "DecodeEngine drained before this request was "
                    "scheduled"))
        return drained

    def close(self, timeout_s: float = 30.0) -> bool:
        self._closed = True
        return self.drain(timeout_s)

    def stop(self):
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=30)
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- introspection -----------------------------------------------------
    def observed_entries(self) -> List[dict]:
        """Manifest handoff compatibility: generative warmup is fully
        determined by (slots, max_ctx, ladder, batch ladder), so there is
        nothing to replay from observed traffic."""
        return []

    def debug_snapshot(self) -> Dict[str, Any]:
        """Live slot map + block tables for ``GET /debug/decode`` and the
        flight recorder: which sequence owns which slot, how many rows it
        committed, and which pool blocks back it — plus the ``kernels``
        section: which attention/dequant path served the last dispatch
        (kernel name, chosen path, fallback reason), straight from
        ``kernels.dispatch_snapshot()``. Dispatch happens at trace time,
        so that section describes the executables this process compiled,
        not per-request routing."""
        with self._cv:
            slots = []
            for slot, req in enumerate(self._slot_req):
                nb = int(self._nblocks[slot])
                entry = {"slot": slot, "active": req is not None,
                         "length": int(self._lengths[slot]),
                         "blocks": [int(b)
                                    for b in self._tables[slot, :nb]]}
                if req is not None:
                    entry.update({
                        "prompt_tokens": int(req.prompt.size),
                        "generated": len(req.tokens),
                        "temperature": req.temperature,
                        "trace_id": req.ctx.trace_id if req.ctx else None,
                    })
                slots.append(entry)
            snap = {
                "model": self.model_name,
                "slots": slots,
                "queue_depth": len(self._pending),
                "pool": {"block_size": self.block_size,
                         "total_blocks": self._alloc.total,
                         "free_blocks": self._alloc.free_count,
                         "max_blocks_per_slot": self.max_blocks,
                         "scratch_block": 0},
                "prefix_cache": {
                    "enabled": self._prefix_cache,
                    "cached_blocks": self._radix.size,
                    "evictions": self._radix.evictions,
                    # most-recently-used first, bounded for the endpoint
                    "nodes": [{"digest": nd.digest, "block": nd.block,
                               "refs": nd.refs,
                               "children": len(nd.children),
                               "last_used": nd.last_used}
                              for nd in sorted(
                                  self._radix.nodes(),
                                  key=lambda n: -n.last_used)[:64]],
                },
                "prefill": {"batch": self.prefill_batch,
                            "buckets": list(self.ladder),
                            "batch_ladder": list(self.batch_ladder)},
                "speculative": {"enabled": self._spec_enabled,
                                "k": self.spec_k},
                "worker_dead": self._worker_dead,
                "draining": self._draining,
                "closed": self._closed,
            }
            try:
                from ..kernels import dispatch_snapshot
                snap["kernels"] = dispatch_snapshot()
            except Exception:
                snap["kernels"] = {}
            if self.mesh is not None:
                from ..common.mesh import mesh_shape, spec_desc
                snap["mesh_shape"] = mesh_shape(self.mesh)
                snap["param_spec"] = spec_desc(self.param_spec)
        with self._stats_lock:
            snap["speculative"]["proposed"] = self._stats["spec_proposed"]
            snap["speculative"]["accepted"] = self._stats["spec_accepted"]
            prop = self._stats["spec_proposed"]
            snap["speculative"]["acceptance_rate"] = (
                round(self._stats["spec_accepted"] / prop, 4)
                if prop else None)
        return snap

    def stats(self) -> Dict[str, Any]:
        with self._stats_lock:
            s = dict(self._stats)
        with self._cv:
            s["active_slots"] = self._active_n
            s["queued"] = len(self._pending)
            s["kv_blocks_free"] = self._alloc.free_count
            s["prefix_cached_blocks"] = self._radix.size
            s["prefix_evictions"] = self._radix.evictions
        s["prefix_cache"] = self._prefix_cache
        s["slots"] = self.slots
        s["max_ctx"] = self.max_ctx
        s["prompt_buckets"] = list(self.ladder)
        s["kv_block_size"] = self.block_size
        s["kv_blocks"] = self.kv_blocks
        s["prefill_batch"] = self.prefill_batch
        s["spec_k"] = self.spec_k if self._spec_enabled else 0
        if s["spec_proposed"]:
            s["spec_acceptance"] = round(
                s["spec_accepted"] / s["spec_proposed"], 4)
        return s
