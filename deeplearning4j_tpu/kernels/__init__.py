"""Pallas TPU kernels — the hand-written hot-op layer.

Role parity: the reference's per-op vendor kernels
(`libnd4j/include/ops/declarable/platform/{cudnn,mkldnn}/`) — ops where
letting the compiler lower naively leaves performance on the table. On TPU
that list is short (XLA fuses most of the op library); the kernels here
cover the known gaps for the flagship workloads:

- `flash_attention`: online-softmax attention with a full Pallas backward —
  no [S,S] HBM materialization in either direction. Its speed against
  XLA attention is not measured on current code.
- `paged_flash_decode`: the decode-side counterpart — walks the paged KV
  block tables in-kernel (scalar-prefetch) with online-softmax
  accumulation, replacing the `jnp.take` gather read of
  `models.causal_lm.paged_decode` (gated by ``DL4J_TPU_PAGED_KERNEL``).

A fused vocab-tiled softmax-xent kernel lived here through round 3 and was
deleted after honest tuning kept it behind XLA at the BERT headline shape
(N=16384, V=30522, f32; best Pallas config tn=256 tv=2048): 0.93x forward,
0.61x training vs XLA's 35.4ms/35.2ms. XLA's exp/reduce fusion already
saturates this op; a kernel would need to fuse the producing matmul to win,
which belongs to a future logits-never-materialized head design.

The kernels run `interpret=True` on CPU so the unit tests exercise the
exact kernel code path hardware-free.
"""
from typing import Dict, Optional

from .flash_attention import flash_attention, flash_attention_with_lse
from .paged_flash_decode import paged_flash_decode

__all__ = ["flash_attention", "flash_attention_with_lse",
           "paged_flash_decode", "attention_dispatch", "kernel_dispatch",
           "dispatch_snapshot"]

_dispatch_logged = False

#: last trace-time path decision per kernel family — what
#: ``DecodeEngine.debug_snapshot`` (GET /debug/decode, flight recorder)
#: reports as "which path served the most recent compile in this process"
_last_dispatch: Dict[str, Dict[str, Optional[str]]] = {}


def kernel_dispatch(kernel: str, path: str, reason: str = "") -> str:
    """Record one trace-time kernel-vs-fallback decision: ticks
    ``dl4j_kernel_dispatch_total{kernel,path}`` and updates the
    last-dispatch snapshot. ``reason`` says why a fallback won (empty for
    the hand-written kernel path). Returns ``path`` so dispatchers can
    tail-call it."""
    _last_dispatch[kernel] = {"kernel": kernel, "path": path,
                              "reason": reason or None}
    try:
        from ..common.environment import environment
        environment().metrics().counter(
            "dl4j_kernel_dispatch_total",
            "Hand-written-kernel vs fallback path decisions per kernel "
            "family, evaluated at trace time",
            labels=("kernel", "path")).labels(
                kernel=kernel, path=path).inc()
    except Exception:
        pass  # observability must never break a trace
    return path


def dispatch_snapshot() -> Dict[str, Dict[str, Optional[str]]]:
    """Copy of the last dispatch decision per kernel family:
    ``{kernel: {"kernel", "path", "reason"}}``. Process-global (dispatch
    happens at trace time, once per compiled executable)."""
    return {k: dict(v) for k, v in _last_dispatch.items()}


def _paged_path(env, head_dim, block_size):
    """Path for ``paged=True`` dispatch: "paged_flash" (the Pallas
    block-table kernel) or "paged" (the XLA gather fallback), plus the
    fallback reason. Deliberately independent of the query length — see
    attention_dispatch's docstring."""
    if head_dim is None or block_size is None:
        # gather-view callers that never hand over tiling info (e.g.
        # paged_prefill) stay on the gather path by contract
        return "paged", "caller provides no tile info (gather-view path)"
    mode = env.paged_kernel()
    if mode == "off":
        return "paged", "DL4J_TPU_PAGED_KERNEL=off"
    if mode == "on":
        return "paged_flash", ""
    # auto: hardware only, and only when the pool layout tiles natively
    import jax
    if jax.default_backend() == "cpu":
        return "paged", "cpu backend (auto gates the kernel to accelerators)"
    from .paged_flash_decode import tileable
    if not tileable(head_dim, block_size):
        return "paged", (f"untileable pool layout: head_dim={head_dim} "
                         f"block_size={block_size}")
    return "paged_flash", ""


def attention_dispatch(seq_len: int, paged: bool = False, *,
                       head_dim: Optional[int] = None,
                       block_size: Optional[int] = None) -> str:
    """Auto-dispatch for ``flash=True`` attention configs: "flash",
    "xla", "paged", or "paged_flash".

    ``paged=True`` marks the paged-KV decode path
    (``models.causal_lm.paged_decode``): when the caller passes the pool
    tiling (``head_dim``/``block_size``) the Pallas block-table kernel
    ("paged_flash") is eligible per ``DL4J_TPU_PAGED_KERNEL`` — "auto"
    (default) takes it on accelerator backends when
    ``paged_flash_decode.tileable`` holds, "on" forces it (interpret
    mode off-accelerator), "off" pins the XLA gather fallback ("paged").
    The decision deliberately ignores ``seq_len``: on the paged path the
    query length is the *per-slot* token count — 1 for the decode step,
    k+1 for the speculative verify — and both must land on the same path
    or a spec-k engine would flap between executables mid-stream. The
    seq<2 XLA pin below applies only to the non-paged (slab) path, where
    seq_len really is the attention width. Gather-view callers that pass
    no tiling info (``paged_prefill``) always get "paged".

    At short sequences the Pallas kernel's blocking does not pay (where
    the crossover lies is not measured on current code), so below
    ``DL4J_TPU_FLASH_MIN_SEQ`` (default 1024) flash-requesting models
    take the XLA path and the decision is recorded with its reason. Evaluated at trace time (shapes are static under jit), so the
    ``dl4j_attn_dispatch_total{path=}`` and
    ``dl4j_kernel_dispatch_total{kernel,path}`` counters tick once per
    compiled executable, and the debug log fires once per process.

    Decode-shaped queries (seq_len < 2 — the KV-cached single-token step
    of ``runtime.generation.DecodeEngine``) take the XLA path
    UNCONDITIONALLY on the non-paged path, whatever
    ``DL4J_TPU_FLASH_MIN_SEQ`` says: a 1-row query can never amortize the
    Pallas kernel's blocking, and the decode executable must stay stable
    across env retunes."""
    global _dispatch_logged
    from ..common.environment import environment

    env = environment()
    reason = ""
    if paged:
        path, reason = _paged_path(env, head_dim, block_size)
    elif int(seq_len) < 2:
        path, reason = "xla", "seq_len<2 decode pin"
    elif int(seq_len) >= env.flash_min_seq():
        path = "flash"
    else:
        path, reason = "xla", "seq_len<DL4J_TPU_FLASH_MIN_SEQ"
    try:
        env.metrics().counter(
            "dl4j_attn_dispatch_total",
            "Attention path decisions for flash=True configs",
            labels=("path",)).labels(path=path).inc()
    except Exception:
        pass  # observability must never break a trace
    kernel_dispatch("paged_decode" if paged else "attention", path, reason)
    if path == "xla" and not _dispatch_logged:
        _dispatch_logged = True
        import logging
        logging.getLogger(__name__).debug(
            "flash=True requested at seq_len=%d < DL4J_TPU_FLASH_MIN_SEQ=%d;"
            " using the XLA attention path (override the threshold via the"
            " env var)", seq_len, env.flash_min_seq())
    return path
