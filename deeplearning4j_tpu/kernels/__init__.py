"""Pallas TPU kernels — the hand-written hot-op layer.

Role parity: the reference's per-op vendor kernels
(`libnd4j/include/ops/declarable/platform/{cudnn,mkldnn}/`) — ops where
letting the compiler lower naively leaves performance on the table. On TPU
that list is short (XLA fuses most of the op library); the kernels here
cover the known gaps for the flagship workloads:

- `flash_attention`: attention with the scores kept in VMEM, forward and a
  full Pallas backward — no [S,S] HBM materialization in either direction.
  Up to S=512 a head is one tile and the backward one fused kernel; longer
  sequences stream tiles with an online softmax.
- `attention_dispatch` / `attention`: the one full-sequence attention core
  the models call. `attention_dispatch` chooses between the kernel and
  XLA by a measured rule (`_flash_rule` has the sweep: on a v5e the kernel
  wins training from S=256 up, 2.9x at BERT's S=512 with heads of 64),
  once per traced model; `attention(q, k, v, path=...)` runs the chosen
  core over packed ``[B,T,H·D]`` or ``[B,T,H,D]`` operands with
  ``Hkv <= H`` KV heads: the kernel with the KV heads repeated here, or
  the one XLA core (f32 scores, key and/or causal mask, f32 softmax); a
  causal call may name a sliding ``window``, which the streaming kernels
  walk as a band of blocks.
- `paged_flash_decode`: the decode-side counterpart — walks the paged KV
  block tables in-kernel (scalar-prefetch) with online-softmax
  accumulation, replacing the `jnp.take` gather read of
  `models.causal_lm.paged_decode` (gated by ``DL4J_TPU_PAGED_KERNEL``).
- `ssm_fused.mamba_chain`: the elementwise chain of a Mamba-2 block
  (`models.hybrid_lm`) around the caller's scan, as two fused operations
  (conv + SiLU; skip + gate + grouped RMSNorm): each operand read once and
  each result written once in the stored dtype, float32 in registers,
  hand-written backwards, time as the minor axis. No dispatch: the model
  calls it on every backend.
- `ssd_scan`: the scan between those two operations, Mamba-2's chunked
  (SSD) recurrence, as a forward and a hand-written backward kernel that
  walk a row's chunks with the ``[Q, Q]`` decay, score and weight tiles
  and the carried state in VMEM; `ops.ssm_scan.ssd_chunked_scan` is its
  entry. No dispatch either.

Packed rows (several documents in a sequence, ``segment_ids``): the
attention core, the flash kernels, `mamba_chain`'s conv and the scan take
the ids and keep each document to itself; `boundary_pass` counts those
passes.

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

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention, flash_attention_with_lse
from .paged_flash_decode import paged_flash_decode

__all__ = ["flash_attention", "flash_attention_with_lse",
           "paged_flash_decode", "attention", "attention_dispatch",
           "kernel_dispatch", "dispatch_snapshot", "boundary_pass"]

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


def boundary_pass(kernel: str, kind: str) -> None:
    """Tick ``dl4j_boundary_kernel_passes_total{kernel,kind}``: one traced
    pass of a kernel that takes document boundaries (packed rows): the fused
    conv + SiLU of `ssm_fused` (kernel "conv_silu", kind fwd | bwd), the
    chunked scan (kernel "ssm_scan", kind fwd | bwd) and the
    flash-attention kernels (kernel "flash", kind fwd | dq | dkv |
    one_tile_fwd | one_tile_bwd). Beside ``dl4j_flash_tiles_total`` and
    ``dl4j_ssm_fused_calls_total``, which count every pass."""
    try:
        from ..common.environment import environment
        environment().metrics().counter(
            "dl4j_boundary_kernel_passes_total",
            "Kernel passes traced with document boundaries (packed rows), "
            "counted at trace time",
            labels=("kernel", "kind")).labels(kernel=kernel, kind=kind).inc()
    except Exception:
        pass  # observability must never break a trace


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
    if jax.default_backend() == "cpu":
        return "paged", "cpu backend (auto gates the kernel to accelerators)"
    from .paged_flash_decode import tileable
    if not tileable(head_dim, block_size):
        return "paged", (f"untileable pool layout: head_dim={head_dim} "
                         f"block_size={block_size}")
    return "paged_flash", ""


#: shortest sequence at which the flash kernel is chosen on an accelerator
#: (``_flash_rule``'s table)
_FLASH_MIN_SEQ = 256
#: narrowest head the sweep covers; below it the one-tile kernel would
#: spend a full-depth MXU pass on each of four or more heads a block
_FLASH_MIN_HEAD_DIM = 64


def _flash_rule(seq_len, head_dim):
    """The measured rule for the non-paged path: ("flash" | "xla", reason).

    One layer's attention core on one TPU v5e chip, ms per layer, q/k/v
    bf16 with B·T = 4,096 tokens and H·D = 1,024 (the training cell's
    sizes), an all-ones key mask when not causal; forward alone, and
    forward+backward under ``jax.grad``. "kernel" is
    ``flash_attention`` as it stands (one tile up to T=512, streaming
    above), "before" the streaming kernel it was at every length
    (``attn_sweep.py``, chip runs of PR 26):

            T    D  causal |  XLA fwd  fwd+bwd | kernel fwd  fwd+bwd | before
          128   64  no     |    0.034    0.185 |      0.129    0.326 | 0.381  1.263
          128   64  yes    |    0.035    0.187 |      0.129    0.322 | 0.297  1.081
          256   64  no     |    0.106    0.549 |      0.125    0.307 | 0.303  1.082
          256   64  yes    |    0.133    0.553 |      0.125    0.302 | 0.325  0.985
          512   64  no     |    0.416    1.169 |      0.118    0.402 | 0.278  0.994
          512   64  yes    |    0.418    1.170 |      0.115    0.374 | 0.305  0.879
         1024   64  no     |    0.819    2.897 |      0.413    1.649 | (same code)
         1024   64  yes    |    0.812    2.908 |      1.180    1.482 | (same code)
         2048   64  no     |    1.619    5.591 |      0.684    3.000 | (same code)
         2048   64  yes    |    3.697    5.583 |      0.820    2.555 | (same code)
          128  128  no     |    0.040    0.142 |      0.125    0.310 | 0.195  0.585
          128  128  yes    |    0.040    0.143 |      0.125    0.306 | 0.155  0.460
          256  128  no     |    0.062    0.243 |      0.080    0.223 | 0.154  0.502
          256  128  yes    |    0.064    0.247 |      0.080    0.218 | 0.163  0.437
          512  128  no     |    0.225    0.706 |      0.067    0.230 | 0.146  0.465
          512  128  yes    |    0.226    0.719 |      0.065    0.214 | 0.157  0.405
         1024  128  no     |    0.994    1.766 |      0.212    0.817 | (same code)
         1024  128  yes    |    0.994    1.768 |      0.269    0.723 | (same code)
         2048  128  no     |    1.983    4.081 |      0.344    1.479 | (same code)
         2048  128  yes    |    1.981    4.072 |      0.414    1.254 | (same code)

    Under ``causal`` the streaming kernels have since skipped the tiles
    the mask empties, fetched nothing for them, masked only the tiles on
    the diagonal and taken 1,024 x 1,024 tiles in all three passes
    (``flash_attention._default_tiles``). The causal rows from T=1,024
    again, the kernel before ("parent", PR 26's streaming code, re-read)
    and after (``attn_sweep.py`` against ``--kernel <the parent's file>``,
    chip runs of PR 28; the rows without ``causal`` read the same on both,
    0.413 / 1.649 and 0.344 / 1.480 at T=1,024, D=64 and T=2,048, D=128):

            T    D  causal |  XLA fwd  fwd+bwd | kernel fwd  fwd+bwd | parent
         1024   64  yes    |    0.812    2.908 |      0.237    1.048 | 0.538  1.481
         1024  128  yes    |    0.994    1.767 |      0.115    0.485 | 0.269  0.723
         2048   64  yes    |    3.698    5.584 |      0.423    1.642 | 0.821  2.556
         2048  128  yes    |    1.981    4.075 |      0.214    0.795 | 0.414  1.254

    And the hybrid language model's attention blocks (``models.hybrid_lm``:
    one sequence of 8,192, 32 query heads of 128 on 2 repeated KV heads,
    B·T = 8,192, H·D = 4,096; ``attn_sweep.py --only 8192x128x1 --tokens
    8192 --hidden 4096 --layers 2``; XLA's path does not fit the chip,
    16.25 GB of scores), 36 of a head's 64 tiles live where the parent
    computed 64 forward and 256 backward:

         8192  128  yes    |    out of memory |      5.236   19.188 | 12.791 39.615

    And latent attention's core (``models.hybrid_lm``'s ``L`` blocks: two
    rows of 8,192, 32 heads whose queries and keys are 192 wide and whose
    values are 128 wide, B·T = 16,384; ``attn_sweep.py --only 8192x192x1
    --v-head-dim 128 --tokens 16384 --hidden 4096 --layers 2``, chip run of
    PR 35; XLA's path does not fit), beside the same two rows at equal
    widths of 128; the 192-wide contraction costs the MXU what 256 costs:

         8192  192/128  yes |  out of memory |     15.207   58.236
         8192  128/128  yes |  out of memory |     10.763   38.769

    The crossover lies between 128 and 256 for both head sizes, causal or
    not, so the rule takes no ``causal``: at 128 XLA wins everything
    (a grid step's fixed cost, about 0.4 µs, is most of a [128, 128] tile's
    time); from 256 up the kernel wins every forward+backward row, by 1.1x
    (T=256, D=128) to 3.3x; forward alone it wins from 512 up and loses
    0.02 ms a layer at 256, which the rule accepts for training's sake.
    One forward row of the first table is out of line, T=1024 D=64 causal
    (1.18 ms against XLA's 0.81): PR 28's sweep did not reproduce it (the
    same code read 0.538) and the kernel now reads 0.237 there, so no row
    is out of line any more.
    ``head_dim`` below 64 was not measured and stays on XLA; ``None`` (a
    caller that does not say) is not checked."""
    if jax.default_backend() == "cpu":
        return "xla", "cpu backend (the kernel would run interpreted)"
    if seq_len < _FLASH_MIN_SEQ:
        return "xla", f"seq_len<{_FLASH_MIN_SEQ} (measured crossover)"
    if head_dim is not None and head_dim < _FLASH_MIN_HEAD_DIM:
        return "xla", f"head_dim<{_FLASH_MIN_HEAD_DIM} (not measured)"
    return "flash", ""


def attention_dispatch(seq_len: int, paged: bool = False, *,
                       head_dim: Optional[int] = None,
                       block_size: Optional[int] = None) -> str:
    """Auto-dispatch for attention: "flash", "xla", "paged", or
    "paged_flash".

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

    The non-paged path follows ``_flash_rule``: what one sweep on the
    chip says wins, from ``seq_len``, ``head_dim`` and the backend (the
    causal rows of the sweep agree with the others), with the reason
    recorded when XLA is taken. On the CPU backend that is always XLA
    (the kernel would run in the Pallas interpreter); a test that wants a
    model on the interpreted kernel substitutes ``_flash_rule`` (the
    ``flash_everywhere`` fixture of ``tests/conftest.py``). Evaluated at
    trace time (shapes are static under jit), so the
    ``dl4j_attn_dispatch_total{path=}`` and
    ``dl4j_kernel_dispatch_total{kernel,path}`` counters tick once per
    traced model (a model asks once for all its layers and hands the
    answer to ``attention`` as ``path``), and the debug log fires once
    per process.

    Decode-shaped queries (seq_len < 2 — the KV-cached single-token step
    of ``runtime.generation.DecodeEngine``) take the XLA path
    UNCONDITIONALLY on the non-paged path, whatever the rule says: a
    1-row query can never amortize the Pallas kernel's blocking, and the
    decode executable must stay stable."""
    global _dispatch_logged
    from ..common.environment import environment

    env = environment()
    reason = ""
    if paged:
        path, reason = _paged_path(env, head_dim, block_size)
    elif int(seq_len) < 2:
        path, reason = "xla", "seq_len<2 decode pin"
    else:
        path, reason = _flash_rule(int(seq_len), head_dim)
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
            "attention at seq_len=%d takes the XLA path: %s",
            seq_len, reason)
    return path


def attention(q, k, v, *, path: str, head_dim: int, mask=None,
              causal: bool = False, scale: Optional[float] = None,
              segment_ids=None, v_head_dim: Optional[int] = None,
              window: Optional[int] = None):
    """The full-sequence attention core on ``path`` ("flash" | "xla", what
    ``attention_dispatch`` answered for the traced model).

    ``q`` is ``[B, T, H*D]`` or ``[B, T, H, D]``; ``k`` and ``v`` likewise
    with ``Hkv <= H`` heads (``H % Hkv == 0``: query head ``i`` reads KV
    head ``i // (H // Hkv)``). ``mask``: optional ``[B, T]`` key validity
    (1 = attend). ``scale`` multiplies the scores ``q . k`` before the
    softmax; None is ``head_dim ** -0.5`` (a model with a scalar attention
    multiplier of its own hands it over here). ``segment_ids``: optional
    ``[B, T]`` int32, the document each position of a packed row belongs
    to: key ``j`` is visible to query ``i`` iff their ids are equal (and
    ``j <= i`` under ``causal``, and ``mask[j]``); None is one document a
    row, and traces to what it traced to before the argument existed.
    ``v_head_dim`` (None: ``head_dim``) is the values' width where it is
    not the queries' and keys' (latent attention: 192-wide scores,
    128-wide values); ``v`` is then ``[B, T, Hkv*Dv]`` or ``[B, T, Hkv,
    Dv]``. ``window`` W (with ``causal``): key ``j`` is visible to query
    ``i`` iff ``i - W < j <= i``, W keys at most, the query's own among
    them; None traces to what it traced to before the argument existed.
    Returns the context in ``q``'s layout and dtype, ``Dv`` wide a head.

    "flash" repeats the KV heads for the query heads that share them (the
    kernel's entry takes as many KV heads as query heads) and calls
    ``flash_attention`` in the layout given (with a window, the streaming
    kernels over the band's blocks alone). "xla" is the plain core: f32
    scores, masks, f32 softmax, weighted sum."""
    D = head_dim
    Dv = D if v_head_dim is None else v_head_dim
    B, T = q.shape[:2]
    H, Hkv = q.size // (B * T * D), k.size // (B * T * D)
    R = H // Hkv
    if path == "flash":
        if R > 1:
            k, v = (jnp.repeat(x.reshape(B, T, Hkv, d), R, axis=2)
                    for x, d in ((k, D), (v, Dv)))
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               head_dim=D, scale=scale,
                               segment_ids=segment_ids, v_head_dim=v_head_dim,
                               window=window)
    big_neg = jnp.finfo(jnp.float32).min
    k, v = (x.reshape(B, T, Hkv, d) for x, d in ((k, D), (v, Dv)))
    s = jnp.einsum("btgrd,bsgd->bgrts", q.reshape(B, T, Hkv, R, D), k,
                   preferred_element_type=jnp.float32) * (
                       D ** -0.5 if scale is None else scale)
    if mask is not None:
        s = jnp.where(mask[:, None, None, None, :].astype(bool), s, big_neg)
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]
        s = jnp.where(same[:, None, None], s, big_neg)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, big_neg)
    if window is not None:
        pos = jnp.arange(T)
        s = jnp.where(pos[:, None] - pos[None, :] < window, s, big_neg)
    prob = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrts,bsgd->btgrd", prob, v).reshape(
        q.shape[:2] + ((H, Dv) if q.ndim == 4 else (H * Dv,)))
