"""Flash attention — Pallas TPU kernels (softmax in VMEM, O(S) memory, fwd+bwd).

Reference counterpart: the vendor-accelerated attention path
(`libnd4j/include/ops/declarable/platform/cudnn/` attention kernels and
`helpers/AttentionHelper.h`). On TPU the hot path is a Pallas kernel that
keeps score tiles in VMEM with f32 softmax statistics and never
materializes the [S, S] probability matrix in HBM — forward OR backward.
Matmuls run in the input dtype with f32 accumulation; max, exp and sum are
f32; probabilities are cast to the input dtype only for p·v, where the XLA
path casts them too.

Two shapes of the same algorithm, chosen by the static sequence length:

**One tile** (padded S <= 512, no tiles given — the training shapes): a
head's whole [S, S] score tile lives in VMEM, so nothing streams, nothing
accumulates across grid steps and nothing but q, k, v is saved for the
backward, which is ONE kernel. Blocks come straight from the [B, S, H*D]
layout of the q/k/v projections, two heads of 64 to a 128-lane block. See
the section comment below.

**Streaming** (longer S, or explicit tiles): q/k/v are [BH, S, D]
(batch*heads flattened; callers reshape) and all three kernels use a 3-D
grid whose innermost dimension is the *sequential* stream (kv blocks for
fwd/dq, q blocks for dkv), so Mosaic double-buffers the streamed blocks
while f32 accumulators persist in VMEM scratch across the sequential steps:

  fwd : grid (BH, nQ, nK)  scratch m/l/acc     outputs o, lse=m+log(l)
  dq  : grid (BH, nQ, nK)  scratch dq_acc      p recomputed from q,k,lse
  dkv : grid (BH, nK, nQ)  scratch dk/dv_acc   ds = p * (g·vᵀ − delta)

delta = rowsum(o ⊙ do) is precomputed with plain XLA (one elementwise pass).

The forward rules keep o and lse for the backward under two names,
``SAVED_OUT`` and ``SAVED_LSE`` (``jax.ad_checkpoint.checkpoint_name``; lse
as the lane-dense ``[BH, S]`` view, the column restored in the backward
rule). A ``jax.checkpoint`` whose policy saves those names
(``models.hybrid_lm._blocks``) then recomputes the rest of its body in the
backward but not the forward kernel; without such a policy the names do
nothing. The one-tile kernels and the (o, lse)-returning variant name
nothing.

Under ``causal=True`` the streaming kernels do work only for the tiles that
hold an unmasked (query, key) pair. A grid step whose tile lies wholly above
the diagonal runs no matmul, no exp and no accumulator update, and its
block index maps name the block that is resident already, so nothing is
fetched for it; of the live tiles only those the diagonal crosses build the
iota/compare/select of the mask. The grids keep their shape (the skipped
steps are the trailing kv steps of fwd/dq and the leading q steps of dkv),
``dl4j_flash_tiles_total{kernel,kind}`` counts both kinds per traced pass,
and a call without ``causal`` traces to kernels without any of this.

**Packed rows.** With ``segment_ids`` (``[B, S]`` int32: the document a
position belongs to) a key is visible only to the queries of its own
document. The kernels take the ids twice, as a column ``[B, S, 1]`` for the
rows of a score tile and as a row ``[B, 1, S]`` for its columns. Under
``causal`` the three passes also take a table of where the documents lie
by blocks (`_DocTable`: for each query block the first key block its
documents reach, for each key block the last query block, and each block's
least and greatest id), made from the ids once a call outside the kernels
and prefetched into SMEM. A tile that lies wholly between two documents is
then skipped as the tiles above the diagonal are: no matmul, no exp, and
index maps clamped to the live run of blocks, so nothing is fetched for it;
a live tile inside one document computes without comparing ids, and only
the tiles a boundary crosses compare them. The outputs are those of every
causal tile computed, to the bit. Ids that do not decrease along a row
give the most skipping; any ids give the same results. A call without
``segment_ids`` traces to the kernels it traced to before: no operand, no
compare. ``dl4j_boundary_kernel_passes_total{kernel,kind}`` counts the
passes traced with ids; `document_tiles` says how a row's grid falls.

**A window.** With ``window`` W (causal calls only) key ``j`` is visible to
query ``i`` iff ``i - W < j <= i``: at most W keys, the query's own among
them. The three passes then walk only the blocks the band reaches
(`_Band`): the inner grid axis of fwd and dq is a query block's band of key
blocks, from the first the band reaches, and that of dkv a key block's band
of query blocks, from the diagonal's, so a row of 16,384 at W = 1,024 and
1,024 x 1,024 tiles has 2 steps a block and not 16. A step past the band's
end (the rows near a sequence's start, whose band is narrower) does no
matmul, no exp and no fetch: its index maps name the band's last block,
resident already. Only the tiles a band edge crosses (the diagonal or the
window's far edge) build the mask; a tile wholly inside the band runs
unmasked. ``dl4j_flash_tiles_total{kernel,kind}`` counts the band's tiles
as ``window_computed`` and the rest of the square grid as
``window_skipped``, ``dl4j_flash_window_passes_total{kernel,window}`` the
passes. A call without ``window`` traces to the kernels it traced to before
the argument existed. A window goes with neither ``segment_ids`` nor a key
mask (not built); a window call always streams.

Sequence lengths that don't divide the tiles are zero-padded to the tile
boundary (padded keys masked off, padded query rows sliced away). A fully
masked row degrades to a uniform softmax — identical to what the XLA
softmax produces for an all-−1e30 row — with one difference under
``causal`` and a key mask together (left padding, a ring's diagonal shard):
a row that sees no valid key averages v over the keys of the tiles its
query block visits, up to the end of the key tile that holds the block's
last diagonal element, not over all S. Such a row is garbage by contract
either way; its lse stays at the −1e30 floor that
``parallel/ring_attention`` reads as "no live key".

Tests run interpret mode on CPU; the real chip runs compiled. Times on the
chip against XLA: `kernels._flash_rule` (measured by `attn_sweep.py`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

#: what the streaming forward rules (`_flash`, `_flash_masked`, `_flash_seg`)
#: name their output and log-sum-exp: a ``jax.checkpoint`` policy that saves
#: these two (``hybrid_lm._blocks``) keeps a rematerialised backward from
#: launching the forward kernel again
SAVED_OUT = "dl4j_flash_out"
SAVED_LSE = "dl4j_flash_lse"


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _params(n_parallel):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _on_live_tile(step, iq, ik, tq, tk, docs=None, band=None):
    """Run ``step(masked)`` once if the [tq, tk] tile (iq, ik) is live
    under a causal mask — with the mask only where the diagonal crosses
    the tile — and not at all if the mask empties it.

    With ``docs`` (a packed call's `_DocTable`, bound to its operand) a
    tile that holds no pair of one document is not live either, and a live
    one runs ``step(masked, apart)``: ``apart`` False where the tile lies
    inside one document, so that its ids need no compare. With ``band`` (a
    windowed call's `_Band`) a tile is live where it holds a pair of the
    band, and masked where a band edge crosses it."""
    if band is not None:
        whole = band.whole(iq, ik)
        pl.when(whole)(lambda: step(False))
        pl.when(band.live(iq, ik) & jnp.logical_not(whole))(
            lambda: step(True))
        return
    live = ik * tk <= iq * tq + (tq - 1)     # some key <= some query
    below = ik * tk + (tk - 1) <= iq * tq    # every key <= every query
    if docs is None:
        pl.when(below)(lambda: step(False))
        pl.when(jnp.logical_and(live, jnp.logical_not(below)))(
            lambda: step(True))
        return
    bh = pl.program_id(0)
    live = live & docs.live(bh, iq, ik)
    whole = docs.whole(bh, iq, ik)
    for masked, where in ((False, below), (True, ~below)):
        for apart, kind in ((False, whole), (True, ~whole)):
            pl.when(live & where & kind)(
                functools.partial(step, masked, apart))


class _DocTable:
    """Where a packed causal call's documents lie, by blocks: one int32
    operand ``[B * 3 (n_q + n_k)]`` that the three streaming passes
    prefetch into SMEM (`_doc_table` makes it). For each batch row, in
    turn: ``lo`` [n_q], the first key block that a query block can share a
    document with; ``hi`` [n_k], the last query block that a key block
    can; the least and the greatest id of each query block; those of each
    key block. A ``bh`` is a grid's first index, ``heads`` of them to a
    batch row. Bound to the operand's ref with `at` (an index map's or the
    kernel's)."""

    def __init__(self, heads, n_q, n_k, ref=None):
        self.heads, self.n_q, self.n_k, self.ref = heads, n_q, n_k, ref

    def at(self, ref):
        return _DocTable(self.heads, self.n_q, self.n_k, ref)

    def _get(self, bh, part, i):
        n_q, n_k = self.n_q, self.n_k
        start = (0, n_q, n_q + n_k, 2 * n_q + n_k, 3 * n_q + n_k,
                 3 * n_q + 2 * n_k)[part]
        # one head a row (the host's count) indexes with plain integers
        row = bh if self.heads == 1 else jax.lax.div(bh, self.heads)
        return self.ref[row * (3 * (n_q + n_k)) + start + i]

    def lo(self, bh, iq):
        return self._get(bh, 0, iq)

    def hi(self, bh, ik):
        return self._get(bh, 1, ik)

    def live(self, bh, iq, ik):
        """Whether tile (iq, ik) can hold a pair of one document: the tiles
        it rules out are a prefix of a query block's key blocks and a
        suffix of a key block's query blocks, which the index maps clamp
        away."""
        return (ik >= self.lo(bh, iq)) & (iq <= self.hi(bh, ik))

    def whole(self, bh, iq, ik):
        """Whether every query and every key of the tile has one id: the
        queries' greatest is the keys' least and the queries' least the
        keys' greatest."""
        return ((self._get(bh, 3, iq) == self._get(bh, 4, ik))
                & (self._get(bh, 2, iq) == self._get(bh, 5, ik)))


def _doc_table(seg, tile_q, tile_k, xp=jnp):
    """`_DocTable`'s operand, ``[B, 3 (n_q + n_k)]`` int32, from a packed
    call's ids ``seg`` [B, S] (S a multiple of both tiles). Made once a
    call, outside the kernels; ``xp`` numpy counts the tiles of a row on
    the host (`document_tiles`). Blocks ``meet`` where their id ranges
    overlap: blocks that do not meet share no document, whatever the ids.
    Where ids do not decrease along a row (a document is one run), the
    blocks that meet a query block are the run of key blocks from ``lo``,
    and those of a key block the run of query blocks up to ``hi``, so
    every tile that is not live holds no pair of one document."""
    B, S = seg.shape
    n_q, n_k = S // tile_q, S // tile_k
    qb, kb = seg.reshape(B, n_q, tile_q), seg.reshape(B, n_k, tile_k)
    q_min, q_max, k_min, k_max = qb.min(-1), qb.max(-1), kb.min(-1), kb.max(-1)
    meet = ((k_max[:, None, :] >= q_min[:, :, None])
            & (k_min[:, None, :] <= q_max[:, :, None]))      # [B, n_q, n_k]
    # a query block meets the key block of its last query, a key block the
    # query block of its first key: neither search comes back empty
    lo = xp.argmax(meet, axis=2)
    hi = n_q - 1 - xp.argmax(meet[:, ::-1, :], axis=1)
    return xp.concatenate([lo, hi, q_min, q_max, k_min, k_max],
                          axis=1).astype(xp.int32)


class _Band:
    """Where a windowed causal call's band lies, by blocks of ``tq``
    queries and ``tk`` keys: key ``j`` is visible to query ``i`` iff ``i -
    window < j <= i``. Query block ``iq`` reaches the key blocks from
    `kv_first` to `kv_last` (its diagonal's); key block ``ik`` the query
    blocks from `q_first` (its diagonal's) to `q_last`. ``width_k`` and
    ``width_q`` are the most blocks a band reaches, the passes' inner grid
    extents. The block methods take grid indices (traced int32) and give
    traced ones; the static tile counts are `computed_tiles`."""

    def __init__(self, window, tq, tk, n_q, n_k):
        self.window, self.tq, self.tk, self.n_q, self.n_k = (
            int(window), tq, tk, n_q, n_k)
        W = self.window
        self.width_k = max((iq * tq + tq - 1) // tk
                           - max(iq * tq - W + 1, 0) // tk + 1
                           for iq in range(n_q))
        self.width_q = max(min((ik * tk + tk + W - 2) // tq, n_q - 1)
                           - ik * tk // tq + 1 for ik in range(n_k))

    def kv_first(self, iq):
        return jax.lax.div(jnp.maximum(iq * self.tq - (self.window - 1), 0),
                           self.tk)

    def kv_block(self, iq, j):
        """The key block that step ``j`` of query block ``iq`` reads: past
        the band's last, the last (resident already)."""
        return jnp.minimum(self.kv_first(iq) + j, jax.lax.div(
            iq * self.tq + (self.tq - 1), self.tk))

    def q_first(self, ik):
        return jax.lax.div(ik * self.tk, self.tq)

    def q_block(self, ik, j):
        """The query block that step ``j`` of key block ``ik`` reads: past
        the band's last, the last (resident already)."""
        last = jnp.minimum(jax.lax.div(
            ik * self.tk + (self.tk + self.window - 2), self.tq),
            self.n_q - 1)
        return jnp.minimum(self.q_first(ik) + j, last)

    def live(self, iq, ik):
        """Whether tile (iq, ik) holds a pair of the band: some key at or
        before some query, some query fewer than ``window`` after some key,
        and the query block inside the grid (dkv's steps run past it)."""
        tq, tk = self.tq, self.tk
        return ((ik * tk <= iq * tq + (tq - 1))
                & (iq * tq <= ik * tk + (tk + self.window - 2))
                & (iq < self.n_q))

    def whole(self, iq, ik):
        """Whether every pair of tile (iq, ik) lies in the band."""
        tq, tk = self.tq, self.tk
        return ((ik * tk + (tk - 1) <= iq * tq)
                & (ik * tk >= iq * tq + (tq - self.window))
                & (iq < self.n_q))

    def computed_tiles(self) -> int:
        """The tiles of one head's square grid that hold a pair of the band
        (static: the host's count)."""
        tq, tk, W = self.tq, self.tk, self.window
        return sum(1 for iq in range(self.n_q) for ik in range(self.n_k)
                   if ik * tk <= iq * tq + tq - 1
                   and iq * tq <= ik * tk + tk + W - 2)


def _causal_mask(s, iq, ik, tq, tk, window=None):
    """Scores of tile (iq, ik) with the pairs a causal mask (and, with
    ``window``, the window's far edge) rules out set to ``_NEG_INF``."""
    q_pos = iq * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    k_pos = ik * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep = keep & (q_pos - k_pos < window)
    return jnp.where(keep, s, _NEG_INF)


def _same_document(s, seg):
    """Scores with the pairs of different documents masked off; ``seg`` is
    (the queries' ids [1, TQ, 1], the keys' ids [1, 1, TK]) refs or None."""
    if seg is None:
        return s
    return jnp.where(seg[0][0] == seg[1][0], s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, scale, causal, n_k, skip, seg=None,
                docs=None, band=None):
    iq, j = pl.program_id(1), pl.program_id(2)
    tq, tk = q_ref.shape[1], k_ref.shape[1]
    # the key block: the grid's own, or a windowed call's step on its band
    ik = j if band is None else band.kv_first(iq) + j
    window = None if band is None else band.window

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _step(masked, apart=True):
        # dots run in the input dtype (bf16 stays on the fast MXU path)
        # with f32 accumulation; softmax stats are always f32
        q, k, v = q_ref[0], k_ref[0], v_ref[0]           # [TQ,D],[TK,D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if mask_ref is not None:
            s = jnp.where(mask_ref[0][:, 0][None, :] != 0, s, _NEG_INF)
        s = _same_document(s, seg if apart else None)
        if masked:
            s = _causal_mask(s, iq, ik, tq, tk, window)

        m_prev = m_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        m_sc[...] = m_new[:, None]
        l_sc[...] = l_sc[...] * alpha[:, None] + jnp.sum(p, axis=-1)[:, None]
        acc_sc[...] = acc_sc[...] * alpha[:, None] + \
            jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    if skip:
        _on_live_tile(_step, iq, ik, tq, tk, docs, band)
    else:
        _step(causal)

    @pl.when(j == n_k - 1)
    def _done():
        l = l_sc[:, 0]
        o_ref[0] = (acc_sc[...] / jnp.maximum(l, 1e-30)[:, None]).astype(
            o_ref.dtype)
        lse_ref[0] = (m_sc[:, 0] + jnp.log(jnp.maximum(l, 1e-30)))[:, None]


def _fwd_kernel_nomask(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_sc, l_sc, acc_sc, **kw):
    _fwd_kernel(q_ref, k_ref, v_ref, None, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, **kw)


def _segmented(kernel, masked: bool, docs=None):
    """``kernel`` (one of the three streaming kernels) for a call with
    document ids: [the `_DocTable` operand first, where ``docs`` is given,]
    after its first refs come [the key mask,] the queries' ids and the
    keys' ids, then the rest as the kernel takes them."""
    def run(*refs, n_in, **kw):
        if docs is not None:
            kw["docs"] = docs.at(refs[0])
            refs = refs[1:]
        first, refs = refs[:n_in], refs[n_in:]
        mask_ref = refs[0] if masked else None
        seg = refs[masked:masked + 2]
        kernel(*first, mask_ref, *refs[masked + 2:], seg=seg, **kw)
    return run


def _kv_block(iq, ik, tile_q, tile_k, skip, lo=None):
    """The k/v block that step (iq, ik) of fwd/dq reads. A step the causal
    mask empties names the last live block of its row instead of its own:
    that block is resident already, so no DMA is issued for it. With
    ``lo`` (a packed call's first key block that the query block's
    documents reach) the steps before it name that block, which the row's
    first live step reads."""
    if not skip:
        return ik
    if lo is not None:
        ik = jnp.maximum(ik, lo)
    return jnp.minimum(ik, jax.lax.div(iq * tile_q + (tile_q - 1), tile_k))


def _q_block(ik, iq, tile_q, tile_k, skip, hi=None):
    """The q-side block that step (ik, iq) of dkv reads: the first live
    one of its column while the steps above the diagonal pass, and with
    ``hi`` (a packed call's last query block that the key block's
    documents reach) the last live one once the steps pass it."""
    if not skip:
        return iq
    iq = jnp.maximum(iq, jax.lax.div(ik * tile_k, tile_q))
    return iq if hi is None else jnp.minimum(iq, hi)


def _count_tiles(kernel, n_q, n_k, tile_q, tile_k, skip, band=None):
    """Tick ``dl4j_flash_tiles_total{kernel,kind}`` with one head's grid:
    the tiles a pass computes and the ones it skips, static per traced
    call; a windowed pass's under kinds of their own, ``window_computed``
    (the band's) and ``window_skipped`` (the rest of the square grid)."""
    if band is not None:
        live, kinds = band.computed_tiles(), ("window_computed",
                                              "window_skipped")
    else:
        live = sum(min(n_k, (iq * tile_q + tile_q - 1) // tile_k + 1)
                   for iq in range(n_q)) if skip else n_q * n_k
        kinds = ("computed", "skipped")
    try:
        from ..common.environment import environment
        tiles = environment().metrics().counter(
            "dl4j_flash_tiles_total",
            "Tiles of one head's grid that a streaming flash-attention "
            "pass computes, and that it skips because a causal mask "
            "empties them, added up at trace time",
            labels=("kernel", "kind"))
        tiles.labels(kernel=kernel, kind=kinds[0]).inc(live)
        tiles.labels(kernel=kernel, kind=kinds[1]).inc(n_q * n_k - live)
    except Exception:
        pass  # observability must never break a trace


def _count_window_pass(kernel, window):
    """Tick ``dl4j_flash_window_passes_total{kernel,window}``: one
    streaming pass traced with a window."""
    try:
        from ..common.environment import environment
        environment().metrics().counter(
            "dl4j_flash_window_passes_total",
            "Streaming flash-attention passes traced with a sliding window, "
            "counted at trace time",
            labels=("kernel", "window")).labels(
                kernel=kernel, window=str(window)).inc()
    except Exception:
        pass  # observability must never break a trace


def _seg_specs(seg, heads, tile_q, tile_k, q_block, k_block):
    """Specs and operands of a packed call's ids: ``seg`` = (column [B, S,
    1], row [B, 1, S]), shared by the ``heads`` heads of a batch row;
    ``q_block`` / ``k_block`` give a grid step's block along S from the
    index map's arguments."""
    if seg is None:
        return [], []
    row = lambda bh: jax.lax.div(bh, heads)
    return ([pl.BlockSpec((1, tile_q, 1),
                          lambda bh, *a: (row(bh), q_block(bh, *a), 0)),
             pl.BlockSpec((1, 1, tile_k),
                          lambda bh, *a: (row(bh), 0, k_block(bh, *a)))],
            list(seg))


def _doc_operand(seg, skip, heads, tile_q, tile_k):
    """(`_DocTable`, [its operand]) of a pass over ``tile_q`` x ``tile_k``
    tiles where the call is packed and causal; (None, []) for any other,
    whose pass traces as it did before documents were skipped."""
    if seg is None or not skip:
        return None, []
    ids = seg[1][:, 0, :]                            # the row view, [B, S]
    S = ids.shape[1]
    return (_DocTable(heads, S // tile_q, S // tile_k),
            [_doc_table(ids, tile_q, tile_k).reshape(-1)])


def _pallas_call(kernel, tables, *, grid, in_specs, out_specs, out_shape,
                 scratch_shapes):
    """A streaming pass's ``pallas_call``: with ``tables`` (a packed causal
    call's `_DocTable` operand) these come first, prefetched into SMEM,
    and every index map takes them after the grid's indices."""
    if not tables:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch_shapes,
            compiler_params=_params(2), interpret=_interpret())
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes)
    return functools.partial(
        pl.pallas_call(kernel, grid_spec=spec, out_shape=out_shape,
                       compiler_params=_params(2), interpret=_interpret()),
        *tables)


def _count_boundary_pass(kind):
    from . import boundary_pass
    boundary_pass("flash", kind)


def _count_split_pass(kernel, D, Dv):
    """Tick ``dl4j_flash_split_width_passes_total{kernel,kind}`` for a
    streaming pass whose values are not as wide as its queries and keys
    (latent attention's 192 / 128); ``kind`` is ``"<D>x<Dv>"``. A call of
    equal widths counts nothing."""
    if D == Dv:
        return
    try:
        from ..common.environment import environment
        environment().metrics().counter(
            "dl4j_flash_split_width_passes_total",
            "Streaming flash-attention passes traced with a value width "
            "other than the query/key width, counted at trace time",
            labels=("kernel", "kind")).labels(
                kernel=kernel, kind=f"{D}x{Dv}").inc()
    except Exception:
        pass  # observability must never break a trace


def _band(window, tile_q, tile_k, n_q, n_k):
    return None if window is None else _Band(window, tile_q, tile_k, n_q, n_k)


def _flash_fwd(q, k, v, mask, scale, causal, tile_q, tile_k,
               skip_empty=True, seg=None, heads=1, window=None):
    """``skip_empty=False`` (tests only) computes and masks every tile of
    a causal call, as the kernels did before they skipped. ``window``: a
    causal call's band (`_Band`), with neither ``mask`` nor ``seg``."""
    BH, S, D = q.shape
    Dv = v.shape[-1]            # the values' width; D where a call names none
    n_q, n_k = S // tile_q, S // tile_k
    skip = causal and skip_empty
    band = _band(window, tile_q, tile_k, n_q, n_k)
    _count_tiles("fwd", n_q, n_k, tile_q, tile_k, skip, band)
    _count_split_pass("fwd", D, Dv)
    grid = (BH, n_q, n_k)
    if band is not None:
        _count_window_pass("fwd", window)
        grid = (BH, n_q, band.width_k)
    docs, tables = _doc_operand(seg, skip, heads, tile_q, tile_k)

    def own_q(bh, iq, ik, *t):
        return bh, iq, 0

    def kv(bh, iq, ik, *t):
        if band is not None:
            return bh, band.kv_block(iq, ik), 0
        lo = None if docs is None else docs.at(*t).lo(bh, iq)
        return bh, _kv_block(iq, ik, tile_q, tile_k, skip, lo), 0

    in_specs = [
        pl.BlockSpec((1, tile_q, D), own_q),
        pl.BlockSpec((1, tile_k, D), kv),
        pl.BlockSpec((1, tile_k, Dv), kv),
    ]
    args = [q, k, v]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, tile_k, 1), kv))
        args.append(mask)
    if seg is None:
        kern = _fwd_kernel if mask is not None else _fwd_kernel_nomask
    else:
        _count_boundary_pass("fwd")
        kern = functools.partial(
            _segmented(_fwd_kernel, mask is not None, docs), n_in=3)
    specs, ids = _seg_specs(seg, heads, tile_q, tile_k,
                            lambda *a: own_q(*a)[1], lambda *a: kv(*a)[1])
    in_specs, args = in_specs + specs, args + ids
    kern = functools.partial(kern, scale=scale, causal=causal, n_k=grid[2],
                             skip=skip)
    if band is not None:
        kern = functools.partial(kern, band=band)
    return _pallas_call(
        kern, tables,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, tile_q, Dv), own_q),
            pl.BlockSpec((1, tile_q, 1), own_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, Dv), jnp.float32),
        ],
    )(*args)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _p_tile(q, k, mask_row, lse, iq, ik, scale, causal, seg=None,
            window=None):
    """Recompute the [TQ, TK] probability tile from saved lse."""
    tq, tk = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask_row is not None:
        s = jnp.where(mask_row[:, 0][None, :] != 0, s, _NEG_INF)
    s = _same_document(s, seg)
    if causal:
        s = _causal_mask(s, iq, ik, tq, tk, window)
    return jnp.exp(s - lse[:, 0][:, None]), s


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, mask_ref,
               dq_ref, dq_sc, *, scale, causal, n_k, skip, seg=None,
               docs=None, band=None):
    iq, j = pl.program_id(1), pl.program_id(2)
    ik = j if band is None else band.kv_first(iq) + j
    window = None if band is None else band.window

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def _step(masked, apart=True):
        q, k, v, g = q_ref[0], k_ref[0], v_ref[0], g_ref[0]
        mrow = mask_ref[0] if mask_ref is not None else None
        p, _ = _p_tile(q, k, mrow, lse_ref[0], iq, ik, scale, masked,
                       seg if apart else None, window)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [TQ, TK]
        ds = p * (dp - delta_ref[0])
        dq_sc[...] += jnp.dot(ds.astype(k.dtype), k,
                              preferred_element_type=jnp.float32) * scale

    if skip:
        _on_live_tile(_step, iq, ik, q_ref.shape[1], k_ref.shape[1], docs,
                      band)
    else:
        _step(causal)

    @pl.when(j == n_k - 1)
    def _done():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _dq_kernel_nomask(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                      dq_ref, dq_sc, **kw):
    _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, None,
               dq_ref, dq_sc, **kw)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, mask_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, scale, causal, n_q, skip,
                seg=None, docs=None, band=None):
    ik, j = pl.program_id(1), pl.program_id(2)
    # the query block: the grid's own, or a windowed call's step on its band
    iq = j if band is None else band.q_first(ik) + j
    window = None if band is None else band.window

    @pl.when(j == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def _step(masked, apart=True):
        q, k, v, g = q_ref[0], k_ref[0], v_ref[0], g_ref[0]
        mrow = mask_ref[0] if mask_ref is not None else None
        p, _ = _p_tile(q, k, mrow, lse_ref[0], iq, ik, scale, masked,
                       seg if apart else None, window)
        dv_sc[...] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dk_sc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if skip:
        _on_live_tile(_step, iq, ik, q_ref.shape[1], k_ref.shape[1], docs,
                      band)
    else:
        _step(causal)

    @pl.when(j == n_q - 1)
    def _done():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _dkv_kernel_nomask(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_sc, dv_sc, **kw):
    _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, None,
                dk_ref, dv_ref, dk_sc, dv_sc, **kw)


def _flash_bwd(q, k, v, mask, o, lse, g, scale, causal, tile_q, tile_k,
               lse_cot=None, skip_empty=True, seg=None, heads=1, window=None):
    BH, S, D = q.shape
    Dv = v.shape[-1]
    cap = _bwd_tile_cap(causal)
    if tile_q > cap and S % cap == 0:
        tile_q = cap
    if tile_k > cap and S % cap == 0:
        tile_k = cap
    n_q, n_k = S // tile_q, S // tile_k
    skip = causal and skip_empty
    band = _band(window, tile_q, tile_k, n_q, n_k)
    for kernel in ("dq", "dkv"):
        _count_tiles(kernel, n_q, n_k, tile_q, tile_k, skip, band)
        _count_split_pass(kernel, D, Dv)
        if band is not None:
            _count_window_pass(kernel, window)
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [BH, S, 1]
    if lse_cot is not None:
        # d lse_j / d s_jk = p_jk, so an lse cotangent enters ds as
        # p * g_lse — algebraically delta' = delta - g_lse with zero
        # kernel changes (ds = p * (dp - delta'))
        delta = delta - lse_cot.astype(jnp.float32)

    def qspec(f, width=D):
        return pl.BlockSpec((1, tile_q, width), f)

    def kspec(f, width=D):
        return pl.BlockSpec((1, tile_k, width), f)

    docs, tables = _doc_operand(seg, skip, heads, tile_q, tile_k)

    # dq: stream kv blocks for each q block
    def own_q(bh, iq, ik, *t):
        return bh, iq, 0

    def kv(bh, iq, ik, *t):
        if band is not None:
            return bh, band.kv_block(iq, ik), 0
        lo = None if docs is None else docs.at(*t).lo(bh, iq)
        return bh, _kv_block(iq, ik, tile_q, tile_k, skip, lo), 0

    in_specs = [qspec(own_q), kspec(kv), kspec(kv, Dv),             # q k v
                qspec(own_q, Dv),                                   # g
                qspec(own_q, 1), qspec(own_q, 1)]                   # lse delta
    args = [q, k, v, g, lse, delta]
    if mask is not None:
        in_specs.append(kspec(kv, 1))
        args.append(mask)

    def kernel(masked, plain, **kw):
        """The pass's kernel for this call's operands."""
        if seg is None:
            kern = masked if mask is not None else plain
        else:
            kern = functools.partial(
                _segmented(masked, mask is not None, docs), n_in=6)
        if band is not None:
            kw["band"] = band
        return functools.partial(kern, scale=scale, causal=causal, skip=skip,
                                 **kw)

    if seg is not None:
        _count_boundary_pass("dq")
        _count_boundary_pass("dkv")
    # a windowed call's inner axes walk the band, not the whole row
    inner_k, inner_q = ((n_k, n_q) if band is None
                        else (band.width_k, band.width_q))
    specs, ids = _seg_specs(seg, heads, tile_q, tile_k,
                            lambda *a: own_q(*a)[1], lambda *a: kv(*a)[1])
    dq = _pallas_call(
        kernel(_dq_kernel, _dq_kernel_nomask, n_k=inner_k), tables,
        grid=(BH, n_q, inner_k),
        in_specs=in_specs + specs,
        out_specs=qspec(own_q),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((tile_q, D), jnp.float32)],
    )(*args, *ids)

    # dk/dv: stream q blocks for each kv block
    def own_kv(bh, ik, iq, *t):
        return bh, ik, 0

    def qs(bh, ik, iq, *t):
        if band is not None:
            return bh, band.q_block(ik, iq), 0
        hi = None if docs is None else docs.at(*t).hi(bh, ik)
        return bh, _q_block(ik, iq, tile_q, tile_k, skip, hi), 0

    in_specs = [qspec(qs), kspec(own_kv), kspec(own_kv, Dv), qspec(qs, Dv),
                qspec(qs, 1), qspec(qs, 1)]
    args = [q, k, v, g, lse, delta]
    if mask is not None:
        in_specs.append(kspec(own_kv, 1))
        args.append(mask)
    specs, _ = _seg_specs(seg, heads, tile_q, tile_k,
                          lambda *a: qs(*a)[1], lambda *a: own_kv(*a)[1])
    dk, dv = _pallas_call(
        kernel(_dkv_kernel, _dkv_kernel_nomask, n_q=inner_q), tables,
        grid=(BH, n_k, inner_q),
        in_specs=in_specs + specs,
        out_specs=[kspec(own_kv), kspec(own_kv, Dv)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((tile_k, D), jnp.float32),
                        pltpu.VMEM((tile_k, Dv), jnp.float32)],
    )(*args, *ids)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing (mask variants split so mask=None stays cheap)
# ---------------------------------------------------------------------------

def _named(o, lse):
    """The forward's (o, lse) under `SAVED_OUT` / `SAVED_LSE`, the lse as
    ``[BH, S]``. A rule returns this ``o`` as its primal output AND keeps
    it as a residual: the block's recomputed forward reads the primal, so
    a name on a copy that only the residuals hold would leave the kernel
    recomputed. The kernel writes lse as ``[BH, S, 1]``, whose minor 1 the
    TPU pads to 128 lanes: saved so, each latent layer's lse would hold
    268 MB where 2 MB are data, so the residual is the lane-dense view and
    the backward rule restores the column."""
    return (checkpoint_name(o, SAVED_OUT),
            checkpoint_name(lse[..., 0], SAVED_LSE))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, tile_q, tile_k):
    o, _ = _flash_fwd(q, k, v, None, scale, causal, tile_q, tile_k)
    return o


def _flash_f(q, k, v, scale, causal, tile_q, tile_k):
    o, lse = _named(*_flash_fwd(q, k, v, None, scale, causal, tile_q,
                                tile_k))
    return o, (q, k, v, o, lse)


def _flash_b(scale, causal, tile_q, tile_k, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, None, o, lse[..., None], g, scale, causal,
                      tile_q, tile_k)


_flash.defvjp(_flash_f, _flash_b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_masked(q, k, v, mask, scale, causal, tile_q, tile_k):
    o, _ = _flash_fwd(q, k, v, mask, scale, causal, tile_q, tile_k)
    return o


def _flash_masked_f(q, k, v, mask, scale, causal, tile_q, tile_k):
    o, lse = _named(*_flash_fwd(q, k, v, mask, scale, causal, tile_q,
                                tile_k))
    return o, (q, k, v, mask, o, lse)


def _flash_masked_b(scale, causal, tile_q, tile_k, res, g):
    q, k, v, mask, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, mask, o, lse[..., None], g, scale,
                            causal, tile_q, tile_k)
    return dq, dk, dv, None


_flash_masked.defvjp(_flash_masked_f, _flash_masked_b)


# a sliding window: causal, no key mask, no document ids

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_window(q, k, v, scale, tile_q, tile_k, window):
    o, _ = _flash_fwd(q, k, v, None, scale, True, tile_q, tile_k,
                      window=window)
    return o


def _flash_window_f(q, k, v, scale, tile_q, tile_k, window):
    o, lse = _named(*_flash_fwd(q, k, v, None, scale, True, tile_q, tile_k,
                                window=window))
    return o, (q, k, v, o, lse)


def _flash_window_b(scale, tile_q, tile_k, window, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, None, o, lse[..., None], g, scale, True,
                      tile_q, tile_k, window=window)


_flash_window.defvjp(_flash_window_f, _flash_window_b)


# packed rows: document ids beside an optional key mask

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_seg(q, k, v, mask, seg_col, seg_row, scale, causal, tile_q,
               tile_k, heads):
    """``mask`` [BH, S, 1] or None; ``seg_col`` [B, S, 1] and ``seg_row``
    [B, 1, S] the document ids of the ``heads`` heads of each batch row."""
    o, _ = _flash_fwd(q, k, v, mask, scale, causal, tile_q, tile_k,
                      seg=(seg_col, seg_row), heads=heads)
    return o


def _flash_seg_f(q, k, v, mask, seg_col, seg_row, scale, causal, tile_q,
                 tile_k, heads):
    o, lse = _named(*_flash_fwd(q, k, v, mask, scale, causal, tile_q, tile_k,
                                seg=(seg_col, seg_row), heads=heads))
    return o, (q, k, v, mask, seg_col, seg_row, o, lse)


def _flash_seg_b(scale, causal, tile_q, tile_k, heads, res, g):
    q, k, v, mask, seg_col, seg_row, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, mask, o, lse[..., None], g, scale,
                            causal, tile_q, tile_k, seg=(seg_col, seg_row),
                            heads=heads)
    return dq, dk, dv, None, None, None


_flash_seg.defvjp(_flash_seg_f, _flash_seg_b)


# (o, lse)-returning variant: the ring/SP path needs the per-block lse to
# merge block outputs exactly; both outputs are differentiable (the lse
# cotangent rides the delta term, see _flash_bwd).

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_lse_masked(q, k, v, mask, scale, causal, tile_q, tile_k):
    o, lse = _flash_fwd(q, k, v, mask, scale, causal, tile_q, tile_k)
    return o, lse


def _flash_lse_masked_f(q, k, v, mask, scale, causal, tile_q, tile_k):
    o, lse = _flash_fwd(q, k, v, mask, scale, causal, tile_q, tile_k)
    return (o, lse), (q, k, v, mask, o, lse)


def _flash_lse_masked_b(scale, causal, tile_q, tile_k, res, g):
    q, k, v, mask, o, lse = res
    g_o, g_lse = g
    dq, dk, dv = _flash_bwd(q, k, v, mask, o, lse, g_o, scale, causal,
                            tile_q, tile_k, lse_cot=g_lse)
    return dq, dk, dv, None


_flash_lse_masked.defvjp(_flash_lse_masked_f, _flash_lse_masked_b)


# ---------------------------------------------------------------------------
# one-tile path: the whole sequence of a head is one [S, S] score tile
# ---------------------------------------------------------------------------
#
# At S_pad <= _ONE_TILE_MAX nothing streams and nothing accumulates across
# grid steps, so the kernels drop what the streaming path needs only for
# that: no m/l/acc scratch, no saved lse, no delta pass, and ONE backward
# kernel that makes dq, dk and dv from one probability tile (5 matmuls and
# one exp pass a head where the dq + dkv pair spends 7 and two).
#
# Blocks come straight from the [B, S, H*D] layout the projections produce
# (a free reshape of [B, S, H, D]): a block is [S, W] with W = a whole
# number of heads filling the 128 lanes (two heads at head_dim 64). A head
# inside the block is selected by zeroing the other heads' lanes of ONE
# operand of each matmul: the contraction then runs over all W lanes at the
# MXU's full depth and the zeroed lanes add exact zeros. No moveaxis around
# the call, no lane slicing inside it.
#
# The forward works on s[q, k] (row statistics by lane reduction, the key
# mask a lane-major [1, S] row). The backward works on the transpose
# sT[k, q], as splash attention does: the statistics are then column
# statistics (plain VPU maxima and sums over sublanes, broadcast back for
# free), dv = pT·do and dk = dsT·q are natural matmuls and only dq
# contracts over the sublane axis. The key mask is needed along sublanes
# there, so the wrapper hands it over a second time as [B, S, 1].

_ONE_TILE_MAX = 512


def _head_lanes(shape, head_dim):
    """[(lane selector or None)] per head of a [S, W] block."""
    n = shape[1] // head_dim
    if n == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return [(lane >= h * head_dim) & (lane < (h + 1) * head_dim)
            for h in range(n)]


def _only(sel, x):
    return x if sel is None else jnp.where(sel, x, jnp.zeros_like(x))


_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_TN = (((0,), (0,)), ((), ()))   # aᵀ · b


def _and(keep, more):
    return more if keep is None else keep & more


def _one_tile_fwd_kernel(*refs, scale, causal, masked, segmented, head_dim):
    (q_ref, k_ref, v_ref), o_ref = refs[:3], refs[-1]
    q, k, v = q_ref[0], k_ref[0], v_ref[0]                      # [S, W]
    S = q.shape[0]
    keep = refs[3][0] != 0 if masked else None                  # [1, S]
    if segmented:       # ids as a column [S, 1] and as a row [1, S]
        keep = _and(keep, refs[-3][0] == refs[-2][0])
    if causal:
        tri = (jax.lax.broadcasted_iota(jnp.int32, (S, S), 0) >=
               jax.lax.broadcasted_iota(jnp.int32, (S, S), 1))
        keep = tri if keep is None else tri & keep
    out = None
    for sel in _head_lanes(q.shape, head_dim):
        s = jax.lax.dot_general(_only(sel, q), k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if keep is not None:
            s = jnp.where(keep, s, _NEG_INF)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        o = jnp.dot(e.astype(v.dtype), v,                       # [S, W]
                    preferred_element_type=jnp.float32)
        o = o * (1.0 / jnp.sum(e, axis=-1, keepdims=True))
        out = o if out is None else jnp.where(sel, o, out)
    o_ref[0] = out.astype(o_ref.dtype)


def _one_tile_bwd_kernel(*refs, scale, causal, masked, segmented, head_dim):
    q, k, v, g = (r[0] for r in refs[:4])                        # [S, W]
    dq_ref, dk_ref, dv_ref = refs[-3:]
    S = q.shape[0]
    keep = refs[4][0] != 0 if masked else None                   # [S, 1]
    if segmented:       # the keys' ids down sT's rows, the queries' across
        keep = _and(keep, refs[-5][0] == refs[-4][0])
    if causal:                      # sT[k, q]: key row <= query column
        tri = (jax.lax.broadcasted_iota(jnp.int32, (S, S), 0) <=
               jax.lax.broadcasted_iota(jnp.int32, (S, S), 1))
        keep = tri if keep is None else tri & keep
    dq = dk = dv = None
    for sel in _head_lanes(q.shape, head_dim):
        kh, vh, qh, gh = (_only(sel, x) for x in (k, v, q, g))
        st = jax.lax.dot_general(kh, q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        if keep is not None:
            st = jnp.where(keep, st, _NEG_INF)
        e = jnp.exp(st - jnp.max(st, axis=0, keepdims=True))
        pt = e * (1.0 / jnp.sum(e, axis=0, keepdims=True))       # [Sk, Sq]
        dpt = jax.lax.dot_general(vh, g, _NT,
                                  preferred_element_type=jnp.float32)
        # delta = rowsum(o ⊙ do) = Σ_k p·dp, here a sum over sublanes
        dst = pt * (dpt - jnp.sum(pt * dpt, axis=0, keepdims=True))
        dst = dst.astype(q.dtype)
        dv_h = jnp.dot(pt.astype(g.dtype), gh,
                       preferred_element_type=jnp.float32)
        dk_h = jnp.dot(dst, qh, preferred_element_type=jnp.float32)
        dq_h = jax.lax.dot_general(dst, kh, _TN,
                                   preferred_element_type=jnp.float32)
        # the selected operand's zero lanes leave each head's product
        # zero outside its own lanes: heads add without a select
        dq = dq_h if dq is None else dq + dq_h
        dk = dk_h if dk is None else dk + dk_h
        dv = dv_h if dv is None else dv + dv_h
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _block_lanes(H, D):
    """Lanes per block: the fewest whole heads that fill 128-lane vregs,
    or every head where H*D does not divide that way."""
    w = D * 128 // math.gcd(D, 128)
    return w if (H * D) % w == 0 else H * D


def _one_tile_call(kernel, ins, mask, seg, n_out, D, scale, causal):
    """``mask``: the key mask laid out as the kernel reads it, or None;
    ``seg``: (ids as a column [B, S, 1], as a row [B, 1, S]) or None."""
    B, S, HD = ins[0].shape
    W = _block_lanes(HD // D, D)
    blk = pl.BlockSpec((1, S, W), lambda b, j: (b, 0, j))
    in_specs, args = [blk] * len(ins), list(ins)
    for extra in ([] if mask is None else [mask]) + list(seg or ()):
        in_specs.append(pl.BlockSpec((1,) + extra.shape[1:],
                                     lambda b, j: (b, 0, 0)))
        args.append(extra)
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal,
                          masked=mask is not None,
                          segmented=seg is not None, head_dim=D),
        grid=(B, HD // W),
        in_specs=in_specs,
        out_specs=[blk] * n_out,
        out_shape=[jax.ShapeDtypeStruct(ins[0].shape, ins[0].dtype)] * n_out,
        # three or four live [S, S] f32 tiles a head at S=512 (1 MiB each)
        # beside the double-buffered [S, W] blocks: over the 16 MiB default
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
    )(*args)


def _col_row(seg):
    """[B, S] ids as the kernels read them, or None."""
    return None if seg is None else (seg[:, :, None], seg[:, None, :])


def _one_tile_fwd(q, k, v, mask, seg, D, scale, causal):
    mrow = None if mask is None else mask[:, None, :]
    if seg is not None:
        _count_boundary_pass("one_tile_fwd")
    return _one_tile_call(_one_tile_fwd_kernel, (q, k, v), mrow,
                          _col_row(seg), 1, D, scale, causal)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _one_tile(q, k, v, mask, seg, D, scale, causal):
    """q/k/v [B, S, H*D]; mask, seg [B, S] int32 or None."""
    return _one_tile_fwd(q, k, v, mask, seg, D, scale, causal)


def _one_tile_f(q, k, v, mask, seg, D, scale, causal):
    return (_one_tile_fwd(q, k, v, mask, seg, D, scale, causal),
            (q, k, v, mask, seg))


def _one_tile_b(D, scale, causal, res, g):
    q, k, v, mask, seg = res
    mcol = None if mask is None else mask[:, :, None]
    if seg is not None:
        _count_boundary_pass("one_tile_bwd")
    dq, dk, dv = _one_tile_call(_one_tile_bwd_kernel, (q, k, v, g), mcol,
                                _col_row(seg), 3, D, scale, causal)
    return dq, dk, dv, None, None


_one_tile.defvjp(_one_tile_f, _one_tile_b)


def _padded_len(S):
    return S if S <= 128 else -(-S // 128) * 128


def _flash_one_tile(q, k, v, mask, seg, causal, scale, D):
    """q/k/v [B, S, H*D] -> [B, S, H*D]."""
    B, S, _ = q.shape
    S_pad = _padded_len(S)
    if mask is not None:
        mask = mask.astype(jnp.int32)
    if S_pad != S:
        pad = [(0, 0), (0, S_pad - S), (0, 0)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
        if mask is None:
            mask = jnp.ones((B, S), jnp.int32)
        mask = jnp.pad(mask, [(0, 0), (0, S_pad - S)])
        if seg is not None:     # the padded keys are masked off already
            seg = jnp.pad(seg, [(0, 0), (0, S_pad - S)], mode="edge")
    out = _one_tile(q, k, v, mask, seg, D, scale, causal)
    return out[:, :S] if S_pad != S else out


def streams(seq_len, head_dim, v_head_dim=None, window=None) -> bool:
    """Whether `flash_attention` called with no tiles takes the streaming
    kernels, whose forward rules name their output and log-sum-exp
    (`SAVED_OUT`, `SAVED_LSE`), rather than the one-tile kernels."""
    return (v_head_dim not in (None, head_dim) or window is not None
            or _padded_len(seq_len) > _ONE_TILE_MAX)


def _fit_tile(want, s_pad):
    """Largest multiple of 128 ≤ want that divides s_pad (s_pad is a
    multiple of 128)."""
    t = min(want, s_pad)
    t -= t % 128
    while s_pad % t:
        t -= 128
    return t


def _default_tiles(causal):
    """The forward's (tile_q, tile_k) where the caller names none, before
    ``_fit_tile``; ``_flash_bwd`` caps both at ``_bwd_tile_cap``.

    Not causal: 2,048 x 512. Causal: 1,024 x 1,024 — a key step costs the
    forward its [TQ, 1] statistics and the accumulator's rescale whatever
    the tile's width, and a skipped step still costs its grid step, so
    few, wide tiles win though they leave more of the diagonal's waste:
    at S=8,192, D=128 a layer's forward took 8.37 ms at 2,048 x 512 (40 of
    64 tiles live), 8.82 at 512 x 512 (136 of 256), 18.08 at 1,024 x 256,
    5.91 at 512 x 1,024 and 5.24 at 1,024 x 1,024 (36 of 64); S=1,024 and
    2,048 at D=64 and 128 order the same way (``attn_sweep.py``, chip runs
    of PR 28; 2,048-wide tiles need a raised VMEM limit and were no
    faster). Larger D and longer S than those were not measured.

    A window takes the causal tiles too: at S=16,384, D=128, W=1,024 (32
    heads) a layer's forward / forward+backward took 5.84 / 19.48 ms at
    1,024 x 1,024 (2 band steps a query block, both masked), 7.23 / 20.24
    at 512 x 512 (3 steps, the middle one unmasked), 6.21 / 20.08 at 512
    x 1,024, 9.45 / 23.58 at 1,024 x 512 and 8.41 / 24.92 at 256 x 512
    (``attn_sweep.py --window 1024 --tiles ...``, chip runs on a v5e); the
    whole causal layer there, 19.56 / 69.63."""
    return (1024, 1024) if causal else (2048, 512)


def _bwd_tile_cap(causal):
    """The backward kernels hold three [TQ, TK] f32 tiles live (p, dp,
    ds), so they cut the forward's 2,048-row tiles to 512; a causal call
    keeps 1,024 x 1,024, which fits the default VMEM limit and leaves 28
    skipped steps a head at S=8,192 where 512 x 512 leaves 120 (13.95 ms a
    layer against 16.98; faster at S=1,024 and 2,048 too)."""
    return 1024 if causal else 512


def _tiles(S, causal, tile_q=None, tile_k=None):
    """(tile_q, tile_k, S_pad) of a streaming call of length ``S``: the
    tiles given, or the defaults fitted to the padded length. The one
    source of the tiles for the kernels and for `document_tiles`."""
    if tile_q is None or tile_k is None:
        if S <= 128:
            return S, S, S
        S_pad = -(-S // 128) * 128
        want_q, want_k = _default_tiles(causal)
        return (_fit_tile(tile_q or want_q, S_pad),
                _fit_tile(tile_k or want_k, S_pad), S_pad)
    tile_q = min(tile_q, max(S, 1))
    tile_k = min(tile_k, max(S, 1))
    lcm = tile_q * tile_k // math.gcd(tile_q, tile_k)
    return tile_q, tile_k, -(-S // lcm) * lcm


def document_tiles(lengths) -> dict:
    """How one head's causal grid of a packed row falls, at the tiles the
    streaming kernels take for the row's length (no tiles given; the
    backward keeps a causal call's): ``lengths`` are the row's documents'
    lengths, end to end. ``{"skipped": tiles on or below the diagonal
    that hold no pair of one document, "whole": live tiles inside one
    document, "boundary": live tiles that a boundary crosses}``, counted
    on the host from the kernels' own `_doc_table`."""
    S = int(sum(lengths))
    tile_q, tile_k, S_pad = _tiles(S, True)
    ids = np.repeat(np.arange(len(lengths), dtype=np.int32),
                    np.asarray(lengths, np.int64))
    ids = np.pad(ids, (0, S_pad - S), mode="edge")[None]
    n_q, n_k = S_pad // tile_q, S_pad // tile_k
    table = _DocTable(1, n_q, n_k, _doc_table(ids, tile_q, tile_k, np)[0])
    out = {"skipped": 0, "whole": 0, "boundary": 0}
    for iq in range(n_q):
        for ik in range(min(n_k, (iq * tile_q + tile_q - 1) // tile_k + 1)):
            kind = ("skipped" if not table.live(0, iq, ik) else
                    "whole" if table.whole(0, iq, ik) else "boundary")
            out[kind] += 1
    return out


def _prep(q, k, v, mask, scale, tile_q, tile_k, causal):
    """Resolve tiles, zero-pad S to the tile boundary, flatten to the
    kernels' [B*H, S_pad, D] layout. Returns (qf, kf, vf, mf, scale,
    tile_q, tile_k, S, S_pad, B, H, D)."""
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    tile_q, tile_k, S_pad = _tiles(S, causal, tile_q, tile_k)
    if S_pad != S:
        pad = [(0, 0), (0, S_pad - S), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        if mask is None:
            mask = jnp.ones((B, S), jnp.int32)
        mask = jnp.pad(mask, [(0, 0), (0, S_pad - S)])
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S_pad, D)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * H, S_pad, D)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * H, S_pad, Dv)
    mf = (jnp.repeat(mask.astype(jnp.int32), H, axis=0)[..., None]
          if mask is not None else None)
    return qf, kf, vf, mf, scale, tile_q, tile_k, S, S_pad, B, H, D


def flash_attention(q, k, v, mask=None, causal: bool = False,
                    scale: float = None, tile_q: int = None,
                    tile_k: int = None, head_dim: int = None,
                    segment_ids=None, v_head_dim: int = None,
                    window: int = None):
    """Flash attention over [B, S, H, D] (BTHD, the framework convention)
    or, with ``head_dim`` given, over [B, S, H*D] as the q/k/v projections
    produce it (the result has the layout of the inputs).

    ``v_head_dim`` (None: ``head_dim``) is the values' width where it is
    not the queries' and keys': ``v`` is then [B, S, H, Dv] or [B, S,
    H*Dv], and so is the result (latent attention scores over 192 and sums
    values of 128). Such a call always streams; the kernels are the same,
    with the value, output and their gradients' blocks ``Dv`` wide.

    mask: optional [B, S] key validity (1 = attend). segment_ids: optional
    [B, S] int32, the document of each position of a packed row: a key is
    visible to the queries of its own document only. ``scale`` multiplies
    the scores (default ``D ** -0.5``). Differentiable in
    q/k/v; O(S) HBM in both forward and backward (the probability matrix
    only ever exists as VMEM tiles).
    Any S is accepted: inputs are zero-padded to the tile boundary (padded
    keys masked off; padded query rows sliced away).

    With no tiles given, a padded length up to 512 takes the one-tile
    kernels (a head's whole [S, S] score tile in VMEM, one fused backward
    kernel, blocks straight from the packed layout) and longer sequences
    stream [tile_q, tile_k] tiles (2048 x 512, or 1024 x 1024 under
    ``causal``, where the tiles the mask empties are skipped, and with
    ``segment_ids`` the tiles between two documents too; shrunk to
    divisors of the padded length). Times on the chip:
    ``kernels._flash_rule``.

    ``window`` W (``causal`` only, with neither ``mask`` nor
    ``segment_ids``): key ``j`` is visible to query ``i`` iff ``i - W < j
    <= i``; the kernels walk the band's blocks alone (the module's
    docstring). Such a call always streams."""
    D = head_dim if head_dim is not None else q.shape[-1]
    Dv = D if v_head_dim is None else v_head_dim
    scale = scale if scale is not None else D ** -0.5
    if window is not None:
        return _flash_windowed(q, k, v, mask, causal, scale, tile_q, tile_k,
                               D, Dv, segment_ids, int(window))
    one_tile = (tile_q is None and tile_k is None
                and not streams(q.shape[1], D, Dv))
    # the one-tile kernels take heads packed, the streaming ones apart;
    # going from one to the other is a free reshape
    shape = q.shape
    if segment_ids is not None:
        segment_ids = segment_ids.astype(jnp.int32)
    if one_tile:
        q, k, v = (x.reshape(shape[:2] + (-1,)) for x in (q, k, v))
        return _flash_one_tile(q, k, v, mask, segment_ids, causal, scale,
                               D).reshape(shape)
    q, k = (x.reshape(shape[:2] + (-1, D)) for x in (q, k))
    v = v.reshape(shape[:2] + (-1, Dv))
    (qf, kf, vf, mf, scale, tile_q, tile_k,
     S, S_pad, B, H, D) = _prep(q, k, v, mask, scale, tile_q, tile_k, causal)
    if segment_ids is not None:
        if S_pad != S:          # the padded keys are masked off already
            segment_ids = jnp.pad(segment_ids, [(0, 0), (0, S_pad - S)],
                                  mode="edge")
        out = _flash_seg(qf, kf, vf, mf, *_col_row(segment_ids), scale,
                         causal, tile_q, tile_k, H)
    elif mf is not None:
        out = _flash_masked(qf, kf, vf, mf, scale, causal, tile_q, tile_k)
    else:
        out = _flash(qf, kf, vf, scale, causal, tile_q, tile_k)
    out = jnp.moveaxis(out.reshape(B, H, S_pad, Dv), 1, 2)
    out = out[:, :S] if S_pad != S else out
    # the inputs' layout, Dv wide a head
    return out.reshape(shape[:2] + ((H, Dv) if len(shape) == 4
                                    else (H * Dv,)))


def _flash_windowed(q, k, v, mask, causal, scale, tile_q, tile_k, D, Dv,
                    segment_ids, window):
    """`flash_attention` with a window, in the inputs' layout. A padded
    key lies after every real query, so the causal mask hides it without
    a key mask; padded query rows are sliced away."""
    if not causal:
        raise ValueError("a window is a causal call's: pass causal=True")
    if mask is not None or segment_ids is not None:
        raise NotImplementedError(
            "a window with a key mask or document ids is not built")
    if window < 1:
        raise ValueError(f"window must be at least 1, got {window}")
    shape = q.shape
    q, k = (x.reshape(shape[:2] + (-1, D)) for x in (q, k))
    v = v.reshape(shape[:2] + (-1, Dv))
    (qf, kf, vf, _, scale, tile_q, tile_k,
     S, S_pad, B, H, D) = _prep(q, k, v, None, scale, tile_q, tile_k, True)
    out = _flash_window(qf, kf, vf, scale, tile_q, tile_k, window)
    out = jnp.moveaxis(out.reshape(B, H, S_pad, Dv), 1, 2)
    out = out[:, :S] if S_pad != S else out
    return out.reshape(shape[:2] + ((H, Dv) if len(shape) == 4
                                    else (H * Dv,)))


def flash_attention_with_lse(q, k, v, mask=None, causal: bool = False,
                             scale: float = None, tile_q: int = None,
                             tile_k: int = None):
    """flash_attention that also returns the log-sum-exp of the scores.

    Returns (out [B, S, H, D], lse [B, H, S] f32). The lse is what a
    sequence-parallel caller (parallel/ring_attention.py) needs to merge
    per-KV-block outputs into the exact global softmax; it is
    differentiable alongside out (the lse cotangent folds into the
    backward kernels' delta term).
    """
    (qf, kf, vf, mf, scale, tile_q, tile_k,
     S, S_pad, B, H, D) = _prep(q, k, v, mask, scale, tile_q, tile_k, causal)
    out, lse = _flash_lse_masked(qf, kf, vf, mf, scale, causal,
                                 tile_q, tile_k)
    out = jnp.moveaxis(out.reshape(B, H, S_pad, D), 1, 2)
    lse = lse.reshape(B, H, S_pad)
    if S_pad != S:
        out, lse = out[:, :S], lse[:, :, :S]
    return out, lse
