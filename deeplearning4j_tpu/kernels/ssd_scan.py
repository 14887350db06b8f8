"""Mamba-2's chunked (SSD) scan as a Pallas kernel pair.

The recurrence and its chunked form are in `ops.ssm_scan`'s docstring; this
is where it runs. One chunk of one group of heads is a grid step: grid
(batch, groups, chunks), chunks innermost and sequential, the group's
``[heads P, N]`` float32 state in VMEM scratch from the row's first chunk
to its last. Inside a step the scores ``C_i . B_j`` of the group are one
product, the two products with the state (what the entering state adds,
what the chunk leaves) run over all the group's heads at once, and a loop
over the heads (``fori_loop``: the code is one head's) builds each head's
``[Q, Q]`` decay and weight tiles, multiplies them with its inputs and
writes its rows of the result. No ``[Q, Q]`` tensor reaches HBM.

**Time is the minor axis**, as in `ssm_fused`, whose operations hand the
scan its operands and take its result: ``x`` ``[b, h, p, t]``, ``B`` and
``C`` ``[b, g, n, t]``, ``dt`` ``[b, h, t]``. A tile is ``[j, i]``, source
step by target step, so ``y[p, i] = sum_j (dt x)[p, j] W[j, i]`` lands in
the layout it is stored in. The decay ``exp(a_i - a_j)`` needs the running
sum ``a`` of ``dt A`` along the lanes and along the sublanes: XLA makes it
once (a cumulative sum in each chunk, ``[b, h, t]`` float32) and hands it
over in both layouts; a head takes its column by a one-hot sum over the
lanes.

**Backward**, hand-written: the chunks from the last to the first, ``d
state`` carried in VMEM as the state is in the forward, every decay and
weight tile recomputed. It keeps from the forward the operands and the
states entering each chunk, ``[b, g, chunks, heads P, N]`` float32 (134 MB
at the two cells' shapes; under per-block recomputation they live from a
block's recomputed forward to its backward): recomputing them would be a
third, forward sweep over the operands before the backward one, for a
tensor the backward sweep reads once. ``d B`` and ``d C`` sum over a
group's heads in float32 (the scores' part in a ``[Q, Q]`` accumulator, the
state's parts inside their products); ``d a`` comes back as a row and a
column part, added by XLA, which also differentiates the cumulative sum and
``dt A`` (``d dt``, ``d A``).

**Document boundaries** (`ops.ssm_scan`): one int32 operand ``[b, 3, t]``
made once from ``segment_ids``: a step's first step of its document inside
its chunk (the in-chunk mask is ``start_i <= j <= i``), whether a step is
of its chunk's last document (what the chunk leaves) and whether it is of
the last document of the chunk before (what the entering state adds; at a
chunk's last step, whether the state is carried at all). Without
``segment_ids`` there is no such operand and no mask but the causal one.

Precision as the einsum form it replaced (`tests/test_ssd_scan.py` keeps
that form as the oracle): matmul operands in the inputs' dtype with
float32 accumulation; ``dt``, ``a``, the decays and the carried state
float32; the weights ``scores * decay``, ``dt x`` and the entering state
rounded to the inputs' dtype before their products.

Every pass is an inner ``jit`` (`ssm_fused._shared_pass`): the blocks of a
model share one traced and lowered function a pass. Three kernel bodies:
the forward, the forward that also writes the entering states (a
``custom_vjp``'s primal and ``fwd`` rule are traced apart) and the
backward. ``dl4j_ssm_scan_passes_total{kind}`` counts the passes at trace
time, ``dl4j_boundary_kernel_passes_total{kernel="ssm_scan"}`` those with
boundaries. On the CPU the kernels run interpreted; on a TPU ``chunk`` has
to be a multiple of 128 and ``p``, ``n`` multiples of 16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssm_fused import _VMEM_LIMIT, _boundary_pass, _interpret, _shared_pass

_HEADS = 8      # most heads a pass of the loop over a group's heads
F32 = jnp.float32
_TN = (((0,), (0,)), ((), ()))      # contract the rows of both operands
_NT = (((1,), (1,)), ((), ()))      # contract the lanes of both operands


def _tick(kind: str, marks) -> None:
    """One traced pass, forward or backward; with boundaries if ``marks``."""
    if marks is not None:
        _boundary_pass("ssm_scan", kind)
    try:
        from ..common.environment import environment
        environment().metrics().counter(
            "dl4j_ssm_scan_passes_total",
            "Passes of the chunked Mamba-2 scan kernels, forward and "
            "backward, counted at trace time",
            labels=("kind",)).labels(kind=kind).inc()
    except Exception:
        pass  # observability must never break a trace


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=F32)


def _rows_sum(v):
    """``[1, lanes]``: the sum over the sublanes."""
    return jnp.sum(v, axis=0, keepdims=True)


def _each_head(r: int, head):
    """``head(h)`` for each of ``r`` heads: a loop whose pass is up to
    `_HEADS` heads, unrolled when the kernel is lowered (the body is traced
    once). One head a pass leaves every step of a head's chain waiting for
    the one before it: a nemotron layer's forward + backward took 3.72 ms
    so, 1.89 ms with its group's 8 heads in one pass."""
    u = max(d for d in range(1, _HEADS + 1) if r % d == 0)

    def some(k):
        def one(s, carry):
            head(k * u + s)
            return carry
        lax.fori_loop(0, u, one, 0, unroll=True)

    if r == u:
        some(0)
    else:
        lax.fori_loop(0, r // u, lambda k, carry: (some(k), carry)[1], 0)


# -- what a chunk's step and a head's pass share ------------------------------

def _tile_mask(q: int, marks):
    """``[j, i]`` bool: source step ``j`` reaches target step ``i`` (``j <=
    i`` and, on a packed row, not before ``i``'s document began)."""
    j = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    i = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    mask = j <= i
    if marks is not None:
        mask = jnp.logical_and(mask, j >= marks[0:1])
    return mask


def _head(h, p: int, n: int, x_ref, dt_ref, a_ref, acol_ref, marks):
    """One head's operands of a chunk: (its rows, ``x`` float32 ``[p, q]``,
    ``dt x`` float32, ``a`` as a row and as a column, the rows ``e`` (what
    the entering state is weighed by), ``te`` (what a step leaves at the
    chunk's end) and ``carried`` (the chunk's decay of the state, along
    its ``n`` lanes), each with its boundary mask)."""
    rows = pl.ds(pl.multiple_of(h * p, p), p)
    a_row = a_ref[0, 0, pl.ds(h, 1), :]                       # [1, q]
    acol = acol_ref[0, 0]                                     # [q, r]
    lane = lax.broadcasted_iota(jnp.int32, acol.shape, 1)
    a_col = jnp.sum(jnp.where(lane == h, acol, 0.0), axis=1, keepdims=True)
    x32 = x_ref[0, 0, rows, :].astype(F32)
    xdt32 = x32 * dt_ref[0, 0, pl.ds(h, 1), :]
    a_last = a_row[:, -1:]
    e, te = jnp.exp(a_row), jnp.exp(a_last - a_row)
    # along the lanes first, then (by its reader) along the sublanes:
    # Mosaic broadcasts a [1, 1] value one way at a time
    carried = jnp.exp(jnp.broadcast_to(a_last, (1, n)))
    if marks is not None:
        e = jnp.where(marks[2:3] > 0, e, 0.0)
        te = jnp.where(marks[1:2] > 0, te, 0.0)
        carried = jnp.where(marks[2:3, -1:] > 0, carried, 0.0)
    return rows, x32, xdt32, a_row, a_col, e, te, carried


def _decay(mask, a_row, a_col):
    """``[j, i]`` float32 ``exp(a_i - a_j)`` where ``mask``, else 0."""
    return jnp.exp(jnp.where(mask, a_row - a_col, -jnp.inf))


# -- forward ----------------------------------------------------------------------

def _fwd_kernel(*refs, p: int, packed: bool, save: bool):
    x_ref, b_ref, c_ref, dt_ref, a_ref, acol_ref = refs[:6]
    refs = refs[6:]
    marks = refs[0][0] if packed else None                    # [3, q]
    refs = refs[packed:]
    y_ref, refs = refs[0], refs[1:]
    states_ref = refs[0] if save else None
    state, scores, yoff, xte = refs[save:]
    dtype, q = x_ref.dtype, x_ref.shape[3]
    bt, ct = b_ref[0, 0], c_ref[0, 0]                         # [n, q]

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    if save:
        states_ref[0, 0, 0] = state[...]
    scores[...] = _dot(bt, ct, _TN)                           # [j, i]
    yoff[...] = _dot(state[...].astype(dtype), ct)            # [r p, i]
    mask = _tile_mask(q, marks)

    def head(h):
        rows, _, xdt32, a_row, a_col, e, te, carried = _head(
            h, p, state.shape[1], x_ref, dt_ref, a_ref, acol_ref, marks)
        weights = (scores[...] * _decay(mask, a_row, a_col)).astype(dtype)
        y = _dot(xdt32.astype(dtype), weights) + yoff[rows, :] * e
        y_ref[0, 0, rows, :] = y.astype(dtype)
        xte[rows, :] = (xdt32 * te).astype(dtype)
        state[rows, :] = state[rows, :] * carried

    _each_head(dt_ref.shape[2], head)
    state[...] += _dot(xte[...], bt, _NT)                     # [r p, n]


# -- backward ---------------------------------------------------------------------

def _bwd_kernel(*refs, p: int, packed: bool):
    (x_ref, b_ref, c_ref, dt_ref, a_ref, acol_ref, states_ref,
     g_ref) = refs[:8]
    refs = refs[8:]
    marks = refs[0][0] if packed else None
    refs = refs[packed:]
    (dx_ref, ddt_ref, darow_ref, dacol_ref, db_ref, dc_ref,
     dstate, dleft, scores, dscores, z, dxte, dz, xte) = refs
    dtype, q = x_ref.dtype, x_ref.shape[3]
    bt, ct = b_ref[0, 0], c_ref[0, 0]

    @pl.when(pl.program_id(2) == 0)                           # the last chunk
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    entering_lp = states_ref[0, 0, 0].astype(dtype)           # [r p, n]
    dleft[...] = dstate[...].astype(dtype)        # d of the state it leaves
    scores[...] = _dot(bt, ct, _TN)
    dscores[...] = jnp.zeros_like(dscores)
    z[...] = _dot(entering_lp, ct)                            # [r p, i]
    dxte[...] = _dot(dleft[...], bt)                          # [r p, j]
    dacol_ref[0, 0] = jnp.zeros_like(dacol_ref[0, 0])
    mask = _tile_mask(q, marks)
    lane = lax.broadcasted_iota(jnp.int32, (1, q), 1)
    head_lane = lax.broadcasted_iota(jnp.int32, dacol_ref.shape[2:], 1)

    def head(h):
        rows, x32, xdt32, a_row, a_col, e, te, carried = _head(
            h, p, dstate.shape[1], x_ref, dt_ref, a_ref, acol_ref, marks)
        g = g_ref[0, 0, rows, :]
        g32 = g.astype(F32)
        decay = _decay(mask, a_row, a_col)
        w32 = scores[...] * decay
        # y = (dt x) W + (state C) e
        dxdt = _dot(g, w32.astype(dtype), _NT)                # [p, j]
        dw = _dot(xdt32.astype(dtype), g, _TN)                # [j, i]
        dscores[...] += dw * decay
        dseg = dw * w32                                       # d (a_i - a_j)
        da = _rows_sum(dseg) + _rows_sum(g32 * z[rows, :]) * e
        dacol_ref[0, 0] += jnp.where(
            head_lane == h, -jnp.sum(dseg, axis=1, keepdims=True), 0.0)
        dz[rows, :] = (g32 * e).astype(dtype)
        # state' = carried state + (dt x te) B^T
        dxte_h = dxte[rows, :]
        xte[rows, :] = (xdt32 * te).astype(dtype)
        dxdt = dxdt + dxte_h * te
        dte = _rows_sum(dxte_h * xdt32) * te                  # d (a_last - a_j)
        dcarried = jnp.sum(_rows_sum(dstate[rows, :]
                                     * states_ref[0, 0, 0, rows, :])
                           * carried, axis=1, keepdims=True)
        da = da - dte + jnp.where(
            lane == q - 1, jnp.sum(dte, axis=1, keepdims=True) + dcarried,
            0.0)
        dx_ref[0, 0, rows, :] = (dxdt * dt_ref[0, 0, pl.ds(h, 1), :]).astype(
            dtype)
        ddt_ref[0, 0, pl.ds(h, 1), :] = _rows_sum(dxdt * x32)
        darow_ref[0, 0, pl.ds(h, 1), :] = da
        dstate[rows, :] = dstate[rows, :] * carried

    _each_head(dt_ref.shape[2], head)
    dstate[...] += _dot(dz[...], ct, _NT)                     # [r p, n]
    ds = dscores[...].astype(dtype)
    db_ref[0, 0] = (_dot(ct, ds, _NT)
                    + _dot(dleft[...], xte[...], _TN)).astype(dtype)
    dc_ref[0, 0] = (_dot(bt, ds)
                    + _dot(entering_lp, dz[...], _TN)).astype(dtype)


# -- the calls ----------------------------------------------------------------------

def _specs(r, p, n, q, step):
    """Block specs by name, for a grid (batch, groups, chunks); ``step``
    maps the grid's chunk index to the chunk."""
    at = lambda *shape: pl.BlockSpec(
        (1, 1) + shape, lambda bi, gi, ci: (bi, gi, 0, step(ci)))
    return {
        "x": at(r * p, q), "bc": at(n, q), "dt": at(r, q),
        "acol": pl.BlockSpec((1, 1, q, r),
                             lambda bi, gi, ci: (bi, gi, step(ci), 0)),
        "marks": pl.BlockSpec((1, 3, q), lambda bi, gi, ci: (bi, 0, step(ci))),
        "states": pl.BlockSpec((1, 1, 1, r * p, n),
                               lambda bi, gi, ci: (bi, gi, step(ci), 0, 0)),
    }


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 3,
        vmem_limit_bytes=_VMEM_LIMIT)


def _decay_sums(dt, A, chunk: int):
    """``a`` [b, g, r, t] float32: the running sum of ``dt A`` inside each
    chunk."""
    b, g, r, t = dt.shape
    da = (dt * A.reshape(g, r, 1)).reshape(b, g, r, t // chunk, chunk)
    return jnp.cumsum(da, axis=-1).reshape(b, g, r, t)


def _grouped(x, dt, B):
    """``x`` [b, g, r p, t] and ``dt`` [b, g, r, t]: the heads by group."""
    b, h, p, t = x.shape
    g = B.shape[1]
    return x.reshape(b, g, h // g * p, t), dt.reshape(b, g, h // g, t)


@_shared_pass("chunk", "save")
def _scan_fwd(x, dt, A, B, C, marks, chunk: int, save: bool):
    """``y`` like ``x`` (and, with ``save``, the states entering each
    chunk)."""
    (b, h, p, t), (g, n) = x.shape, B.shape[1:3]
    r, c, q = h // g, t // chunk, chunk
    xg, dtg = _grouped(x, dt, B)
    a = _decay_sums(dtg, A.astype(F32), q)
    sp = _specs(r, p, n, q, lambda ci: ci)
    packed = marks is not None
    out_shape = [jax.ShapeDtypeStruct(xg.shape, x.dtype)]
    out_specs = [sp["x"]]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, g, c, r * p, n), F32))
        out_specs.append(sp["states"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, packed=packed, save=save),
        grid=(b, g, c),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["dt"], sp["dt"],
                  sp["acol"]] + [sp["marks"]] * packed,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r * p, n), F32),          # state
                        pltpu.VMEM((q, q), F32),              # scores
                        pltpu.VMEM((r * p, q), F32),          # state C
                        pltpu.VMEM((r * p, q), x.dtype)],     # dt x te
        compiler_params=_params(), interpret=_interpret(),
    )(xg, B, C, dtg, a, jnp.swapaxes(a, 2, 3), *([marks] * packed))
    y = out[0].reshape(x.shape)
    return (y, out[1]) if save else y


@_shared_pass("chunk")
def _scan_bwd(x, dt, A, B, C, marks, states, gy, chunk: int):
    """(``d x``, ``d dt``, ``d A``, ``d B``, ``d C``)."""
    (b, h, p, t), (g, n) = x.shape, B.shape[1:3]
    r, c, q = h // g, t // chunk, chunk
    xg, dtg = _grouped(x, dt, B)
    a, pull = jax.vjp(lambda dt_, A_: _decay_sums(dt_, A_, q), dtg,
                      A.astype(F32))
    sp = _specs(r, p, n, q, lambda ci: c - 1 - ci)
    packed = marks is not None
    rows = lambda k, dtype: jax.ShapeDtypeStruct((b, g, k, t), dtype)
    big = lambda dtype: pltpu.VMEM((r * p, q), dtype)
    dx, ddt, da_row, da_col, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, packed=packed),
        grid=(b, g, c),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["dt"], sp["dt"],
                  sp["acol"], sp["states"], sp["x"]] + [sp["marks"]] * packed,
        out_specs=[sp["x"], sp["dt"], sp["dt"], sp["acol"], sp["bc"],
                   sp["bc"]],
        out_shape=[rows(r * p, x.dtype), rows(r, F32), rows(r, F32),
                   jax.ShapeDtypeStruct((b, g, t, r), F32),
                   rows(n, B.dtype), rows(n, C.dtype)],
        scratch_shapes=[pltpu.VMEM((r * p, n), F32),          # d state
                        pltpu.VMEM((r * p, n), x.dtype),      # d what it leaves
                        pltpu.VMEM((q, q), F32),              # scores
                        pltpu.VMEM((q, q), F32),              # d scores
                        big(F32), big(F32),                   # state C, d (dt x te)
                        big(x.dtype), big(x.dtype)],          # d (state C), dt x te
        compiler_params=_params(), interpret=_interpret(),
    )(xg, B, C, dtg, a, jnp.swapaxes(a, 2, 3), states,
      gy.reshape(xg.shape), *([marks] * packed))
    ddt_a, dA = pull(da_row + jnp.swapaxes(da_col, 2, 3))
    return (dx.reshape(x.shape), (ddt + ddt_a).reshape(dt.shape),
            dA.reshape(A.shape).astype(A.dtype), dB, dC)


def _marks(segment_ids, chunk: int):
    """``[b, 3, t]`` int32 from ids ``[b, t]`` (``t`` a multiple of
    ``chunk``, ids non-decreasing along a row): row 0 the chunk's step at
    which a step's document begins (or the chunk, if it began before), row
    1 whether a step is of its chunk's last document, row 2 whether it is
    of the last document of the chunk before (of its own chunk's, in the
    first chunk, where the entering state is zero whatever this says)."""
    b, t = segment_ids.shape
    doc = segment_ids.reshape(b, t // chunk, chunk)
    first = jnp.pad(doc[..., 1:] != doc[..., :-1], [(0, 0), (0, 0), (1, 0)],
                    constant_values=True)
    start = lax.cummax(
        jnp.where(first, lax.broadcasted_iota(jnp.int32, doc.shape, 2), 0),
        axis=2)
    last = doc[..., -1:]
    before = jnp.concatenate([last[:, :1], last[:, :-1]], axis=1)
    return jnp.stack([start, (doc == last).astype(jnp.int32),
                      (doc == before).astype(jnp.int32)],
                     axis=1).reshape(b, 3, t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, B, C, marks, chunk: int):
    _tick("fwd", marks)
    return _scan_fwd(x, dt, A, B, C, marks, chunk, False)


def _ssd_f(x, dt, A, B, C, marks, chunk):
    _tick("fwd", marks)
    y, states = _scan_fwd(x, dt, A, B, C, marks, chunk, True)
    return y, (x, dt, A, B, C, marks, states)


def _ssd_b(chunk, res, gy):
    _tick("bwd", res[5])
    return _scan_bwd(*res, gy, chunk) + (None,)


_ssd.defvjp(_ssd_f, _ssd_b)


def ssd_chunked_scan(x, dt, A, B, C, chunk: int, segment_ids=None):
    """``y`` [b, h, p, t] of `ops.ssm_scan`'s recurrence, time minor.

    x: [b, h, p, t] inputs per head; dt: [b, h, t] float32 step sizes
    (after softplus); A: [h] float32, negative; B, C: [b, g, n, t] with
    ``h % g == 0`` (head ``i`` uses group ``i // (h // g)``). ``t`` need
    not be a multiple of ``chunk``: the tail is padded with ``dt = 0``,
    which leaves the state as it is and adds nothing, and sliced away.
    ``segment_ids`` [b, t] int32, non-decreasing along ``t``: the state
    does not cross from one id to the next (None: one document a row).
    """
    t = x.shape[3]
    pad = -t % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
                       for v in (x, dt, B, C))
    marks = None
    if segment_ids is not None:
        marks = _marks(jnp.pad(segment_ids, [(0, 0), (0, pad)], mode="edge"),
                       chunk)
    y = _ssd(x, dt.astype(F32), A, B, C, marks, chunk)
    return y[..., :t] if pad else y
