"""One chip's share of a sparse-expert layer: route over ALL the experts,
compute the part of the result that the experts held HERE give, drop
nothing.

A chip of an expert-parallel job holds a contiguous range of the layer's
experts. Every token is routed over all ``n_experts`` (the router keeps
its published width, its experts per token and its normalisation over all
of them); the assignments that fall on held experts are sorted by expert,
the held experts' matrices (two an expert, ``W2 relu(W1 u)^2``, or the
gated three, ``Down (silu(Gate u) * Up u)`` with ``[Gate ; Up]`` held as
one matrix so that an expert is still two grouped products) are applied to
them by a grouped matrix product (group ``e`` is the rows sorted to expert ``e``; jax's Pallas
``megablox.gmm``, which visits only the row tiles that hold rows — XLA's
own lowering of ``lax.ragged_dot`` on the TPU computes every group over
every row, 8 to 45 times the work at 8 experts, measured), and the
results are weighted by their gates and added back per token. What the experts held elsewhere would add is not computed and not
stood in for: on one chip the layer runs without its exchange.

No token is dropped however uneven the load. Shapes have to be static, so
the sorted rows go through a buffer of fixed ``capacity``, of which the
first rows are real. The expected load is ``tokens * top_k * held /
n_experts``; a token can land on at most ``min(top_k, held)`` held
experts, so ``tokens * min(top_k, held)`` rows are the worst case, far
more. The buffer holds four times the expected load. A step whose load
fits it (all but pathological ones) makes one pass; a step whose load
does not (``lax.cond`` on the count) walks the rest of the sorted rows
through the same buffer, one buffer-full after another, to the worst
case's end. The arithmetic is the same either way, and the worst case
costs time, not memory.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..common.tracing import model_scope


def _megablox():
    """jax's Pallas grouped-matmul kernels (the module; the package
    exports its own differentiable wrapper under the module's name)."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _tile(width):
    """The tile of one of a product's two wide dimensions. One pass over
    the dimension, the whole width as the tile, while the kernel's three
    blocks fit VMEM beside the other dimensions' tiles: up to 1,536 (read
    on the chip at 768 and at the gated experts' [Gate ; Up] of 2 x 768
    only, PR 35; no cell has another width under 1,536, so 1,152 and 1,280
    are unread). Above that, the largest multiple of 128 from 384 to 1,024
    that divides it (2,688 -> 896 read, PR 27; 2,048 -> 1,024 read, PR 35:
    whole, it does not fit), else the whole width up to 2,048 (1,856, which
    no multiple of 128 divides; read, PR 27), else 512 with a partial last
    tile (unread)."""
    if width <= 1536:
        return width
    whole = [t for t in range(1024, 383, -128) if width % t == 0]
    return whole[0] if whole else width if width <= 2048 else 512


def _tiling(m, k, n, interpret):
    """(row, contraction, output) tile sizes. Rows in tiles of 128: the
    held experts' runs of rows are a few hundred long and a tile that
    straddles two runs is computed twice, so a short row tile wastes
    least. On the chip at the published widths, one expert layer's two
    products forward and backward over 4,143 rows in 8 runs: 2.70 ms at
    (128, 896, 1856) against 4.09 ms at (512, 512, 512) (chip run, PR
    27). The gated experts' (2,048 -> 2 x 768 -> 2,048), 8,192 rows in 16
    runs of a 32,768-row buffer: 3.08 ms with the 1,536 of [Gate ; Up] as
    one tile against 3.53 at 768, 3.81 at 512-wide contractions, 3.19 and
    3.39 at row tiles of 256 and 512; every dimension whole does not fit
    VMEM (chip run, PR 35)."""
    if interpret:
        return (128, 128, 128)
    return (128, _tile(k), _tile(n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(rows, w, group_sizes, transpose_w=False):
    """``rows[start_e:end_e] @ w[e]`` (``@ w[e].T`` with ``transpose_w``)
    for each group ``e`` of consecutive rows (``group_sizes`` int32 [g];
    rows past their sum are left undefined): [m, k] x [g, k, n] (or
    [g, n, k]) -> float32 [m, n], ``m`` a multiple of 128. Differentiable
    in ``rows`` and ``w``: a grouped product with the other transposition
    for the rows, a transposed grouped product for the weights, which
    lands in ``w``'s own layout."""
    interpret = jax.default_backend() == "cpu"
    m, k = rows.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    return _megablox().gmm(rows, w, group_sizes, jnp.float32,
                           _tiling(m, k, n, interpret),
                           transpose_rhs=transpose_w, interpret=interpret)


def _grouped_matmul_fwd(rows, w, group_sizes, transpose_w):
    return (grouped_matmul(rows, w, group_sizes, transpose_w),
            (rows, w, group_sizes))


def _grouped_matmul_bwd(transpose_w, res, dy):
    kernels = _megablox()
    rows, w, group_sizes = res
    interpret = jax.default_backend() == "cpu"
    m, k = rows.shape
    dy = dy.astype(rows.dtype)
    d_rows = kernels.gmm(dy, w, group_sizes, rows.dtype,
                         _tiling(m, dy.shape[1], k, interpret),
                         transpose_rhs=not transpose_w, interpret=interpret)
    # [g, k, n] = rows^T dy per group; with w held [g, n, k], dy^T rows
    a, b = (dy, rows) if transpose_w else (rows, dy)
    d_w = kernels.tgmm(a.swapaxes(0, 1), b, group_sizes, w.dtype,
                       _tiling(m, a.shape[1], b.shape[1], interpret),
                       num_actual_groups=w.shape[0], interpret=interpret)
    return d_rows, d_w, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def route(u, router_w, top_k: int, scaling: float,
          scoring_func: str = "sigmoid"):
    """Router over all experts: (``idx`` [t, top_k] int32 expert ids,
    ``gates`` [t, top_k] float32), from the float32 logits ``u . W_r``.
    "sigmoid" (DeepSeek-V3, nemotron_h): ``s = sigmoid(logits)``, the
    ``top_k`` largest ``s`` weighted ``scaling * s_k / (sum of the chosen s
    + 1e-20)``. "softmax" (``norm_topk_prob``): ``s = softmax(logits)``
    over all experts, the ``top_k`` largest weighted ``scaling * s_k /
    (sum of the chosen s)``; a ``scaling`` of 1 multiplies nothing."""
    logits = jnp.einsum(
        "te,en->tn", u.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
    if scoring_func == "softmax":
        chosen, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        gates = chosen / jnp.sum(chosen, -1, keepdims=True)
        if scaling != 1.0:
            gates = scaling * gates
        return idx.astype(jnp.int32), gates
    if scoring_func != "sigmoid":
        raise ValueError(f"unknown scoring_func {scoring_func!r}")
    s = jax.nn.sigmoid(logits)
    chosen, idx = lax.top_k(s, top_k)
    gates = scaling * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), gates


def _buffer_full(u, w1, w2, order, gates, group_sizes, load, capacity, i,
                 act):
    """What the sorted rows ``[i * capacity, (i + 1) * capacity)`` add:
    the held experts' outputs, weighted by their gates and summed per
    token. float32 [t, e]."""
    t, top_k = gates.shape
    with model_scope("moe_route"):
        first = i * capacity
        # the part of each expert's run of rows that lies in this buffer
        ends = jnp.cumsum(group_sizes)
        sizes = (jnp.clip(ends - first, 0, capacity)
                 - jnp.clip(ends - group_sizes - first, 0, capacity))
        slots = lax.dynamic_slice(order, (first,), (capacity,))
        real = first + jnp.arange(capacity) < load
        token = slots // top_k
        # rows past the real ones are zeroed on the way in and out, so
        # that nothing the grouped product leaves there can reach a token
        rows = jnp.where(real[:, None], u[token], 0)
    with model_scope("moe_experts"):
        h = grouped_matmul(rows, w1, sizes, True)
        if act == "silu":       # w1 = [Gate ; Up]: one product for both
            a, b = jnp.split(h, 2, axis=-1)
            h = (jax.nn.silu(a) * b).astype(u.dtype)
        else:
            h = jnp.square(jax.nn.relu(h)).astype(u.dtype)
        y = grouped_matmul(h, w2, sizes)
    with model_scope("moe_route"):
        gate = jnp.where(real, gates.reshape(-1)[slots], 0.0)
        y = jnp.where(real[:, None], y, 0.0) * gate[:, None]
        return jax.ops.segment_sum(y, token, num_segments=t)


def routed_experts(u, w1, w2, idx, gates, first_expert: int,
                   n_experts: int, act: str = "relu2"
                   ) -> Tuple[jax.Array, jax.Array]:
    """(``out`` [t, e] float32, ``expert_tokens`` [held] int32): the sum
    over the assignments ``(token, k)`` with ``first_expert <= idx <
    first_expert + held`` of ``gates * W2_e relu(W1_e u)^2`` (``act``
    "relu2") or ``gates * Down_e (silu(Gate_e u) * Up_e u)`` (``act``
    "silu"), and how many assignments each held expert received.

    u: [t, e] tokens; w1, w2: both [held, f, e], the held experts' two
    matrices with the hidden width ``f`` second (``W1_e`` as a model file
    holds it, ``W2_e`` transposed: the minor dimension is then the one
    that fills the chip's 128-wide tiles at the published widths), expert
    ``first_expert + i`` at index ``i``; under "silu" ``w1`` is [held, 2 f,
    e], ``Gate_e``'s rows above ``Up_e``'s, and ``w2`` is ``Down_e``
    transposed; idx, gates: ``route``'s."""
    if act not in ("relu2", "silu"):
        raise ValueError(f"unknown expert activation {act!r}")
    t, top_k = idx.shape
    held = w1.shape[0]
    worst = t * min(top_k, held)
    # a whole number of the kernel's 128-row tiles
    capacity = -(-min(worst, 4 * t * top_k * held // n_experts) // 128) * 128
    buffers = -(-worst // capacity)
    with model_scope("moe_route"):
        local = idx.reshape(-1) - first_expert
        keys = jnp.where((local >= 0) & (local < held), local, held)
        # held assignments first, by expert; stable, so that the rows of
        # one expert stay in token order
        order = jnp.argsort(keys, stable=True)
        order = jnp.pad(order, (0, max(buffers * capacity - t * top_k, 0)))
        group_sizes = jnp.sum(
            keys[:, None] == jnp.arange(held, dtype=keys.dtype),
            axis=0, dtype=jnp.int32)
        load = jnp.sum(group_sizes)
    one = lambda i: _buffer_full(u, w1, w2, order, gates, group_sizes, load,
                                 capacity, i, act)
    out = one(0)
    if buffers > 1:
        def rest():
            later = jax.checkpoint(one)
            return lax.scan(lambda acc, i: (acc + later(i), None),
                            jnp.zeros_like(out),
                            jnp.arange(1, buffers))[0]
        with model_scope("moe_route"):
            out = out + lax.cond(load > capacity, rest,
                                 lambda: jnp.zeros_like(out))
    return out, group_sizes
