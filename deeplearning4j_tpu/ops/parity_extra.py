"""Parity-op tail: remaining reference ops not covered by a family module.

Reference: `headers/parity_ops.h` stragglers (Assert, confusion_matrix,
fake_quant*, compare_and_bitpack, create_view, norm, min_max_datatype,
broadcastgradientargs), `headers/convo.h` deconv2d_tf + conv2d_input_bp,
`headers/decoder.h` ctc_beam, `headers/util.h` print_variable,
`headers/BarnesHutTsne.h` (t-SNE kernels).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import op
from .conv_ops import deconv2d


@op("Assert", "parity", differentiable=False)
def assert_op(condition, *data, message="assertion failed"):
    """Host-checked assert (reference Assert).

    Eager: raises AssertionError immediately. Under jit the condition is
    routed through a host callback that raises when it is False at runtime,
    so an imported graph keeps its checks when compiled, instead of
    silently dropping them."""
    try:
        ok = bool(jnp.all(condition))
    except jax.errors.TracerBoolConversionError:
        def _host_check(ok_value):
            if not bool(np.all(ok_value)):
                raise AssertionError(message)

        jax.debug.callback(_host_check, jnp.all(condition))
        return jnp.asarray(True)
    if not ok:
        raise AssertionError(message)
    return jnp.asarray(True)


@op("confusion_matrix", "parity", differentiable=False)
def confusion_matrix(labels, predictions, num_classes=None, weights=None):
    n = int(num_classes) if num_classes is not None else \
        int(jnp.maximum(jnp.max(labels), jnp.max(predictions))) + 1
    idx = labels.astype(jnp.int32) * n + predictions.astype(jnp.int32)
    w = weights if weights is not None else jnp.ones_like(idx, jnp.float32)
    cm = jnp.zeros((n * n,), w.dtype).at[idx].add(w)
    return cm.reshape(n, n)


@op("fake_quant_with_min_max_vars", "parity")
def fake_quant_with_min_max_vars(x, min_val, max_val, num_bits=8,
                                 narrow_range=False):
    qmin = 1.0 if narrow_range else 0.0
    qmax = float(2 ** int(num_bits) - 1)
    mn = jnp.asarray(min_val, x.dtype)
    mx = jnp.asarray(max_val, x.dtype)
    scale = (mx - mn) / (qmax - qmin)
    # zero point via inv-scale multiply, not division: XLA lowers x/s to
    # x * (1/s) whose reciprocal rounding can push an exact half-integer
    # (e.g. 127.5 for [-1.5, 1.5]) off the std::round nudge TF computes
    inv_scale = (qmax - qmin) / (mx - mn)
    zero = qmin - mn * inv_scale
    # std::round semantics (half-away-from-zero; zero >= qmin >= 0 after
    # clip), not jnp.round's half-to-even
    zero = jnp.clip(jnp.floor(zero + 0.5), qmin, qmax)
    nudged_min = (qmin - zero) * scale
    nudged_max = (qmax - zero) * scale
    clipped = jnp.clip(x, nudged_min, nudged_max)
    q = jnp.round((clipped - nudged_min) * inv_scale)
    return q * scale + nudged_min


@op("fake_quant_with_min_max_vars_per_channel", "parity")
def fake_quant_per_channel(x, min_val, max_val, num_bits=8,
                           narrow_range=False):
    return fake_quant_with_min_max_vars(x, min_val, max_val, num_bits,
                                        narrow_range)


@op("compare_and_bitpack", "parity", differentiable=False)
def compare_and_bitpack(x, threshold):
    """Pack (x > threshold) bits into uint8, 8 values per byte (TF op)."""
    bits = (x > threshold).astype(jnp.uint8)
    flat = bits.reshape(bits.shape[:-1] + (bits.shape[-1] // 8, 8))
    weights = jnp.asarray([128, 64, 32, 16, 8, 4, 2, 1], jnp.uint8)
    return jnp.sum(flat * weights, axis=-1).astype(jnp.uint8)


@op("create_view", "parity", differentiable=False)
def create_view(x, *index_args, **_):
    """Reference create_view builds a strided view; functionally a slice
    alias (views are emulated at the NDArray layer)."""
    return jnp.asarray(x)


@op("norm", "parity")
def norm(x, mode=0, dims=None, keep_dims=False):
    """Reference norm op: mode 0=fro, 1=max, 2=1-norm, ...; dims optional."""
    axis = tuple(dims) if dims else None
    if mode in (0, "fro", "euclidean"):
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axis,
                                keepdims=keep_dims))
    if mode in (1, "max", "inf"):
        return jnp.max(jnp.abs(x), axis=axis, keepdims=keep_dims)
    return jnp.sum(jnp.abs(x), axis=axis, keepdims=keep_dims)


@op("min_max_datatype", "datatypes", differentiable=False)
def min_max_datatype(dtype, min_or_max=0):
    from ..common.dtype import DataType
    dt = DataType.from_any(dtype).jax
    if jnp.issubdtype(dt, jnp.floating):
        info = jnp.finfo(dt)
    else:
        info = jnp.iinfo(dt)
    return jnp.asarray(info.min if min_or_max == 0 else info.max, dt)


@op("broadcastgradientargs", "parity", differentiable=False)
def broadcast_gradient_args(shape_a, shape_b):
    """Axes each operand was broadcast over (TF BroadcastGradientArgs) —
    the reduction axes for each grad in a broadcast binary op's bp."""
    sa = [int(s) for s in np.asarray(shape_a)]
    sb = [int(s) for s in np.asarray(shape_b)]
    rank = max(len(sa), len(sb))
    pa = [1] * (rank - len(sa)) + sa
    pb = [1] * (rank - len(sb)) + sb
    ra = [i for i in range(rank) if pa[i] == 1 and pb[i] != 1]
    rb = [i for i in range(rank) if pb[i] == 1 and pa[i] != 1]
    return (np.asarray(ra, np.int64), np.asarray(rb, np.int64))


@op("deconv2d_tf", "conv")
def deconv2d_tf(output_shape, weights, grad_out, strides=(1, 1),
                padding="SAME", data_format="NHWC"):
    """TF Conv2DBackpropInput flavor: explicit output shape tensor
    (reference deconv2d_tf)."""
    return deconv2d(grad_out, weights, None, strides=strides,
                    padding=padding, data_format=data_format)


@op("conv2d_input_bp", "conv")
def conv2d_input_bp(input_shape, weights, grad_out, strides=(1, 1),
                    padding="SAME", dilation=(1, 1), data_format="NCHW"):
    """Gradient of conv2d wrt its input (reference conv2d_input_bp)."""
    shape = tuple(int(s) for s in np.asarray(input_shape))

    def fwd(x):
        from .conv_ops import conv2d
        return conv2d(x, weights, None, strides=strides, padding=padding,
                      dilation=dilation, data_format=data_format)

    zeros = jnp.zeros(shape, weights.dtype)
    _, vjp = jax.vjp(fwd, zeros)
    return vjp(grad_out)[0]


@op("ctc_beam", "decoder", differentiable=False)
def ctc_beam(logits, sequence_length=None, beam_width=8, blank_index=0,
             top_paths=1):
    """CTC beam-search decoder (reference headers/decoder.h ctc_beam).

    logits: [B, T, C] (or [T, C]). Host-side numpy beam search — decode is
    not a training-path op. Returns (paths [B, top, T], log_probs
    [B, top])."""
    arr = np.asarray(jax.device_get(logits), np.float32)
    if arr.ndim == 2:
        arr = arr[None]
    B, T, C = arr.shape
    logp = arr - np.logaddexp.reduce(arr, axis=-1, keepdims=True)
    out_paths = np.full((B, top_paths, T), -1, np.int64)
    out_logp = np.full((B, top_paths), -np.inf, np.float32)
    for b in range(B):
        Tb = int(sequence_length[b]) if sequence_length is not None else T
        # beam: prefix tuple -> (p_blank, p_nonblank) in log space
        beams = {(): (0.0, -np.inf)}
        for t in range(Tb):
            new = {}
            for prefix, (pb, pnb) in beams.items():
                for c in range(C):
                    p = logp[b, t, c]
                    if c == blank_index:
                        key = prefix
                        npb, nnb = new.get(key, (-np.inf, -np.inf))
                        new[key] = (np.logaddexp(npb,
                                                 np.logaddexp(pb, pnb) + p),
                                    nnb)
                    else:
                        key = prefix + (c,)
                        npb, nnb = new.get(key, (-np.inf, -np.inf))
                        if prefix and prefix[-1] == c:
                            nnb = np.logaddexp(nnb, pb + p)
                            opb, onb = new.get(prefix, (-np.inf, -np.inf))
                            new[prefix] = (opb, np.logaddexp(onb, pnb + p))
                        else:
                            nnb = np.logaddexp(nnb,
                                               np.logaddexp(pb, pnb) + p)
                        new[key] = (npb, nnb)
            ranked = sorted(new.items(),
                            key=lambda kv: -np.logaddexp(*kv[1]))
            beams = dict(ranked[:beam_width])
        ranked = sorted(beams.items(), key=lambda kv: -np.logaddexp(*kv[1]))
        for k, (prefix, probs) in enumerate(ranked[:top_paths]):
            out_paths[b, k, :len(prefix)] = prefix
            out_logp[b, k] = np.logaddexp(*probs)
    return jnp.asarray(out_paths), jnp.asarray(out_logp)


@op("print_variable", "util", differentiable=False)
def print_variable(x, message=""):
    jax.debug.print(message + "{x}", x=x)
    return x


# -- Barnes-Hut t-SNE kernels (reference BarnesHutTsne.h) -----------------

@op("barnes_symmetrized", "tsne", differentiable=False)
def barnes_symmetrized(row_p, col_p, val_p, n=None):
    """Symmetrize a sparse CSR affinity matrix: P = (P + P^T) / 2.

    Returns dense [n, n] (TPU: dense linear algebra beats host CSR)."""
    rows = np.asarray(row_p).astype(np.int64)
    cols = np.asarray(col_p).astype(np.int64)
    vals = np.asarray(val_p)
    n = int(n) if n is not None else len(rows) - 1
    dense = np.zeros((n, n), vals.dtype)
    for i in range(n):
        for k in range(rows[i], rows[i + 1]):
            dense[i, cols[k]] = vals[k]
    sym = (dense + dense.T) / 2.0
    return jnp.asarray(sym)


@op("barnes_edge_forces", "tsne")
def barnes_edge_forces(p_matrix, y):
    """Attractive edge forces of t-SNE: sum_j p_ij (y_i - y_j) / (1+|d|^2)."""
    diff = y[:, None, :] - y[None, :, :]            # [n, n, d]
    dist = 1.0 + jnp.sum(diff * diff, axis=-1)
    w = p_matrix / dist
    return jnp.einsum("ij,ijd->id", w, diff)


@op("barnes_gains", "tsne", differentiable=False)
def barnes_gains(gains, grad, prev_grad, min_gain=0.01):
    """t-SNE adaptive gain update (reference barnes_gains)."""
    same_sign = (grad * prev_grad) > 0
    new = jnp.where(same_sign, gains * 0.8, gains + 0.2)
    return jnp.maximum(new, min_gain)


@op("cell_contains", "tsne", differentiable=False)
def cell_contains(corner, width, point):
    """Barnes-Hut quadtree membership test."""
    lo = corner - width / 2.0
    hi = corner + width / 2.0
    return jnp.all((point >= lo) & (point <= hi), axis=-1)
