"""Mamba-2's selective state-space recurrence in its chunked (SSD) form.

Per head, with state ``S in R^{P x N}`` and ``S_0 = 0``::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t . C_t

(Dao & Gu 2024, "Transformers are SSMs", section 6). The sequence is cut
into chunks of ``chunk`` steps. Inside a chunk the recurrence is unrolled
into a masked, decay-weighted attention-like product
``y_i = sum_{j<=i} exp(a_i - a_j) (C_i . B_j) dt_j x_j`` with ``a`` the
running sum of ``dt * A``; each chunk's contribution to the state is one
matmul, the states are carried from chunk to chunk by a short scan over
the chunks (T / chunk steps, elementwise, float32), and what the state
entering a chunk adds to its outputs is one more matmul. Everything is
plain ``jax.numpy``: the backward pass is autodiff's, through the same
matmuls transposed and the chunk scan reversed.

Precision: matmul operands in the inputs' dtype (bfloat16 in training)
with float32 accumulation; ``dt``, the decays, their running sums and the
carried state are float32.

**Packed rows.** With ``segment_ids`` (``[b, t]`` int32, non-decreasing
along a row: the document a step belongs to) the state is reset at a
document's first step::

    S_t = [d_t = d_{t-1}] * exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t

so a document's outputs are what it gives run alone. In the chunked form
that is four masks and no new product: on the in-chunk decay (``d_i =
d_j``), on what a chunk leaves in the state at its end (``d_j`` = the
chunk's last ``d``), on the chunk-to-chunk decay (the chunk's last ``d``
= the previous chunk's last ``d``) and on what the entering state adds
(``d_i`` = the previous chunk's last ``d``). Without ``segment_ids``
none of them is traced.

Not a registered op (nothing in the graph layer calls it): the hybrid
language model's Mamba-2 mixer (`models.hybrid_lm`) is its caller.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ssd_chunked_scan(x, dt, A, B, C, chunk: int, segment_ids=None):
    """``y`` [b, t, h, p] of the recurrence above.

    x: [b, t, h, p] inputs per head; dt: [b, t, h] float32 step sizes
    (after softplus); A: [h] float32, negative; B, C: [b, t, g, n] with
    ``h % g == 0`` (head ``i`` uses group ``i // (h // g)``). ``t`` need
    not be a multiple of ``chunk``: the tail is padded with ``dt = 0``,
    which leaves the state as it is and adds nothing, and sliced away.
    ``segment_ids`` [b, t] int32, non-decreasing along ``t``: the state
    does not cross from one id to the next (None: one document a row).
    """
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    r = h // g
    pad = -t % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, B, C))
    c = (t + pad) // chunk
    dtype = x.dtype
    if segment_ids is not None:
        doc = jnp.pad(segment_ids, [(0, 0), (0, pad)], mode="edge")
        doc = doc.reshape(b, c, 1, 1, chunk)                 # like ``a``
        last = doc[..., -1:]                                 # [b,c,1,1,1]
        # the last id of the chunk before (none before the first: the
        # entering state is zero there whatever the mask says)
        before = jnp.concatenate([last[:, :1], last[:, :-1]], axis=1)
    x = x.reshape(b, c, chunk, g, r, p)
    B = B.reshape(b, c, chunk, g, n)
    C = C.reshape(b, c, chunk, g, n)
    dt = dt.astype(jnp.float32).reshape(b, c, chunk, g, r)
    # a: running sum of dt*A inside each chunk, steps minor-most
    a = jnp.cumsum(dt * A.astype(jnp.float32).reshape(g, r), axis=2)
    a = jnp.moveaxis(a, 2, -1)                               # [b,c,g,r,q]
    xdt32 = x.astype(jnp.float32) * dt[..., None]            # dt_j x_j
    xdt = xdt32.astype(dtype)

    # inside a chunk: decay-weighted causal product
    seg = a[..., :, None] - a[..., None, :]                  # a_i - a_j
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    if segment_ids is not None:
        causal = causal & (doc[..., :, None] == doc[..., None, :])
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))        # [b,c,g,r,i,j]
    scores = jnp.einsum("bcign,bcjgn->bcgij", C, B,
                        preferred_element_type=jnp.float32)
    weights = (scores[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", weights, xdt,
                   preferred_element_type=jnp.float32)

    if c > 1:
        # what each chunk leaves in the state at its end
        to_end = jnp.exp(a[..., -1:] - a)                    # [b,c,g,r,j]
        if segment_ids is not None:
            to_end = jnp.where(doc == last, to_end, 0.0)
        left = jnp.einsum(
            "bcjgn,bcjgrp->bcgrpn", B,
            (xdt32 * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype),
            preferred_element_type=jnp.float32)
        chunk_decay = jnp.exp(a[..., -1])                    # [b,c,g,r]
        if segment_ids is not None:
            chunk_decay = jnp.where((last == before)[..., 0], chunk_decay,
                                    0.0)

        def carry(state, inp):
            dec, add = inp
            return state * dec[..., None, None] + add, state

        _, entering = lax.scan(
            carry, jnp.zeros((b, g, r, p, n), jnp.float32),
            (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(left, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)              # [b,c,g,r,p,n]
        from_state = jnp.einsum("bcign,bcgrpn->bcigrp", C,
                                entering.astype(dtype),
                                preferred_element_type=jnp.float32)
        from_entering = jnp.exp(a)                           # [b,c,g,r,i]
        if segment_ids is not None:
            from_entering = jnp.where(doc == before, from_entering, 0.0)
        y = y + from_state * jnp.moveaxis(from_entering, -1, 2)[..., None]
    y = y.astype(dtype).reshape(b, t + pad, h, p)
    return y[:, :t] if pad else y
