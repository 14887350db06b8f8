"""Mamba-2's selective state-space recurrence in its chunked (SSD) form.

Per head, with state ``S in R^{P x N}`` and ``S_0 = 0``::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t . C_t

(Dao & Gu 2024, "Transformers are SSMs", section 6). The sequence is cut
into chunks of ``chunk`` steps. Inside a chunk the recurrence is unrolled
into a masked, decay-weighted attention-like product
``y_i = sum_{j<=i} exp(a_i - a_j) (C_i . B_j) dt_j x_j`` with ``a`` the
running sum of ``dt * A``; each chunk's contribution to the state is one
matmul, the states are carried from chunk to chunk (T / chunk steps,
float32), and what the state entering a chunk adds to its outputs is one
more matmul.

**Where it runs:** `kernels.ssd_scan`, a Pallas kernel pair (forward and a
hand-written backward) that walks a row's chunks in order with the decay,
score and weight tiles and the carried state in VMEM; `ssd_chunked_scan`
here is that module's one entry under the name the model imports. Every
backend runs it (the CPU interpreted); there is no other path. The einsum
form it replaced (ten einsums and a ``lax.scan``, differentiated by jax)
lives on in ``tests/test_ssd_scan.py`` as the oracle.

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

Operands are time minor, as `kernels.ssm_fused` hands them over: ``x`` [b,
h, p, t], ``dt`` [b, h, t], ``B`` and ``C`` [b, g, n, t].

Not a registered op (nothing in the graph layer calls it): the hybrid
language model's Mamba-2 mixer (`models.hybrid_lm`) is its caller.
"""
from ..kernels.ssd_scan import ssd_chunked_scan

__all__ = ["ssd_chunked_scan"]
