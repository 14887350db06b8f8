"""Mamba-2's chunked scan as the Pallas kernel pair of `kernels.ssd_scan`
(interpreted here), behind its one entry `ops.ssm_scan.ssd_chunked_scan`:
values and every gradient against the einsum form it replaced, kept below
as the oracle, and against the recurrence taken one step after another
(`benchmark/reference/granite_hybrid.py`)."""
import inspect
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import granite_hybrid as ref  # noqa: E402
from deeplearning4j_tpu.common.metrics import registry  # noqa: E402
from deeplearning4j_tpu.kernels import ssd_scan  # noqa: E402
from deeplearning4j_tpu.models import hybrid_lm  # noqa: E402
from deeplearning4j_tpu.ops import ssm_scan  # noqa: E402
from deeplearning4j_tpu.ops.ssm_scan import ssd_chunked_scan  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16


# -- the oracle: the einsum form the package ran until PR 34 ------------------

def einsum_scan(x, dt, A, B, C, chunk: int, segment_ids=None):
    """``y`` [b, t, h, p], steps major: x [b, t, h, p], dt [b, t, h] float32,
    A [h], B, C [b, t, g, n]. Plain ``jax.numpy``, differentiated by jax:
    ``[chunks, heads, Q, Q]`` float32 decay and score tensors and all."""
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
        doc = doc.reshape(b, c, 1, 1, chunk)
        last = doc[..., -1:]
        before = jnp.concatenate([last[:, :1], last[:, :-1]], axis=1)
    x = x.reshape(b, c, chunk, g, r, p)
    B = B.reshape(b, c, chunk, g, n)
    C = C.reshape(b, c, chunk, g, n)
    dt = dt.astype(F32).reshape(b, c, chunk, g, r)
    a = jnp.cumsum(dt * A.astype(F32).reshape(g, r), axis=2)
    a = jnp.moveaxis(a, 2, -1)                               # [b,c,g,r,q]
    xdt32 = x.astype(F32) * dt[..., None]
    xdt = xdt32.astype(dtype)
    seg = a[..., :, None] - a[..., None, :]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    if segment_ids is not None:
        causal = causal & (doc[..., :, None] == doc[..., None, :])
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("bcign,bcjgn->bcgij", C, B,
                        preferred_element_type=F32)
    weights = (scores[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", weights, xdt,
                   preferred_element_type=F32)
    to_end = jnp.exp(a[..., -1:] - a)
    if segment_ids is not None:
        to_end = jnp.where(doc == last, to_end, 0.0)
    left = jnp.einsum(
        "bcjgn,bcjgrp->bcgrpn", B,
        (xdt32 * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype),
        preferred_element_type=F32)
    chunk_decay = jnp.exp(a[..., -1])
    if segment_ids is not None:
        chunk_decay = jnp.where((last == before)[..., 0], chunk_decay, 0.0)

    def carry(state, inp):
        dec, add = inp
        return state * dec[..., None, None] + add, state

    _, entering = lax.scan(
        carry, jnp.zeros((b, g, r, p, n), F32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(left, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)
    from_state = jnp.einsum("bcign,bcgrpn->bcigrp", C, entering.astype(dtype),
                            preferred_element_type=F32)
    from_entering = jnp.exp(a)
    if segment_ids is not None:
        from_entering = jnp.where(doc == before, from_entering, 0.0)
    y = y + from_state * jnp.moveaxis(from_entering, -1, 2)[..., None]
    y = y.astype(dtype).reshape(b, t + pad, h, p)
    return y[:, :t] if pad else y


def kernel_scan(x, dt, A, B, C, chunk, segment_ids=None):
    """The entry on the oracles' steps-major operands."""
    tm = lambda v: jnp.moveaxis(v, 1, -1)
    return jnp.moveaxis(ssd_chunked_scan(tm(x), tm(dt), A, tm(B), tm(C),
                                         chunk, segment_ids), -1, 1)


def recurrence(x, dt, A, B, C, chunk, segment_ids=None):
    """One step after another, float32."""
    if segment_ids is None:
        segment_ids = jnp.zeros(x.shape[:2], jnp.int32)
    return ref.recurrence(x.astype(F32), dt, A, B.astype(F32), C.astype(F32),
                          segment_ids, chunk)


def inputs(t, b=2, h=4, p=8, g=2, n=16, dtype=F32, seed=3, slow=False):
    k = jax.random.split(jax.random.key(seed), 6)
    scale = 0.05 if slow else 0.5
    return ((jax.random.normal(k[0], (b, t, h, p)).astype(dtype),
             jax.nn.softplus(jax.random.normal(k[1], (b, t, h))),
             -scale * jnp.exp(jax.random.normal(k[2], (h,))),
             jax.random.normal(k[3], (b, t, g, n)).astype(dtype),
             jax.random.normal(k[4], (b, t, g, n)).astype(dtype)),
            jax.random.normal(k[5], (b, t, h, p)).astype(dtype))


def segments(t, *starts):
    """[len(starts), t] ids: row r's documents start at 0 and at
    ``starts[r]``."""
    rows = []
    for s in starts:
        first = np.zeros(t, np.int32)
        first[list(s)] = 1
        rows.append(np.cumsum(first, dtype=np.int32))
    return jnp.asarray(np.stack(rows))


def gradients(f, args, ct, chunk, seg):
    return jax.grad(lambda *a: jnp.sum(
        f(*a, chunk, seg).astype(F32) * ct.astype(F32)),
        argnums=(0, 1, 2, 3, 4))(*args)


def close(got, want, dtype, what=""):
    """float32: equal to rounding (the kernel sums in another order).
    bfloat16: every product rounds its operands as the oracle's does, so
    values agree to a rounding of the result and gradients to a few."""
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    scale = float(np.abs(want).max()) or 1.0
    rtol, atol = (1e-4, 2e-5) if dtype == F32 else (2e-2, 2e-2)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol * scale,
                               err_msg=what)


# (t, chunk, b, h, p, g, n, the rows' document starts or None)
CASES = {
    "tiny-nemotron": (40, 8, 2, 4, 8, 2, 16, None),
    "tiny-granite": (40, 8, 2, 4, 8, 1, 16, None),
    "ragged-tail": (21, 8, 2, 4, 8, 2, 16, None),
    "shorter-than-a-chunk": (5, 8, 2, 4, 8, 2, 16, None),
    "one-head-a-group": (32, 16, 1, 2, 8, 2, 16, None),
    "boundary-inside-a-chunk": (40, 8, 2, 4, 8, 2, 16, ([3, 21], [12])),
    "boundary-on-a-chunks-edge": (40, 8, 2, 4, 8, 1, 16, ([8, 16], [32])),
    "document-over-several-chunks": (64, 8, 2, 4, 8, 2, 16,
                                     ([50], [1, 2, 3, 60])),
    "one-step-documents": (32, 8, 2, 4, 8, 1, 16, ([7, 8, 9], [31])),
    "packed-ragged-tail": (21, 8, 2, 4, 8, 2, 16, ([20], [5, 16])),
    "packed-one-chunk": (7, 8, 2, 4, 8, 1, 16, ([3], [6])),
}


def case_operands(case, dtype):
    t, chunk, b, h, p, g, n, starts = CASES[case]
    args, ct = inputs(t, b, h, p, g, n, dtype, slow=starts is not None)
    seg = None if starts is None else segments(t, *starts)
    return args, ct, chunk, seg


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_values_match_the_einsum_form_and_the_recurrence(case, dtype):
    args, _, chunk, seg = case_operands(case, dtype)
    got = kernel_scan(*args, chunk, seg)
    assert got.dtype == dtype and got.shape == args[0].shape
    close(got, einsum_scan(*args, chunk, seg), dtype, "einsum form")
    close(got, recurrence(*args, chunk, seg), dtype, "recurrence")
    if seg is not None:       # and the reset is felt
        assert float(jnp.abs(kernel_scan(*args, chunk).astype(F32)
                             - got.astype(F32)).max()) > 1e-2


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_gradient_matches_the_einsum_form(case, dtype):
    args, ct, chunk, seg = case_operands(case, dtype)
    got = gradients(kernel_scan, args, ct, chunk, seg)
    want = gradients(einsum_scan, args, ct, chunk, seg)
    for name, a, b in zip(("x", "dt", "A", "B", "C"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        close(a, b, dtype, f"d {name}")


@pytest.mark.parametrize("case", ["tiny-nemotron", "ragged-tail",
                                  "boundary-inside-a-chunk",
                                  "document-over-several-chunks",
                                  "one-step-documents"])
def test_every_gradient_matches_the_recurrences(case):
    args, ct, chunk, seg = case_operands(case, F32)
    got = gradients(kernel_scan, args, ct, chunk, seg)
    want = gradients(recurrence, args, ct, chunk, seg)
    for name, a, b in zip(("x", "dt", "A", "B", "C"), got, want):
        close(a, b, F32, f"d {name}")


# the two cells' widths (64 heads of 64, state 128) at a short T:
# (chunk, groups, document starts)
WIDTHS = {"nemotron-chunk-128-8-groups": (128, 8, None),
          "granite-chunk-256-1-group-packed": (256, 1, ([100, 256, 300],))}


@pytest.mark.parametrize("case", sorted(WIDTHS))
def test_the_cells_widths_at_a_short_row(case):
    chunk, g, starts = WIDTHS[case]
    t = 2 * chunk + 40
    args, ct = inputs(t, 1, 64, 64, g, 128, BF16, slow=True)
    seg = None if starts is None else segments(t, *starts)
    close(kernel_scan(*args, chunk, seg), einsum_scan(*args, chunk, seg),
          BF16)
    got = gradients(kernel_scan, args, ct, chunk, seg)
    want = gradients(einsum_scan, args, ct, chunk, seg)
    for name, a, b in zip(("x", "dt", "A", "B", "C"), got, want):
        close(a, b, BF16, f"d {name}")


def test_each_packed_document_scans_as_it_does_alone():
    args, ct, chunk, seg = case_operands("document-over-several-chunks", F32)
    x, dt, A, B, C = args
    y = kernel_scan(*args, chunk, seg)
    dx = gradients(kernel_scan, args, ct, chunk, seg)[0]
    for row in range(2):
        cuts = [0] + (np.flatnonzero(np.diff(np.asarray(seg[row]))) + 1
                      ).tolist() + [x.shape[1]]
        for lo, hi in zip(cuts[:-1], cuts[1:]):
            cut = lambda v: v[row:row + 1, lo:hi]
            alone = (cut(x), cut(dt), A, cut(B), cut(C))
            close(cut(y), kernel_scan(*alone, chunk), F32)
            close(cut(dx), gradients(kernel_scan, alone, cut(ct), chunk,
                                     None)[0], F32)


def test_one_document_a_row_said_or_unsaid():
    """To the bit; and without ids no boundary operand and no boundary
    mask is traced: the kernels take one operand fewer and compare nothing
    with a step's marks."""
    args, _, chunk, _ = case_operands("tiny-nemotron", F32)
    zeros = jnp.zeros(args[0].shape[:2], jnp.int32)
    np.testing.assert_array_equal(kernel_scan(*args, chunk),
                                  kernel_scan(*args, chunk, zeros))
    text = lambda seg: str(jax.make_jaxpr(
        lambda *a: gradients(kernel_scan, a, a[0], chunk, seg))(*args))
    plain, packed = text(None), text(zeros)
    assert "i32[2,3," in packed and "= ge " in packed and "= gt " in packed
    assert not [w for w in ("i32[2,", "= ge ", "= gt ") if w in plain]


def _passes(name):
    fam = registry().get(name)
    return {} if fam is None else {
        labels: child.value() for labels, child in fam.children()}


@pytest.mark.parametrize("granite", [False, True],
                         ids=["nemotron", "granite-packed"])
@pytest.mark.parametrize("remat,fwd", [(True, 2), (False, 1)],
                         ids=["remat", "no-remat"])
def test_a_traced_step_counts_its_scan_passes(remat, fwd, granite):
    """``dl4j_ssm_scan_passes_total{kind}`` at trace time: under per-block
    recomputation each Mamba-2 block traces the scan twice forward (the
    forward and the recomputed forward) and once backward; a packed batch
    ticks ``dl4j_boundary_kernel_passes_total{kernel="ssm_scan"}`` as
    often, a plain one not at all."""
    config = hybrid_lm.HybridLMConfig.tiny(granite=granite)
    blocks = config.pattern.count(hybrid_lm.MAMBA)
    params = jax.eval_shape(
        lambda: hybrid_lm.init_params(jax.random.key(0), config))
    opt = jax.eval_shape(hybrid_lm.init_opt_state, params)
    ids = jax.ShapeDtypeStruct((1, 24), jnp.int32)
    batch = {"input_ids": ids, **({"segment_ids": ids} if granite else {})}
    step = hybrid_lm.make_train_step(config, remat=remat)
    names = ("dl4j_ssm_scan_passes_total",
             "dl4j_boundary_kernel_passes_total")
    before = [_passes(n) for n in names]
    step.lower(params, opt, batch, 0)
    scan, boundary = ({k: v - b.get(k, 0) for k, v in _passes(n).items()}
                      for n, b in zip(names, before))
    want = {"fwd": blocks * fwd, "bwd": blocks}
    assert scan == {(kind,): n for kind, n in want.items()}
    assert {k[1]: v for k, v in boundary.items()
            if k[0] == "ssm_scan" and v} == (want if granite else {})


def test_one_entry_one_path():
    """The package holds one scan: `ops.ssm_scan` names the kernel
    module's one public function, neither module keeps an einsum, reads
    the environment or asks for the backend to choose an implementation,
    and the entry has no argument that picks one."""
    assert ssm_scan.ssd_chunked_scan is ssd_scan.ssd_chunked_scan
    public = [n for n, v in vars(ssd_scan).items()
              if callable(v) and not n.startswith("_")
              and getattr(v, "__module__", None) == ssd_scan.__name__]
    assert public == ["ssd_chunked_scan"]
    assert list(inspect.signature(ssd_chunked_scan).parameters) == [
        "x", "dt", "A", "B", "C", "chunk", "segment_ids"]
    for mod in (ssm_scan, ssd_scan):
        code = inspect.getsource(mod).split('"""', 2)[2]
        for word in ("einsum", "os.environ", "getenv", "default_backend",
                     "DL4J_TPU"):
            assert word not in code, (mod.__name__, word)
    assert "ssd_chunked_scan" in inspect.getsource(hybrid_lm._mamba)
    assert "einsum_scan" not in inspect.getsource(hybrid_lm)
