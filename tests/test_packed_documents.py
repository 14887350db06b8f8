"""Packed rows: several documents laid end to end in one sequence, told
apart by ``segment_ids``. The chunked scan, the attention core (XLA and the
flash kernels, interpreted) and the whole hybrid model at the tiny granite
shape, against the benchmark's plain reference
(`benchmark/reference/granite_hybrid.py`) and against each document run
alone. The fused conv's part is in `test_ssm_fused.py`."""
import functools
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import granite_hybrid as ref  # noqa: E402
from deeplearning4j_tpu.common.metrics import registry  # noqa: E402
from deeplearning4j_tpu.kernels import attention, flash_attention  # noqa: E402
from deeplearning4j_tpu.models import hybrid_lm  # noqa: E402
from deeplearning4j_tpu.ops import ssm_scan  # noqa: E402

# the package re-exports the kernel function under its module's name
fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

with open(os.path.join(ROOT, "benchmark/tests/configs/granite-tiny.json")) as f:
    CFG = json.load(f)
D = ref.dims(CFG)
F32 = jnp.float32


def ssd_chunked_scan(x, dt, A, B, C, chunk, segment_ids=None):
    """The scan on the references' steps-major operands (the entry's are
    time minor)."""
    tm = lambda v: jnp.moveaxis(v, 1, -1)
    return jnp.moveaxis(ssm_scan.ssd_chunked_scan(
        tm(x), tm(dt), A, tm(B), tm(C), chunk, segment_ids), -1, 1)


def segments(t, *starts):
    """[len(starts), t] ids: row r's documents start at 0 and at
    ``starts[r]``."""
    rows = []
    for s in starts:
        first = np.zeros(t, np.int32)
        first[list(s)] = 1
        rows.append(np.cumsum(first, dtype=np.int32))
    return jnp.asarray(np.stack(rows))


def documents(seg_row):
    """(start, stop) of each document of one row."""
    seg_row = np.asarray(seg_row)
    cuts = [0] + (np.flatnonzero(np.diff(seg_row)) + 1).tolist() + [
        seg_row.size]
    return list(zip(cuts[:-1], cuts[1:]))


# -- the chunked scan --------------------------------------------------------

def scan_inputs(t, seed=3, b=2, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(k[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, h))),
            # slow decays: a state that crossed a boundary would be felt
            -0.05 * jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (b, t, g, n)),
            jax.random.normal(k[4], (b, t, g, n)))


SCAN_CASES = {
    # (t, chunk, the two rows' document starts)
    "inside-a-chunk": (40, 8, ([3, 21], [12])),
    "on-a-chunks-first-step": (40, 8, ([8, 16], [32])),
    "spanning-several-chunks": (64, 8, ([50], [1, 2, 3, 60])),
    "ragged-tail": (21, 8, ([20], [5, 16])),
    "one-chunk": (7, 8, ([3], [6])),
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_chunked_scan_with_boundaries_is_the_recurrence(case):
    t, chunk, starts = SCAN_CASES[case]
    args, seg = scan_inputs(t), segments(t, *starts)
    got = ssd_chunked_scan(*args, chunk, segment_ids=seg)
    np.testing.assert_allclose(got, ref.recurrence(*args, seg, chunk),
                               rtol=2e-5, atol=2e-5)
    # and the reset is felt: the state carried over reads otherwise
    carried = ssd_chunked_scan(*args, chunk)
    assert float(jnp.abs(carried - got).max()) > 1e-2


@pytest.mark.parametrize("case", ["inside-a-chunk", "on-a-chunks-first-step",
                                  "spanning-several-chunks"])
def test_chunked_scan_gradients_with_boundaries(case):
    t, chunk, starts = SCAN_CASES[case]
    args, seg = scan_inputs(t, seed=4), segments(t, *starts)
    ct = jax.random.normal(jax.random.key(9), args[0].shape)
    grad = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * ct),
                              argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(
            grad(lambda *a: ssd_chunked_scan(*a, chunk, segment_ids=seg)),
            grad(lambda *a: ref.recurrence(*a, seg, chunk))):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_each_document_scans_as_it_does_alone():
    t, chunk, starts = SCAN_CASES["spanning-several-chunks"]
    x, dt, A, B, C = scan_inputs(t)
    seg = segments(t, *starts)
    y = ssd_chunked_scan(x, dt, A, B, C, chunk, segment_ids=seg)
    for row in range(2):
        for lo, hi in documents(seg[row]):
            alone = ssd_chunked_scan(x[row:row + 1, lo:hi],
                                     dt[row:row + 1, lo:hi], A,
                                     B[row:row + 1, lo:hi],
                                     C[row:row + 1, lo:hi], chunk)
            np.testing.assert_allclose(y[row:row + 1, lo:hi], alone,
                                       rtol=2e-5, atol=2e-5)


def test_scan_without_ids_is_the_scan_it_was():
    """One document a row, said or unsaid, to the bit; and no boundary
    operand or mask is traced where no ids are given."""
    args = scan_inputs(40)
    plain = ssd_chunked_scan(*args, 8)
    one = ssd_chunked_scan(*args, 8, segment_ids=jnp.zeros((2, 40), jnp.int32))
    np.testing.assert_array_equal(plain, one)
    text = str(jax.make_jaxpr(lambda *a: ssd_chunked_scan(*a, 8))(*args))
    assert "i32[2" not in text and "= ge " not in text
    assert "= gt " not in text


# -- the attention core -------------------------------------------------------

def qkv(b, t, h, hkv, d, seed=11):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, t, h * d)),
            jax.random.normal(ks[1], (b, t, hkv * d)),
            jax.random.normal(ks[2], (b, t, hkv * d)),
            jax.random.normal(ks[3], (b, t, h * d)))


ATTN_CASES = {
    # (b, t, heads, kv heads, head size, tiles, starts, causal, scale)
    "one-tile": (2, 96, 4, 4, 16, {}, ([5, 64, 65], [1]), True, None),
    "one-tile-padded": (1, 200, 2, 2, 64, {}, ([5, 130],), True, 0.05),
    "one-tile-not-causal": (1, 128, 2, 1, 16, {}, ([50],), False, None),
    "streaming": (1, 128, 4, 2, 16, dict(tile_q=32, tile_k=32),
                  ([5, 64, 65, 100],), True, None),
    "streaming-uneven-tiles": (2, 128, 4, 2, 16, dict(tile_q=64, tile_k=16),
                               ([31, 32, 33], [127]), True, 0.1),
    "streaming-default-tiles": (1, 640, 2, 1, 64, {}, ([5, 300, 500],), True,
                                None),
    "streaming-not-causal": (1, 128, 2, 1, 16, dict(tile_q=32, tile_k=32),
                             ([50],), False, None),
}


@pytest.mark.parametrize("case", sorted(ATTN_CASES))
def test_flash_kernels_with_segment_ids_match_the_xla_core(case):
    """Forward, dq, dk and dv of the kernel path (interpreted) against the
    plain core, both told the documents."""
    b, t, h, hkv, d, tiles, starts, causal, scale = ATTN_CASES[case]
    q, k, v, ct = qkv(b, t, h, hkv, d)
    seg = segments(t, *starts)

    def kernel(q, k, v):
        k, v = (jnp.repeat(x.reshape(b, t, hkv, d), h // hkv, axis=2)
                for x in (k, v))
        return flash_attention(q.reshape(b, t, h, d), k, v, causal=causal,
                               scale=scale, segment_ids=seg,
                               **tiles).reshape(q.shape)

    plain = lambda q, k, v: attention(q, k, v, path="xla", head_dim=d,
                                      causal=causal, scale=scale,
                                      segment_ids=seg)
    np.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                               rtol=2e-4, atol=2e-5)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * ct),
                               argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(grads(kernel), grads(plain)):
        np.testing.assert_allclose(a, w, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("path", ["xla", "flash"])
def test_each_document_attends_as_it_does_alone(path):
    b, t, h, hkv, d = 2, 96, 4, 2, 16
    q, k, v, _ = qkv(b, t, h, hkv, d, seed=12)
    seg = segments(t, [5, 64, 65], [40])
    whole = attention(q, k, v, path=path, head_dim=d, causal=True,
                      scale=0.2, segment_ids=seg)
    for row in range(b):
        for lo, hi in documents(seg[row]):
            cut = lambda x: x[row:row + 1, lo:hi]
            alone = attention(cut(q), cut(k), cut(v), path="xla", head_dim=d,
                              causal=True, scale=0.2)
            np.testing.assert_allclose(cut(whole), alone, rtol=2e-4,
                                       atol=2e-5)


def test_segment_ids_and_a_key_mask_together():
    """Padding keys masked off and documents apart, streaming kernels over
    a length that does not tile."""
    b, t, h, d = 1, 100, 2, 16
    q, k, v, _ = qkv(b, t, h, h, d, seed=13)
    seg = segments(t, [50])
    mask = jnp.ones((b, t), jnp.int32).at[0, 90:].set(0)
    got = flash_attention(q.reshape(b, t, h, d), k.reshape(b, t, h, d),
                          v.reshape(b, t, h, d), mask=mask, segment_ids=seg,
                          tile_q=32, tile_k=32).reshape(q.shape)
    want = attention(q, k, v, path="xla", head_dim=d, mask=mask,
                     segment_ids=seg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def tile_kinds(ids, tile_q, tile_k):
    """By brute force over one row's ids, the causal tiles (those on or
    below the diagonal) of a ``tile_q`` x ``tile_k`` grid: ``skipped`` where
    no causal pair is of one document, ``whole`` where every one is,
    ``boundary`` else."""
    ids = np.asarray(ids)
    S = ids.size
    out = {"skipped": 0, "whole": 0, "boundary": 0}
    for iq in range(S // tile_q):
        q = np.arange(iq * tile_q, (iq + 1) * tile_q)[:, None]
        for ik in range(S // tile_k):
            k = np.arange(ik * tile_k, (ik + 1) * tile_k)[None, :]
            causal = q >= k
            if not causal.any():
                continue
            same = ids[q] == ids[k]
            kind = ("skipped" if not (same & causal).any() else
                    "whole" if same[causal].all() else "boundary")
            out[kind] += 1
    return out


# (t, tile_q, tile_k): each row's documents make skipped, whole-document
# and boundary tiles; "ragged" is no multiple of its tiles
DOC_TILE_CASES = {"square": (128, 16, 16), "ragged": (120, 16, 16),
                  "tall": (128, 32, 16), "wide": (128, 16, 32)}
DOC_STARTS = ([3, 40, 41, 90], [64])


@pytest.mark.parametrize("masked", [False, True], ids=["ids", "ids+keymask"])
@pytest.mark.parametrize("case", sorted(DOC_TILE_CASES))
def test_skipped_document_tiles_change_no_bit(case, masked):
    """The streaming passes of a packed causal call skip the tiles that
    lie wholly between two documents and compare no ids inside one: o,
    lse, dq, dk and dv equal those of the same passes with every causal
    tile computed and compared (``skip_empty=False``), to the bit, and
    match the XLA core."""
    t, tile_q, tile_k = DOC_TILE_CASES[case]
    b, h, d = 2, 2, 16
    q, k, v, ct = (x.reshape(b, t, h, d) for x in qkv(b, t, h, h, d, seed=14))
    seg = segments(t, *DOC_STARTS)
    mask = None
    if masked:          # a quarter of the keys off; a document's first on
        rs = np.random.RandomState(15)
        m = (rs.rand(b, t) > 0.25).astype(np.int32)
        m[np.asarray(jnp.diff(seg, axis=1, prepend=-1)) != 0] = 1
        mask = jnp.asarray(m)

    (qf, kf, vf, mf, scale, tq, tk, _, S_pad, *_) = fa._prep(
        q, k, v, mask, None, tile_q, tile_k, True)
    ids = jnp.pad(seg, [(0, 0), (0, S_pad - t)], mode="edge")
    kinds = [tile_kinds(row, tq, tk) for row in np.asarray(ids)]
    assert all(sum(n[kind] for n in kinds) for kind in kinds[0])
    col_row = (ids[:, :, None], ids[:, None, :])
    gf = jax.random.normal(jax.random.key(16), qf.shape)

    @functools.partial(jax.jit, static_argnums=0)
    def passes(skip_empty):     # one program: forward, dq, dkv
        o, lse = fa._flash_fwd(qf, kf, vf, mf, scale, True, tq, tk,
                               skip_empty=skip_empty, seg=col_row, heads=h)
        return (o, lse) + tuple(fa._flash_bwd(
            qf, kf, vf, mf, o, lse, gf, scale, True, tq, tk,
            skip_empty=skip_empty, seg=col_row, heads=h))

    for name, a, w in zip(("o", "lse", "dq", "dk", "dv"), passes(True),
                          passes(False)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w),
                                      err_msg=name)

    kernel = lambda q, k, v: flash_attention(
        q, k, v, mask=mask, causal=True, segment_ids=seg, tile_q=tile_q,
        tile_k=tile_k)
    plain = lambda q, k, v: attention(
        q, k, v, path="xla", head_dim=d, mask=mask, causal=True,
        segment_ids=seg)
    np.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                               rtol=2e-4, atol=2e-5)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * ct),
                               argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(grads(kernel), grads(plain)):
        np.testing.assert_allclose(a, w, rtol=2e-3, atol=2e-4)


def test_a_tile_between_documents_is_never_computed():
    """Two documents that fill whole tiles, and NaN (which poisons whatever
    reads it) in the first one's values, then in the second one's output
    cotangent: the second document's o and dq, then the first one's dk and
    dv, stay finite, as no pass computes a tile between the two; with every
    causal tile computed they do not."""
    b, t, h, d, tile = 1, 64, 1, 16, 16
    q, k, v, _ = (x.reshape(b, t, h, d) for x in qkv(b, t, h, h, d, seed=17))
    ids = segments(t, [32])
    (qf, kf, vf, mf, scale, tq, tk, *_) = fa._prep(q, k, v, None, None,
                                                    tile, tile, True)
    col_row = (ids[:, :, None], ids[:, None, :])
    g = jax.random.normal(jax.random.key(18), qf.shape)

    def passes(skip_empty, vf, g):
        o, lse = fa._flash_fwd(qf, kf, vf, mf, scale, True, tq, tk,
                               skip_empty=skip_empty, seg=col_row, heads=h)
        return dict(zip(("o", "dq", "dk", "dv"), (o,) + fa._flash_bwd(
            qf, kf, vf, mf, o, lse, g, scale, True, tq, tk,
            skip_empty=skip_empty, seg=col_row, heads=h)))

    first, second = slice(0, 32), slice(32, t)
    for poisoned, read in (((vf.at[:, first].set(jnp.nan), g),
                            {"o": second, "dq": second}),
                           ((vf, g.at[:, second].set(jnp.nan)),
                            {"dk": first, "dv": first})):
        got, every = passes(True, *poisoned), passes(False, *poisoned)
        for name, rows in read.items():
            assert np.isfinite(np.asarray(got[name][:, rows])).all(), name
            assert not np.isfinite(np.asarray(every[name][:, rows])).all()


def test_the_scale_is_the_callers():
    q, k, v, _ = qkv(1, 32, 2, 2, 16)
    default = attention(q, k, v, path="xla", head_dim=16, causal=True)
    same = attention(q, k, v, path="xla", head_dim=16, causal=True,
                     scale=16 ** -0.5)
    np.testing.assert_array_equal(default, same)
    other = attention(q, k, v, path="xla", head_dim=16, causal=True,
                      scale=1 / 64)
    assert float(jnp.abs(other - default).max()) > 1e-3


# -- the model ---------------------------------------------------------------

def program_config(dtype=F32, **kw):
    base = dict(
        vocab_size=D["V"], hidden_size=D["E"],
        hybrid_override_pattern=ref.pattern(CFG), norm_eps=D["eps"],
        mamba_num_heads=D["H"], mamba_head_dim=D["P"],
        ssm_state_size=D["N"], n_groups=D["G"], conv_kernel=D["K"],
        chunk_size=D["chunk"], num_attention_heads=D["heads"],
        num_key_value_heads=D["kv_heads"], head_dim=D["D"],
        intermediate_size=D["F"], mlp_hidden_act="silu",
        embedding_multiplier=D["m_e"], residual_multiplier=D["m_r"],
        attention_multiplier=D["m_a"], logits_scaling=D["m_l"],
        tie_word_embeddings=True, rescale_layers=D["depth"], dtype=dtype)
    base.update(kw)
    return hybrid_lm.HybridLMConfig(**base)


T = 45


@pytest.fixture(scope="module")
def inputs():
    key = jax.random.key(5)
    flat = ref.make_flat_params(key, CFG)
    ids = ref.make_ids(key, CFG, 2, T)
    seg = segments(T, [7, 8, 30], [22])
    return flat, ids, seg


def as_f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(F32), tree)


def named_norms(tree):
    names = ref.leaf_names(tree)
    return ref.expand({n: ref.leaf_norm(n, x) for n, x in
                       zip(names, jax.tree_util.tree_leaves(tree))})


def test_the_tiny_granite_shape():
    c = hybrid_lm.HybridLMConfig.tiny(granite=True)
    assert set(c.pattern) == {"M", "*", "-"} and c.pattern[1::2] == "---"
    assert c.tie_word_embeddings and c.n_groups == 1
    p = hybrid_lm.init_params(jax.random.key(0), c)
    assert "head" not in p
    assert p["blocks"][1]["mlp_in"].shape == (32, 2 * 48)
    assert p["blocks"][1]["mlp_out"].shape == (48, 32)
    assert ref.pattern(CFG) == "M-*-M-"
    # the program's tree and the reference's hold the same leaves
    flat = ref.make_flat_params(jax.random.key(0), CFG)
    mine = hybrid_lm.init_params(jax.random.key(0), program_config())
    assert ({k: v.shape for k, v in zip(ref.leaf_names(mine),
                                        jax.tree_util.tree_leaves(mine))}
            == {k: v.shape for k, v in flat.items()})


def test_packed_forward_matches_the_reference(inputs):
    flat, ids, seg = inputs
    got = hybrid_lm.forward(as_f32(ref.nest(flat)), ids, program_config(),
                            segment_ids=seg)
    f32 = as_f32(flat)
    want = ref.logits(ref.hidden(f32, ids, seg, D), f32["final_norm"],
                      f32["embed"], D)
    assert got.shape == (2, T, D["V"]) and got.dtype == F32
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_packed_loss_and_gradients_match_the_reference(inputs):
    flat, ids, seg = inputs
    batch = {"input_ids": ids, "segment_ids": seg}
    (loss, _), grads = jax.value_and_grad(
        lambda p: hybrid_lm.lm_loss(p, batch, program_config()),
        has_aux=True)(as_f32(ref.nest(flat)))
    (want, per_tok), want_grads = jax.value_and_grad(
        lambda w: ref.loss(w, ids, seg, D), has_aux=True)(as_f32(flat))
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    # a document's last token, and the row's, predict nothing
    silent = np.asarray(per_tok == 0.0)
    assert silent[0, [6, 7, 29, T - 1]].all() and silent[1, [21, T - 1]].all()
    assert int(silent.sum()) == 6
    got = named_norms(grads)
    for k, v in want_grads.items():
        assert got[k] == pytest.approx(float(jnp.linalg.norm(v)), rel=2e-4,
                                       abs=1e-9), k


def test_first_step_by_layers_is_the_whole_expressions(inputs):
    """The reference against itself: its layer-by-layer backward gives the
    gradient norms of `jax.grad` of the whole model."""
    flat, ids, seg = inputs
    step = ref.first_step(flat, ids[:1], seg[:1], CFG, lr=1e-4)
    (want, per_tok), grads = jax.value_and_grad(
        lambda w: ref.loss(w, ids[:1], seg[:1], D), has_aux=True)(
        as_f32(flat))
    assert step["loss"] == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(step["token_loss"], per_tok, rtol=1e-5,
                               atol=1e-6)
    assert set(step["grad_norms"]) == set(flat)
    for k, v in grads.items():
        assert step["grad_norms"][k] == pytest.approx(
            float(jnp.linalg.norm(v)), rel=1e-4, abs=1e-10), k


@pytest.mark.parametrize("who", ["program", "reference"])
def test_a_packed_row_is_its_documents_run_alone(inputs, who):
    """The whole model: every document's logits in the packed rows are the
    logits it gets as a sequence of its own."""
    flat, ids, seg = inputs
    f32 = as_f32(flat)
    if who == "program":
        params, c = as_f32(ref.nest(flat)), program_config()
        run = lambda ids, seg: hybrid_lm.forward(params, ids, c,
                                                 segment_ids=seg)
    else:
        run = lambda ids, seg: ref.logits(
            ref.hidden(f32, ids, jnp.zeros_like(ids) if seg is None else seg,
                       D), f32["final_norm"], f32["embed"], D)
    packed = run(ids, seg)
    for row in range(2):
        for lo, hi in documents(seg[row]):
            alone = run(ids[row:row + 1, lo:hi], None)
            np.testing.assert_allclose(packed[row:row + 1, lo:hi], alone,
                                       atol=3e-5, rtol=3e-4)
    # and the boundaries are felt: the rows as single documents read apart
    assert float(jnp.abs(run(ids, None) - packed).max()) > 1e-4


@pytest.mark.parametrize("key,value,least", [
    ("embedding_multiplier", 1.0, 1e-3), ("residual_multiplier", 1.0, 1e-3),
    # one attention layer of heads of 8: 1/8 for 8 ** -0.5 moves little
    ("attention_multiplier", None, 1e-7), ("logits_scaling", 1.0, 1e-3)])
def test_each_multiplier_is_in_the_model(inputs, key, value, least):
    """The four multipliers against the reference: with all four the
    logits are the reference's (above); with any one at its neutral value
    they are not."""
    flat, ids, seg = inputs
    params = as_f32(ref.nest(flat))
    full = hybrid_lm.forward(params, ids, program_config(), segment_ids=seg)
    less = hybrid_lm.forward(params, ids, program_config(**{key: value}),
                             segment_ids=seg)
    assert float(jnp.abs(full - less).max()) > least


def test_the_head_is_the_embedding(inputs):
    flat, ids, seg = inputs
    c = program_config()
    params = as_f32(ref.nest(flat))
    h, _ = hybrid_lm.hidden_states(params, ids, c, segment_ids=seg)
    logits = hybrid_lm._logits(params, h, c)
    normed = hybrid_lm._rms_norm(h, params["final_norm"], c.norm_eps)
    np.testing.assert_allclose(
        logits, jnp.einsum("bte,ve->btv", normed, params["embed"],
                           precision="highest") / D["m_l"],
        rtol=1e-5, atol=1e-6)
    # both uses of the matrix reach its gradient
    g = jax.grad(lambda p: hybrid_lm.lm_loss(
        p, {"input_ids": ids, "segment_ids": seg}, c)[0])(params)["embed"]
    unused = np.setdiff1d(np.arange(D["V"]), np.asarray(ids))
    assert unused.size and float(jnp.abs(g[unused]).max()) > 0


def test_one_packed_train_step_matches_the_reference(inputs):
    """bfloat16 parameters, float32 activations, per-block recomputation:
    the step's loss, each position's loss, Adam's first moment and each
    leaf's change against the reference's."""
    flat, ids, seg = inputs
    want = ref.first_step(flat, ids[:1], seg[:1], CFG, lr=1e-4)
    params = jax.tree_util.tree_map(jnp.copy, ref.nest(flat))
    start = ref.nest(flat)
    step = hybrid_lm.make_train_step(program_config(), None,
                                     learning_rate=1e-4, remat=True)
    params, opt, aux = step(params, hybrid_lm.init_opt_state(params),
                            {"input_ids": ids[:1], "segment_ids": seg[:1]}, 0)
    assert float(aux["loss"]) == pytest.approx(want["loss"], rel=1e-5)
    np.testing.assert_allclose(aux["token_loss"], want["token_loss"],
                               rtol=1e-4, atol=1e-5)
    assert params["embed"].dtype == jnp.bfloat16
    moments = ref.expand({n: ref.leaf_norm(n, m) / (1 - ref.ADAM_B1)
                          for n, m in zip(ref.leaf_names(params), opt[1])})
    for k, v in want["grad_norms"].items():
        assert moments[k] == pytest.approx(v, rel=5e-3, abs=1e-9), k
    change = named_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(F32) - b.astype(F32), params, start))
    big = np.median(list(want["change_norms"].values()))
    for k, v in want["change_norms"].items():
        assert abs(change[k] - v) <= 0.02 * max(v, big), k


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_the_planted_faults_change_the_reference(inputs, fault):
    flat, ids, seg = inputs
    f32 = as_f32(flat)
    sound = ref.hidden(f32, ids, seg, D)
    faulty = ref.hidden(f32, ids, seg, D, fault=fault)
    moved = np.asarray(jnp.abs(faulty - sound).max(axis=-1))
    assert moved[0, 7:].max() > 1e-4 and moved[1, 22:].max() > 1e-4
    # nothing before a row's first boundary can tell
    assert moved[0, :7].max() == 0 and moved[1, :22].max() == 0


# -- what a call without ids traces ------------------------------------------

def _boundary_passes():
    fam = registry().get("dl4j_boundary_kernel_passes_total")
    return {} if fam is None else {
        labels: child.value() for labels, child in fam.children()}


def test_the_nemotron_tiny_model_is_what_it_was(flash_everywhere):
    """A model that is handed no ids: its loss to the bit of what the
    parent commit computed (recorded there), no boundary-aware kernel pass
    traced, no per-position loss in the step's aux."""
    c = hybrid_lm.HybridLMConfig.tiny()
    params = hybrid_lm.init_params(jax.random.key(0), c)
    ids = jax.random.randint(jax.random.key(1), (2, 37), 0, 96)
    before = _boundary_passes()
    loss, _ = hybrid_lm.lm_loss(params, {"input_ids": ids}, c)
    assert float(loss).hex() == "0x1.241ff20000000p+2"
    step = hybrid_lm.make_train_step(c, None, remat=True)
    _, _, aux = step(params, hybrid_lm.init_opt_state(params),
                     {"input_ids": ids}, 0)
    assert set(aux) == {"loss", "expert_tokens"}
    assert _boundary_passes() == before


def test_a_packed_step_counts_its_boundary_kernel_passes(flash_everywhere):
    """``dl4j_boundary_kernel_passes_total{kernel,kind}`` at trace time: the
    tiny granite pattern has two Mamba-2 blocks and one attention block;
    under per-block recomputation a block's forward is traced twice."""
    c = hybrid_lm.HybridLMConfig.tiny(granite=True)
    params = hybrid_lm.init_params(jax.random.key(0), c)
    batch = {"input_ids": jnp.zeros((1, 24), jnp.int32),
             "segment_ids": segments(24, [9])}
    before = _boundary_passes()
    step = hybrid_lm.make_train_step(c, None, remat=True)
    _, _, aux = step(params, hybrid_lm.init_opt_state(params), batch, 0)
    assert aux["token_loss"].shape == (1, 24)
    after = _boundary_passes()
    got = {k: after[k] - before.get(k, 0) for k in after}
    assert got[("conv_silu", "fwd")] == 4 and got[("conv_silu", "bwd")] == 2
    assert got[("ssm_scan", "fwd")] == 4 and got[("ssm_scan", "bwd")] == 2
    assert got[("flash", "one_tile_fwd")] == 2
    assert got[("flash", "one_tile_bwd")] == 1


def test_observe_packed_feeds_the_packing_counters():
    names = ("dl4j_packed_rows_total", "dl4j_packed_documents_total",
             "dl4j_packed_attended_pairs_total")
    value = lambda n: registry().get(n).value() if registry().get(n) else 0.0
    before = [value(n) for n in names]
    hybrid_lm.observe_packed([[3, 5], [8]])
    after = [value(n) for n in names]
    assert [a - b for a, b in zip(after, before)] == [2, 3, 6 + 15 + 36]


DOC_TILE_ROWS = {
    # a row of the cell's packing at half its length; one document; 16
    # documents of one tile each
    "packed": lambda: ref.pack_rows(3_800_000_001, 1, 8192, {
        "median": 512, "sigma": 1.5, "min": 32, "max": 8192})[1][0],
    "one-document": lambda: [8192],
    "block-documents": lambda: [1024] * 16,
}


@pytest.mark.parametrize("row", sorted(DOC_TILE_ROWS))
def test_observe_packed_counts_the_flash_document_tiles(row):
    """``dl4j_flash_doc_tiles_total{kind}``, fed on the host from a row's
    lengths, is a brute-force count over the row's ids at the streaming
    kernels' tile."""
    lengths = DOC_TILE_ROWS[row]()
    fam = lambda: registry().get("dl4j_flash_doc_tiles_total")
    read = lambda: {kind: fam().labels(kind=kind).value() if fam() else 0.0
                    for kind in ("skipped", "whole", "boundary")}
    before = read()
    hybrid_lm.observe_packed([lengths])
    got = {kind: n - before[kind] for kind, n in read().items()}
    S = sum(lengths)
    tile_q, tile_k, _ = fa._tiles(S, True)
    assert tile_q == tile_k == 1024
    ids = np.repeat(np.arange(len(lengths)), lengths)
    assert got == tile_kinds(ids, tile_q, tile_k)
    n = S // 1024
    if row == "one-document":
        assert got == {"skipped": 0, "whole": n * (n + 1) // 2, "boundary": 0}
    if row == "block-documents":        # the diagonal alone is live
        assert got == {"skipped": n * (n - 1) // 2, "whole": n, "boundary": 0}
    if row == "packed":
        assert all(got.values())


def test_pack_rows_cuts_a_stream_of_documents():
    packing = {"median": 16, "sigma": 1.0, "min": 4, "max": 96}
    seg, lengths = ref.pack_rows(2_500_000_011, 4, 96, packing)
    assert seg.shape == (4, 96) and seg.dtype == np.int32
    again, _ = ref.pack_rows(2_500_000_011, 4, 96, packing)
    np.testing.assert_array_equal(seg, again)
    for row, lens in zip(seg, lengths):
        assert sum(lens) == 96 and row[0] == 0
        assert (np.diff(row) >= 0).all() and row[-1] == len(lens) - 1
        assert np.bincount(row).tolist() == lens
    # a document that a row's end cut goes on at the next row's start: but
    # for those pieces every length is inside the clip
    inner = [n for lens in lengths for n in lens[1:-1]]
    assert inner and min(inner) >= 4 and max(inner) <= 96
    other, _ = ref.pack_rows(7, 4, 96, packing)
    assert not np.array_equal(seg, other)
