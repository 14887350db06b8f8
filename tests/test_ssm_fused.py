"""The fused Mamba-2 chain (`kernels.ssm_fused.mamba_chain`: conv + SiLU,
the caller's scan, skip + gate + grouped RMSNorm), interpreted on the CPU,
against the plain
``jax.numpy`` chain they took the place of in `models.hybrid_lm._mamba`
(kept here as the oracle): values and every gradient, in float32 and in
bfloat16, at shapes that tile and shapes that do not."""
import ast
import inspect
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.common.metrics import registry
from deeplearning4j_tpu.kernels import ssm_fused
from deeplearning4j_tpu.models import hybrid_lm

F32 = jnp.float32
EPS = 1e-5


def steps_major(a):
    return jnp.swapaxes(a, 1, 2)


def plain_chain(zxbcdt, conv_w, conv_b, D, weight, scan, d_inner, G,
                segment_ids=None):
    """`_mamba`'s lines between ``in_proj`` and ``out_proj`` as they were
    before the fused operations: [B, T, F] in, [B, T, d_inner] out. With
    ``segment_ids`` [B, T] a tap counts only inside its step's document."""
    B, T, _ = zxbcdt.shape
    H, K, conv_dim = D.shape[0], conv_w.shape[0], conv_w.shape[1]
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
    padded = jnp.pad(xBC.astype(F32), [(0, 0), (K - 1, 0), (0, 0)])
    if segment_ids is None:
        conv = sum(padded[:, k:k + T] * conv_w[k] for k in range(K))
    else:
        before = jnp.pad(segment_ids, [(0, 0), (K - 1, 0)],
                         constant_values=-1)
        conv = sum(padded[:, k:k + T] * conv_w[k]
                   * (before[:, k:k + T] == segment_ids)[..., None]
                   for k in range(K))
    xBC = jax.nn.silu(conv + conv_b).astype(zxbcdt.dtype)
    n = (conv_dim - d_inner) // 2
    x, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + n], axis=-1)
    y = scan(x, Bm, Cm, dt)
    y = y.astype(F32) + (D[:, None] * x.astype(F32).reshape(B, T, H, -1)
                         ).reshape(B, T, d_inner)
    y = (y * jax.nn.silu(z.astype(F32))).astype(zxbcdt.dtype)
    y32 = y.astype(F32).reshape(B, T, G, d_inner // G)
    y32 = y32 * lax.rsqrt(jnp.mean(jnp.square(y32), -1, keepdims=True) + EPS)
    return (y32.reshape(B, T, d_inner) * weight).astype(zxbcdt.dtype)


def fused_chain(zxbcdt, conv_w, conv_b, D, weight, scan, d_inner, G,
                segment_ids=None):
    return steps_major(ssm_fused.mamba_chain(
        steps_major(zxbcdt), conv_w, conv_b, D, weight, EPS, G,
        lambda *a: steps_major(scan(*map(steps_major, a))),
        segment_ids=segment_ids))


# (B, T, d_inner, n, heads, groups, time tile or None for the module's)
CASES = {
    # the tiny configuration's widths (benchmark/tests/configs/nemotron-
    # tiny.json: d_inner 32, 2 groups x state 16, 4 heads) at its T
    "tiny-config": (2, 37, 32, 32, 4, 2, None),
    # T shorter than one tile, widths that need padding (groups of 12)
    "short-odd-widths": (1, 5, 24, 20, 4, 2, None),
    # T not a multiple of the tile: three tiles, the last of 44 steps
    "ragged-tail": (1, 300, 32, 32, 4, 2, 128),
    # a tile boundary inside the conv's halo: the last tile holds 2 steps
    "boundary-in-halo": (2, 258, 32, 16, 2, 1, 128),
    # widths and T that tile as they come: nothing is padded or copied
    "tiled": (1, 256, 64, 32, 4, 2, 128),
    # two of the module's own tiles, the second of one step
    "second-tile-of-one-step": (1, ssm_fused._TILE + 1, 16, 16, 1, 1, None),
}


@pytest.fixture
def tiles(monkeypatch):
    """Sets the module's time tile for a case that names one: the tile is
    a constant of the module, not an argument."""
    def use(tile):
        if tile is not None:
            monkeypatch.setattr(ssm_fused, "_TILE", tile)
    return use


def operands(case, dtype):
    B, T, d_inner, n, H, G, tile = CASES[case]
    ks = jax.random.split(jax.random.key(sum(map(ord, case))), 7)
    conv_dim = d_inner + 2 * n
    zxbcdt = jax.random.normal(
        ks[0], (B, T, d_inner + conv_dim + H), F32).astype(dtype)
    conv_w = jax.random.uniform(ks[1], (4, conv_dim), F32, -.5, .5)
    conv_b = jax.random.uniform(ks[2], (conv_dim,), F32, -.5, .5)
    D = 1 + .1 * jax.random.normal(ks[3], (H,), F32)
    weight = 1 + .1 * jax.random.normal(ks[4], (d_inner,), F32)
    mix = jax.random.normal(ks[5], (n, d_inner), F32) / n ** .5
    ct = jax.random.normal(ks[6], (B, T, d_inner), F32)

    def scan(x, Bm, Cm, dt):
        """A stand-in for the scan that reads all four results."""
        y = x.astype(F32) * 0.5 + (Bm.astype(F32) * Cm.astype(F32)) @ mix
        y = y * (1 + jnp.mean(jax.nn.softplus(dt.astype(F32)), -1,
                              keepdims=True))
        return y.astype(x.dtype)

    return (zxbcdt, conv_w, conv_b, D, weight), (scan, d_inner, G), tile, ct


def tolerance(dtype):
    # bfloat16: the oracle rounds the gated value before its norm, the
    # fused operation does not; both round x B C and the result
    return dict(rtol=2e-5, atol=2e-5) if dtype == F32 else dict(
        rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_values_match_the_plain_chain(case, dtype, tiles):
    args, static, tile, _ = operands(case, dtype)
    tiles(tile)
    want = plain_chain(*args, *static)
    got = fused_chain(*args, *static)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(got.astype(F32), want.astype(F32),
                               **tolerance(dtype))


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_gradient_matches_the_plain_chain(case, dtype, tiles):
    """d zxbcdt (z, x B C and dt channels in one buffer), d conv_w,
    d conv_b, d D and the norm's d weight."""
    args, static, tile, ct = operands(case, dtype)
    tiles(tile)
    assert_same_gradients(args, static, ct, dtype)


def assert_same_gradients(args, static, ct, dtype, **packed):
    def grads(chain):
        return jax.grad(lambda *a: jnp.sum(
            chain(*a, *static, **packed).astype(F32) * ct),
            argnums=(0, 1, 2, 3, 4))(*args)

    want, got = grads(plain_chain), grads(fused_chain)
    for name, g, w in zip(("zxbcdt", "conv_w", "conv_b", "D", "weight"),
                          got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = g.astype(F32), w.astype(F32)
        scale = float(jnp.max(jnp.abs(w)))
        tol = tolerance(dtype)
        np.testing.assert_allclose(g / scale, w / scale, err_msg=name,
                                   rtol=tol["rtol"], atol=tol["atol"])


# -- packed rows: the conv stops at a document's first step ---------------------

# case -> per row, the steps at which a document starts (beside step 0)
PACKED = {
    # boundaries around the tile edges 128 and 256: the step before, on and
    # after; a document of one step; one that starts on the row's last step
    "ragged-tail": ([5, 127, 128, 129, 256, 257],),
    "boundary-in-halo": ([128, 256, 257], [1, 2, 3]),
    "short-odd-widths": ([2, 3],),
    "tiny-config": ([10, 11], [36]),
}


def segment_ids(case):
    B, T = CASES[case][:2]
    rows = []
    for starts in PACKED[case]:
        first = np.zeros(T, np.int32)
        first[starts] = 1
        rows.append(np.cumsum(first, dtype=np.int32))
    assert len(rows) == B
    return jnp.asarray(np.stack(rows))


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(PACKED))
def test_packed_values_and_gradients_match_the_plain_chain(case, dtype,
                                                           tiles):
    """The interpreted Pallas forward and backward with document
    boundaries against the ``jnp`` chain that compares the ids tap by
    tap."""
    args, static, tile, ct = operands(case, dtype)
    tiles(tile)
    seg = segment_ids(case)
    want = plain_chain(*args, *static, segment_ids=seg)
    got = fused_chain(*args, *static, segment_ids=seg)
    np.testing.assert_allclose(got.astype(F32), want.astype(F32),
                               **tolerance(dtype))
    # the boundaries are felt
    apart = fused_chain(*args, *static)
    assert float(jnp.abs(apart.astype(F32) - got.astype(F32)).max()) > 1e-2
    assert_same_gradients(args, static, ct, dtype, segment_ids=seg)


def test_each_document_convolves_as_it_does_alone(tiles):
    args, (scan, d_inner, G), tile, _ = operands("ragged-tail", F32)
    tiles(tile)
    seg = segment_ids("ragged-tail")
    # a scan that mixes nothing along time: the chain is then step-local
    # but for the conv
    local = lambda x, Bm, Cm, dt: x * 0.5
    packed = fused_chain(*args, local, d_inner, G, segment_ids=seg)
    cuts = [0] + PACKED["ragged-tail"][0] + [CASES["ragged-tail"][1]]
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        alone = fused_chain(args[0][:, lo:hi], *args[1:], local, d_inner, G)
        np.testing.assert_allclose(packed[:, lo:hi], alone, rtol=2e-5,
                                   atol=2e-5)


def test_one_document_a_row_said_or_unsaid(tiles):
    args, static, tile, _ = operands("ragged-tail", F32)
    tiles(tile)
    zeros = jnp.zeros(args[0].shape[:2], jnp.int32)
    np.testing.assert_array_equal(
        fused_chain(*args, *static, segment_ids=zeros),
        fused_chain(*args, *static))


@pytest.mark.parametrize("reads", ["x", "x-twice", "B-C", "dt", "nothing"])
def test_a_scan_may_read_what_it_likes(reads, tiles):
    """The one cotangent buffer holds whatever the caller's scan reads of
    its operands: an operand left unread sends zeros back, one read twice
    the sum, and d zxbcdt is defined in every channel either way."""
    args, (_, d_inner, G), tile, ct = operands("ragged-tail", F32)
    tiles(tile)
    mix = jax.random.normal(jax.random.key(3), (32, d_inner), F32) / 6

    def scan(x, Bm, Cm, dt):
        return {"x": lambda: jnp.tanh(x),
                "x-twice": lambda: x * jnp.roll(x, 1, axis=1),
                "B-C": lambda: (Bm * Cm) @ mix,
                "dt": lambda: jnp.repeat(jax.nn.softplus(dt), 8, axis=-1),
                "nothing": lambda: jnp.ones_like(x)}[reads]()

    assert_same_gradients(args, (scan, d_inner, G), ct, F32)


def test_only_the_chain_is_public():
    """The two operations are halves of a pair (one's backward completes
    the other's cotangent buffer): the module offers the chain alone, and
    no time tile to choose."""
    public = [n for n, v in vars(ssm_fused).items()
              if callable(v) and not n.startswith("_")
              and getattr(v, "__module__", None) == ssm_fused.__name__]
    assert public == ["mamba_chain"]
    assert "tile" not in inspect.signature(ssm_fused.mamba_chain).parameters


def _fused_calls():
    fam = registry().get("dl4j_ssm_fused_calls_total")
    return {} if fam is None else {
        labels: child.value() for labels, child in fam.children()}


@pytest.mark.parametrize("remat,fwd", [(True, 2), (False, 1)],
                         ids=["remat", "no-remat"])
def test_a_traced_step_counts_its_fused_passes(remat, fwd):
    """``dl4j_ssm_fused_calls_total{op,kind}`` at trace time: under
    per-block recomputation each Mamba-2 block traces its two operations
    twice forward (the forward and the recomputed forward) and once
    backward."""
    config = hybrid_lm.HybridLMConfig.tiny()
    blocks = config.pattern.count(hybrid_lm.MAMBA)
    params = jax.eval_shape(
        lambda: hybrid_lm.init_params(jax.random.key(0), config))
    opt = jax.eval_shape(hybrid_lm.init_opt_state, params)
    batch = {"input_ids": jax.ShapeDtypeStruct((1, 24), jnp.int32)}
    step = hybrid_lm.make_train_step(config, remat=remat)
    before = _fused_calls()
    step.lower(params, opt, batch, 0)
    after = _fused_calls()
    delta = {k: after[k] - before.get(k, 0) for k in after}
    assert delta == {(op, kind): blocks * n
                     for op in ("conv_silu", "gate_norm")
                     for kind, n in (("fwd", fwd), ("bwd", 1))}


def test_the_model_holds_no_second_copy_of_the_chain():
    """`_mamba` calls the fused operations and nothing else between its
    projections: no inline conv, gate or group norm, no branch that
    chooses an implementation."""
    tree = ast.parse(textwrap.dedent(inspect.getsource(hybrid_lm._mamba)))
    calls = [ast.unparse(n.func) for n in ast.walk(tree)
             if isinstance(n, ast.Call)]
    assert calls.count("mamba_chain") == 1
    assert not {"jax.nn.silu", "jnp.pad", "_rms_norm", "sum", "jnp.split",
                "os.environ.get", "jax.default_backend"} & set(calls)
    assert not [n for n in ast.walk(tree)
                if isinstance(n, (ast.If, ast.IfExp, ast.Try))]
    assert "jax.nn.silu" not in inspect.getsource(hybrid_lm._mamba)
    # the pre-norms' and the final norm's, as it was
    assert list(inspect.signature(hybrid_lm._rms_norm).parameters) == [
        "x", "w", "eps", "groups"]
