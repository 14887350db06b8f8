"""The window-and-full-attention / softmax-routed expert decoder
(`models.hybrid_lm` with `W` and `*` blocks under a config-driven rotary
embedding, `ops.moe` with a softmax router and no shared expert,
`kernels.flash_attention` with a window) at a tiny size on the CPU, seeded
random weights, against the benchmark's plain reference
(`benchmark/reference/mellum2.py`): pattern `WEWE*E`, 4 experts held of
16, top 4, a window of 5."""
import importlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import mellum2 as ref  # noqa: E402
from deeplearning4j_tpu import kernels  # noqa: E402
from deeplearning4j_tpu.models import hybrid_lm  # noqa: E402
from deeplearning4j_tpu.ops import moe  # noqa: E402

fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

with open(os.path.join(ROOT, "benchmark/tests/configs/mellum-tiny.json")) as f:
    CFG = json.load(f)
with open(os.path.join(ROOT, "benchmark/configs/mellum2-12b-a2.5b.json")) as f:
    REAL = json.load(f)
D = ref.dims(CFG)
F32 = jnp.float32


def program_config(dtype=F32, **kw):
    """The tiny configuration file as the program's config."""
    base = dict(
        vocab_size=D["V"], hidden_size=D["E"],
        hybrid_override_pattern=D["pattern"], norm_eps=D["eps"],
        num_attention_heads=D["heads"], num_key_value_heads=D["kv_heads"],
        head_dim=D["D"], sliding_window=D["window"],
        rope_parameters=D["rope"], moe_hidden_act="silu",
        n_routed_experts=D["experts"], num_experts_per_tok=D["top_k"],
        moe_intermediate_size=D["F"], moe_shared_expert_intermediate_size=0,
        scoring_func="softmax", routed_scaling_factor=1.0,
        first_expert=D["first"], experts_held=D["held"],
        rescale_layers=D["depth"], dtype=dtype)
    base.update(kw)
    return hybrid_lm.HybridLMConfig(**base)


@pytest.fixture(scope="module")
def inputs():
    key = jax.random.key(5)
    flat = ref.make_flat_params(key, CFG)
    ids = ref.make_batches(key, CFG, 2, 2, 37)["input_ids"]
    return flat, ids


def as_f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(F32), tree)


def named_norms(tree):
    names = ref.leaf_names(tree)
    return ref.expand({n: ref.leaf_norm(n, x) for n, x in
                       zip(names, jax.tree_util.tree_leaves(tree))})


# -- the windowed kernels ------------------------------------------------------

def gqa_inputs(t, seed=0, b=2, h=4, hkv=2, d=8):
    k = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k[0], (b, t, h * d), F32),
            jax.random.normal(k[1], (b, t, hkv * d), F32),
            jax.random.normal(k[2], (b, t, hkv * d), F32),
            jax.random.normal(k[3], (b, t, h * d), F32))


# (T, window, tiles): a window below, equal to and above a tile, T not a
# multiple of the tile, tiles of two sizes, the smallest window, one longer
# than the row
WINDOWED = [(96, 20, (32, 32)), (96, 32, (32, 32)), (96, 50, (32, 32)),
            (100, 24, (32, 32)), (128, 40, (32, 64)), (128, 40, (64, 32)),
            (96, 1, (32, 32)), (64, 200, (32, 32))]


@pytest.mark.parametrize("t,window,tiles", WINDOWED,
                         ids=lambda x: str(x).replace(" ", ""))
def test_windowed_passes_are_the_masked_xla_core(t, window, tiles):
    """The interpreted fwd, dq and dkv passes over the band's blocks give
    the XLA core under an explicit window mask: the output and all three
    gradients."""
    q, k, v, ct = gqa_inputs(t)

    def run(path):
        def loss(q, k, v):
            if path == "xla":
                o = kernels.attention(q, k, v, path="xla", head_dim=8,
                                      causal=True, window=window)
            else:
                kk, vv = (jnp.repeat(x.reshape(2, t, 2, 8), 2, axis=2)
                          .reshape(2, t, 32) for x in (k, v))
                o = fa.flash_attention(q, kk, vv, causal=True, head_dim=8,
                                       window=window, tile_q=tiles[0],
                                       tile_k=tiles[1])
            return jnp.sum(o * ct), o
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    (_, want), want_g = run("xla")
    (_, got), got_g = run("flash")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * max(float(jnp.abs(b).max()),
                                                   1.0))
    # by hand, one head: key j visible to query i iff i - window < j <= i
    s = jnp.einsum("td,sd->ts", q[0, :, :8], k[0, :, :8]) / np.sqrt(8.0)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where((j <= i) & (j > i - window), s, -jnp.inf),
                       axis=-1)
    np.testing.assert_allclose(want[0, :, :8], p @ v[0, :, :8],
                               rtol=2e-5, atol=2e-5)


def test_the_default_tiles_take_the_window():
    q, k, v, _ = gqa_inputs(160, seed=2)
    want = kernels.attention(q, k, v, path="xla", head_dim=8, causal=True,
                             window=48)
    got = kernels.attention(q, k, v, path="flash", head_dim=8, causal=True,
                            window=48)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert fa.streams(64, 8, window=16) and not fa.streams(64, 8)


@pytest.mark.parametrize("S,W,tq,tk", [(16384, 1024, 1024, 1024),
                                       (16384, 1024, 512, 512),
                                       (16384, 1024, 512, 1024),
                                       (384, 100, 128, 64), (256, 7, 64, 128)])
def test_the_band_walks_each_live_tile_once(S, W, tq, tk):
    """The narrowed grids: fwd/dq's steps of each query block, and dkv's of
    each key block, name blocks inside the band, and the steps that compute
    (those the kernels' `live` test passes) are the band's tiles, each
    once; a step that computes nothing names a block its row read last."""
    n_q, n_k = S // tq, S // tk
    band = fa._Band(W, tq, tk, n_q, n_k)
    live = {(iq, ik) for iq in range(n_q) for ik in range(n_k)
            if ik * tk <= iq * tq + tq - 1 and iq * tq <= ik * tk + tk + W - 2}
    assert band.computed_tiles() == len(live)
    seen = []
    for iq in range(n_q):
        prev = None
        for j in range(band.width_k):
            ik = int(band.kv_first(iq)) + j
            block = int(band.kv_block(iq, j))
            if bool(band.live(iq, ik)):
                assert block == ik
                seen.append((iq, ik))
            else:
                assert block == prev
            prev = block
    assert sorted(seen) == sorted(live)
    seen = []
    for ik in range(n_k):
        for j in range(band.width_q):
            iq = int(band.q_first(ik)) + j
            if bool(band.live(iq, ik)):
                assert int(band.q_block(ik, j)) == iq
                seen.append((iq, ik))
            else:
                assert int(band.q_block(ik, j)) < iq
    assert sorted(seen) == sorted(live)
    # a tile wholly inside the band needs no mask
    for iq, ik in live:
        keys = np.arange(ik * tk, ik * tk + tk)[None, :]
        queries = np.arange(iq * tq, iq * tq + tq)[:, None]
        inside = bool(np.all((keys <= queries) & (keys > queries - W)))
        assert bool(band.whole(iq, ik)) == inside


def test_the_cells_band_and_its_counters():
    """At the cell's T = 16,384, W = 1,024: 31 of a head's 256 tiles at
    1,024 x 1,024 (2 steps a query block), 93 of 1,024 at 512 x 512 (3 steps,
    the middle one unmasked); the counters say so for each pass."""
    b = fa._Band(1024, 1024, 1024, 16, 16)
    assert (b.computed_tiles(), b.width_k, b.width_q) == (31, 2, 2)
    assert not any(bool(b.whole(iq, ik)) for iq in range(16)
                   for ik in range(16))
    b = fa._Band(1024, 512, 512, 32, 32)
    assert (b.computed_tiles(), b.width_k, b.width_q) == (93, 3, 3)
    assert bool(b.whole(5, 4)) and not bool(b.whole(5, 3))
    from deeplearning4j_tpu.common.metrics import registry

    def count(name):
        fam = registry().get(name)
        return {} if fam is None else {k: c.value() for k, c in fam.children()}

    tiles0 = count("dl4j_flash_tiles_total")
    passes0 = count("dl4j_flash_window_passes_total")
    q, k, v, _ = gqa_inputs(96, seed=6)
    kk, vv = (jnp.repeat(x.reshape(2, 96, 2, 8), 2, axis=2).reshape(2, 96, 32)
              for x in (k, v))
    jax.grad(lambda q: jnp.sum(fa.flash_attention(
        q, kk, vv, causal=True, head_dim=8, window=20, tile_q=32,
        tile_k=32)))(q)
    tiles, passes = count("dl4j_flash_tiles_total"), count(
        "dl4j_flash_window_passes_total")
    for kernel in ("fwd", "dq", "dkv"):
        assert passes[(kernel, "20")] - passes0.get((kernel, "20"), 0) >= 1
        done = tiles[(kernel, "window_computed")] - tiles0.get(
            (kernel, "window_computed"), 0)
        skipped = tiles[(kernel, "window_skipped")] - tiles0.get(
            (kernel, "window_skipped"), 0)
        # 3 x 3 tiles: the diagonal's 3 and the 2 just below it
        assert done % 5 == 0 and skipped % 4 == 0 and done // 5 == \
            skipped // 4 >= 1


def test_a_window_refuses_what_is_not_built():
    q, k, v, _ = gqa_inputs(64)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, q, q, head_dim=8, window=8)
    with pytest.raises(NotImplementedError):
        fa.flash_attention(q, q, q, causal=True, head_dim=8, window=8,
                           segment_ids=jnp.zeros((2, 64), jnp.int32))
    with pytest.raises(NotImplementedError):
        fa.flash_attention(q, q, q, causal=True, head_dim=8, window=8,
                           mask=jnp.ones((2, 64), jnp.int32))


# -- rotary and YaRN --------------------------------------------------------------

def test_yarn_frequencies_follow_the_published_formula():
    """At the published sizes (D = 128, theta 5e5, x16 over 8,192, betas 32
    and 1) the ramp runs from pair 18 to pair 35; every frequency is the
    formula's, in float64 by hand."""
    params = REAL["rope_parameters"]["full_attention"]
    assert hybrid_lm.yarn_range(128, 5e5, 8192, 32, 1) == (18, 35)
    inv, factor = hybrid_lm.rope_frequencies(128, params)
    assert factor == pytest.approx(1.2772588722239782)
    i = np.arange(64, dtype=np.float64)
    base = 5e5 ** (2 * i / 128)
    ramp = np.clip((i - 18) / (35 - 18), 0, 1)
    want = (1 - ramp) / base + ramp / (16 * base)
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    # below the range the original frequency, above it a sixteenth
    assert float(inv[17]) == pytest.approx(1 / base[17], rel=2e-6)
    assert float(inv[36]) == pytest.approx(1 / (16 * base[36]), rel=2e-6)
    default, one = hybrid_lm.rope_frequencies(
        128, REAL["rope_parameters"]["sliding_attention"])
    np.testing.assert_allclose(default, 1 / base, rtol=2e-6)
    assert one == 1.0
    # the reference's own reading of the formula
    ref_inv, ref_factor = ref.frequencies(128, params)
    np.testing.assert_allclose(ref_inv, want, rtol=2e-6)
    assert ref_factor == factor
    # the published factor is YaRN's own default, 0.1 ln(factor) + 1
    assert factor == pytest.approx(0.1 * math.log(16) + 1)


@pytest.mark.parametrize("shape", [(2, 9, 3, 8), (1, 17, 4, 16)])
def test_half_split_rotary_is_a_complex_rotation(shape):
    """The pair (x_i, x_{i+d/2}) as a complex number times a e^{i pos f}."""
    x = jax.random.normal(jax.random.key(1), shape, F32)
    d, t = shape[-1], shape[1]
    inv = jnp.asarray(0.3 ** np.arange(d // 2), F32)
    got = hybrid_lm.rotary_half(x, inv, 1.25)
    z = np.asarray(x)[..., :d // 2] + 1j * np.asarray(x)[..., d // 2:]
    turn = 1.25 * np.exp(1j * np.arange(t)[:, None] * np.asarray(inv)[None])
    want = z * turn[None, :, None, :]
    np.testing.assert_allclose(got, np.concatenate([want.real, want.imag],
                                                   axis=-1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, ref.rope(x, inv, 1.25), rtol=1e-5,
                               atol=1e-5)
    bf = hybrid_lm.rotary_half(x.astype(jnp.bfloat16), inv)
    assert bf.dtype == jnp.bfloat16


# -- the router and the held shares -------------------------------------------

def expert_inputs(t=64, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    E, F, n = D["E"], D["F"], D["experts"]
    return dict(u=jax.random.normal(k[0], (1, t, E)),
                router=0.5 * jax.random.normal(k[1], (E, n)),
                w1=jax.random.normal(k[2], (n, 2 * F, E)) / 4,
                w2=jax.random.normal(k[3], (n, F, E)) / 4)


def test_the_softmax_router_is_the_references():
    """Top k of a float32 softmax over every expert, renormalised over the
    k; the sigmoid router is left as it was."""
    x = expert_inputs()
    u = x["u"][0]
    idx, gates = moe.route(u, x["router"], 4, 1.0, "softmax")
    logits = np.asarray(u, np.float64) @ np.asarray(x["router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    order = np.argsort(-p, axis=-1)[:, :4]
    np.testing.assert_array_equal(np.asarray(idx), order)
    chosen = np.take_along_axis(p, order, -1)
    np.testing.assert_allclose(gates, chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    s_idx, s_gates = moe.route(u, x["router"], 4, 2.5)
    s = 1 / (1 + np.exp(-logits))
    chosen = np.take_along_axis(s, np.asarray(s_idx), -1)
    np.testing.assert_allclose(s_gates, 2.5 * chosen / chosen.sum(
        -1, keepdims=True), rtol=1e-5)
    with pytest.raises(ValueError, match="scoring_func"):
        moe.route(u, x["router"], 4, 1.0, "tanh")


def test_the_shares_add_up():
    """The routed parts that all four shares of 4 give equal the uncut
    reference's expert layer over all 16 (there is no shared expert to
    count once), and one share alone is the reference told that share."""
    x = expert_inputs()
    whole = dict(D, held=D["experts"], first=0)
    weights = {k: x[k] for k in ("router", "w1", "w2")}
    want, want_counts = ref.experts(x["u"], weights, whole, "f32", None)
    u = x["u"][0]
    idx, gates = moe.route(u, x["router"], D["top_k"], 1.0, "softmax")
    total, counts = 0.0, []
    for first in range(0, D["experts"], D["held"]):
        own = slice(first, first + D["held"])
        part, n = moe.routed_experts(u, x["w1"][own], x["w2"][own], idx,
                                     gates, first, D["experts"], act="silu")
        total = total + part
        counts += n.tolist()
    np.testing.assert_allclose(total, want[0], rtol=2e-4, atol=2e-4)
    assert counts == want_counts.tolist()
    assert sum(counts) == u.shape[0] * D["top_k"]
    part, _ = moe.routed_experts(u, x["w1"][8:12], x["w2"][8:12], idx, gates,
                                 8, D["experts"], act="silu")
    held = dict(weights, w1=x["w1"][8:12], w2=x["w2"][8:12])
    np.testing.assert_allclose(
        part, ref.experts(x["u"], held, dict(D, first=8), "f32", None)[0][0],
        rtol=2e-4, atol=2e-4)


# -- the model against the reference ----------------------------------------

def test_tiny_config_is_the_models_shape(inputs):
    assert D["pattern"] == "WEWE*E" and (D["held"], D["experts"]) == (4, 16)
    c = program_config()
    mine = hybrid_lm.init_params(jax.random.key(0), c)
    flat = inputs[0]
    assert ({k: v.shape for k, v in zip(ref.leaf_names(mine),
                                        jax.tree_util.tree_leaves(mine))}
            == {k: v.shape for k, v in flat.items()})
    # no shared expert: no such leaves
    assert not any("shared" in k for k in ref.leaf_names(mine))


def test_loss_and_every_gradient_match_the_reference(inputs):
    flat, ids = inputs
    want = ref.first_step(flat, ids[0], CFG, lr=1e-4)
    c = program_config()
    (loss, (counts, _)), grads = jax.value_and_grad(
        lambda p: hybrid_lm._loss_terms(p, {"input_ids": ids[0]}, c, False),
        has_aux=True)(as_f32(ref.nest(flat)))
    assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
    assert counts.shape == (3, 4)
    assert counts.tolist() == want["expert_tokens"]
    got = named_norms(grads)
    assert set(got) == set(want["grad_norms"])
    for k, v in want["grad_norms"].items():
        assert got[k] == pytest.approx(v, rel=2e-4, abs=1e-9), k
    # the whole-model expression gives what the block-by-block pass gives
    whole, _ = ref.losses({k: v.astype(F32) for k, v in flat.items()},
                          ids[0], D, attn_rows=16)
    assert float(whole) == pytest.approx(want["loss"], rel=1e-5)


def test_each_planted_fault_moves_the_references_gradients(inputs):
    """The faults the limits are read against change what they say they
    change (each moves some gradient norm by more than round-off)."""
    flat, ids = inputs
    want = ref.first_step(flat, ids[0], CFG, lr=1e-4)["grad_norms"]
    for fault in ref.FAULTS:
        got = ref.first_step(flat, ids[0], CFG, lr=1e-4,
                             fault=fault)["grad_norms"]
        assert max(abs(got[k] - v) / max(v, 1e-9)
                   for k, v in want.items()) > 1e-3, fault


def test_one_train_step_matches_the_reference(inputs):
    """bfloat16 parameters, float32 activations: the step's loss, Adam's
    first moment and each leaf's change against the reference's."""
    flat, ids = inputs
    lr = 1e-4
    want = ref.first_step(flat, ids[0], CFG, lr=lr)
    params = jax.tree_util.tree_map(jnp.copy, ref.nest(flat))
    start = ref.nest(flat)
    c = program_config()
    step = hybrid_lm.make_train_step(c, None, learning_rate=lr, remat=True)
    params, opt, aux = step(params, hybrid_lm.init_opt_state(params),
                            {"input_ids": ids[0]}, 0)
    assert float(aux["loss"]) == pytest.approx(want["loss"], rel=1e-5)
    assert aux["expert_tokens"].tolist() == want["expert_tokens"]
    moments = ref.expand({n: ref.leaf_norm(n, m) / (1 - ref.ADAM_B1)
                          for n, m in zip(ref.leaf_names(params), opt[1])})
    for k, v in want["grad_norms"].items():
        # the step's gradients of bfloat16 leaves are bfloat16
        assert moments[k] == pytest.approx(v, rel=5e-3, abs=1e-9), k
    change = named_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(F32) - b.astype(F32), params, start))
    big = np.median(list(want["change_norms"].values()))
    for k, v in want["change_norms"].items():
        assert abs(change[k] - v) <= 0.02 * max(v, big), k


def test_the_model_on_the_interpreted_kernel(inputs, flash_everywhere):
    """The whole tiny model with its three cores on the flash path (the
    two window layers on the windowed kernels, the full one on the causal)
    gives the XLA path's loss and gradients."""
    flat, ids = inputs
    want = ref.first_step(flat, ids[0], CFG, lr=1e-4)
    (loss, _), grads = jax.value_and_grad(
        lambda p: hybrid_lm._loss_terms(p, {"input_ids": ids[0]},
                                        program_config(), True),
        has_aux=True)(as_f32(ref.nest(flat)))
    assert kernels.dispatch_snapshot()["attention"]["path"] == "flash"
    assert float(loss) == pytest.approx(want["loss"], rel=2e-5)
    got = named_norms(grads)
    for k, v in want["grad_norms"].items():
        assert got[k] == pytest.approx(v, rel=1e-3, abs=1e-8), k


def test_the_window_block_has_a_scope_of_its_own(inputs, flash_everywhere):
    """`W` cores run under ``attn_window``, the `*` core under
    ``attn_core``; the rotary under ``rope``, inside ``attn``."""
    flat, ids = inputs
    text = jax.jit(lambda p: hybrid_lm._loss_terms(
        p, {"input_ids": ids[0]}, program_config(), False)[0]).lower(
        as_f32(ref.nest(flat))).as_text(debug_info=True)
    for scope in ("attn_window", "attn_core", "rope"):
        assert f"dl4j.{scope}" in text or scope in text, scope


def test_window_blocks_refuse_what_they_cannot_do(inputs):
    flat, ids = inputs
    seg = {"input_ids": ids[0], "segment_ids": jnp.zeros_like(ids[0])}
    with pytest.raises(NotImplementedError):
        hybrid_lm._loss_terms(as_f32(ref.nest(flat)), seg, program_config(),
                              False)
    with pytest.raises(ValueError, match="sliding_window"):
        hybrid_lm._loss_terms(as_f32(ref.nest(flat)),
                              {"input_ids": ids[0]},
                              program_config(sliding_window=None), False)
    with pytest.raises(ValueError, match="both"):
        hybrid_lm._attention_path(program_config(
            hybrid_override_pattern="WL"), 16)
