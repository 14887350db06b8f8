"""The latent-attention / gated-expert / multi-token-prediction decoder
(`models.hybrid_lm` with `L` blocks and an MTP module, `ops.moe` with
gated-SiLU experts, `kernels.attention` with a value width of its own) at
a tiny size on the CPU, seeded random weights, against the benchmark's
plain reference (`benchmark/reference/joyai_flash.py`): pattern `L-LELE`,
4 experts held of 16, top 4, one MTP module."""
import hashlib
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

from benchmark.reference import joyai_flash as ref  # noqa: E402
from deeplearning4j_tpu import kernels  # noqa: E402
from deeplearning4j_tpu.models import hybrid_lm  # noqa: E402
from deeplearning4j_tpu.ops import moe  # noqa: E402

with open(os.path.join(ROOT, "benchmark/tests/configs/joyai-tiny.json")) as f:
    CFG = json.load(f)
D = ref.dims(CFG)
F32 = jnp.float32


def program_config(dtype=F32, **kw):
    """The tiny configuration file as the program's config."""
    base = dict(
        vocab_size=D["V"], hidden_size=D["E"],
        hybrid_override_pattern=D["pattern"], norm_eps=D["eps"],
        num_attention_heads=D["heads"], q_lora_rank=D["q_rank"],
        kv_lora_rank=D["kv_rank"], qk_nope_head_dim=D["dn"],
        qk_rope_head_dim=D["dr"], v_head_dim=D["dv"], rope_theta=D["theta"],
        intermediate_size=D["Fd"], mlp_hidden_act="silu",
        moe_hidden_act="silu", n_routed_experts=D["experts"],
        num_experts_per_tok=D["top_k"], moe_intermediate_size=D["F"],
        moe_shared_expert_intermediate_size=D["Fs"],
        routed_scaling_factor=D["scale"], first_expert=D["first"],
        experts_held=D["held"], rescale_layers=D["depth"],
        num_nextn_predict_layers=D["mtp"], mtp_loss_weight=D["lam"],
        dtype=dtype)
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


# -- the model against the reference ----------------------------------------

def test_tiny_config_is_the_familys_shape(inputs):
    assert D["pattern"] == "L-LELE" and (D["held"], D["experts"]) == (4, 16)
    assert (D["top_k"], D["mtp"], D["lam"]) == (4, 1, 0.3)
    c = hybrid_lm.HybridLMConfig.tiny(latent=True)
    assert c.pattern == D["pattern"] and c.qk_head_dim == 12
    mine = hybrid_lm.init_params(jax.random.key(0), c)
    flat = inputs[0]
    assert ({k: v.shape for k, v in zip(ref.leaf_names(mine),
                                        jax.tree_util.tree_leaves(mine))}
            == {k: v.shape for k, v in flat.items()})
    # the module's parameters are leaves of the tree Adam walks
    moments = hybrid_lm.init_opt_state(mine)[1]
    assert len(jax.tree_util.tree_leaves(moments)) == len(flat)
    assert mine["mtp"]["merge"].shape == (2 * D["E"], D["E"])
    assert mine["mtp"]["merge"].dtype == jnp.bfloat16
    assert mine["blocks"][2]["q_norm"].dtype == F32


def test_forward_logits_match_the_reference(inputs):
    flat, ids = inputs
    logits = hybrid_lm.forward(as_f32(ref.nest(flat)), ids[0],
                               program_config())
    want = ref.logits({k: v.astype(F32) for k, v in flat.items()}, ids[0], D)
    assert logits.shape == (2, 37, D["V"]) and logits.dtype == F32
    np.testing.assert_allclose(logits, want, atol=2e-5, rtol=2e-4)


def test_both_losses_and_gradients_match_the_reference(inputs):
    flat, ids = inputs
    want = ref.first_step(flat, ids[0], CFG, lr=1e-4)
    c = program_config()
    (loss, (counts, _, mtp, _)), grads = jax.value_and_grad(
        lambda p: hybrid_lm._loss_terms(p, {"input_ids": ids[0]}, c, False),
        has_aux=True)(as_f32(ref.nest(flat)))
    assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
    assert float(mtp) == pytest.approx(want["mtp_loss"], rel=1e-5)
    assert float(loss) == pytest.approx(
        want["main_loss"] + 0.3 * want["mtp_loss"], rel=1e-6)
    # two expert blocks of the main stack, then the module's
    assert counts.shape == (3, 4)
    assert counts.tolist() == want["expert_tokens"]
    got = named_norms(grads)
    assert set(got) == set(want["grad_norms"])
    for k, v in want["grad_norms"].items():
        assert got[k] == pytest.approx(v, rel=2e-4, abs=1e-9), k
    # the whole-model expression gives what the block-by-block pass gives
    main, mtp_ref, _ = ref.losses({k: v.astype(F32) for k, v in flat.items()},
                                  ids[0], D, attn_rows=16)
    assert float(main) == pytest.approx(want["main_loss"], rel=1e-5)
    assert float(mtp_ref) == pytest.approx(want["mtp_loss"], rel=1e-5)


def test_one_train_step_matches_the_reference(inputs):
    """bfloat16 parameters, float32 activations: the step's two losses,
    Adam's first moment and each leaf's change against the reference's."""
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
    assert float(aux["mtp_loss"]) == pytest.approx(want["mtp_loss"], rel=1e-5)
    assert aux["expert_tokens"].shape == (3, 4)
    assert params["embed"].dtype == jnp.bfloat16
    assert params["mtp"]["blocks"][1]["router"].dtype == F32
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
    # the counters: six assignments a token and block; the MTP positions
    from deeplearning4j_tpu.common.metrics import registry
    fam = registry().get("dl4j_mtp_positions_total")
    before = fam.value() if fam is not None else 0.0
    loss = hybrid_lm.observe(aux, c, 2 * 37)
    assert loss == pytest.approx(want["loss"], rel=1e-5)
    assert (registry().get("dl4j_mtp_positions_total").value() - before
            == 2 * 35)


def test_no_module_traces_nothing(inputs):
    """`num_nextn_predict_layers` 0: no `mtp` subtree, no third auxiliary,
    the loss is the next-token loss alone."""
    flat, ids = inputs
    c = program_config(num_nextn_predict_layers=0)
    tree = as_f32(ref.nest(flat))
    tree.pop("mtp")
    loss, terms = hybrid_lm._loss_terms(tree, {"input_ids": ids[0]}, c, False)
    assert len(terms) == 2 and terms[0].shape == (2, 4)
    want = ref.first_step(flat, ids[0], CFG, lr=1e-4)
    assert float(loss) == pytest.approx(want["main_loss"], rel=1e-5)
    assert "mtp" not in hybrid_lm.init_params(jax.random.key(0), c)


def test_mtp_predicts_two_ahead(inputs):
    """The module's loss is taken against x_{t+2}: against x_{t+1} (the
    reference's planted fault) it reads another number, which the
    program's does not equal."""
    flat, ids = inputs
    c = program_config()
    _, (_, _, mtp, per_pos) = hybrid_lm._loss_terms(
        as_f32(ref.nest(flat)), {"input_ids": ids[0]}, c, False)
    f32 = {k: v.astype(F32) for k, v in flat.items()}
    right = float(ref.losses(f32, ids[0], D, attn_rows=16)[1])
    shifted = float(ref.losses(f32, ids[0], D, attn_rows=16,
                               fault="mtp_shift")[1])
    assert float(mtp) == pytest.approx(right, rel=1e-5)
    assert abs(shifted - right) > 1e-3 * right
    # position by position: the reference's terms, nothing from T - 2 on
    want = ref.first_step(flat, ids[0], CFG, lr=1e-4)["mtp_token_loss"]
    np.testing.assert_allclose(per_pos, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(per_pos[:, -2:]).max()) == 0.0
    assert float(per_pos[:, :-2].min()) > 0.0
    # and the module sees Emb(x_{t+1}): another token at position t + 1
    # moves the module's loss at t and leaves the main logits at <= t alone
    moved = ids[0].at[:, 20].set((ids[0][:, 20] + 1) % D["V"])
    _, (_, _, mtp_moved, _) = hybrid_lm._loss_terms(
        as_f32(ref.nest(flat)), {"input_ids": moved}, c, False)
    assert abs(float(mtp_moved) - float(mtp)) > 1e-6


# -- rotary -------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(2, 9, 3, 8), (1, 17, 4)])
def test_rotary_is_a_complex_rotation(shape):
    """Pair (x_2i, x_2i+1) as the complex number x_2i + i x_2i+1, times
    exp(i pos theta^(-2i/d)); with or without a heads axis."""
    theta = 3.2e7
    x = jax.random.normal(jax.random.key(1), shape, F32)
    d, t = shape[-1], shape[1]
    z = np.asarray(x).reshape(shape[:-1] + (d // 2, 2))
    z = z[..., 0] + 1j * z[..., 1]
    freq = theta ** (-np.arange(0, d, 2) / d)
    turn = np.exp(1j * np.arange(t)[:, None] * freq[None, :])
    turn = turn.reshape((1, t) + (1,) * (len(shape) - 3) + (d // 2,))
    want = z * turn
    want = np.stack([want.real, want.imag], axis=-1).reshape(shape)
    np.testing.assert_allclose(hybrid_lm.rotary(x, theta), want,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref.rope(x, theta), want, rtol=1e-5, atol=1e-5)
    # position 0 is not turned; the norm of a pair is kept
    np.testing.assert_allclose(hybrid_lm.rotary(x, theta)[:, 0], x[:, 0],
                               rtol=1e-6)


def test_rotary_keeps_bfloat16_and_its_scope():
    x = jax.random.normal(jax.random.key(2), (1, 6, 2, 4)).astype(jnp.bfloat16)
    assert hybrid_lm.rotary(x, 1e4).dtype == jnp.bfloat16
    text = jax.jit(lambda x: hybrid_lm.rotary(x, 1e4)).lower(x).as_text(
        debug_info=True)
    assert "dl4j.rope" in text


# -- the attention core with a value width of its own ---------------------------

def attention_inputs(b=2, t=40, h=4, d=12, dv=8, seed=3):
    k = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k[0], (b, t, h, d), F32),
            jax.random.normal(k[1], (b, t, h, d), F32),
            jax.random.normal(k[2], (b, t, h, dv), F32))


@pytest.mark.parametrize("t,tiles", [(40, {}), (160, {}),
                                     (96, dict(tile_q=32, tile_k=32))])
def test_flash_with_a_value_width_is_the_xla_core(t, tiles):
    """192 / 128 in small: 12-wide scores, 8-wide values, causal; one tile
    (t <= 128 streams as one), padded (160 -> 256), several tiles."""
    q, k, v = attention_inputs(t=t)
    want = kernels.attention(q, k, v, path="xla", head_dim=12, v_head_dim=8,
                             causal=True)
    assert want.shape == (2, t, 4, 8)
    if tiles:
        got = kernels.flash_attention(q, k, v, causal=True, v_head_dim=8,
                                      **tiles)
    else:
        got = kernels.attention(q, k, v, path="flash", head_dim=12,
                                v_head_dim=8, causal=True)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # by hand, one head
    s = jnp.einsum("td,sd->ts", q[0, :, 1], k[0, :, 1]) / np.sqrt(12.0)
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), s,
                                 -jnp.inf), axis=-1)
    np.testing.assert_allclose(want[0, :, 1], p @ v[0, :, 1],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("packed_layout", [False, True])
def test_flash_with_a_value_width_has_the_xla_cores_gradients(packed_layout):
    q, k, v = attention_inputs(t=96, seed=4)
    if packed_layout:       # [B, T, H*D] as the projections hand it over
        q, k, v = (x.reshape(x.shape[:2] + (-1,)) for x in (q, k, v))
    ct = jax.random.normal(jax.random.key(9), v.shape[:2] + (
        (4 * 8,) if packed_layout else (4, 8)))

    def grads(path):
        return jax.grad(lambda q, k, v: jnp.sum(kernels.attention(
            q, k, v, path=path, head_dim=12, v_head_dim=8,
            causal=True) * ct), argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(grads("flash"), grads("xla")):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_a_value_width_counts_its_passes():
    from deeplearning4j_tpu.common.metrics import registry
    name = "dl4j_flash_split_width_passes_total"

    def count():
        fam = registry().get(name)
        return {} if fam is None else {k: c.value() for k, c in fam.children()}

    before = count()
    q, k, v = attention_inputs(t=24, seed=6)
    jax.grad(lambda q: jnp.sum(kernels.flash_attention(
        q, k, v, causal=True, v_head_dim=8)))(q)
    kernels.flash_attention(q, k, q, causal=True)       # equal widths: none
    after = count()
    for kernel in ("fwd", "dq", "dkv"):
        key = (kernel, "12x8")
        assert after[key] - before.get(key, 0.0) >= 1.0
    assert set(after) - set(before) <= {(k_, "12x8")
                                        for k_ in ("fwd", "dq", "dkv")}


def test_the_model_on_the_interpreted_kernel(inputs, flash_everywhere):
    """The whole tiny model with its seven cores on the flash path gives
    the XLA path's losses."""
    flat, ids = inputs
    want = ref.first_step(flat, ids[0], CFG, lr=1e-4)
    loss, (_, _, mtp, _) = hybrid_lm._loss_terms(
        as_f32(ref.nest(flat)), {"input_ids": ids[0]}, program_config(),
        False)
    assert kernels.dispatch_snapshot()["attention"]["path"] == "flash"
    assert float(loss) == pytest.approx(want["loss"], rel=2e-5)
    assert float(mtp) == pytest.approx(want["mtp_loss"], rel=2e-5)


# -- gated experts --------------------------------------------------------------

def expert_inputs(t=64, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    E, F, n = D["E"], D["F"], D["experts"]
    return dict(u=jax.random.normal(k[0], (1, t, E)),
                router=0.5 * jax.random.normal(k[1], (E, n)),
                w1=jax.random.normal(k[2], (n, 2 * F, E)) / 4,
                w2=jax.random.normal(k[3], (n, F, E)) / 4,
                shared_w1=jax.random.normal(k[4], (E, 2 * D["Fs"])) / 4,
                shared_w2=jax.random.normal(k[5], (D["Fs"], E)) / 4)


def gated(u, w1, w2):
    """Down (silu(Gate u) * Up u) of one expert, w1 = [Gate ; Up]."""
    a, b = jnp.split(jnp.einsum("te,fe->tf", u, w1, precision="highest"), 2,
                     axis=-1)
    return jnp.einsum("tf,fe->te", jax.nn.silu(a) * b, w2,
                      precision="highest")


def test_the_shares_add_up():
    """The routed parts that all four shares of 4 give, the shared expert
    counted once, equal the uncut reference's expert layer over all 16."""
    x = expert_inputs()
    whole = dict(D, held=D["experts"], first=0)
    weights = {k: x[k] for k in ("router", "w1", "w2", "shared_w1",
                                 "shared_w2")}
    want, want_counts = ref.experts(x["u"], weights, whole, "f32", None)
    u = x["u"][0]
    idx, gates = moe.route(u, x["router"], D["top_k"], D["scale"])
    total, counts = 0.0, []
    for first in range(0, D["experts"], D["held"]):
        own = slice(first, first + D["held"])
        part, n = moe.routed_experts(u, x["w1"][own], x["w2"][own], idx,
                                     gates, first, D["experts"], act="silu")
        total = total + part
        counts += n.tolist()
    shared = hybrid_lm._gated_mlp(u, x["shared_w1"], x["shared_w2"])
    np.testing.assert_allclose(total + shared, want[0], rtol=2e-4, atol=2e-4)
    assert counts == want_counts.tolist()
    assert sum(counts) == u.shape[0] * D["top_k"]
    # one share alone is what the reference gives when told the same share
    part, _ = moe.routed_experts(u, x["w1"][4:8], x["w2"][4:8], idx, gates,
                                 4, D["experts"], act="silu")
    share = dict(D, first=4)
    held = dict(weights, w1=x["w1"][4:8], w2=x["w2"][4:8])
    np.testing.assert_allclose(
        part + shared, ref.experts(x["u"], held, share, "f32", None)[0][0],
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t", [64, 200])
def test_no_token_is_dropped_when_all_route_to_one_held_expert(t):
    """Every token's first choice is held expert 2 (the others are not
    held): far more than the buffer's share, so at t = 200 the walk over
    further buffers runs too, and every token's term is there."""
    x = expert_inputs(t, seed=1)
    u = x["u"][0]
    idx = jnp.stack([jnp.full((t,), 2)] + [jnp.full((t,), 12 + j)
                                            for j in range(3)], axis=1)
    gates = jax.random.uniform(jax.random.key(2), (t, 4), F32, 0.5, 1.5)
    out, counts = moe.routed_experts(u, x["w1"][:4], x["w2"][:4], idx, gates,
                                     0, D["experts"], act="silu")
    assert counts.tolist() == [0, 0, t, 0]
    want = gates[:, :1] * gated(u, x["w1"][2], x["w2"][2])
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)
    assert float(jnp.abs(out).max(axis=1).min()) > 0     # no row is empty


def test_overflow_walk_has_the_same_gradients():
    """The path a step takes when its load passes the buffer gives the
    gradients of the dense sum, through the gate and the up half alike."""
    t = 200
    x = expert_inputs(t, seed=2)
    u, w1, w2 = x["u"][0], x["w1"][:4], x["w2"][:4]
    idx = jnp.stack([jnp.arange(t) % 2 + 1] + [jnp.full((t,), 12 + j)
                                               for j in range(3)], axis=1)
    gates = jax.random.uniform(jax.random.key(2), (t, 4), F32, 0.5, 1.5)
    ct = jax.random.normal(jax.random.key(3), u.shape)

    def dense(u, w1, w2, gates):
        out = 0.0
        for e in (1, 2):
            g = jnp.where(idx[:, 0] == e, gates[:, 0], 0.0)
            out = out + g[:, None] * gated(u, w1[e], w2[e])
        return jnp.sum(out * ct)

    def mine(u, w1, w2, gates):
        out, _ = moe.routed_experts(u, w1, w2, idx, gates, 0, D["experts"],
                                    act="silu")
        return jnp.sum(out * ct)

    got = jax.grad(mine, argnums=(0, 1, 2, 3))(u, w1, w2, gates)
    want = jax.grad(dense, argnums=(0, 1, 2, 3))(u, w1, w2, gates)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-3,
                                   atol=2e-4 * float(jnp.abs(b).max()))


def test_an_unknown_activation_is_refused():
    x = expert_inputs(8)
    with pytest.raises(ValueError, match="activation"):
        moe.routed_experts(x["u"][0], x["w1"][:4], x["w2"][:4],
                           jnp.zeros((8, 4), jnp.int32), jnp.ones((8, 4)), 0,
                           16, act="gelu")
    with pytest.raises(ValueError, match="moe_hidden_act"):
        hybrid_lm.init_params(jax.random.key(0), program_config(
            moe_hidden_act="gelu"))


def test_latent_blocks_refuse_what_they_cannot_do(inputs):
    flat, ids = inputs
    with pytest.raises(NotImplementedError):
        hybrid_lm._loss_terms(
            as_f32(ref.nest(flat)),
            {"input_ids": ids[0], "segment_ids": jnp.zeros_like(ids[0])},
            program_config(), False)
    with pytest.raises(ValueError, match="both"):
        hybrid_lm._attention_path(program_config(
            hybrid_override_pattern="L*"), 16)


# -- what the other models lower to ---------------------------------------------

#: sha256 (first 16 hex digits) of the lowered text of the PARENT commit
#: (c3be9ee, PR 34), read with jax 0.9.0 on the CPU by the same expressions
#: as below: the new arguments (`v_head_dim`, `act`, the MTP switch, the
#: latent letters) change nothing that a model without them lowers to.
#: "attention-stream" was read again in PR 36 (the parent's was
#: 855b47610231eceb): the streaming forward rule now keeps its lse as the
#: lane-dense ``[BH, S]`` view, one slice and one reshape more in the
#: forward and one broadcast back to ``[BH, S, 1]`` in the backward, and
#: its two `checkpoint_name`s (which lower to nothing) advance MLIR's symbol
#: uniquifier by one; every other op is the parent's. The two models lower
#: on the XLA path, where no name exists, and keep their hashes
PARENT_TEXT = {
    "nemotron": "5c3d8f14c6dc93f2",
    "granite": "d921ac3e6b0e3ca4",
    "attention-flash": "2d6ebe082e35b381",
    "attention-xla": "52bf4823f5911f18",
    "attention-stream": "618f81acaffb5d58",
}


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _model_text(c, seg=False):
    p = jax.eval_shape(lambda: hybrid_lm.init_params(jax.random.key(0), c))
    batch = {"input_ids": jax.ShapeDtypeStruct((2, 24), jnp.int32)}
    if seg:
        batch["segment_ids"] = jax.ShapeDtypeStruct((2, 24), jnp.int32)
    return jax.jit(lambda p, b: jax.grad(
        lambda p: hybrid_lm._loss_terms(p, b, c, True)[0])(p)).lower(
        p, batch).as_text()


def _attention_text(path, t, h, hkv, d):
    q = jax.ShapeDtypeStruct((2 if t < 1024 else 1, t, h * d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((q.shape[0], t, hkv * d), jnp.bfloat16)
    g = lambda q, k, v: jax.grad(lambda q, k, v: kernels.attention(
        q, k, v, path=path, head_dim=d, causal=True).astype(F32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    return jax.jit(g).lower(q, kv, kv).as_text()


@pytest.mark.parametrize("what", sorted(PARENT_TEXT))
def test_equal_widths_lower_to_the_parents_text(what):
    if jax.__version__ != "0.9.0":
        pytest.skip("the parent's text was read with jax 0.9.0")
    if what == "nemotron":
        text = _model_text(hybrid_lm.HybridLMConfig.tiny())
    elif what == "granite":
        text = _model_text(hybrid_lm.HybridLMConfig.tiny(granite=True), True)
    elif what == "attention-stream":
        text = _attention_text("flash", 1024, 2, 2, 128)
    else:
        text = _attention_text(what.split("-")[1], 256, 4, 2, 64)
    assert _sha(text) == PARENT_TEXT[what]
