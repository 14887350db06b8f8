"""The hybrid Mamba-2 / sparse-expert / grouped-query language model
(`models.hybrid_lm`, `ops.ssm_scan`, `ops.moe`) at a tiny size on the CPU,
seeded random weights, against the benchmark's plain reference
(`benchmark/reference/nemotron_h.py`): pattern `MEM*E`, 8 experts held of
16, top 2."""
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

from benchmark.reference import nemotron_h as ref  # noqa: E402
from deeplearning4j_tpu.models import hybrid_lm  # noqa: E402
from deeplearning4j_tpu.ops import moe  # noqa: E402
from deeplearning4j_tpu.ops import ssm_scan  # noqa: E402

with open(os.path.join(ROOT, "benchmark/tests/configs/nemotron-tiny.json")) as f:
    CFG = json.load(f)
D = ref.dims(CFG)
F32 = jnp.float32


def ssd_chunked_scan(x, dt, A, B, C, chunk, segment_ids=None):
    """The scan on the references' steps-major operands (the entry's are
    time minor)."""
    tm = lambda v: jnp.moveaxis(v, 1, -1)
    return jnp.moveaxis(ssm_scan.ssd_chunked_scan(
        tm(x), tm(dt), A, tm(B), tm(C), chunk, segment_ids), -1, 1)


def program_config(dtype=F32, **kw):
    """The tiny configuration file as the program's config."""
    base = dict(
        vocab_size=D["V"], hidden_size=D["E"],
        hybrid_override_pattern=D["pattern"], mamba_num_heads=D["H"],
        mamba_head_dim=D["P"], ssm_state_size=D["N"], n_groups=D["G"],
        conv_kernel=D["K"], chunk_size=D["chunk"],
        num_attention_heads=D["heads"], num_key_value_heads=D["kv_heads"],
        head_dim=D["D"], n_routed_experts=D["experts"],
        num_experts_per_tok=D["top_k"], moe_intermediate_size=D["F"],
        moe_shared_expert_intermediate_size=D["Fs"],
        routed_scaling_factor=D["scale"], first_expert=D["first"],
        experts_held=D["held"], rescale_layers=D["depth"], dtype=dtype)
    base.update(kw)
    return hybrid_lm.HybridLMConfig(**base)


@pytest.fixture(scope="module")
def inputs():
    key = jax.random.key(5)
    flat = ref.make_flat_params(key, CFG)
    ids = ref.make_batches(key, CFG, 2, 2, 37)["input_ids"]
    return flat, ids


def own_copy(flat):
    """The nested parameters in buffers of their own: a train step donates
    what it is given, and `flat` is shared by the module's tests."""
    return jax.tree_util.tree_map(jnp.copy, ref.nest(flat))


def as_f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(F32), tree)


def named_norms(tree):
    names = ref.leaf_names(tree)
    return ref.expand({n: ref.leaf_norm(n, x) for n, x in
                       zip(names, jax.tree_util.tree_leaves(tree))})


# -- the model against the reference ----------------------------------------

def test_tiny_config_is_the_issues():
    assert D["pattern"] == "MEM*E" and (D["held"], D["experts"]) == (8, 16)
    assert D["top_k"] == 2
    assert hybrid_lm.HybridLMConfig.tiny().pattern == "MEM*E"


def test_forward_logits_match_the_reference(inputs):
    flat, ids = inputs
    c = program_config()
    logits = hybrid_lm.forward(as_f32(ref.nest(flat)), ids[0], c)
    f32 = {k: v.astype(F32) for k, v in flat.items()}
    h = f32["embed"][ids[0]]
    for i, kind in enumerate(D["pattern"]):
        h, _ = ref.block(kind, h, ref.block_weights(f32, i), D)
    want = jnp.einsum("bte,ev->btv", ref._rms(h, f32["final_norm"], D["eps"]),
                      f32["head"], precision="highest")
    assert logits.shape == (2, 37, D["V"]) and logits.dtype == F32
    np.testing.assert_allclose(logits, want, atol=2e-5, rtol=2e-4)


def test_loss_and_gradients_match_the_reference(inputs):
    flat, ids = inputs
    want = ref.first_step(flat, ids[0], CFG, lr=1e-4)
    (loss, counts), grads = jax.value_and_grad(
        lambda p: hybrid_lm.lm_loss(p, {"input_ids": ids[0]},
                                    program_config()),
        has_aux=True)(as_f32(ref.nest(flat)))
    assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
    assert counts.tolist() == want["expert_tokens"]
    got = named_norms(grads)
    assert set(got) == set(want["grad_norms"])
    for k, v in want["grad_norms"].items():
        assert got[k] == pytest.approx(v, rel=2e-4, abs=1e-9), k


def test_one_train_step_matches_the_reference(inputs):
    """bfloat16 parameters, float32 activations: the step's loss, Adam's
    first moment and each leaf's change against the reference's."""
    flat, ids = inputs
    lr = 1e-4
    want = ref.first_step(flat, ids[0], CFG, lr=lr)
    params, start = own_copy(flat), ref.nest(flat)
    step = hybrid_lm.make_train_step(program_config(), None,
                                     learning_rate=lr, remat=True)
    params, opt, aux = step(params, hybrid_lm.init_opt_state(params),
                            {"input_ids": ids[0]}, 0)
    assert float(aux["loss"]) == pytest.approx(want["loss"], rel=1e-5)
    assert aux["expert_tokens"].dtype == jnp.int32
    assert aux["expert_tokens"].shape == (2, 8)
    # parameters keep their stored types through the step
    assert params["embed"].dtype == jnp.bfloat16
    assert params["blocks"][0]["A_log"].dtype == F32
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


def test_learning_rate_may_be_a_schedule(inputs):
    """A schedule `iteration -> rate` is traced into the step: at an
    iteration where it gives the constant's rate the step is the
    constant's; where it gives less, a float32 leaf moves by less."""
    flat, ids = inputs
    lr = 1e-3
    ramp = lambda it: lr * jnp.minimum(1.0, (it + 1) / 4)
    c = program_config()

    def a_log_after(rate, it):
        params = own_copy(flat)
        step = hybrid_lm.make_train_step(c, None, learning_rate=rate,
                                         remat=False)
        params, _, _ = step(params, hybrid_lm.init_opt_state(params),
                            {"input_ids": ids[0]}, it)
        return np.asarray(params["blocks"][0]["A_log"])

    start = np.asarray(ref.nest(flat)["blocks"][0]["A_log"])
    np.testing.assert_array_equal(a_log_after(ramp, 3), a_log_after(lr, 3))
    moved = lambda x: np.linalg.norm(x - start)
    assert 0 < moved(a_log_after(ramp, 0)) < 0.3 * moved(a_log_after(lr, 0))


def test_remat_gives_the_same_loss_and_gradients(inputs):
    flat, ids = inputs
    params = as_f32(ref.nest(flat))
    run = lambda remat: jax.value_and_grad(
        lambda p: hybrid_lm.lm_loss(p, {"input_ids": ids[1]},
                                    program_config(), remat)[0])(params)
    (l0, g0), (l1, g1) = run(False), run(True)
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)


def test_make_train_step_refuses_a_mesh_and_a_wrong_depth(inputs):
    flat, ids = inputs
    with pytest.raises(NotImplementedError):
        hybrid_lm.make_train_step(program_config(), mesh=object())
    with pytest.raises(ValueError):
        hybrid_lm.forward(ref.nest(flat), ids[0],
                          program_config(hybrid_override_pattern="ME"))
    with pytest.raises(ValueError):
        hybrid_lm.init_params(jax.random.key(0),
                              program_config(hybrid_override_pattern="MX"))


def test_init_params_follows_the_stated_initialisation():
    c = hybrid_lm.HybridLMConfig.tiny(rescale_layers=52)
    p = hybrid_lm.init_params(jax.random.key(1), c)
    m, e = p["blocks"][0], p["blocks"][1]
    assert p["embed"].dtype == jnp.bfloat16 and e["router"].dtype == F32
    assert e["w1"].shape == (8, 24, 32) and e["router"].shape == (32, 16)
    assert m["in_proj"].shape == (32, 32 + (32 + 2 * 2 * 16) + 4)
    A = np.exp(np.asarray(m["A_log"]))
    assert (A >= 1).all() and (A <= 16).all()
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    assert np.asarray(m["D"]).tolist() == [1.0] * 4
    # matrices that write into the residual stream are scaled by 1/sqrt(52)
    ratio = float(jnp.std(e["shared_w2"].astype(F32))
                  / jnp.std(e["shared_w1"].astype(F32)))
    assert ratio == pytest.approx(52 ** -0.5, rel=0.1)
    n = sum(x.size for x in jax.tree_util.tree_leaves(p))
    assert n == sum(x.size for x in jax.tree_util.tree_leaves(
        ref.nest(ref.make_flat_params(jax.random.key(0), CFG))))


# -- the chunked scan against the recurrence --------------------------------

def scan_inputs(t, seed=3, b=2, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(k[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, h))),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (b, t, g, n)),
            jax.random.normal(k[4], (b, t, g, n)))


@pytest.mark.parametrize("t,chunk", [(21, 8), (32, 8), (5, 8), (40, 16)])
def test_chunked_scan_is_the_recurrence(t, chunk):
    """T not a multiple of the chunk, several chunks, less than one."""
    args = scan_inputs(t)
    # the reference's recurrence: one time step after another
    np.testing.assert_allclose(ssd_chunked_scan(*args, chunk),
                               ref.recurrence(*args, chunk),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,chunk", [(21, 8), (32, 16)])
def test_chunked_scan_gradients_are_the_recurrences(t, chunk):
    args = scan_inputs(t, seed=4)
    ct = jax.random.normal(jax.random.key(9), args[0].shape)
    grad = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * ct),
                              argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(grad(lambda *a: ssd_chunked_scan(*a, chunk)),
                    grad(lambda *a: ref.recurrence(*a, chunk))):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_state_crosses_chunk_boundaries():
    """An input in the first chunk is felt in the last: with a slow decay
    the output at t = 31 depends on x at t = 0, and dropping the carried
    state (the benchmark's planted fault) loses it."""
    x, dt, A, B, C = scan_inputs(32, seed=6)
    A = jnp.full_like(A, -0.01)
    y = ssd_chunked_scan(x, dt, A, B, C, 8)
    moved = ssd_chunked_scan(x.at[:, 0].add(1.0), dt, A, B, C, 8)
    assert float(jnp.abs(moved - y)[:, 31].max()) > 1e-3
    dropped = ref.recurrence(x, dt, A, B, C, 8, carry=False)
    assert float(jnp.abs(dropped - y)[:, 8:].max()) > 1e-2
    np.testing.assert_allclose(dropped[:, :8], y[:, :8], rtol=2e-5, atol=2e-5)


# -- the expert layer ---------------------------------------------------------

def expert_inputs(t=64, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    E, F, n = D["E"], D["F"], D["experts"]
    return dict(u=jax.random.normal(k[0], (1, t, E)),
                router=0.5 * jax.random.normal(k[1], (E, n)),
                w1=jax.random.normal(k[2], (n, F, E)) / 4,
                w2=jax.random.normal(k[3], (n, F, E)) / 4,
                shared_w1=jax.random.normal(k[4], (E, D["Fs"])) / 4,
                shared_w2=jax.random.normal(k[4], (D["Fs"], E)) / 4)


def test_the_shares_add_up():
    """The routed parts that all shares give, the shared expert counted
    once, equal the uncut reference's expert layer."""
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
                                     gates, first, D["experts"])
        total = total + part
        counts += n.tolist()
    shared = hybrid_lm._relu2_mlp(u, x["shared_w1"], x["shared_w2"])
    np.testing.assert_allclose(total + shared, want[0], rtol=2e-4, atol=2e-4)
    assert counts == want_counts.tolist()
    assert sum(counts) == u.shape[0] * D["top_k"]


@pytest.mark.parametrize("t", [64, 200])
def test_no_token_is_dropped_when_all_route_to_one_held_expert(t):
    """Every token's first choice is held expert 3 (its second is not
    held): 4x the expected load is passed (at t = 200 the buffer holds
    128 rows, so the walk over further buffers runs too) and every
    token's term is there."""
    x = expert_inputs(t, seed=1)
    u = x["u"][0]
    idx = jnp.stack([jnp.full((t,), 3), jnp.full((t,), 12)], axis=1)
    gates = jax.random.uniform(jax.random.key(2), (t, 2), F32, 0.5, 1.5)
    out, counts = moe.routed_experts(u, x["w1"][:8], x["w2"][:8], idx, gates,
                                     0, D["experts"])
    assert counts.tolist() == [0, 0, 0, t, 0, 0, 0, 0]
    h = jnp.square(jax.nn.relu(jnp.einsum("te,fe->tf", u, x["w1"][3],
                                          precision="highest")))
    want = gates[:, :1] * jnp.einsum("tf,fe->te", h, x["w2"][3],
                                     precision="highest")
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)
    assert float(jnp.abs(out).min(axis=1).max()) > 0     # no row is empty


def test_overflow_walk_has_the_same_gradients():
    """The path a step takes when its load passes the buffer gives the
    gradients of the dense sum."""
    t = 200
    x = expert_inputs(t, seed=2)
    u, w1, w2 = x["u"][0], x["w1"][:8], x["w2"][:8]
    idx = jnp.stack([jnp.arange(t) % 2 + 3, jnp.full((t,), 12)], axis=1)
    gates = jax.random.uniform(jax.random.key(2), (t, 2), F32, 0.5, 1.5)
    ct = jax.random.normal(jax.random.key(3), u.shape)

    def dense(u, w1, w2, gates):
        out = 0.0
        for e in (3, 4):
            g = jnp.where(idx[:, 0] == e, gates[:, 0], 0.0)
            out = out + g[:, None] * hybrid_lm._relu2_mlp(u, w1[e].T, w2[e])
        return jnp.sum(out * ct)

    sparse = lambda u, w1, w2, gates: jnp.sum(ct * moe.routed_experts(
        u, w1, w2, idx, gates, 0, D["experts"])[0])
    for a, b in zip(jax.grad(sparse, argnums=(0, 1, 2, 3))(u, w1, w2, gates),
                    jax.grad(dense, argnums=(0, 1, 2, 3))(u, w1, w2, gates)):
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(b).max()))


def test_expert_tokens_sum_to_the_held_assignments(inputs):
    flat, ids = inputs
    c = program_config()
    params = as_f32(ref.nest(flat))
    _, counts = hybrid_lm.hidden_states(params, ids[0], c)
    for block, n in zip((1, 4), counts):
        u = hybrid_lm._rms_norm(
            _hidden_before(params, ids[0], c, block),
            params["blocks"][block]["norm"], c.norm_eps).reshape(-1, D["E"])
        idx, _ = moe.route(u, params["blocks"][block]["router"], D["top_k"],
                           D["scale"])
        held = int(jnp.sum((idx >= D["first"])
                           & (idx < D["first"] + D["held"])))
        assert int(n.sum()) == held
        assert n.tolist() == [int(jnp.sum(idx == D["first"] + e))
                              for e in range(D["held"])]


def _hidden_before(params, ids, c, block):
    cut = hybrid_lm.HybridLMConfig(**{
        **c.__dict__, "hybrid_override_pattern": c.pattern[:block]})
    h, _ = hybrid_lm.hidden_states(
        dict(params, blocks=params["blocks"][:block]), ids, cut)
    return h


def test_observe_feeds_the_expert_load_counters(inputs):
    from deeplearning4j_tpu.common.metrics import registry
    flat, ids = inputs
    c = program_config()
    params = own_copy(flat)
    step = hybrid_lm.make_train_step(c, None, remat=False)
    value = lambda name: (registry().get(name).value()
                          if registry().get(name) else 0.0)
    names = ("dl4j_moe_assignments_total", "dl4j_moe_held_assignments_total")
    before = [value(n) for n in names]
    _, _, aux = step(params, hybrid_lm.init_opt_state(params),
                     {"input_ids": ids[0]}, 0)
    loss = hybrid_lm.observe(aux, c, ids[0].size)
    assert loss == pytest.approx(float(aux["loss"]))
    counts = np.asarray(aux["expert_tokens"])
    assert value(names[0]) - before[0] == ids[0].size * D["top_k"] * 2
    assert value(names[1]) - before[1] == counts.sum()
    assert registry().get("dl4j_moe_max_expert_tokens").value() == counts.max()
    per_expert = registry().get("dl4j_moe_expert_tokens_total")
    assert len(per_expert.children()) == counts.size


def test_grouped_query_attention_shares_kv_heads(inputs):
    """Query head i reads KV head i // (H / H_kv): the XLA core against a
    per-head loop."""
    flat, ids = inputs
    c = program_config()
    p = as_f32(ref.nest(flat))["blocks"][3]
    u = jax.random.normal(jax.random.key(8), (1, 19, D["E"]))
    got = hybrid_lm._attention(p, u, c, path="xla")
    H, Hkv, Dh = D["heads"], D["kv_heads"], D["D"]
    q = (u @ p["wq"]).reshape(1, 19, H, Dh)
    k = (u @ p["wk"]).reshape(1, 19, Hkv, Dh)
    v = (u @ p["wv"]).reshape(1, 19, Hkv, Dh)
    heads = []
    for i in range(H):
        s = q[0, :, i] @ k[0, :, i // (H // Hkv)].T / np.sqrt(Dh)
        s = jnp.where(jnp.tril(jnp.ones((19, 19), bool)), s, -jnp.inf)
        heads.append(jax.nn.softmax(s, -1) @ v[0, :, i // (H // Hkv)])
    want = jnp.concatenate(heads, axis=-1) @ p["wo"]
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)


def test_flash_path_matches_the_xla_core(inputs):
    """The kernel path (interpreted on the CPU), K/V heads repeated for
    the query heads that share them."""
    flat, ids = inputs
    c = program_config()
    p = as_f32(ref.nest(flat))["blocks"][3]
    u = jax.random.normal(jax.random.key(8), (1, 24, D["E"]))
    np.testing.assert_allclose(hybrid_lm._attention(p, u, c, path="flash"),
                               hybrid_lm._attention(p, u, c, path="xla"),
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("tile_q,tile_k", [(64, 16), (16, 64), (32, 32)])
def test_streaming_causal_kernel_with_uneven_tiles(tile_q, tile_k):
    """The streaming kernels under a causal mask with uneven tiles, as
    the cell's 2,048 x 512 (some tiles wholly masked): forward and
    gradients against plain masked softmax, grouped-query heads
    repeated."""
    from deeplearning4j_tpu.kernels import flash_attention
    ks = jax.random.split(jax.random.key(11), 4)
    B, T, Hkv, R, Dh = 1, 128, 2, 2, 16
    q = jax.random.normal(ks[0], (B, T, Hkv * R, Dh))
    k = jax.random.normal(ks[1], (B, T, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, T, Hkv, Dh))
    ct = jax.random.normal(ks[3], q.shape)

    def kernel(q, k, v):
        return flash_attention(q, jnp.repeat(k, R, 2), jnp.repeat(v, R, 2),
                               causal=True, tile_q=tile_q, tile_k=tile_k)

    def plain(q, k, v):
        s = jnp.einsum("btgrd,bsgd->bgrts", q.reshape(B, T, Hkv, R, Dh),
                       k) / np.sqrt(Dh)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        return jnp.einsum("bgrts,bsgd->btgrd", jax.nn.softmax(s, -1),
                          v).reshape(q.shape)

    np.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                               rtol=2e-4, atol=2e-5)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * ct),
                               argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads(kernel), grads(plain)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
