"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached — the one file in the suite that describes a chip.

The TPU compiler is installed with jax; it compiles for a ``v5e:2x2``
topology without a device, and refuses what the chip's compiler would
refuse (a slice off the tiling, too much VMEM, a program that does not
fit). Nothing runs, so these say nothing about results or times — they
guard every later PR against a kernel that stops compiling, at no chip
time. Interpret-mode tests cannot: they accept any shape.

Only one process may load libtpu, so the topology is described inside a
fixture (never at import, never in a ``skipif``), the kernels compile in
this test process, and every case lives in this one file.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

# the package re-exports each kernel function under its module's name
fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")
pfd = importlib.import_module("deeplearning4j_tpu.kernels.paged_flash_decode")
sf = importlib.import_module("deeplearning4j_tpu.kernels.ssm_fused")
ssd = importlib.import_module("deeplearning4j_tpu.kernels.ssd_scan")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    from deeplearning4j_tpu.common.mesh import MeshConfig, make_mesh
    return make_mesh(MeshConfig(data=2, tensor=2), devices=topo.devices)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to jax's persistent
    cache but cannot be read back without the chip (the next one warns
    and recompiles): switch the cache off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch, no_persistent_cache):
    """Steer the kernels to compile (not interpret) though the backend
    here is the CPU."""
    for mod in (fa, pfd, sf, ssd):  # each binds its own name for the switch
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled, text


# (B, H, S, D, masked); the last is the training cell's: one-tile kernels
FLASH_SHAPES = [(4, 12, 2048, 64, False), (32, 12, 128, 64, False),
                (1, 12, 8192, 64, False), (8, 16, 512, 64, True)]


# (B, H, S, D): the hybrid cell's attention blocks, and what
# ``models.causal_lm`` would hand the kernel at a 2,048-token context
CAUSAL_SHAPES = [(1, 32, 8192, 128), (1, 12, 2048, 64)]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("shape", CAUSAL_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_hybrid_causal_attention_compiles_for_v5e(shape, grad, one_chip,
                                                  compiled_kernels):
    """The hybrid model's attention core as its training cell runs it:
    one sequence of 8,192, 32 query heads of 128 (the two KV heads
    repeated), causal, through the streaming kernels with the tiles they
    choose for a causal call — inside the VMEM limit they ask for, the
    skipped tiles' branches and clamped block indices accepted by Mosaic."""
    B, H, S, D = shape
    spec = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    core = lambda q, k, v: fa.flash_attention(q, k, v, causal=True)
    if grad:
        fn = jax.grad(lambda q, k, v: jnp.sum(
            core(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    else:
        fn = core
    compiled, text = _compile(fn, spec, spec, spec)
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (3 if grad else 1)
    assert compiled.memory_analysis().temp_size_in_bytes < B * H * S * S


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_latent_attention_core_compiles_for_v5e(grad, one_chip,
                                                compiled_kernels):
    """Latent attention's core as the JoyAI cell runs it: two rows of
    8,192, 32 heads whose queries and keys are 192 wide (128 + 64 rotary,
    not a multiple of the 128 lanes) and whose values are 128 wide, causal,
    through the same streaming kernels with the value, output and their
    gradients' blocks 128 wide."""
    B, H, S, D, Dv = 2, 32, 8192, 192, 128
    qk = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    v = _sds((B, S, H, Dv), jnp.bfloat16, one_chip)
    core = lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                              v_head_dim=Dv)
    if grad:
        fn = jax.grad(lambda q, k, v: jnp.sum(
            core(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    else:
        fn = core
    compiled, text = _compile(fn, qk, qk, v)
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (3 if grad else 1)
    assert compiled.memory_analysis().temp_size_in_bytes < B * H * S * S
    shapes = [o.shape for o in jax.tree_util.tree_leaves(
        jax.eval_shape(fn, qk, qk, v))]
    assert shapes == ([(B, S, H, D), (B, S, H, D), (B, S, H, Dv)] if grad
                      else [(B, S, H, Dv)])


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("tiles", [None, (1024, 1024)],
                         ids=["default", "1024x1024"])
def test_windowed_attention_compiles_for_v5e(tiles, grad, one_chip,
                                             compiled_kernels):
    """The Mellum2 cell's window layers: one row of 16,384, 32 query heads
    of 128 (the 4 KV heads repeated), a window of 1,024, through the three
    streaming passes over the band's blocks (their inner grid axis a few
    blocks long, the index maps clamped to the band)."""
    B, H, S, D = 1, 32, 16384, 128
    spec = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    sized = {} if tiles is None else dict(tile_q=tiles[0], tile_k=tiles[1])
    core = lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                              window=1024, **sized)
    if grad:
        fn = jax.grad(lambda q, k, v: jnp.sum(
            core(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    else:
        fn = core
    compiled, text = _compile(fn, spec, spec, spec)
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (3 if grad else 1)
    # no float32 band of scores in HBM: the relayouts of q, k, v, o alone
    assert compiled.memory_analysis().temp_size_in_bytes < \
        B * H * S * 1024 * 4


def test_window_cell_step_compiles_for_v5e(one_chip, compiled_kernels,
                                           monkeypatch):
    """The Mellum2 cell's whole training step (`WEWEWE*E`, 16 of 64
    experts, a 24,576-row vocabulary slice, one row of 16,384, per-block
    recomputation, Adam) compiles for one v5e and fits its 16 GB: the
    three window cores, the full one and the grouped expert products as
    Mosaic kernels (PERF.md gives the compiled sizes)."""
    import json
    from benchmark.drivers import train_hybrid_lm, train_window_lm
    from benchmark.reference import mellum2
    from deeplearning4j_tpu.models import _optim, hybrid_lm
    # the expert kernels are interpreted, and the dispatcher answers XLA,
    # where jax's backend is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root,
                           "benchmark/configs/mellum2-12b-a2.5b.json")) as f:
        cfg = json.load(f)
    c = train_window_lm._program_config(cfg)
    rate = train_hybrid_lm.learning_rate(cfg["train"])

    def step(params, opt, batch, it):
        (loss, terms), grads = jax.value_and_grad(
            lambda p: hybrid_lm._loss_terms(p, batch, c, True),
            has_aux=True)(params)
        params, opt = _optim.adam_apply(params, grads, opt, rate(it), it)
        return params, opt, loss, terms[0]

    on_chip = lambda x: _sds(x.shape, x.dtype, one_chip)
    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: mellum2.nest(mellum2.make_flat_params(jax.random.key(0),
                                                      cfg))))
    opt = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        hybrid_lm.init_opt_state, params))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == \
        595_153_152
    batch = {"input_ids": _sds((1, 16384), jnp.int32, one_chip)}
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, batch, _sds((), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    # 4 cores x 3 passes, and 8 grouped products a layer (forward, its
    # recomputation, two backward each)
    assert text.count('custom_call_target="tpu_custom_call"') == 12 + 4 * 8
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14e9


# (B, H, S, D): the packed granite cell's attention layer (32 query heads of
# 64, the 8 KV heads repeated, scores times 1/64), and a one-tile length
PACKED_SHAPES = [(1, 32, 16384, 64), (2, 4, 512, 64)]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("shape", PACKED_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_packed_causal_attention_compiles_for_v5e(shape, grad, one_chip,
                                                  compiled_kernels):
    """The kernels with document ids: the ids' column and row blocks (one
    lane wide, one sublane high) beside the tiles, shared by a batch row's
    heads through the block index; the streaming passes with the
    documents' table as their first operand, prefetched into SMEM, their
    index maps clamped by it and the tiles' branches chosen by it."""
    B, H, S, D = shape
    spec = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    ids = _sds((B, S), jnp.int32, one_chip)
    core = lambda q, k, v, seg: fa.flash_attention(
        q, k, v, causal=True, scale=1 / 64, segment_ids=seg)
    if grad:
        fn = jax.grad(lambda q, k, v, seg: jnp.sum(
            core(q, k, v, seg).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    else:
        fn = core
    compiled, text = _compile(fn, spec, spec, spec, ids)
    one_tile = S <= 512
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == ((2 if one_tile else 3) if grad else 1)
    assert compiled.memory_analysis().temp_size_in_bytes < B * H * S * S
    if not one_tile:
        tile_q, tile_k, _ = fa._tiles(S, True)
        table = "operand_layout_constraints={s32[%d]{0}" % (
            B * 3 * (S // tile_q + S // tile_k))
        assert all(table in line for line in calls)


# the packed granite cell's Mamba-2 layers: B = 1, T = 16,384, d_inner
# 4,096 in ONE group of state 128 (a gate tile holds the whole group),
# 64 heads; zxbcdt is [1, 8512, 16384]
GRANITE = dict(B=1, T=16384, d_inner=4096, n=128, heads=64, groups=1)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("op", ["conv_silu", "gate_norm"])
def test_packed_mamba_ops_compile_for_v5e(op, grad, one_chip,
                                          compiled_kernels):
    """`ssm_fused.mamba_chain`'s halves at the granite cell's shapes: the
    conv with the steps-since-the-document's-start operand (forward and
    the in-place backward), the gate norm over one group of 4,096
    channels."""
    m = GRANITE
    conv_dim = m["d_inner"] + 2 * m["n"]
    bf = lambda *shape: _sds(shape, jnp.bfloat16, one_chip)
    f32 = lambda *shape: _sds(shape, jnp.float32, one_chip)
    zxbcdt = bf(m["B"], m["d_inner"] + conv_dim + m["heads"], m["T"])
    x = bf(m["B"], m["d_inner"], m["T"])
    if op == "conv_silu":
        specs = [zxbcdt, f32(4, conv_dim), f32(conv_dim),
                 _sds((m["B"], 1, m["T"]), jnp.int32, one_chip)]
        fn = lambda z, w, b, since: sf._conv_silu(
            z, w, b, since, m["d_inner"], m["groups"])[1:]
        calls, wrt = 3, (0, 1, 2)
    else:
        specs = [x, x, zxbcdt, f32(m["heads"]), f32(m["d_inner"])]
        fn = lambda y, x, zg, D, w: (sf._gate_norm(y, x, zg, D, w, 1e-5,
                                                   m["groups"]),)
        calls, wrt = 1, (0, 1, 2, 3, 4)
    if grad:
        fwd, calls = fn, 2 * calls
        fn = jax.grad(lambda *a: sum(
            jnp.sum(o.astype(jnp.float32) ** 2) for o in fwd(*a)),
            argnums=wrt)
    _, text = _compile(fn, *specs)
    assert text.count('custom_call_target="tpu_custom_call"') == calls


# (B, T, heads, head_dim, groups, state, chunk, packed): the scan of the
# nemotron cell's Mamba-2 blocks and of the packed granite cell's layers
SCAN_SHAPES = {"nemotron": (1, 8192, 64, 64, 8, 128, 128, False),
               "granite-packed": (1, 16384, 64, 64, 1, 128, 256, True)}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("cell", sorted(SCAN_SHAPES))
def test_ssd_scan_compiles_for_v5e(cell, grad, one_chip, compiled_kernels):
    """The chunked scan's kernels at the two cells' shapes: a grid step
    holds one chunk of one group's heads (8 of 64 on nemotron, all 64 on
    granite: 2 MB operand blocks, the float32 state and the step's
    scratch) inside the VMEM limit the call asks for; transposed-operand
    products, a head's dynamic rows and the one-hot column accepted by
    Mosaic; and no temporary of [chunks, heads, Q, Q] elements."""
    B, T, H, P, G, N, Q, packed = SCAN_SHAPES[cell]
    bf = lambda *shape: _sds(shape, jnp.bfloat16, one_chip)
    specs = [bf(B, H, P, T), _sds((B, H, T), jnp.float32, one_chip),
             _sds((H,), jnp.float32, one_chip), bf(B, G, N, T),
             bf(B, G, N, T)]
    if packed:
        specs.append(_sds((B, T), jnp.int32, one_chip))
    fn = lambda x, dt, A, Bm, Cm, seg=None: ssd.ssd_chunked_scan(
        x, dt, A, Bm, Cm, Q, seg)
    if grad:
        fwd = fn
        fn = jax.grad(lambda *a: jnp.sum(fwd(*a).astype(jnp.float32) ** 2),
                      argnums=(0, 1, 2, 3, 4))
    compiled, text = _compile(fn, *specs)
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (2 if grad else 1)
    # forward: nothing but the running sums; backward: the entering states
    # (float32, 134 MB) and d a. The einsum form's float32 [chunks, heads,
    # Q, Q] tensors were 268 MB (nemotron) and 1.07 GB (granite) each
    assert compiled.memory_analysis().temp_size_in_bytes < (
        3e8 if grad else 2e7)


# the hybrid cell's Mamba-2 blocks: B = 1, T = 8,192, d_inner 4,096,
# 8 groups x state 128, 64 heads; zxbcdt is [1, 10304, 8192], time minor
MAMBA = dict(B=1, T=8192, d_inner=4096, n=1024, heads=64, groups=8)


def _mamba_specs(one_chip):
    m = MAMBA
    conv_dim = m["d_inner"] + 2 * m["n"]
    bf = lambda *shape: _sds(shape, jnp.bfloat16, one_chip)
    f32 = lambda *shape: _sds(shape, jnp.float32, one_chip)
    return dict(
        zxbcdt=bf(m["B"], m["d_inner"] + conv_dim + m["heads"], m["T"]),
        conv_w=f32(4, conv_dim), conv_b=f32(conv_dim),
        y=bf(m["B"], m["d_inner"], m["T"]), x=bf(m["B"], m["d_inner"], m["T"]),
        D=f32(m["heads"]), weight=f32(m["d_inner"]))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("op", ["conv_silu", "gate_norm"])
def test_fused_mamba_ops_compile_for_v5e(op, grad, one_chip,
                                         compiled_kernels):
    """The two halves of `ssm_fused.mamba_chain` at the hybrid cell's
    shapes, forward and backward: the conv's three windows (and their
    three in-place backward calls), the gate norm's one call each way.
    Compiled, not run: apart from its pair the gate's backward leaves the
    rest of ``zg``'s cotangent undefined."""
    m, sp = MAMBA, _mamba_specs(one_chip)
    if op == "conv_silu":
        specs = [sp["zxbcdt"], sp["conv_w"], sp["conv_b"]]
        fn = lambda z, w, b: sf._conv_silu(z, w, b, None, m["d_inner"],
                                          m["groups"])[1:]
        calls = 3
    else:
        specs = [sp["y"], sp["x"], sp["zxbcdt"], sp["D"], sp["weight"]]
        fn = lambda y, x, zg, D, w: (sf._gate_norm(y, x, zg, D, w, 1e-5,
                                                   m["groups"]),)
        calls = 1
    if grad:
        fwd, calls = fn, 2 * calls      # the loss reads the forward's values
        fn = jax.grad(lambda *a: sum(
            jnp.sum(o.astype(jnp.float32) ** 2) for o in fwd(*a)),
            argnums=tuple(range(len(specs))))
    _, text = _compile(fn, *specs)
    assert text.count('custom_call_target="tpu_custom_call"') == calls
    if grad:
        # one cotangent buffer for zxbcdt: nothing pads or adds pieces
        assert "pad(" not in text.split("ENTRY")[1]


def test_mamba_block_gradient_keeps_the_chain_out_of_f32_hbm(
        one_chip, compiled_kernels):
    """A whole Mamba-2 block's ``jax.grad`` at the cell's shapes: no
    top-level instruction of the module yields the chain's float32
    tensors ([T, conv_dim] or [T, d_inner], either axis minor), none a
    float32 [chunks, heads, Q, Q] tensor of the scan's (they stay in its
    kernels' VMEM), and under the scan's scope no transpose or copy of a
    [T, d_inner] operand."""
    import re
    from deeplearning4j_tpu.models import hybrid_lm
    m = MAMBA
    c = hybrid_lm.HybridLMConfig(
        hidden_size=2688, mamba_num_heads=m["heads"], mamba_head_dim=64,
        ssm_state_size=128, n_groups=m["groups"], chunk_size=128)
    assert (c.d_inner, c.conv_dim) == (m["d_inner"], m["d_inner"] + 2 * m["n"])
    p = {name: _sds(shape, jnp.bfloat16 if how in ("matrix", "residual_out")
                    else jnp.float32, one_chip)
         for name, (shape, how) in hybrid_lm._mixer_shapes(c, "M").items()}
    u = _sds((m["B"], m["T"], c.hidden_size), jnp.bfloat16, one_chip)
    _, text = _compile(jax.grad(lambda p, u: jnp.sum(
        hybrid_lm._mamba(p, u, c).astype(jnp.float32) ** 2),
        argnums=(0, 1)), p, u)
    # the chain's 8 calls and the scan's 2 (its forward that keeps the
    # entering states, its backward)
    assert text.count('custom_call_target="tpu_custom_call"') == 10
    T, chain = m["T"], (c.conv_dim, c.d_inner)
    Q = c.chunk_size
    for line in text.split("ENTRY")[1].splitlines():
        made = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) [\w\-]+\(", line)
        for dims in re.findall(r"f32\[([\d,]+)\]", made.group(1) if made
                               else ""):
            dims = [int(d) for d in dims.split(",")]
            assert not (len(dims) == 3 and sorted(dims[1:]) in
                        [sorted((T, w)) for w in chain]), line[:200]
            scopes = re.findall(r"dl4j\.(\w+)", line)
            size = 1
            for d in dims:
                size *= d
            if scopes[-1:] in (["ssm"], ["ln"]):
                assert size not in [T * w for w in chain], line[:200]
            # the scan's decay, score and weight tiles stay in VMEM
            assert size != T // Q * m["heads"] * Q * Q, line[:200]
        # and the scan takes the chain's operands as they lie: nothing of
        # T x d_inner elements is transposed or copied under its scope
        if re.findall(r"dl4j\.(\w+)", line)[-1:] == ["ssm_scan"] and made:
            moved = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                             r"(transpose|copy)\(", line)
            if moved:
                size = 1
                for d in moved.group(1).split(","):
                    size *= int(d)
                assert size < T * c.d_inner, line[:200]


def test_grouped_matmul_compiles_for_v5e(one_chip, monkeypatch,
                                         no_persistent_cache):
    """The expert layer's grouped product at the published widths, forward
    and backward: a buffer of 12,288 sorted rows, 8 held experts of
    2,688 x 1,856 (neither a multiple of the 512-wide tiles) and back,
    both matrices held [expert, 1,856, 2,688]."""
    from deeplearning4j_tpu.ops import moe
    # the kernel is interpreted where jax's backend is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = _sds((12288, 2688), jnp.bfloat16, one_chip)
    w1 = _sds((8, 1856, 2688), jnp.bfloat16, one_chip)       # [g, F, E]
    w2 = _sds((8, 1856, 2688), jnp.bfloat16, one_chip)
    sizes = _sds((8,), jnp.int32, one_chip)

    def loss(rows, w1, w2, sizes):
        h = moe.grouped_matmul(rows, w1, sizes, True)
        h = jnp.square(jax.nn.relu(h)).astype(rows.dtype)
        return jnp.sum(moe.grouped_matmul(h, w2, sizes))

    _, text = _compile(jax.grad(loss, argnums=(0, 1, 2)), rows, w1, w2, sizes)
    # the first product forward (the gradient needs no value of the
    # second), two for the rows and two for the weights back
    assert text.count('custom_call_target="tpu_custom_call"') == 5


def test_gated_grouped_matmul_compiles_for_v5e(one_chip, monkeypatch,
                                               no_persistent_cache):
    """The JoyAI cell's expert layer: a buffer of 32,768 sorted rows (four
    times the 8,192 expected of 16,384 tokens x 8 over 16 of 256 experts),
    16 held experts whose [Gate ; Up] is one [1,536, 2,048] matrix and
    whose Down is [768, 2,048] transposed, gated SiLU between the two
    grouped products, forward and backward."""
    from deeplearning4j_tpu.ops import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe._tiling(32768, 2048, 1536, False) == (128, 1024, 1536)
    assert moe._tiling(32768, 768, 2048, False) == (128, 768, 1024)
    # the nemotron cell's tiles are where PR 27 read them
    assert moe._tiling(12288, 2688, 1856, False) == (128, 896, 1856)
    rows = _sds((32768, 2048), jnp.bfloat16, one_chip)
    w1 = _sds((16, 1536, 2048), jnp.bfloat16, one_chip)      # [g, 2 F, E]
    w2 = _sds((16, 768, 2048), jnp.bfloat16, one_chip)       # [g, F, E]
    sizes = _sds((16,), jnp.int32, one_chip)

    def loss(rows, w1, w2, sizes):
        a, b = jnp.split(moe.grouped_matmul(rows, w1, sizes, True), 2, -1)
        h = (jax.nn.silu(a) * b).astype(rows.dtype)
        return jnp.sum(moe.grouped_matmul(h, w2, sizes))

    _, text = _compile(jax.grad(loss, argnums=(0, 1, 2)), rows, w1, w2, sizes)
    assert text.count('custom_call_target="tpu_custom_call"') == 5


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("shape", FLASH_SHAPES,
                         ids=lambda s: "x".join(map(str, s[:4]))
                         + ("-mask" if s[4] else ""))
def test_flash_attention_compiles_for_v5e(shape, grad, one_chip,
                                          compiled_kernels):
    B, H, S, D, masked = shape
    spec = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    specs = [spec, spec, spec] + (
        [_sds((B, S), jnp.int32, one_chip)] if masked else [])
    if grad:
        def fn(q, k, v, *mask):
            return jax.grad(lambda q, k, v: jnp.sum(
                fa.flash_attention(q, k, v, *mask).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))(q, k, v)
    else:
        fn = fa.flash_attention
    compiled, _ = _compile(fn, *specs)
    if S > 128:  # O(S) memory: no [S, S] scores among the temporaries
        assert compiled.memory_analysis().temp_size_in_bytes < \
            B * H * S * S * 2


def test_bert_default_gradient_runs_the_kernel_on_v5e(one_chip,
                                                      compiled_kernels,
                                                      monkeypatch):
    """The training cell's attention (T=512, 16 heads of 64, B=8) through
    ``bert.mlm_loss`` as the benchmark's driver calls it — no flag: on an
    accelerator backend the dispatcher picks the kernel, the program holds
    the forward and the fused backward custom call of each layer, and each
    layer's [B, H, T, T] bf16 probabilities are gone from the temporaries
    the XLA path keeps for the backward. Widths are BERT-large's; depth and
    vocabulary are cut so that the layers, not the head, fill the
    temporaries."""
    from deeplearning4j_tpu.kernels import dispatch_snapshot
    from deeplearning4j_tpu.models import bert
    B, T, layers = 8, 512, 2
    config = bert.BertConfig(vocab_size=1024, hidden_size=1024,
                             num_layers=layers, num_heads=16,
                             intermediate_size=4096)
    params = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: bert.init_params(jax.random.key(0), config)))
    batch = {k: _sds((B, T), jnp.int32, one_chip)
             for k in ("input_ids", "labels", "attention_mask")}

    def temporaries(**kw):
        return jax.jit(jax.grad(lambda p, b: bert.mlm_loss(
            p, b, config, **kw))).lower(params, batch).compile()

    xla = temporaries(use_flash=False)
    assert "tpu_custom_call" not in xla.as_text()
    # the dispatcher asks jax for the backend, which here is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernel = temporaries()
    assert dispatch_snapshot()["attention"] == {
        "kernel": "attention", "path": "flash", "reason": None}
    assert kernel.as_text().count('custom_call_target="tpu_custom_call"') \
        == 2 * layers
    saved = config.num_heads * B * T * T * 2 * layers
    assert (kernel.memory_analysis().temp_size_in_bytes
            < xla.memory_analysis().temp_size_in_bytes - saved)


PAGED_CASES = [(1, 12, 64, jnp.bfloat16), (1, 16, 128, jnp.bfloat16),
               (5, 16, 128, jnp.bfloat16), (1, 16, 128, jnp.float32)]


@pytest.mark.parametrize(
    "case", PAGED_CASES,
    ids=lambda c: f"Q{c[0]}-H{c[1]}-D{c[2]}-{jnp.dtype(c[3]).name}")
def test_paged_flash_decode_compiles_for_v5e(case, one_chip,
                                             compiled_kernels):
    """Pool [512, 16, H, D], 8 slots x 64 table columns (max_ctx 1024 at
    the default block size). head_dim 64 compiles too: ``tileable()``
    keeps ``auto`` off it as a performance guess, not a compile limit."""
    Q, H, D, dtype = case
    S, MB, NB, Bs = 8, 64, 512, 16
    sds = lambda shape, dt: _sds(shape, dt, one_chip)
    _compile(pfd.paged_flash_decode,
             sds((S, Q, H, D), dtype), sds((NB, Bs, H, D), dtype),
             sds((NB, Bs, H, D), dtype), sds((S, MB), jnp.int32),
             sds((S,), jnp.int32))


DEQUANT_CASES = [(8, 768, 3072, jnp.bfloat16), (1, 768, 3072, jnp.bfloat16),
                 (256, 3072, 768, jnp.bfloat16), (8, 2048, 1024, jnp.float32)]


@pytest.mark.parametrize(
    "case", DEQUANT_CASES,
    ids=lambda c: f"{c[0]}x{c[1]}x{c[2]}-{jnp.dtype(c[3]).name}")
def test_fused_dequant_matmul_compiles_for_v5e(case, one_chip,
                                               compiled_kernels):
    from deeplearning4j_tpu.quant.transforms import _fused_dequant_matmul
    M, K, N, dtype = case
    sds = lambda shape, dt: _sds(shape, dt, one_chip)
    _compile(lambda x, q, s: _fused_dequant_matmul(x, q, s, False),
             sds((M, K), dtype), sds((K, N), jnp.int8),
             sds((N,), jnp.float32))


def test_paged_decode_call_site_compiles_for_v5e(one_chip, compiled_kernels):
    """The kernel inside its call site: ``models.causal_lm.paged_decode``
    hands it the strided per-layer slice ``cache_k[:, i]`` of the
    ``[blocks, layers, block, heads, head_dim]`` pool. Serving widths
    (hidden 768, 12 heads of 64, vocab 32000), depth cut to 2."""
    from deeplearning4j_tpu.common.environment import environment
    from deeplearning4j_tpu.models import causal_lm
    config = causal_lm.CausalLMConfig(num_layers=2)
    S, MB, Bs = 8, 64, 16
    place = lambda t: jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    params = place(jax.eval_shape(
        lambda: causal_lm.init_params(jax.random.key(0), config)))
    cache = place(jax.eval_shape(
        lambda: causal_lm.init_paged_kv_cache(config, S * MB + 1, Bs)))
    sds = lambda shape: _sds(shape, jnp.int32, one_chip)
    env = environment()
    env.set_paged_kernel("on")
    try:
        _compile(lambda p, c, t, tok, ln: causal_lm.paged_decode(
            p, c, t, tok, ln, config),
            params, cache, sds((S, MB)), sds((S, 1)), sds((S,)))
    finally:
        env.set_paged_kernel(None)


def test_sharded_bert_step_compiles_for_the_2x2_mesh(mesh_2x2,
                                                     no_persistent_cache):
    """A data=2 x tensor=2 BERT loss+grad compiled for the described
    mesh: the compiler partitions it and puts the collectives in."""
    from deeplearning4j_tpu.models import bert
    config = bert.BertConfig.tiny()
    params = jax.tree_util.tree_map(
        lambda a, s: _sds(a.shape, a.dtype, NamedSharding(mesh_2x2, s)),
        jax.eval_shape(lambda: bert.init_params(jax.random.key(0), config)),
        bert.param_specs(config))
    batch = {k: _sds((8, 32), jnp.int32, NamedSharding(mesh_2x2, P("data")))
             for k in ("input_ids", "labels", "attention_mask")}
    compiled = jax.jit(jax.value_and_grad(
        lambda p, b: bert.mlm_loss(p, b, config, mesh=mesh_2x2))).lower(
            params, batch).compile()
    assert "all-reduce" in compiled.as_text()


@pytest.mark.parametrize("n_seq", [1, 4])
def test_ring_flash_compiles_for_v5e(n_seq, topo, compiled_kernels):
    """The flash kernel per KV block inside the sequence-parallel ring
    (``shard_map`` around ``pallas_call``), at the bench's shape: one
    device degenerates to a single scan step; four carry the ring's
    collective-permutes."""
    from deeplearning4j_tpu.common.mesh import MeshConfig, make_mesh
    from deeplearning4j_tpu.parallel.ring_attention import ring_attention
    mesh = make_mesh(MeshConfig(data=1, seq=n_seq),
                     devices=topo.devices[:n_seq])
    spec = _sds((4, 2048, 12, 64), jnp.float32,
                NamedSharding(mesh, P(None, "seq")))
    _, text = _compile(
        lambda q, k, v: ring_attention(q, k, v, mesh, use_flash=True),
        spec, spec, spec)
    assert ("collective-permute" in text) == (n_seq > 1)
