"""The recomputation policy of `hybrid_lm._blocks`: a rematerialised block
keeps its streaming flash core's output and log-sum-exp from the first
forward (`flash_attention.SAVED_OUT` / `SAVED_LSE`), so the backward runs
the forward kernel once per core, not twice, and computes what the
unrematerialised gradient computes. On the CPU with the interpreted
kernels (`flash_everywhere`), at tiny widths: a ``*`` block (nemotron), a
``*`` block on packed rows (granite) and ``L`` blocks (JoyAI, MTP module
included)."""
import collections
import json
import os
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import granite_hybrid, joyai_flash, nemotron_h  # noqa: E402,E501
from deeplearning4j_tpu.common.metrics import registry  # noqa: E402
from deeplearning4j_tpu.models import hybrid_lm  # noqa: E402

F32 = jnp.float32
KINDS = ("nemotron", "granite", "joyai")


def tiny(kind, **kw):
    """(config, T, packed): T = 640 puts a ``*`` core on the streaming
    kernels (up to 512 it takes the one-tile kernel, which names nothing);
    a latent core streams at any length."""
    if kind == "nemotron":
        return hybrid_lm.HybridLMConfig.tiny(dtype=F32, **kw), 640, False
    if kind == "granite":
        return (hybrid_lm.HybridLMConfig.tiny(granite=True, dtype=F32, **kw),
                640, True)
    return hybrid_lm.HybridLMConfig.tiny(latent=True, dtype=F32, **kw), 24, False


def cell_pattern(kind):
    """The block pattern of the kind's benchmark cell."""
    name, ref = {"nemotron": ("nemotron-twotower-30b-a3b", nemotron_h),
                 "granite": ("granite-4.0-h-micro", granite_hybrid),
                 "joyai": ("joyai-llm-flash", joyai_flash)}[kind]
    with open(os.path.join(ROOT, f"benchmark/configs/{name}.json")) as f:
        cfg = json.load(f)
    return ref.pattern(cfg) if kind == "granite" else ref.dims(cfg)["pattern"]


def batch(T, packed, seed=0):
    b = {"input_ids": jax.random.randint(jax.random.key(seed), (1, T), 0, 96)}
    if packed:      # three documents, the last cut by the row's end
        b["segment_ids"] = jnp.asarray(
            np.repeat([0, 1, 2], [200, 300, T - 500])[None], jnp.int32)
    return b


def loss_and_grad(c, remat):
    return lambda p, b: jax.value_and_grad(
        lambda p: hybrid_lm._loss_terms(p, b, c, remat)[0])(p)


def flash_kernels(jaxpr, out=None):
    """{"fwd" | "dq" | "dkv": streaming flash kernels in the jaxpr}: the
    forward writes (o, lse [.., 1] float32), dq one output, dkv two."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            src = eqn.params["jaxpr"].debug_info.func_src_info
            avals = eqn.params["out_avals"]
            if "flash_attention.py" in src and "one_tile" not in src:
                lse = avals[-1]
                out["dq" if len(avals) == 1 else
                    "fwd" if lse.shape[-1] == 1 and lse.dtype == F32
                    else "dkv"] += 1
            continue
        todo = list(eqn.params.values())
        while todo:
            v = todo.pop()
            if isinstance(v, (tuple, list)):
                todo.extend(v)
            elif isinstance(v, jax.extend.core.ClosedJaxpr):
                flash_kernels(v.jaxpr, out)
            elif isinstance(v, jax.extend.core.Jaxpr):
                flash_kernels(v, out)
    return out


def saved_cores():
    fam = registry().get("dl4j_remat_saved_cores_total")
    return {} if fam is None else {k: c.value() for k, c in fam.children()}


def grown(before, after):
    return {k[0]: after[k] - before.get(k, 0.0) for k in after
            if after[k] != before.get(k, 0.0)}


# the cores of each cell's traced step: nemotron's two `*`, granite's one,
# JoyAI's six trunk `L` and the MTP module's one
CELL_CORES = {"nemotron": {"*": 2}, "granite": {"*": 1}, "joyai": {"L": 7}}


@pytest.mark.parametrize("kind", KINDS)
def test_the_backward_runs_one_forward_kernel_per_core(kind, flash_everywhere):
    """The step of the kind's cell, traced at tiny widths: the streaming
    forward kernel once per attention core (a bare checkpoint has it
    twice), the dq and dkv kernels once each, and the counter ticks once
    per rematerialised core."""
    c, T, packed = tiny(kind, hybrid_override_pattern=cell_pattern(kind))
    p = jax.eval_shape(lambda: hybrid_lm.init_params(jax.random.key(0), c))
    b = jax.eval_shape(lambda: batch(T, packed))
    before = saved_cores()
    jaxpr = jax.make_jaxpr(loss_and_grad(c, True))(p, b).jaxpr
    cores = sum(CELL_CORES[kind].values())
    assert dict(flash_kernels(jaxpr)) == {"fwd": cores, "dq": cores,
                                          "dkv": cores}
    assert grown(before, saved_cores()) == CELL_CORES[kind]
    # the unrematerialised step has as many, and counts nothing
    before = saved_cores()
    jaxpr = jax.make_jaxpr(loss_and_grad(c, False))(p, b).jaxpr
    assert flash_kernels(jaxpr)["fwd"] == cores
    assert grown(before, saved_cores()) == {}


@pytest.mark.parametrize("kind", KINDS)
def test_the_saved_cores_give_the_unrematerialised_gradients(
        kind, flash_everywhere):
    """Loss and every gradient leaf of ``remat=True`` equal ``remat=False``
    (what the bare checkpoint computed) to float32 round-off."""
    c, T, packed = tiny(kind)
    p = hybrid_lm.init_params(jax.random.key(1), c)
    p = jax.tree_util.tree_map(lambda x: x.astype(F32), p)
    b = batch(T, packed, seed=2)
    loss, grads = jax.jit(loss_and_grad(c, True))(p, b)
    want_loss, want = jax.jit(loss_and_grad(c, False))(p, b)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))
