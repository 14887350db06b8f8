"""Pallas kernels vs XLA reference implementations (interpret mode on CPU).

VERDICT round-1 item 9: kernels/ was an empty placeholder. These tests run
the exact kernel bodies through the Pallas interpreter.
"""
import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import (attention, flash_attention,
                                        flash_attention_with_lse)

# the package re-exports the function under its module's name
fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")


def _ref_attention_with_lse(q, k, v, mask=None, causal=False):
    S, D = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :] != 0, s, -1e30)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1))


def _ref_attention(q, k, v, mask=None, causal=False):
    return _ref_attention_with_lse(q, k, v, mask, causal)[0]


class TestFlashAttention:
    def _qkv(self, rs, B=2, S=128, H=2, D=16):
        mk = lambda: jnp.asarray(rs.randn(B, S, H, D).astype(np.float32))
        return mk(), mk(), mk()

    def test_matches_reference(self):
        rs = np.random.RandomState(0)
        q, k, v = self._qkv(rs)
        out = flash_attention(q, k, v, tile_q=64, tile_k=64)
        ref = _ref_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_masked(self):
        rs = np.random.RandomState(1)
        q, k, v = self._qkv(rs)
        mask = np.ones((2, 128), np.int32)
        mask[:, 100:] = 0
        out = flash_attention(q, k, v, mask=jnp.asarray(mask),
                              tile_q=64, tile_k=64)
        ref = _ref_attention(q, k, v, mask=jnp.asarray(mask))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_causal(self):
        rs = np.random.RandomState(2)
        q, k, v = self._qkv(rs, S=64)
        out = flash_attention(q, k, v, causal=True, tile_q=32, tile_k=32)
        ref = _ref_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_gradients_flow(self):
        rs = np.random.RandomState(3)
        q, k, v = self._qkv(rs, S=64)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, tile_q=32,
                                           tile_k=32) ** 2)

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        def ref_loss(q, k, v):
            return jnp.sum(_ref_attention(q, k, v) ** 2)

        rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), atol=1e-4)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), atol=1e-4)

    def test_masked_gradients_match_reference(self):
        """The Pallas backward (dq/dkv kernels) under a key mask."""
        rs = np.random.RandomState(4)
        q, k, v = self._qkv(rs, S=64)
        mask = np.ones((2, 64), np.int32)
        mask[:, 50:] = 0
        mask = jnp.asarray(mask)

        def loss(fn):
            def f(q, k, v):
                return jnp.sum(fn(q, k, v) ** 2)
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        got = loss(lambda q, k, v: flash_attention(q, k, v, mask=mask,
                                                   tile_q=32, tile_k=32))
        want = loss(lambda q, k, v: _ref_attention(q, k, v, mask=mask))
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4)

    def test_causal_gradients_match_reference(self):
        rs = np.random.RandomState(5)
        q, k, v = self._qkv(rs, S=64)

        def loss(fn):
            def f(q, k, v):
                return jnp.sum(fn(q, k, v) * jnp.cos(fn(q, k, v)))
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        got = loss(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                   tile_q=32, tile_k=32))
        want = loss(lambda q, k, v: _ref_attention(q, k, v, causal=True))
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4)

    def test_mismatched_tiles_grad(self):
        """tile_q != tile_k exercises the lcm padding in the backward too."""
        rs = np.random.RandomState(6)
        q, k, v = self._qkv(rs, S=96)

        def f(q, k, v):
            return jnp.sum(flash_attention(q, k, v, tile_q=64,
                                           tile_k=32) ** 2)

        def rf(q, k, v):
            return jnp.sum(_ref_attention(q, k, v) ** 2)

        got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(rf, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4)


class TestCausalTileSkipping:
    """Under ``causal=True`` the streaming kernels compute only the tiles
    that hold an unmasked (query, key) pair, fetch nothing for the others
    and mask only the tiles the diagonal crosses: the same numbers as plain
    masked softmax, and bitwise those of the same kernels computing and
    masking every tile (``skip_empty=False`` of the private wrappers)."""

    @staticmethod
    def _inputs(S, masked, seed=20):
        rs = np.random.RandomState(seed)
        q, k, v, ct = (jnp.asarray(rs.randn(2, S, 1, 16).astype(np.float32))
                       for _ in range(4))
        ct_lse = jnp.asarray(rs.randn(2, 1, S).astype(np.float32))
        mask = None
        if masked:                       # right padding: key 0 stays valid
            mask = np.ones((2, S), np.int32)
            mask[0, S - 27:] = 0
            mask[1, S - 1:] = 0
            mask = jnp.asarray(mask)
        return q, k, v, ct, ct_lse, mask

    @pytest.mark.parametrize("with_lse", [False, True], ids=["out", "lse"])
    @pytest.mark.parametrize("masked", [False, True],
                             ids=["nomask", "keymask"])
    @pytest.mark.parametrize("S", [128, 96])
    @pytest.mark.parametrize("tiles", [(64, 16), (16, 64), (32, 32),
                                       (128, 32)],
                             ids=lambda t: f"{t[0]}x{t[1]}")
    def test_matches_plain_softmax_and_the_unskipped_kernels(
            self, tiles, S, masked, with_lse):
        tile_q, tile_k = tiles
        q, k, v, ct, ct_lse, mask = self._inputs(S, masked)

        def kernel(q, k, v):
            if with_lse:
                return flash_attention_with_lse(
                    q, k, v, mask=mask, causal=True, tile_q=tile_q,
                    tile_k=tile_k)
            return flash_attention(q, k, v, mask=mask, causal=True,
                                   tile_q=tile_q, tile_k=tile_k), None

        def plain(q, k, v):
            out, lse = _ref_attention_with_lse(q, k, v, mask, causal=True)
            return out, lse if with_lse else None

        def run(fn):
            def loss(q, k, v):
                out, lse = fn(q, k, v)
                total = jnp.sum(out * ct)
                if lse is not None:
                    total = total + jnp.sum(lse * ct_lse)
                return total, (out, lse)
            (_, fwd), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return fwd, grads

        ((out, lse), got), ((ref, ref_lse), want) = run(kernel), run(plain)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        if with_lse:
            np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                       atol=2e-5)
        for name, g, w in zip("qkv", got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4, err_msg=f"d{name}")

        # bitwise: the same tiles with nothing skipped, every tile masked
        (qf, kf, vf, mf, scale, tq, tk, *_) = fa._prep(
            q, k, v, mask, None, tile_q, tile_k, True)
        gf = jnp.asarray(np.random.RandomState(21).randn(*qf.shape)
                         .astype(np.float32))
        cot = (jnp.asarray(np.random.RandomState(22).randn(*qf.shape[:2], 1)
                           .astype(np.float32)) if with_lse else None)

        @functools.partial(jax.jit, static_argnums=0)
        def passes(skip_empty):     # one program: forward, dq, dkv
            o, l = fa._flash_fwd(qf, kf, vf, mf, scale, True, tq, tk,
                                 skip_empty=skip_empty)
            return (o, l) + tuple(fa._flash_bwd(
                qf, kf, vf, mf, o, l, gf, scale, True, tq, tk,
                lse_cot=cot, skip_empty=skip_empty))

        got, want = passes(True), passes(False)
        for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)

    def test_row_without_a_valid_key_averages_the_visited_tiles(self):
        """The edge the module's docstring states: under a causal mask AND
        a key mask (left padding) a row may see no valid key. Its softmax
        is uniform over the keys of the tiles its query block visits, not
        over all S; its lse sits at the -1e30 floor that
        ``parallel/ring_attention`` reads as "no live key"; every other
        row is exact."""
        S, pad, t = 128, 40, 32
        q, k, v, *_ = self._inputs(S, False, seed=23)
        mask = np.ones((2, S), np.int32)
        mask[:, :pad] = 0
        mask = jnp.asarray(mask)
        out, lse = flash_attention_with_lse(q, k, v, mask=mask, causal=True,
                                            tile_q=t, tile_k=t)
        ref, ref_lse = _ref_attention_with_lse(q, k, v, mask, causal=True)
        np.testing.assert_allclose(np.asarray(out[:, pad:]),
                                   np.asarray(ref[:, pad:]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse[:, :, pad:]),
                                   np.asarray(ref_lse[:, :, pad:]), atol=2e-5)
        assert np.all(np.asarray(lse[:, :, :pad]) < -1e29)
        for row in range(pad):
            visited = (row // t + 1) * t
            np.testing.assert_allclose(
                np.asarray(out[:, row]),
                np.asarray(jnp.mean(v[:, :visited], axis=1)), atol=2e-5,
                err_msg=f"row {row}")
        # with every tile computed the same row averages all S keys
        (qf, kf, vf, mf, scale, tq, tk, *_) = fa._prep(q, k, v, mask, None,
                                                       t, t, True)
        whole, _ = fa._flash_fwd(qf, kf, vf, mf, scale, True, tq, tk,
                                 skip_empty=False)
        np.testing.assert_allclose(
            np.asarray(whole.reshape(2, 1, S, 16)[:, :, 0]),
            np.asarray(jnp.mean(v, axis=1)), atol=2e-5)


class TestFlashTilesCounter:
    """``dl4j_flash_tiles_total{kernel,kind}``: one head's grid, ticked
    once per traced pass."""

    @staticmethod
    def _ticks(trace):
        """(what ``trace()`` returns, the counter's growth over it)."""
        from deeplearning4j_tpu.common.metrics import registry

        def read():
            fam = registry().get("dl4j_flash_tiles_total")
            return {(kernel, kind): fam.labels(kernel=kernel,
                                               kind=kind).value()
                    if fam else 0.0
                    for kernel in ("fwd", "dq", "dkv")
                    for kind in ("computed", "skipped")}
        before = read()
        out = trace()
        return out, {key: n - before[key] for key, n in read().items()}

    def _trace_grad(self, causal, shape, dtype=jnp.float32, **tiles):
        x = jax.ShapeDtypeStruct(shape, dtype)
        grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, **tiles).astype(jnp.float32)),
            argnums=(0, 1, 2))
        return self._ticks(lambda: str(jax.make_jaxpr(grad)(x, x, x)))

    @staticmethod
    def _each_pass(computed, skipped):
        return {(kernel, kind): n for kernel in ("fwd", "dq", "dkv")
                for kind, n in (("computed", computed), ("skipped", skipped))}

    def test_causal_trace_counts_live_and_skipped_tiles(self):
        text, ticks = self._trace_grad(True, (1, 1024, 3, 64),
                                       tile_q=512, tile_k=512)
        assert ticks == self._each_pass(3, 1)
        # init, done and the two bodies (below / on the diagonal) a kernel
        assert len(re.findall(r"\bcond\[", text)) == 3 * 4

    def test_non_causal_trace_skips_nothing_and_keeps_its_kernels(self):
        text, ticks = self._trace_grad(False, (1, 1024, 3, 64),
                                       tile_q=512, tile_k=512)
        assert ticks == self._each_pass(4, 0)
        # the kernels as they were: init and done are the only branches
        assert text.count("pallas_call") == 3
        assert len(re.findall(r"\bcond\[", text)) == 3 * 2

    def test_hybrid_shape_counts(self):
        """T=8,192, head_dim 128, no tiles given: a head of the hybrid
        cell's attention blocks ticks 1,024 x 1,024 tiles in all three
        passes, 36 on or below the diagonal and 28 above it."""
        _, ticks = self._trace_grad(True, (1, 8192, 1, 128), jnp.bfloat16)
        assert ticks == self._each_pass(36, 28)


def _pallas_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


class TestAttentionEntry:
    """``kernels.attention``: the one full-sequence core the models call,
    on either path, in either layout, with as many or fewer KV heads."""

    B, T, H, D = 2, 48, 4, 16

    @pytest.mark.parametrize("masking", ["none", "mask", "causal",
                                         "mask+causal"])
    @pytest.mark.parametrize("kv_heads", [4, 2, 1])
    @pytest.mark.parametrize("layout", ["packed", "heads"])
    @pytest.mark.parametrize("path", ["xla", "flash"])
    def test_core(self, path, layout, kv_heads, masking):
        """Against plain softmax over explicitly repeated KV heads, forward
        and under ``jax.grad``; both layouts give the same context; "xla"
        never reaches ``pallas_call``, "flash" always does."""
        B, T, H, D = self.B, self.T, self.H, self.D
        R = H // kv_heads
        ks = jax.random.split(jax.random.key(kv_heads), 4)
        q = jax.random.normal(ks[0], (B, T, H, D))
        k = jax.random.normal(ks[1], (B, T, kv_heads, D))
        v = jax.random.normal(ks[2], (B, T, kv_heads, D))
        ct = jax.random.normal(ks[3], q.shape)
        mask = None
        if "mask" in masking:   # key 0 stays valid: no causal row is empty
            mask = jnp.asarray(np.arange(T)[None, :] % (np.arange(B)[:, None]
                                                        + 3) != 1, jnp.int32)
        causal = "causal" in masking

        def entry(q, k, v, layout=layout):
            if layout == "packed":
                q, k, v = (x.reshape(B, T, -1) for x in (q, k, v))
            ctx = attention(q, k, v, path=path, head_dim=D, mask=mask,
                            causal=causal)
            assert ctx.shape == q.shape and ctx.dtype == q.dtype
            return ctx.reshape(B, T, H, D)

        def plain(q, k, v):
            return _ref_attention(q, jnp.repeat(k, R, 2), jnp.repeat(v, R, 2),
                                  mask, causal)

        got, grads = jax.value_and_grad(
            lambda *a: jnp.sum(entry(*a) * ct), argnums=(0, 1, 2))(q, k, v)
        want, wgrads = jax.value_and_grad(
            lambda *a: jnp.sum(plain(*a) * ct), argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
        for g, w in zip(grads, wgrads):
            assert g.shape == w.shape
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
        other = "heads" if layout == "packed" else "packed"
        np.testing.assert_array_equal(entry(q, k, v),
                                      entry(q, k, v, layout=other))
        assert (_pallas_calls(entry, q, k, v) > 0) == (path == "flash")


class TestOneTilePath:
    """The training cell's shape family (S=512, head_dim 64): with no tiles
    given, a head's whole [S, S] score tile is one kernel step and the
    backward is one fused kernel; longer sequences still stream."""

    S, D = 512, 64

    def _qkv(self, seed, B=1, H=2, S=None):
        rs = np.random.RandomState(seed)
        mk = lambda: jnp.asarray(
            rs.randn(B, S or self.S, H, self.D).astype(np.float32))
        return mk(), mk(), mk(), mk()

    def _mask(self, kind, B):
        if kind == "none":
            return None
        mask = np.ones((B, self.S), np.int32)
        if kind == "padding":
            mask[0, 300:] = 0
            mask[-1, 511:] = 0
        return jnp.asarray(mask)

    @pytest.mark.parametrize("mask_kind", ["ones", "padding", "none"])
    def test_forward_matches_reference(self, mask_kind):
        q, k, v, _ = self._qkv(10, B=2)
        mask = self._mask(mask_kind, 2)
        out = flash_attention(q, k, v, mask=mask)
        ref = _ref_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.parametrize("mask_kind", ["ones", "padding", "none"])
    def test_gradients_match_reference(self, mask_kind):
        q, k, v, ct = self._qkv(11, B=2)
        mask = self._mask(mask_kind, 2)

        def grads(fn):
            return jax.grad(lambda q, k, v: jnp.sum(
                fn(q, k, v, mask=mask) * ct), argnums=(0, 1, 2))(q, k, v)

        assert _pallas_calls(lambda q, k, v: jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, mask=mask)),
            argnums=(0, 1, 2))(q, k, v), q, k, v) == 2   # fwd + fused bwd
        for name, g, w in zip("qkv", grads(flash_attention),
                              grads(_ref_attention)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4, err_msg=f"d{name}")

    def test_causal_gradients_match_reference(self):
        q, k, v, ct = self._qkv(12, S=256)
        for name, g, w in zip("qkv", *(jax.grad(lambda q, k, v: jnp.sum(
                fn(q, k, v, causal=True) * ct), argnums=(0, 1, 2))(q, k, v)
                for fn in (flash_attention, _ref_attention))):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4, err_msg=f"d{name}")

    @pytest.mark.parametrize("H,D", [(1, 128), (3, 64), (4, 32)])
    def test_head_packing(self, H, D):
        """One head a block (D=128), an odd head count (every head in one
        block) and four heads a block."""
        rs = np.random.RandomState(13)
        q, k, v, ct = (jnp.asarray(rs.randn(1, 128, H, D).astype(np.float32))
                       for _ in range(4))
        for g, w in zip(*(jax.grad(lambda q, k, v: jnp.sum(
                fn(q, k, v) * ct), argnums=(0, 1, 2))(q, k, v)
                for fn in (flash_attention, _ref_attention))):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4)

    def test_packed_layout_is_the_same_kernel(self):
        """``head_dim=`` takes and returns [B, S, H*D], as the projections
        produce it: the same numbers as the [B, S, H, D] call."""
        q, k, v, _ = self._qkv(14)
        mask = self._mask("padding", 1)
        pack = lambda x: x.reshape(x.shape[:2] + (-1,))
        got = flash_attention(pack(q), pack(k), pack(v), mask=mask,
                              head_dim=self.D)
        want = flash_attention(q, k, v, mask=mask)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(pack(want)))

    def test_s1024_still_streams(self):
        q, k, v, ct = self._qkv(15, S=1024)
        mask = np.ones((1, 1024), np.int32)
        mask[0, 900:] = 0
        mask = jnp.asarray(mask)

        def grads(fn):
            return jax.grad(lambda q, k, v: jnp.sum(
                fn(q, k, v, mask=mask) * ct), argnums=(0, 1, 2))

        assert _pallas_calls(grads(flash_attention), q, k, v) == 3
        for g, w in zip(grads(flash_attention)(q, k, v),
                        grads(_ref_attention)(q, k, v)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4)


class TestNonDivisibleShapes:
    """Regression: non-tile-multiple shapes must pad, not silently corrupt."""

    def test_flash_attention_odd_seq_len(self):
        rs = np.random.RandomState(7)
        B, S, H, D = 2, 200, 2, 16   # 200 % 128 != 0
        mk = lambda: jnp.asarray(rs.randn(B, S, H, D).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        out = flash_attention(q, k, v)
        ref = _ref_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_flash_attention_odd_seq_with_mask_and_grad(self):
        rs = np.random.RandomState(8)
        B, S, H, D = 1, 150, 2, 8
        mk = lambda: jnp.asarray(rs.randn(B, S, H, D).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        mask = np.ones((B, S), np.int32)
        mask[:, 120:] = 0
        out = flash_attention(q, k, v, mask=jnp.asarray(mask))
        ref = _ref_attention(q, k, v, mask=jnp.asarray(mask))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        g = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, mask=jnp.asarray(mask)) ** 2))(q)
        gr = jax.grad(lambda q: jnp.sum(
            _ref_attention(q, k, v, mask=jnp.asarray(mask)) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4)

