"""Shape-bucketed inference engine tests (runtime/inference.py).

Covers the serving contract: bucket-ladder padding correctness (padded vs
exact outputs equal after slicing), the compile-counter bound (K distinct
request batch sizes -> at most ceil(log2(max_batch))+1 compiles), warmup
pre-compiling the bucket set, micro-batcher coalescing under concurrent
submits, and the bucketing wired into the direct output() paths of all
three frontends.
"""
import math
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu.autodiff.samediff import SameDiff
from deeplearning4j_tpu.common.environment import environment
from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
from deeplearning4j_tpu.runtime.inference import (InferenceEngine,
                                                  bucket_for, bucket_ladder,
                                                  pad_batch)


@pytest.fixture(autouse=True)
def _clean_env():
    env = environment()
    prev_bucketing = env.inference_bucketing()
    prev_max = env.inference_max_batch()
    env.reset_compile_count()
    yield env
    env.set_inference_bucketing(prev_bucketing)
    env.set_inference_max_batch(prev_max)
    env.reset_compile_count()


def _mlp(n_in=6, hidden=8, n_out=3, seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="tanh"))
            .layer(OutputLayer(n_in=hidden, n_out=n_out))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph(n_in=6, n_out=3):
    conf = (NeuralNetConfiguration.builder().seed(1)
            .graph_builder()
            .add_inputs("in")
            .add_layer("d1", DenseLayer(n_in=n_in, n_out=8,
                                        activation="relu"), "in")
            .add_layer("out", OutputLayer(n_in=8, n_out=n_out), "d1")
            .set_outputs("out")
            .build())
    return ComputationGraph(conf).init()


def _x(n, n_in=6, seed=0):
    return np.random.RandomState(seed + n).randn(n, n_in).astype(np.float32)


class TestBucketLadder:
    def test_default_ladder_is_powers_of_two(self):
        assert bucket_ladder(16) == (1, 2, 4, 8, 16)
        assert bucket_ladder(1) == (1,)

    def test_non_power_max_is_included(self):
        assert bucket_ladder(24) == (1, 2, 4, 8, 16, 24)

    def test_explicit_buckets_sorted_deduped(self):
        assert bucket_ladder(99, buckets=[8, 2, 8, 32]) == (2, 8, 32)

    def test_bucket_for(self):
        ladder = bucket_ladder(16)
        assert bucket_for(1, ladder) == 1
        assert bucket_for(3, ladder) == 4
        assert bucket_for(16, ladder) == 16
        assert bucket_for(17, ladder) is None

    def test_pad_batch(self):
        x = jnp.ones((3, 5))
        p = pad_batch(x, 8)
        assert p.shape == (8, 5)
        assert np.all(np.asarray(p)[3:] == 0.0)
        assert pad_batch(x, 3) is x


#: a batch-5 and a batch-8 compile of the same matmul + softmax are two
#: programs: XLA's CPU backend may vectorise and order their sums apart,
#: so the engine owes the padded rows' values, not their bits. Read on
#: this CPU: 2 ulp (multilayer, n=3), 1 (graph, n=3), 8 (samediff's
#: softmax); held to twice the largest. A row that padding reached would
#: be wrong in its leading digits.
_PADDED_MAX_ULP = 16


class TestPaddedEquality:
    """Padded-bucket outputs must match exact-shape outputs after slicing."""

    def test_multilayer_matches_exact(self, _clean_env):
        net = _mlp()
        for n in (1, 3, 5, 7, 11):
            x = _x(n)
            _clean_env.set_inference_bucketing(False)
            exact = np.asarray(net.output(x).jax())
            _clean_env.set_inference_bucketing(True)
            bucketed = np.asarray(net.output(x).jax())
            assert bucketed.shape == exact.shape
            np.testing.assert_array_max_ulp(bucketed, exact,
                                            maxulp=_PADDED_MAX_ULP)

    def test_graph_matches_exact(self, _clean_env):
        net = _graph()
        for n in (3, 5, 9):
            x = _x(n)
            _clean_env.set_inference_bucketing(False)
            exact = np.asarray(net.output(x)[0].jax())
            _clean_env.set_inference_bucketing(True)
            bucketed = np.asarray(net.output(x)[0].jax())
            assert bucketed.shape == exact.shape
            np.testing.assert_array_max_ulp(bucketed, exact,
                                            maxulp=_PADDED_MAX_ULP)

    def test_samediff_matches_exact(self, _clean_env):
        sd = SameDiff.create()
        x = sd.placeholder("x", (None, 4))
        w = sd.var("w", np.random.RandomState(0).randn(4, 3)
                   .astype(np.float32))
        out = sd.nn.softmax(x.mmul(w))
        data = _x(5, n_in=4)
        _clean_env.set_inference_bucketing(False)
        exact = np.asarray(sd.output({"x": data}, [out])[out.name].jax())
        _clean_env.set_inference_bucketing(True)
        bucketed = np.asarray(sd.output({"x": data}, [out])[out.name].jax())
        assert bucketed.shape == exact.shape
        np.testing.assert_array_max_ulp(bucketed, exact,
                                        maxulp=_PADDED_MAX_ULP)

    def test_samediff_batch_reduction_falls_back_exact(self, _clean_env):
        # a scalar (batch-reduced) output would change value under padding;
        # the shape gate must fall back to the exact compile
        sd = SameDiff.create()
        x = sd.placeholder("x", (None, 4))
        s = x.mean()
        data = _x(5, n_in=4)
        got = float(sd.output({"x": data}, [s])[s.name].jax())
        assert got == pytest.approx(float(np.mean(data)), rel=1e-6)

    def test_predict_rides_bucketing(self, _clean_env):
        net = _mlp()
        x = _x(7)
        _clean_env.set_inference_bucketing(False)
        exact = np.asarray(net.predict(x).jax())
        _clean_env.set_inference_bucketing(True)
        bucketed = np.asarray(net.predict(x).jax())
        np.testing.assert_array_equal(bucketed, exact)


class TestCompileCounter:
    def test_direct_output_path_bound(self, _clean_env):
        """K >= 8 distinct batch sizes -> <= ceil(log2(max_batch))+1
        compiles through MultiLayerNetwork.output()."""
        max_batch = 16
        _clean_env.set_inference_max_batch(max_batch)
        net = _mlp()
        _clean_env.reset_compile_count()
        sizes = [1, 2, 3, 5, 7, 9, 11, 13, 15, 16]
        for n in sizes:
            net.output(_x(n))
        bound = math.ceil(math.log2(max_batch)) + 1
        assert len(set(sizes)) >= 8
        assert _clean_env.compile_count() <= bound

    def test_naive_path_pays_per_shape(self, _clean_env):
        _clean_env.set_inference_bucketing(False)
        net = _mlp()
        _clean_env.reset_compile_count()
        sizes = [1, 3, 5, 7, 9, 11, 13, 15]
        for n in sizes:
            net.output(_x(n))
        assert _clean_env.compile_count() == len(sizes)

    def test_engine_bound(self, _clean_env):
        net = _mlp()
        eng = InferenceEngine(net, max_batch=16)
        _clean_env.reset_compile_count()
        for n in (1, 2, 3, 5, 7, 9, 11, 13, 15, 16):
            out = eng.infer(_x(n))
            assert out.shape[0] == n
        assert _clean_env.compile_count() <= math.ceil(math.log2(16)) + 1

    def test_compile_listener_hook(self, _clean_env):
        seen = []
        _clean_env.add_compile_listener(seen.append)
        try:
            net = _mlp()
            net.output(_x(3))  # bucket 4
            net.output(_x(4))  # same compiled shape: no new event
            net.output(_x(9))  # new bucket (16)
        finally:
            _clean_env.remove_compile_listener(seen.append)
        assert len(seen) == _clean_env.compile_count() == 2


class TestWarmup:
    def test_warmup_precompiles_ladder(self, _clean_env):
        net = _mlp()
        eng = InferenceEngine(net, max_batch=8)
        _clean_env.reset_compile_count()
        warmed = eng.warmup(_x(1))
        assert warmed == [1, 2, 4, 8]
        assert _clean_env.compile_count() == 4
        # traffic after warmup compiles nothing new
        for n in (1, 2, 3, 4, 5, 6, 7, 8):
            eng.infer(_x(n))
        assert _clean_env.compile_count() == 4

    def test_warmup_selected_sizes(self, _clean_env):
        net = _mlp()
        eng = InferenceEngine(net, max_batch=16)
        _clean_env.reset_compile_count()
        assert eng.warmup(_x(1), batch_sizes=[3, 4, 12]) == [4, 16]
        assert _clean_env.compile_count() == 2


class TestEngineDispatch:
    def test_engine_matches_exact(self, _clean_env):
        net = _mlp()
        eng = InferenceEngine(net, max_batch=16)
        x = _x(6)
        _clean_env.set_inference_bucketing(False)
        exact = np.asarray(net.output(x).jax())
        np.testing.assert_array_equal(np.asarray(eng.infer(x).jax()), exact)

    def test_oversize_batch_chunks(self, _clean_env):
        net = _mlp()
        eng = InferenceEngine(net, max_batch=4)
        x = _x(10)
        out = np.asarray(eng.infer(x).jax())
        assert out.shape[0] == 10
        _clean_env.set_inference_bucketing(False)
        exact = np.asarray(net.output(x).jax())
        np.testing.assert_allclose(out, exact, rtol=1e-6, atol=1e-7)
        # compile bound holds even though 10 > max_batch
        assert _clean_env.compile_count() <= math.ceil(math.log2(4)) + 1 + 1

    def test_graph_engine(self, _clean_env):
        net = _graph()
        eng = InferenceEngine(net, max_batch=8)
        x = _x(5)
        _clean_env.set_inference_bucketing(False)
        exact = np.asarray(net.output(x)[0].jax())
        got = eng.infer(x)
        np.testing.assert_array_equal(np.asarray(got[0].jax()), exact)

    def test_samediff_engine(self, _clean_env):
        sd = SameDiff.create()
        x = sd.placeholder("x", (None, 4))
        w = sd.var("w", np.random.RandomState(3).randn(4, 2)
                   .astype(np.float32))
        out = sd.nn.softmax(x.mmul(w))
        eng = InferenceEngine(sd, outputs=[out], max_batch=8)
        data = _x(3, n_in=4)
        _clean_env.set_inference_bucketing(False)
        exact = np.asarray(sd.output({"x": data}, [out])[out.name].jax())
        got = eng.infer({"x": data})
        np.testing.assert_array_equal(np.asarray(got[out.name].jax()), exact)

    def test_samediff_engine_requires_outputs(self):
        with pytest.raises(ValueError, match="outputs"):
            InferenceEngine(SameDiff.create())

    def test_stats(self, _clean_env):
        net = _mlp()
        eng = InferenceEngine(net, max_batch=8)
        eng.infer(_x(3))
        s = eng.stats()
        assert s["requests"] == 1 and s["dispatches"] == 1
        assert s["rows_real"] == 3 and s["rows_padded"] == 1
        assert s["bucket_dispatches"] == {4: 1}
        assert s["buckets"] == [1, 2, 4, 8]


class TestMicroBatcher:
    def test_coalesces_concurrent_submits(self, _clean_env):
        net = _mlp()
        # no warmup: the first dispatch compiles, guaranteeing the rest of
        # the burst queues behind it and coalesces; generous delay window
        eng = InferenceEngine(net, max_batch=32, max_delay_ms=150.0)
        xs = [_x(3, seed=i) for i in range(8)]
        with eng:
            futs = [eng.submit(x) for x in xs]
            outs = [f.result(timeout=60) for f in futs]
        _clean_env.set_inference_bucketing(False)
        for x, out in zip(xs, outs):
            exact = np.asarray(net.output(x).jax())
            assert out.shape == exact.shape
            np.testing.assert_allclose(np.asarray(out.jax()), exact,
                                       rtol=1e-6, atol=1e-7)
        s = eng.stats()
        assert s["requests"] == 8
        assert s["dispatches"] < 8  # at least one coalesced dispatch
        assert s["coalesced"] >= 2

    def test_submit_from_many_threads(self, _clean_env):
        net = _mlp()
        eng = InferenceEngine(net, max_batch=16, max_delay_ms=50.0)
        results = {}

        def worker(i):
            x = _x(2, seed=100 + i)
            results[i] = (x, eng.submit(x).result(timeout=60))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.stop()
        _clean_env.set_inference_bucketing(False)
        for i, (x, out) in results.items():
            exact = np.asarray(net.output(x).jax())
            np.testing.assert_allclose(np.asarray(out.jax()), exact,
                                       rtol=1e-6, atol=1e-7)

    def test_window_respects_max_batch(self, _clean_env):
        net = _mlp()
        eng = InferenceEngine(net, max_batch=4, max_delay_ms=100.0)
        with eng:
            futs = [eng.submit(_x(3, seed=i)) for i in range(4)]
            for f in futs:
                assert f.result(timeout=60).shape[0] == 3
        # 3-row requests cannot pair up under max_batch=4
        assert eng.stats()["dispatches"] == 4

    def test_submit_oversize_raises(self):
        eng = InferenceEngine(_mlp(), max_batch=4)
        with pytest.raises(ValueError, match="exceeds max_batch"):
            eng.submit(_x(5))


class TestSerializationKwargGuard:
    def test_array_kwarg_raises_clean_error(self):
        """An array-valued kwarg with no FlatBuffers packing must raise the
        ValueError naming the op, not numpy's ambiguous-truth TypeError."""
        from deeplearning4j_tpu.autodiff.serialization import _fb_pack_kwargs
        from deeplearning4j_tpu.ops.registry import OpRegistry

        class Node:
            name = "pad_1"
            op_name = "pad"
            kwargs = {"paddings": np.array([[0, 1], [0, 0]])}

        opdef = OpRegistry.get().lookup("pad")
        with pytest.raises(ValueError, match="pad"):
            _fb_pack_kwargs(Node(), opdef)
