"""AOT compile cache: keying, store round trips, corruption recovery,
warmup concurrency/idempotence, and the warmup manifest.

The cache contract under test (ISSUE 4 acceptance): same config -> hit;
changed dtype / batch bucket / donation / remat-grad_accum knob / mesh
spec -> miss; corrupted cache file -> recompile + warning, never an
exception; DL4J_TPU_CACHE_DIR="" disables everything.
"""
import json
import logging
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common.environment import (SystemProperties,
                                                   environment)
from deeplearning4j_tpu.common.metrics import registry
from deeplearning4j_tpu.runtime import compile_cache
from deeplearning4j_tpu.runtime.compile_cache import (AOTCompileCache,
                                                      cache_key)
from deeplearning4j_tpu.runtime.inference import InferenceEngine, counted_jit


@pytest.fixture
def fresh_cache(tmp_path):
    """A private cache dir for one test, resolved through the real env
    layering, restored afterwards."""
    env = environment()
    prev = env.property_override(SystemProperties.CACHE_DIR)
    env.set_cache_dir(str(tmp_path))
    compile_cache.reset_cache()
    yield compile_cache.cache()
    if prev is None:
        env.clear_property(SystemProperties.CACHE_DIR)
    else:
        env.set_property(SystemProperties.CACHE_DIR, prev)
    compile_cache.reset_cache()


def _model(p, x):
    for w in p:
        x = jnp.tanh(x @ w)
    return x


def _params(n=3, d=16, dtype=jnp.float32):
    return [jnp.full((d, d), 0.1, dtype) for _ in range(n)]


def _x(b=4, d=16, dtype=jnp.float32):
    return jnp.ones((b, d), dtype)


def _key_of(fn, *args, **jit_kwargs):
    return cache_key(jax.jit(fn, **jit_kwargs).lower(*args), jit_kwargs)


# ---------------------------------------------------------------------------
# cache keying
# ---------------------------------------------------------------------------

class TestCacheKey:
    def test_same_config_same_key(self):
        k1 = _key_of(_model, _params(), _x())
        k2 = _key_of(_model, _params(), _x())
        assert k1 == k2

    def test_changed_dtype_misses(self):
        k1 = _key_of(_model, _params(), _x())
        k2 = _key_of(_model, _params(dtype=jnp.bfloat16),
                     _x(dtype=jnp.bfloat16))
        assert k1 != k2

    def test_changed_batch_bucket_misses(self):
        assert _key_of(_model, _params(), _x(b=4)) != \
            _key_of(_model, _params(), _x(b=8))

    def test_changed_model_structure_misses(self):
        # same input signature, different closure -> different program
        assert _key_of(_model, _params(n=3), _x()) != \
            _key_of(_model, _params(n=4), _x())

    def test_donation_misses(self):
        def addone(x):
            return x + 1.0  # same shape: the donation is actually usable

        k1 = _key_of(addone, _x())
        k2 = _key_of(addone, _x(), donate_argnums=(0,))
        assert k1 != k2

    def test_remat_knob_misses(self):
        env = environment()
        k1 = _key_of(_model, _params(), _x())
        env.set_training_remat("layer")
        try:
            k2 = _key_of(_model, _params(), _x())
        finally:
            env.clear_property(SystemProperties.TRAINING_REMAT)
        assert k1 != k2

    def test_grad_accum_knob_misses(self):
        env = environment()
        k1 = _key_of(_model, _params(), _x())
        env.set_training_grad_accum(4)
        try:
            k2 = _key_of(_model, _params(), _x())
        finally:
            env.clear_property(SystemProperties.TRAINING_GRAD_ACCUM)
        assert k1 != k2

    def test_mesh_spec_misses(self):
        from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P)

        devs = np.asarray(jax.devices()[:2]).reshape(2)
        mesh = Mesh(devs, ("data",))
        repl = NamedSharding(mesh, P())
        sharded = NamedSharding(mesh, P("data"))
        k1 = _key_of(_model, _params(), _x(),
                     in_shardings=(repl, repl))
        k2 = _key_of(_model, _params(), _x(),
                     in_shardings=(repl, sharded))
        assert k1 != k2


# ---------------------------------------------------------------------------
# store round trip through counted_jit
# ---------------------------------------------------------------------------

class TestStoreRoundTrip:
    def test_miss_then_hit_with_identical_result(self, fresh_cache):
        cc = fresh_cache
        f1 = counted_jit(_model, tag="tcc:1")
        ref = np.asarray(f1(_params(), _x()))
        assert cc.stats["misses"] == 1 and cc.stats["puts"] == 1
        assert cc.entry_count() == 1

        jax.clear_caches()  # drop in-memory jax caches: "restart"
        f2 = counted_jit(_model, tag="tcc:2")
        out = np.asarray(f2(_params(), _x()))
        assert cc.stats["hits"] == 1
        np.testing.assert_array_equal(ref, out)

    def test_hit_entry_survives_repeated_calls(self, fresh_cache):
        f1 = counted_jit(_model, tag="tcc:1")
        ref = np.asarray(f1(_params(), _x()))
        jax.clear_caches()
        f2 = counted_jit(_model, tag="tcc:2")
        for _ in range(3):
            np.testing.assert_array_equal(np.asarray(f2(_params(), _x())),
                                          ref)

    def test_pytree_output_round_trip(self, fresh_cache):
        def fn(p, x):
            return {"h": x @ p[0], "n": jnp.sum(x)}

        f1 = counted_jit(fn, tag="tcc:1")
        ref = f1(_params(1), _x())
        jax.clear_caches()
        f2 = counted_jit(fn, tag="tcc:2")
        out = f2(_params(1), _x())
        assert fresh_cache.stats["hits"] == 1
        assert set(out) == {"h", "n"}
        np.testing.assert_array_equal(np.asarray(ref["h"]),
                                      np.asarray(out["h"]))
        np.testing.assert_array_equal(np.asarray(ref["n"]),
                                      np.asarray(out["n"]))

    def test_compile_seconds_histogram_labels(self, fresh_cache):
        f1 = counted_jit(_model, tag="tsec:1")
        f1(_params(), _x())
        jax.clear_caches()
        f2 = counted_jit(_model, tag="tsec:2")
        f2(_params(), _x())
        fam = registry().get("dl4j_compile_seconds")
        assert fam is not None
        labels = {key for key, _ in fam.children()}
        assert ("tsec", "miss") in labels
        assert ("tsec", "hit") in labels

    def test_donated_entries_bypass_the_store(self, fresh_cache):
        cc = fresh_cache
        f = counted_jit(lambda p, x: [w + x.sum() for w in p], tag="tdon:1",
                        donate_argnums=(0,))
        f(_params(), _x())
        assert cc.stats["puts"] == 0  # never serialized
        fam = registry().get("dl4j_compiles_total")
        assert any(key == ("tdon", "bypass") for key, _ in fam.children())

    def test_stale_entry_falls_back_to_live_jit(self, fresh_cache):
        f = counted_jit(lambda p, x: x @ p, tag="tstale:1")
        f(jnp.ones((16, 16)), _x())
        # same data signature (x), params re-initialized with a NEW shape:
        # the AOT entry cannot accept the call and must fall back, not raise
        out = f(jnp.ones((16, 32)), _x())
        assert out.shape == (4, 32)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_x() @ jnp.ones((16, 32))),
                                   rtol=1e-6)
        # ... and the fallback is counted under its own label
        assert _observed("tstale", "bypass:call-error") == 1

    def test_counted_jit_lowers_like_the_jit_it_wraps(self):
        f = counted_jit(lambda p, x: x @ p, tag="tlower:1")
        lowered = f.lower(jnp.ones((16, 16)), _x())
        assert "stablehlo" in lowered.as_text()
        assert lowered.compile()(jnp.ones((16, 16)), _x()).shape == (4, 16)

    def test_sharded_predict_hits_store_on_warm_restart(self, fresh_cache):
        # the fleet regression: a mesh-sharded predict executable must be
        # a raw-store HIT after restart (reloaded with its device
        # assignment and in/out shardings), not a silent bypass
        from deeplearning4j_tpu.common.mesh import (MODEL, serving_mesh,
                                                    shard_params)
        from jax.sharding import NamedSharding, PartitionSpec as P

        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices")
        cc = fresh_cache
        mesh = serving_mesh()
        params = shard_params(mesh, _params())
        x = jax.device_put(_x(), NamedSharding(mesh, P()))
        f1 = counted_jit(_model, tag="tshard:1")
        ref = np.asarray(f1(params, x))
        assert cc.stats["misses"] == 1 and cc.stats["puts"] == 1

        jax.clear_caches()  # "restart"
        f2 = counted_jit(_model, tag="tshard:2")
        out = f2(params, x)
        assert cc.stats["hits"] == 1, \
            "sharded executable must round-trip the raw store"
        np.testing.assert_array_equal(ref, np.asarray(out))
        # the reloaded output is still mesh-sharded, not silently gathered
        assert isinstance(out.sharding, NamedSharding)
        assert out.sharding.spec == P(None, MODEL)

    def test_sharded_and_host_args_key_separately(self, fresh_cache):
        from deeplearning4j_tpu.common.mesh import serving_mesh, shard_params

        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices")
        cc = fresh_cache
        mesh = serving_mesh()
        f = counted_jit(_model, tag="tsk:1")
        f(_params(), _x())
        f2 = counted_jit(_model, tag="tsk:2")
        f2(shard_params(mesh, _params()), _x())
        # same shapes, different placement: two distinct entries
        assert cc.stats["puts"] == 2 and cc.entry_count() == 2

    def test_disabled_via_empty_dir(self):
        env = environment()
        prev = env.property_override(SystemProperties.CACHE_DIR)
        env.set_cache_dir("")
        compile_cache.reset_cache()
        try:
            assert compile_cache.cache() is None
            f = counted_jit(_model, tag="toff:1")
            out = f(_params(), _x())
            assert out.shape == (4, 16)
            fam = registry().get("dl4j_compiles_total")
            assert any(key == ("toff", "bypass")
                       for key, _ in fam.children())
        finally:
            if prev is None:
                env.clear_property(SystemProperties.CACHE_DIR)
            else:
                env.set_property(SystemProperties.CACHE_DIR, prev)
            compile_cache.reset_cache()


# ---------------------------------------------------------------------------
# corruption recovery: a bad cache may cost a compile, never an exception
# ---------------------------------------------------------------------------

def _observed(kind, cache_label):
    """Observation count of dl4j_compile_seconds{kind, cache}."""
    from deeplearning4j_tpu.common.metrics import registry
    fam = registry().get("dl4j_compile_seconds")
    return sum(child.count() for key, child in
               (fam.children() if fam else [])
               if key == (kind, cache_label))


def _entry_files(cc, ext):
    return [os.path.join(cc.aot_dir, n) for n in os.listdir(cc.aot_dir)
            if n.endswith(ext)]


class TestCorruptionRecovery:
    def _seed_entry(self, cc):
        f = counted_jit(_model, tag="tcor:seed")
        ref = np.asarray(f(_params(), _x()))
        assert cc.entry_count() == 1
        jax.clear_caches()
        return ref

    def _rerun(self):
        f = counted_jit(_model, tag="tcor:rerun")
        return np.asarray(f(_params(), _x()))

    def test_corrupt_payload_recompiles_with_warning(self, fresh_cache,
                                                     caplog):
        ref = self._seed_entry(fresh_cache)
        for p in _entry_files(fresh_cache, ".bin"):
            with open(p, "wb") as fh:
                fh.write(b"garbage")
        with caplog.at_level(logging.WARNING,
                             logger="deeplearning4j_tpu.runtime"
                                    ".compile_cache"):
            out = self._rerun()
        np.testing.assert_array_equal(ref, out)
        assert fresh_cache.stats["corrupt"] >= 1
        assert any("recompiling" in r.getMessage() for r in caplog.records)
        # the recompile re-stored a valid entry
        assert fresh_cache.stats["puts"] == 2

    def test_corrupt_meta_recompiles(self, fresh_cache):
        ref = self._seed_entry(fresh_cache)
        for p in _entry_files(fresh_cache, ".json"):
            with open(p, "w") as fh:
                fh.write("{not json")
        out = self._rerun()
        np.testing.assert_array_equal(ref, out)
        assert fresh_cache.stats["corrupt"] >= 1

    def test_format_version_mismatch_recompiles(self, fresh_cache):
        ref = self._seed_entry(fresh_cache)
        for p in _entry_files(fresh_cache, ".json"):
            with open(p) as fh:
                meta = json.load(fh)
            meta["format"] = 999
            with open(p, "w") as fh:
                json.dump(meta, fh)
        out = self._rerun()
        np.testing.assert_array_equal(ref, out)
        assert fresh_cache.stats["corrupt"] >= 1

    def test_undeserializable_payload_recompiles(self, fresh_cache):
        """Payload passes the checksum but is not an executable (stale
        artifact from another backend): deserialize fails -> recompile."""
        ref = self._seed_entry(fresh_cache)
        for p in _entry_files(fresh_cache, ".bin"):
            key = os.path.basename(p)[:-4]
            meta_p = os.path.join(fresh_cache.aot_dir, key + ".json")
            with open(meta_p) as fh:
                meta = json.load(fh)
            fresh_cache.put(key, b"not-an-executable",
                            {"kept_var_idx": meta["kept_var_idx"],
                             "device_ids": meta["device_ids"]})
        before = _observed("tcor", "bypass:deserialize-error")
        out = self._rerun()
        np.testing.assert_array_equal(ref, out)
        # the drop is counted under its own label, not passed off as a miss
        assert _observed("tcor", "bypass:deserialize-error") == before + 1

    def test_entry_records_its_devices_and_loads(self, fresh_cache):
        """jaxlib's deserialize needs the executable's devices: they are
        stored with the entry, and a healthy entry loads as a hit with no
        deserialize-error observed."""
        ref = self._seed_entry(fresh_cache)
        (meta_p,) = _entry_files(fresh_cache, ".json")
        with open(meta_p) as fh:
            assert json.load(fh)["device_ids"] == [jax.devices()[0].id]
        before = _observed("tcor", "bypass:deserialize-error")
        hits = fresh_cache.stats["hits"]
        np.testing.assert_array_equal(ref, self._rerun())
        assert fresh_cache.stats["hits"] == hits + 1
        assert _observed("tcor", "hit") >= 1
        assert _observed("tcor", "bypass:deserialize-error") == before

    def test_truncated_payload_recompiles(self, fresh_cache):
        ref = self._seed_entry(fresh_cache)
        for p in _entry_files(fresh_cache, ".bin"):
            with open(p, "rb") as fh:
                data = fh.read()
            with open(p, "wb") as fh:
                fh.write(data[:len(data) // 2])
        out = self._rerun()
        np.testing.assert_array_equal(ref, out)
        assert fresh_cache.stats["corrupt"] >= 1


# ---------------------------------------------------------------------------
# LRU size capping
# ---------------------------------------------------------------------------

class TestLRUCap:
    def test_oldest_entry_evicted_beyond_cap(self, tmp_path):
        cc = AOTCompileCache(str(tmp_path), max_bytes=100)
        cc.put("k1", b"x" * 80, {"kept_var_idx": [0]})
        old = os.path.join(cc.aot_dir, "k1.bin")
        os.utime(old, (1.0, 1.0))  # force k1 to be the LRU entry
        cc.put("k2", b"y" * 80, {"kept_var_idx": [0]})
        assert cc.stats["evictions"] >= 1
        assert cc.get("k1") is None
        got = cc.get("k2")
        assert got is not None and got[0] == b"y" * 80

    def test_hit_refreshes_recency(self, tmp_path):
        cc = AOTCompileCache(str(tmp_path), max_bytes=180)
        cc.put("k1", b"x" * 80, {"kept_var_idx": [0]})
        cc.put("k2", b"y" * 80, {"kept_var_idx": [0]})
        for p in (os.path.join(cc.aot_dir, "k1.bin"),
                  os.path.join(cc.aot_dir, "k2.bin")):
            os.utime(p, (1.0, 1.0))
        assert cc.get("k1") is not None  # touch k1: k2 becomes LRU
        cc.put("k3", b"z" * 80, {"kept_var_idx": [0]})
        assert cc.get("k1") is not None
        assert cc.get("k2") is None

    def test_uncapped_when_nonpositive(self, tmp_path):
        cc = AOTCompileCache(str(tmp_path), max_bytes=0)
        for i in range(5):
            cc.put(f"k{i}", b"x" * 1000, {"kept_var_idx": [0]})
        assert cc.entry_count() == 5
        assert cc.stats["evictions"] == 0


# ---------------------------------------------------------------------------
# eligibility (what may be wrapped as a raw executable)
# ---------------------------------------------------------------------------

class TestEligibility:
    def test_plain_arrays_eligible(self):
        assert compile_cache._eligible((_params(), _x()), {})

    def test_python_scalars_eligible(self):
        assert compile_cache._eligible((_params(), 3, 0.5, True), {})

    def test_donation_ineligible(self):
        assert not compile_cache._eligible((_params(), _x()),
                                           {"donate_argnums": (0,)})

    def test_shardings_ineligible(self):
        assert not compile_cache._eligible((_params(), _x()),
                                           {"in_shardings": object()})

    def test_prng_key_ineligible(self):
        assert not compile_cache._eligible((_x(), jax.random.key(0)), {})

    def test_multi_device_array_eligible(self):
        # mesh-sharded committed args joined the raw store (their device
        # assignment + shardings fold into the cache key and the entry
        # meta carries the shardings for reload)
        from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P)

        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices")
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
        x = jax.device_put(_x(b=4), NamedSharding(mesh, P("data")))
        assert compile_cache._eligible((x,), {})

    def test_placement_fingerprint_distinguishes_shardings(self):
        # the same shapes on different layouts must key differently —
        # a replicated and a sharded executable are not interchangeable
        from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P)

        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices")
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
        host = (_x(b=4),)
        sharded = (jax.device_put(_x(b=4),
                                  NamedSharding(mesh, P("data"))),)
        repl = (jax.device_put(_x(b=4), NamedSharding(mesh, P())),)
        fps = {compile_cache._placement_fingerprint(a)
               for a in (host, sharded, repl)}
        assert len(fps) == 3


# ---------------------------------------------------------------------------
# warmup: concurrency, idempotence, manifest
# ---------------------------------------------------------------------------

def _mlp(n_in=6, hidden=8, n_out=3):
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer

    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_in=hidden, n_out=n_out))
            .build())
    return MultiLayerNetwork(conf).init()


def _req(b=1, n_in=6):
    return jnp.zeros((b, n_in), jnp.float32)


class TestWarmupGuard:
    def test_warmup_idempotent(self):
        eng = InferenceEngine(_mlp(), max_batch=8)
        assert eng.warmup(_req()) == [1, 2, 4, 8]
        d0 = eng.stats()["dispatches"]
        assert d0 == 4
        assert eng.warmup(_req()) == [1, 2, 4, 8]  # same buckets reported
        assert eng.stats()["dispatches"] == d0     # nothing re-dispatched

    def test_concurrent_warmup_no_double_compile(self):
        eng = InferenceEngine(_mlp(), max_batch=8)
        barrier = threading.Barrier(2)
        results, errors = [], []

        def go():
            try:
                barrier.wait(timeout=30)
                results.append(eng.warmup(_req()))
            except Exception as e:  # pragma: no cover - diagnostic
                errors.append(e)

        threads = [threading.Thread(target=go) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors
        assert results == [[1, 2, 4, 8], [1, 2, 4, 8]]
        # each bucket dispatched (and therefore compiled) exactly once
        assert eng.stats()["dispatches"] == 4
        assert all(v == 1
                   for v in eng.stats()["bucket_dispatches"].values())

    def test_warmup_serial_worker_override(self):
        eng = InferenceEngine(_mlp(), max_batch=4)
        assert eng.warmup(_req(), workers=1) == [1, 2, 4]
        assert eng.stats()["dispatches"] == 3


class TestWarmupManifest:
    def test_traffic_records_manifest(self, tmp_path):
        man = str(tmp_path / "warmup.json")
        eng = InferenceEngine(_mlp(), max_batch=8, manifest_path=man)
        eng.infer(_req(b=3))  # bucket 4
        eng.infer(_req(b=1))  # bucket 1
        assert os.path.exists(man)
        with open(man) as f:
            doc = json.load(f)
        assert doc["version"] == 1
        buckets = sorted(b for e in doc["entries"] for b in e["buckets"])
        assert buckets == [1, 4]
        assert doc["entries"][0]["inputs"][0]["shape"] == [6]

    def test_restart_replays_manifest(self, tmp_path):
        man = str(tmp_path / "warmup.json")
        eng = InferenceEngine(_mlp(), max_batch=8, manifest_path=man)
        eng.infer(_req(b=3))
        eng.infer(_req(b=7))  # bucket 8

        # "restart": fresh model + engine, warmup with no example replays
        eng2 = InferenceEngine(_mlp(), max_batch=8, manifest_path=man)
        env = environment()
        c0 = env.compile_count()
        assert eng2.warmup() == [4, 8]
        warm_compiles = env.compile_count() - c0
        assert warm_compiles == 2
        # yesterday's shapes now serve without compiling anything new
        eng2.infer(_req(b=3))
        eng2.infer(_req(b=7))
        assert env.compile_count() - c0 == warm_compiles

    def test_explicit_save_and_replay(self, tmp_path):
        eng = InferenceEngine(_mlp(), max_batch=8)
        eng.infer(_req(b=2))
        path = eng.save_manifest(str(tmp_path / "m.json"))
        entries = InferenceEngine.load_manifest(path)
        assert entries and entries[0]["buckets"] == [2]

    def test_save_without_path_raises(self):
        eng = InferenceEngine(_mlp(), max_batch=8)
        with pytest.raises(ValueError):
            eng.save_manifest()

    def test_corrupt_manifest_skipped_with_warning(self, tmp_path, caplog):
        man = tmp_path / "warmup.json"
        man.write_text("{broken")
        eng = InferenceEngine(_mlp(), max_batch=8,
                              manifest_path=str(man))
        with caplog.at_level(logging.WARNING):
            assert eng.warmup() == []  # skipped, no exception
        assert any("unreadable" in r.getMessage() for r in caplog.records)

    def test_warmup_without_example_or_manifest_is_noop(self):
        eng = InferenceEngine(_mlp(), max_batch=8)
        assert eng.warmup() == []
        assert eng.stats()["dispatches"] == 0


# ---------------------------------------------------------------------------
# warm_compile (CI cache pre-baking for train steps)
# ---------------------------------------------------------------------------

class TestWarmCompile:
    def test_warm_compile_populates_backstop_without_stepping(
            self, fresh_cache, monkeypatch):
        # the backstop defaults off on the CPU backend (DL4J_TPU_XLA_CACHE
        # =auto); force it on to exercise the wiring — which exists only
        # where the outside has not placed jax's cache itself
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("DL4J_TPU_XLA_CACHE", "on")
        compile_cache.reset_cache()
        try:
            net = _mlp()
            before = jax.tree_util.tree_map(np.asarray, net._params)
            x = np.random.RandomState(0).randn(8, 6).astype(np.float32)
            y = np.zeros((8, 3), np.float32)
            y[np.arange(8), np.arange(8) % 3] = 1.0
            label = net.warm_compile(x, y)
            assert label == "bypass"  # donated train steps: backstop only
            # params untouched (nothing executed, nothing donated)
            after = jax.tree_util.tree_map(np.asarray, net._params)
            for b, a in zip(jax.tree_util.tree_leaves(before),
                            jax.tree_util.tree_leaves(after)):
                np.testing.assert_array_equal(b, a)
            xla_dir = os.path.join(fresh_cache.base_dir, "xla")
            assert os.path.isdir(xla_dir) and os.listdir(xla_dir)
        finally:
            # detach the backstop before the env var reverts to auto —
            # fixture teardown order must not leave it wired for the
            # rest of the suite
            monkeypatch.setenv("DL4J_TPU_XLA_CACHE", "off")
            compile_cache.reset_cache()

    def test_backstop_defaults_off_on_cpu(self, fresh_cache):
        """DL4J_TPU_XLA_CACHE=auto: on the CPU backend the store is
        active but jax's compilation-cache dir stays unwired (XLA:CPU
        deserialized-executable instability; see _backstop_wanted)."""
        assert environment().xla_cache() == "auto"
        assert fresh_cache is not None  # the store itself is on
        assert not compile_cache._backstop_wanted()
        assert jax.config.jax_compilation_cache_dir is None

    def test_warm_buckets_precompiles_direct_output_path(self):
        net = _mlp()
        env = environment()
        c0 = env.compile_count()
        warmed = net.warm_buckets(_req(), batch_sizes=[1, 3])
        assert warmed == [1, 4]
        compiles = env.compile_count() - c0
        assert compiles == 2
        # the direct output() path reuses the warmed executables
        net.output(_req(b=3))
        assert env.compile_count() - c0 == compiles


# ---------------------------------------------------------------------------
# attention auto-dispatch satellite
# ---------------------------------------------------------------------------

class TestAttentionDispatch:
    def test_rule_default(self, monkeypatch):
        """Unset, the measured rule decides: XLA on the CPU backend at any
        length; on an accelerator the kernel from the crossover up, from
        seq_len and head_dim, with the reason recorded for XLA."""
        from deeplearning4j_tpu import kernels
        from deeplearning4j_tpu.kernels import (attention_dispatch,
                                                dispatch_snapshot)

        for seq in (128, 512, 1024, 4096):
            assert attention_dispatch(seq, head_dim=64) == "xla"
        assert "cpu backend" in dispatch_snapshot()["attention"]["reason"]

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        lo = kernels._FLASH_MIN_SEQ
        for head_dim in (64, 128):
            assert attention_dispatch(lo // 2, head_dim=head_dim) == "xla"
            assert str(lo) in dispatch_snapshot()["attention"]["reason"]
            assert attention_dispatch(lo, head_dim=head_dim) == "flash"
            assert dispatch_snapshot()["attention"]["reason"] is None
            assert attention_dispatch(512, head_dim=head_dim) == "flash"
            assert attention_dispatch(4096, head_dim=head_dim) == "flash"
        assert attention_dispatch(512, head_dim=32) == "xla"  # not measured
        assert "head_dim" in dispatch_snapshot()["attention"]["reason"]
        assert attention_dispatch(1, head_dim=64) == "xla"  # decode pin

    def test_threshold_env_override(self, monkeypatch):
        """The rule is the seam: a substituted one decides, on any
        backend (what the environment's threshold was used as)."""
        from deeplearning4j_tpu import kernels
        from deeplearning4j_tpu.kernels import attention_dispatch

        monkeypatch.setattr(
            kernels, "_flash_rule", lambda seq_len, head_dim:
            ("flash", "") if seq_len >= 64 else ("xla", "seq_len<64"))
        assert attention_dispatch(128) == "flash"
        assert attention_dispatch(32) == "xla"

    def test_dispatch_counter(self, monkeypatch):
        from deeplearning4j_tpu.kernels import attention_dispatch

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fam = registry().counter("dl4j_attn_dispatch_total",
                                 "Attention path decisions for flash=True "
                                 "configs", labels=("path",))
        x0 = fam.labels(path="xla").value()
        f0 = fam.labels(path="flash").value()
        attention_dispatch(8)
        attention_dispatch(8192)
        assert fam.labels(path="xla").value() == x0 + 1
        assert fam.labels(path="flash").value() == f0 + 1

    def test_bert_flash_below_threshold_takes_xla_path(self):
        """flash=True at short seq must produce bitwise the XLA result —
        proof the dispatch silently switched paths."""
        from deeplearning4j_tpu.models import bert

        config = bert.BertConfig.tiny()
        params = bert.init_params(jax.random.key(0), config)
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, config.vocab_size, (2, 16)),
                          jnp.int32)
        out_flash = bert.encode(params, ids, config=config, use_flash=True)
        out_xla = bert.encode(params, ids, config=config, use_flash=False)
        np.testing.assert_array_equal(np.asarray(out_flash),
                                      np.asarray(out_xla))

    def test_bert_default_step_on_cpu_is_the_xla_step(self):
        """``make_train_step``'s default lets the dispatcher decide; on the
        CPU backend that is the XLA path, bit for bit the ``use_flash=False``
        step, decided once per trace and with the reason on record."""
        from deeplearning4j_tpu.kernels import dispatch_snapshot
        from deeplearning4j_tpu.models import bert

        config = bert.BertConfig.tiny()
        rng = np.random.RandomState(0)
        ids = rng.randint(0, config.vocab_size, (2, 16))
        batch = {"input_ids": jnp.asarray(ids, jnp.int32),
                 "labels": jnp.asarray(np.where(rng.rand(2, 16) < 0.3, ids,
                                                -100), jnp.int32),
                 "attention_mask": jnp.ones((2, 16), jnp.int32)}
        fam = registry().counter("dl4j_kernel_dispatch_total", "",
                                 labels=("kernel", "path"))
        xla = fam.labels(kernel="attention", path="xla")

        def run(**kw):
            params = bert.init_params(jax.random.key(0), config)
            step = bert.make_train_step(config, None, learning_rate=1e-3,
                                        remat=False, **kw)
            return step(params, bert.init_opt_state(params), batch, 0)

        x0 = xla.value()
        got = run()
        assert xla.value() == x0 + 1
        snap = dispatch_snapshot()["attention"]
        assert snap["path"] == "xla" and "cpu backend" in snap["reason"]
        want = run(use_flash=False)
        assert xla.value() == x0 + 1        # False never asks
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# pluggable artifact stores: tiers, concurrent writers, fleet handoff
# ---------------------------------------------------------------------------

@pytest.fixture
def tiered_cache(tmp_path):
    """Local + remote tiered cache over private dirs (the shared-store
    deployment in miniature), env triple restored afterwards."""
    env = environment()
    saved = {p: env.property_override(p)
             for p in (SystemProperties.CACHE_DIR,
                       SystemProperties.REMOTE_CACHE,
                       SystemProperties.CACHE_TIER)}
    env.set_cache_dir(str(tmp_path / "local"))
    env.set_remote_cache(str(tmp_path / "remote"))
    env.set_cache_tier("auto")
    compile_cache.reset_cache()
    yield compile_cache.cache()
    for prop, value in saved.items():
        if value is None:
            env.clear_property(prop)
        else:
            env.set_property(prop, value)
    compile_cache.reset_cache()


def _remote_paths(store, key):
    return store._paths(key)


class TestArtifactStores:
    def test_default_store_is_local_dir(self, fresh_cache):
        """No remote configured -> behavior-identical LocalDirStore with
        today's flat <base>/aot layout."""
        assert isinstance(fresh_cache.store,
                          compile_cache.LocalDirStore)
        assert fresh_cache.aot_dir.endswith(os.path.join("", "aot"))
        fresh_cache.put("k1", b"payload", {"kept_var_idx": [0]})
        assert os.path.exists(os.path.join(fresh_cache.aot_dir, "k1.bin"))
        assert os.path.exists(os.path.join(fresh_cache.aot_dir, "k1.json"))
        tiers = fresh_cache.store.tiers()
        assert [t.tier for t in tiers] == ["local"]
        assert tiers[0].describe()["backend"] == "local-dir"

    def test_tiered_put_populates_both_tiers(self, tiered_cache):
        assert isinstance(tiered_cache.store, compile_cache.TieredStore)
        tiered_cache.put("ab" * 32, b"payload", {"kept_var_idx": [0]})
        store = tiered_cache.store
        assert store.local.contains("ab" * 32)
        assert store.remote.contains("ab" * 32)
        # content-addressed remote layout: objects/<key[:2]>/<key>.bin
        payload_p, _ = _remote_paths(store.remote, "ab" * 32)
        assert os.sep + os.path.join("objects", "ab") + os.sep in payload_p

    def test_local_miss_falls_through_and_backfills(self, tiered_cache):
        tiered_cache.put("cd" * 32, b"payload", {"kept_var_idx": [0]})
        tiered_cache.store.local.clear()
        assert not tiered_cache.store.local.contains("cd" * 32)
        got = tiered_cache.get("cd" * 32)
        assert got is not None and got[0] == b"payload"
        assert tiered_cache.stats["hits"] == 1
        # the remote hit was written back into the local tier
        assert tiered_cache.store.local.contains("cd" * 32)

    def test_corrupt_local_refetches_from_remote(self, tiered_cache,
                                                 caplog):
        """Digest mismatch on the local copy -> delete + transparent
        refetch from the shared store, surfaced on the existing
        corruption warning path."""
        tiered_cache.put("ef" * 32, b"payload", {"kept_var_idx": [0]})
        with open(os.path.join(tiered_cache.aot_dir,
                               "ef" * 32 + ".bin"), "wb") as fh:
            fh.write(b"garbage")
        with caplog.at_level(logging.WARNING,
                             logger="deeplearning4j_tpu.runtime"
                                    ".compile_cache"):
            got = tiered_cache.get("ef" * 32)
        assert got is not None and got[0] == b"payload"
        assert tiered_cache.stats["corrupt"] == 1
        assert any("refetched from remote" in r.getMessage()
                   for r in caplog.records)
        # the backfill healed the local copy
        healed = tiered_cache.store.local.get("ef" * 32)
        assert healed is not None and healed[0] == b"payload"

    def test_corrupt_remote_deleted_with_warning(self, tiered_cache,
                                                 caplog):
        """A bad shared-store entry is deleted for the whole fleet and
        reported as a miss via the existing recompiling warning."""
        store = tiered_cache.store
        store.remote.put("12" * 32, b"payload",
                         compile_cache._stamp_meta(b"payload", {}))
        payload_p, _ = _remote_paths(store.remote, "12" * 32)
        with open(payload_p, "wb") as fh:
            fh.write(b"garbage")
        with caplog.at_level(logging.WARNING,
                             logger="deeplearning4j_tpu.runtime"
                                    ".compile_cache"):
            assert tiered_cache.get("12" * 32) is None
        assert tiered_cache.stats["corrupt"] == 1
        assert tiered_cache.stats["misses"] == 1
        assert not store.remote.contains("12" * 32)
        assert any("recompiling" in r.getMessage()
                   for r in caplog.records)

    def test_half_written_entry_detected_and_dropped(self, tmp_path):
        """Satellite regression: an interleaved half-written entry (a
        writer that died mid-payload AFTER the meta landed) must fail the
        digest check and be deleted, never served."""
        store = compile_cache.RemoteStore(str(tmp_path))
        meta = compile_cache._stamp_meta(b"full-payload-bytes", {})
        store.put("ab" * 32, b"full-payload-bytes", meta)
        payload_p, _ = _remote_paths(store, "ab" * 32)
        with open(payload_p, "wb") as fh:
            fh.write(b"full-pay")  # torn write: correct prefix, truncated
        with pytest.raises(compile_cache.CorruptEntryError):
            store.get("ab" * 32)
        assert not store.contains("ab" * 32)
        # a crashed writer's leftover tmp file is not an entry either
        with open(payload_p + compile_cache._tmp_suffix(), "wb") as fh:
            fh.write(b"partial")
        assert store.keys() == []
        assert store.stat()["entries"] == 0

    def test_concurrent_same_key_writers_converge(self, tmp_path):
        """N threads racing a put of the same key: unique tmp files +
        atomic rename mean the survivor is always a valid entry."""
        store = compile_cache.RemoteStore(str(tmp_path))
        payload = b"x" * 4096
        meta = compile_cache._stamp_meta(payload, {"kept_var_idx": [0]})
        errs = []

        def writer():
            try:
                for _ in range(20):
                    assert store.put("fe" * 32, payload, meta)
            except Exception as e:  # pragma: no cover - failure path
                errs.append(e)

        threads = [threading.Thread(target=writer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        got = store.get("fe" * 32)
        assert got is not None and got[0] == payload
        # no tmp litter survived the races
        shard = os.path.dirname(_remote_paths(store, "fe" * 32)[0])
        assert [n for n in os.listdir(shard) if ".tmp" in n] == []

    def test_tmp_suffixes_are_unique(self):
        out = set()

        def grab():
            for _ in range(50):
                out.add(compile_cache._tmp_suffix())

        threads = [threading.Thread(target=grab) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(out) == 200

    def test_remote_only_tier(self, tmp_path):
        env = environment()
        saved = {p: env.property_override(p)
                 for p in (SystemProperties.CACHE_DIR,
                           SystemProperties.REMOTE_CACHE,
                           SystemProperties.CACHE_TIER)}
        try:
            env.set_cache_dir(str(tmp_path / "base"))
            env.set_remote_cache(str(tmp_path / "remote"))
            env.set_cache_tier("remote")
            compile_cache.reset_cache()
            cc = compile_cache.cache()
            assert isinstance(cc.store, compile_cache.RemoteStore)
            assert cc.aot_dir is None
            cc.put("ba" * 32, b"payload", {"kept_var_idx": [0]})
            assert cc.get("ba" * 32)[0] == b"payload"
            assert cc.entry_count() == 1
        finally:
            for prop, value in saved.items():
                if value is None:
                    env.clear_property(prop)
                else:
                    env.set_property(prop, value)
            compile_cache.reset_cache()

    def test_shared_remote_not_lru_capped(self, tmp_path):
        """One replica's byte cap must never evict the fleet's shared
        entries: enforce_cap only prunes the local tier."""
        local = compile_cache.LocalDirStore(str(tmp_path / "l"))
        remote = compile_cache.RemoteStore(str(tmp_path / "r"))
        store = compile_cache.TieredStore(local, remote)
        for i in range(4):
            key = f"{i:02d}" * 32
            store.put(key, b"x" * 80,
                      compile_cache._stamp_meta(b"x" * 80, {}))
        assert store.enforce_cap(100) > 0
        assert local.stat()["bytes"] <= 100
        assert remote.stat()["entries"] == 4


class TestTieredInventory:
    def test_inventory_reports_tiers(self, tiered_cache):
        tiered_cache.put("aa" * 32, b"x" * 100, {"kept_var_idx": [0]})
        tiered_cache.put("bb" * 32, b"y" * 50, {"kept_var_idx": [0]})
        tiered_cache.store.local.delete("bb" * 32)  # remote-only entry
        inv = compile_cache.inventory()
        assert inv["enabled"] and inv["entry_count"] == 1
        by_tier = {t["tier"]: t for t in inv["tiers"]}
        assert set(by_tier) == {"local", "remote"}
        assert by_tier["local"]["backend"] == "local-dir"
        assert by_tier["remote"]["backend"] == "remote-fs"
        assert by_tier["local"]["entry_count"] == 1
        assert by_tier["remote"]["entry_count"] == 2
        assert by_tier["local"]["payload_bytes"] >= 100
        assert by_tier["remote"]["payload_bytes"] >= 150

    def test_debug_endpoint_serves_tier_listing(self, tiered_cache):
        """/debug/compile_cache with a tiered store: per-tier backend,
        entry counts, and bytes ride the existing inventory document."""
        import urllib.request

        from deeplearning4j_tpu.ui.server import UIServer

        tiered_cache.put("cc" * 32, b"z" * 64, {"kept_var_idx": [0]})
        ui = UIServer(port=0)
        port = ui.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/compile_cache",
                    timeout=5) as r:
                doc = json.loads(r.read())
        finally:
            ui.stop()
        assert doc["enabled"] and doc["entry_count"] == 1
        tiers = {t["tier"]: t for t in doc["tiers"]}
        assert tiers["local"]["entry_count"] == 1
        assert tiers["remote"]["entry_count"] == 1
        assert tiers["remote"]["payload_bytes"] >= 64

    def test_store_gauges_track_mutations(self, tiered_cache):
        reg = registry()
        tiered_cache.put("dd" * 32, b"p" * 128, {"kept_var_idx": [0]})
        g_entries = reg.get("dl4j_cache_store_entries")
        g_bytes = reg.get("dl4j_cache_store_bytes")
        assert g_entries.labels(tier="local").value() == 1
        assert g_entries.labels(tier="remote").value() == 1
        assert g_bytes.labels(tier="remote").value() >= 128
        tiered_cache.clear()  # local-only clear: remote keeps the entry
        assert g_entries.labels(tier="local").value() == 0
        assert g_entries.labels(tier="remote").value() == 1


class TestFleetHandoff:
    def test_push_to_remote_publishes_missing_entries(self, tmp_path):
        env = environment()
        saved = {p: env.property_override(p)
                 for p in (SystemProperties.CACHE_DIR,
                           SystemProperties.REMOTE_CACHE,
                           SystemProperties.CACHE_TIER)}
        try:
            # seed executables with NO remote configured (yesterday's
            # replica), then attach the shared store and push on drain
            env.set_cache_dir(str(tmp_path / "local"))
            env.set_remote_cache(None)
            compile_cache.reset_cache()
            cc = compile_cache.cache()
            cc.put("ab" * 32, b"one", {"kept_var_idx": [0]})
            cc.put("cd" * 32, b"two", {"kept_var_idx": [0]})
            mdir = compile_cache.serving_manifest_dir()
            with open(os.path.join(mdir, "toy.warmup.json"), "w") as fh:
                json.dump([{"inputs": [], "buckets": [1]}], fh)
            env.set_remote_cache(str(tmp_path / "remote"))
            compile_cache.reset_cache()
            pushed = compile_cache.push_to_remote()
            assert pushed == {"executables": 2, "manifests": 1}
            remote = compile_cache.RemoteStore(str(tmp_path / "remote"))
            assert remote.stat()["entries"] == 2
            assert os.path.exists(os.path.join(
                remote.manifest_dir(), "toy.warmup.json"))
            # idempotent: nothing new to publish the second time
            assert compile_cache.push_to_remote()["executables"] == 0
        finally:
            for prop, value in saved.items():
                if value is None:
                    env.clear_property(prop)
                else:
                    env.set_property(prop, value)
            compile_cache.reset_cache()

    def test_pull_from_remote_warms_empty_local(self, tmp_path):
        env = environment()
        saved = {p: env.property_override(p)
                 for p in (SystemProperties.CACHE_DIR,
                           SystemProperties.REMOTE_CACHE,
                           SystemProperties.CACHE_TIER)}
        try:
            remote = compile_cache.RemoteStore(str(tmp_path / "remote"))
            for key, payload in (("ab" * 32, b"one"), ("cd" * 32, b"two")):
                remote.put(key, payload,
                           compile_cache._stamp_meta(payload, {}))
            os.makedirs(remote.manifest_dir(), exist_ok=True)
            with open(os.path.join(remote.manifest_dir(),
                                   "toy.warmup.json"), "w") as fh:
                json.dump([{"inputs": [], "buckets": [1]}], fh)
            env.set_cache_dir(str(tmp_path / "local2"))  # empty joiner
            env.set_remote_cache(str(tmp_path / "remote"))
            compile_cache.reset_cache()
            pulled = compile_cache.pull_from_remote()
            assert pulled == {"executables": 2, "manifests": 1}
            cc = compile_cache.cache()
            assert cc.store.local.contains("ab" * 32)
            assert cc.store.local.contains("cd" * 32)
            assert os.path.exists(os.path.join(
                compile_cache.serving_manifest_dir(),
                "toy.warmup.json"))
            # the boot pull landed on the pull-latency histogram
            fam = registry().get("dl4j_cache_pull_seconds")
            hits = sum(child.count()
                       for key, child in fam.children()
                       if key == ("hit",))
            assert hits >= 2
        finally:
            for prop, value in saved.items():
                if value is None:
                    env.clear_property(prop)
                else:
                    env.set_property(prop, value)
            compile_cache.reset_cache()

    def test_handoff_noop_without_remote_store(self, fresh_cache):
        fresh_cache.put("ab" * 32, b"one", {"kept_var_idx": [0]})
        assert compile_cache.push_to_remote() == {"executables": 0,
                                                  "manifests": 0}
        assert compile_cache.pull_from_remote() == {"executables": 0,
                                                    "manifests": 0}
        assert compile_cache.pull_manifests() == 0
