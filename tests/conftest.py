"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the reference's DummyTransport
in-JVM fake-cluster pattern, SURVEY.md §4 "distributed without a cluster"):
sharding/collective code paths execute for real, just on host devices.
Must run before jax is imported anywhere.
"""
import os

# Force CPU: unit tests never touch an accelerator — they run on the
# virtual 8-device CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavier chaos/perf loops excluded from the tier-1 run "
        "(-m 'not slow')")


#: modules that exercise the concurrent serving stack hard enough to
#: double as deadlock detectors: the DL105 runtime lock-order tracker
#: (common.locks, DL4J_TPU_LOCK_CHECK) is armed for them and any
#: recorded order inversion fails the module at teardown
_LOCK_CHECK_MODULES = {"test_serving.py", "test_resilience.py",
                       "test_generation.py"}


@pytest.fixture(scope="module", autouse=True)
def _lock_order_check(request):
    name = os.path.basename(str(request.node.fspath))
    if name not in _LOCK_CHECK_MODULES:
        yield
        return
    from deeplearning4j_tpu.common import locks
    locks.clear_violations()
    prev_env = os.environ.get("DL4J_TPU_LOCK_CHECK")
    os.environ["DL4J_TPU_LOCK_CHECK"] = "1"
    prev = locks.set_lock_check(True)
    try:
        yield
    finally:
        locks.set_lock_check(prev)
        if prev_env is None:
            os.environ.pop("DL4J_TPU_LOCK_CHECK", None)
        else:
            os.environ["DL4J_TPU_LOCK_CHECK"] = prev_env
        found = locks.violations()
        locks.clear_violations()
    assert not found, (
        f"lock-order inversions recorded while running {name} "
        f"(DL4J_TPU_LOCK_CHECK): {found}")


@pytest.fixture
def flash_everywhere(monkeypatch):
    """``kernels.attention_dispatch``'s rule answers "flash" at every
    shape, on the CPU backend too (where the kernel runs interpreted): how
    a test steers a model onto the kernel. The decode pin and the paged
    branch sit in front of the rule and must not follow it."""
    from deeplearning4j_tpu import kernels
    monkeypatch.setattr(kernels, "_flash_rule",
                        lambda seq_len, head_dim: ("flash", ""))


@pytest.fixture(scope="session", autouse=True)
def _compile_cache_tmpdir(tmp_path_factory):
    """Point the AOT executable cache (DL4J_TPU_CACHE_DIR) at a per-run
    tmpdir for the whole suite: tests exercise the real cache code paths
    but never read another run's entries or litter the user cache dir."""
    d = tmp_path_factory.mktemp("dl4j-tpu-compile-cache")
    prev = os.environ.get("DL4J_TPU_CACHE_DIR")
    os.environ["DL4J_TPU_CACHE_DIR"] = str(d)
    from deeplearning4j_tpu.runtime import compile_cache
    compile_cache.reset_cache()
    yield str(d)
    if prev is None:
        os.environ.pop("DL4J_TPU_CACHE_DIR", None)
    else:
        os.environ["DL4J_TPU_CACHE_DIR"] = prev
    compile_cache.reset_cache()
