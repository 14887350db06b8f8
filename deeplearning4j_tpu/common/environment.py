"""Layered environment/config system.

Reference: the four config layers of SURVEY §5 —
(1) backend selection (Maven artifact → here: JAX platform),
(2) env vars (`ND4JEnvironmentVars.java`, 192 lines),
(3) system properties (`ND4JSystemProperties.java`, 204 lines),
(4) runtime singleton (`Nd4j.getEnvironment()` → native `sd::Environment`,
    `libnd4j/include/system/Environment.h:41`).

TPU mapping: properties resolve env vars first (DL4J_TPU_* then the
documented legacy ND4J names), then programmatic overrides, then defaults.
The runtime singleton exposes the reference Environment getters
(debug/verbose/maxThreads/precision knobs) wired to their JAX equivalents.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional


class EnvironmentVars:
    """Documented env var names (ND4JEnvironmentVars analog)."""
    BACKEND_PRIORITY_CPU = "BACKEND_PRIORITY_CPU"
    BACKEND_PRIORITY_GPU = "BACKEND_PRIORITY_GPU"
    ND4J_RESOURCES_DIR = "ND4J_RESOURCES_DIR"
    DL4J_TPU_DEBUG = "DL4J_TPU_DEBUG"
    DL4J_TPU_VERBOSE = "DL4J_TPU_VERBOSE"
    DL4J_TPU_MAX_THREADS = "DL4J_TPU_MAX_THREADS"
    DL4J_TPU_PLATFORM = "JAX_PLATFORMS"
    DL4J_TPU_DEFAULT_DTYPE = "DL4J_TPU_DEFAULT_DTYPE"
    #: legacy spelling of DEFAULT_DTYPE, still honored (second in line)
    DL4J_TPU_DTYPE = "DL4J_TPU_DTYPE"
    DL4J_TPU_MATMUL_PRECISION = "DL4J_TPU_MATMUL_PRECISION"
    DL4J_TPU_NAN_PANIC = "DL4J_TPU_NAN_PANIC"
    DL4J_TPU_INF_PANIC = "DL4J_TPU_INF_PANIC"
    DL4J_TPU_PROFILING = "DL4J_TPU_PROFILING"
    DL4J_TPU_EAGER_JIT = "DL4J_TPU_EAGER_JIT"
    DL4J_TPU_HOME = "DL4J_TPU_HOME"
    #: dataset download root (datasets/fetchers.py) and the native-lib
    #: build cache (native/__init__.py) — declared here for the DL102
    #: knob registry; both are resolved by their owning modules
    DL4J_TPU_DATA = "DL4J_TPU_DATA"
    DL4J_TPU_NATIVE_CACHE = "DL4J_TPU_NATIVE_CACHE"
    DL4J_TPU_LOCK_CHECK = "DL4J_TPU_LOCK_CHECK"
    DL4J_TPU_CACHE_DIR = "DL4J_TPU_CACHE_DIR"
    DL4J_TPU_CACHE_MAX_BYTES = "DL4J_TPU_CACHE_MAX_BYTES"
    DL4J_TPU_REMOTE_CACHE = "DL4J_TPU_REMOTE_CACHE"
    DL4J_TPU_CACHE_TIER = "DL4J_TPU_CACHE_TIER"
    DL4J_TPU_XLA_CACHE = "DL4J_TPU_XLA_CACHE"
    DL4J_TPU_WARMUP_THREADS = "DL4J_TPU_WARMUP_THREADS"
    DL4J_TPU_PAGED_KERNEL = "DL4J_TPU_PAGED_KERNEL"
    DL4J_TPU_FUSED_DEQUANT = "DL4J_TPU_FUSED_DEQUANT"
    DL4J_TPU_INFERENCE_BUCKETING = "DL4J_TPU_INFERENCE_BUCKETING"
    DL4J_TPU_INFERENCE_MAX_BATCH = "DL4J_TPU_INFERENCE_MAX_BATCH"
    DL4J_TPU_DECODE_SLOTS = "DL4J_TPU_DECODE_SLOTS"
    DL4J_TPU_DECODE_MAX_CTX = "DL4J_TPU_DECODE_MAX_CTX"
    DL4J_TPU_DECODE_MAX_TOKENS = "DL4J_TPU_DECODE_MAX_TOKENS"
    DL4J_TPU_KV_BLOCK_SIZE = "DL4J_TPU_KV_BLOCK_SIZE"
    DL4J_TPU_SPEC_DRAFT_K = "DL4J_TPU_SPEC_DRAFT_K"
    DL4J_TPU_PREFIX_CACHE = "DL4J_TPU_PREFIX_CACHE"
    DL4J_TPU_QUANT = "DL4J_TPU_QUANT"
    DL4J_TPU_QUANT_MAX_DIVERGENCE = "DL4J_TPU_QUANT_MAX_DIVERGENCE"
    DL4J_TPU_QUANT_MIN_TOP1 = "DL4J_TPU_QUANT_MIN_TOP1"
    DL4J_TPU_REMAT = "DL4J_TPU_REMAT"
    DL4J_TPU_GRAD_ACCUM = "DL4J_TPU_GRAD_ACCUM"
    DL4J_TPU_ZERO1 = "DL4J_TPU_ZERO1"
    DL4J_TPU_METRICS = "DL4J_TPU_METRICS"
    DL4J_TPU_TRACE_BUFFER = "DL4J_TPU_TRACE_BUFFER"
    DL4J_TPU_SERVING_MAX_CONCURRENT = "DL4J_TPU_SERVING_MAX_CONCURRENT"
    DL4J_TPU_SERVING_QUEUE_DEPTH = "DL4J_TPU_SERVING_QUEUE_DEPTH"
    DL4J_TPU_SERVING_HIGH_WATER = "DL4J_TPU_SERVING_HIGH_WATER"
    DL4J_TPU_SERVING_TIMEOUT_S = "DL4J_TPU_SERVING_TIMEOUT_S"
    DL4J_TPU_SERVING_DRAIN_TIMEOUT_S = "DL4J_TPU_SERVING_DRAIN_TIMEOUT_S"
    DL4J_TPU_SERVING_RETAIN = "DL4J_TPU_SERVING_RETAIN"
    DL4J_TPU_SERVING_MANIFEST_DIR = "DL4J_TPU_SERVING_MANIFEST_DIR"
    DL4J_TPU_SLO_OBJECTIVE = "DL4J_TPU_SLO_OBJECTIVE"
    DL4J_TPU_SLO_LATENCY_MS = "DL4J_TPU_SLO_LATENCY_MS"
    DL4J_TPU_SLO_WINDOWS = "DL4J_TPU_SLO_WINDOWS"
    DL4J_TPU_SLO_READYZ = "DL4J_TPU_SLO_READYZ"
    DL4J_TPU_REQUEST_RING = "DL4J_TPU_REQUEST_RING"
    DL4J_TPU_DEBUG_ENDPOINTS = "DL4J_TPU_DEBUG_ENDPOINTS"
    DL4J_TPU_FAULTS = "DL4J_TPU_FAULTS"
    DL4J_TPU_BREAKER_THRESHOLD = "DL4J_TPU_BREAKER_THRESHOLD"
    DL4J_TPU_BREAKER_PROBE_S = "DL4J_TPU_BREAKER_PROBE_S"
    DL4J_TPU_AUTO_ROLLBACK = "DL4J_TPU_AUTO_ROLLBACK"
    DL4J_TPU_AUTO_ROLLBACK_OPENS = "DL4J_TPU_AUTO_ROLLBACK_OPENS"
    DL4J_TPU_ENGINE_MAX_RESTARTS = "DL4J_TPU_ENGINE_MAX_RESTARTS"
    DL4J_TPU_WATCHDOG_FACTOR = "DL4J_TPU_WATCHDOG_FACTOR"
    DL4J_TPU_PROFILE_DIR = "DL4J_TPU_PROFILE_DIR"
    DL4J_TPU_FLIGHT_RECORDER_DIR = "DL4J_TPU_FLIGHT_RECORDER_DIR"
    DL4J_TPU_FLEET_POLL_S = "DL4J_TPU_FLEET_POLL_S"
    DL4J_TPU_FLEET_RETRIES = "DL4J_TPU_FLEET_RETRIES"
    DL4J_TPU_FLEET_TIMEOUT_S = "DL4J_TPU_FLEET_TIMEOUT_S"
    DL4J_TPU_FLEET_RETRY_BUDGET = "DL4J_TPU_FLEET_RETRY_BUDGET"
    DL4J_TPU_FLEET_HEDGE_PCTL = "DL4J_TPU_FLEET_HEDGE_PCTL"
    DL4J_TPU_FLEET_BROWNOUT_FRAC = "DL4J_TPU_FLEET_BROWNOUT_FRAC"
    DL4J_TPU_FLEET_DEFAULT_PRIORITY = "DL4J_TPU_FLEET_DEFAULT_PRIORITY"
    DL4J_TPU_FLEET_AGG_RETENTION_S = "DL4J_TPU_FLEET_AGG_RETENTION_S"
    DL4J_TPU_FLEET_AGG_MAX_SAMPLES = "DL4J_TPU_FLEET_AGG_MAX_SAMPLES"
    XLA_FLAGS = "XLA_FLAGS"


class SystemProperties:
    """Programmatic property keys (ND4JSystemProperties analog)."""
    DTYPE = "dtype"
    DEBUG = "debug"
    VERBOSE = "verbose"
    MAX_THREADS = "max_threads"
    MATMUL_PRECISION = "matmul_precision"
    NAN_PANIC = "nan_panic"
    INF_PANIC = "inf_panic"
    PROFILING = "profiling"
    EAGER_JIT = "eager_jit"
    HOME = "home"
    LOCK_CHECK = "lock_check"
    RESOURCES_DIR = "resources_dir"
    LOG_INITIALIZATION = "log_initialization"
    CACHE_DIR = "cache_dir"
    CACHE_MAX_BYTES = "cache_max_bytes"
    REMOTE_CACHE = "remote_cache"
    CACHE_TIER = "cache_tier"
    XLA_CACHE = "xla_cache"
    WARMUP_THREADS = "warmup_threads"
    PAGED_KERNEL = "paged_kernel"
    FUSED_DEQUANT = "fused_dequant"
    INFERENCE_BUCKETING = "inference_bucketing"
    INFERENCE_MAX_BATCH = "inference_max_batch"
    DECODE_SLOTS = "decode_slots"
    DECODE_MAX_CTX = "decode_max_ctx"
    DECODE_MAX_TOKENS = "decode_max_tokens"
    KV_BLOCK_SIZE = "kv_block_size"
    SPEC_DRAFT_K = "spec_draft_k"
    PREFIX_CACHE = "prefix_cache"
    QUANT = "quant"
    QUANT_MAX_DIVERGENCE = "quant_max_divergence"
    QUANT_MIN_TOP1 = "quant_min_top1"
    TRAINING_REMAT = "training_remat"
    TRAINING_GRAD_ACCUM = "training_grad_accum"
    TRAINING_ZERO1 = "training_zero1"
    METRICS = "metrics"
    TRACE_BUFFER = "trace_buffer"
    SERVING_MAX_CONCURRENT = "serving_max_concurrent"
    SERVING_QUEUE_DEPTH = "serving_queue_depth"
    SERVING_HIGH_WATER = "serving_high_water"
    SERVING_TIMEOUT_S = "serving_timeout_s"
    SERVING_DRAIN_TIMEOUT_S = "serving_drain_timeout_s"
    SERVING_RETAIN = "serving_retain"
    SERVING_MANIFEST_DIR = "serving_manifest_dir"
    SLO_OBJECTIVE = "slo_objective"
    SLO_LATENCY_MS = "slo_latency_ms"
    SLO_WINDOWS = "slo_windows"
    SLO_READYZ = "slo_readyz"
    REQUEST_RING = "request_ring"
    DEBUG_ENDPOINTS = "debug_endpoints"
    FAULTS = "faults"
    BREAKER_THRESHOLD = "breaker_threshold"
    BREAKER_PROBE_S = "breaker_probe_s"
    AUTO_ROLLBACK = "auto_rollback"
    AUTO_ROLLBACK_OPENS = "auto_rollback_opens"
    ENGINE_MAX_RESTARTS = "engine_max_restarts"
    WATCHDOG_FACTOR = "watchdog_factor"
    PROFILE_DIR = "profile_dir"
    FLIGHT_RECORDER_DIR = "flight_recorder_dir"
    FLEET_POLL_S = "fleet_poll_s"
    FLEET_RETRIES = "fleet_retries"
    FLEET_TIMEOUT_S = "fleet_timeout_s"
    FLEET_RETRY_BUDGET = "fleet_retry_budget"
    FLEET_HEDGE_PCTL = "fleet_hedge_pctl"
    FLEET_BROWNOUT_FRAC = "fleet_brownout_frac"
    FLEET_DEFAULT_PRIORITY = "fleet_default_priority"
    FLEET_AGG_RETENTION_S = "fleet_agg_retention_s"
    FLEET_AGG_MAX_SAMPLES = "fleet_agg_max_samples"


_ENV_FOR_PROP = {
    # a tuple means "first name set wins" (legacy spellings trail)
    SystemProperties.DTYPE: (EnvironmentVars.DL4J_TPU_DEFAULT_DTYPE,
                             EnvironmentVars.DL4J_TPU_DTYPE),
    SystemProperties.DEBUG: EnvironmentVars.DL4J_TPU_DEBUG,
    SystemProperties.VERBOSE: EnvironmentVars.DL4J_TPU_VERBOSE,
    SystemProperties.MAX_THREADS: EnvironmentVars.DL4J_TPU_MAX_THREADS,
    SystemProperties.MATMUL_PRECISION:
        EnvironmentVars.DL4J_TPU_MATMUL_PRECISION,
    SystemProperties.NAN_PANIC: EnvironmentVars.DL4J_TPU_NAN_PANIC,
    SystemProperties.INF_PANIC: EnvironmentVars.DL4J_TPU_INF_PANIC,
    SystemProperties.PROFILING: EnvironmentVars.DL4J_TPU_PROFILING,
    SystemProperties.EAGER_JIT: EnvironmentVars.DL4J_TPU_EAGER_JIT,
    SystemProperties.HOME: EnvironmentVars.DL4J_TPU_HOME,
    SystemProperties.LOCK_CHECK: EnvironmentVars.DL4J_TPU_LOCK_CHECK,
    SystemProperties.RESOURCES_DIR: EnvironmentVars.ND4J_RESOURCES_DIR,
    SystemProperties.CACHE_DIR: EnvironmentVars.DL4J_TPU_CACHE_DIR,
    SystemProperties.CACHE_MAX_BYTES:
        EnvironmentVars.DL4J_TPU_CACHE_MAX_BYTES,
    SystemProperties.REMOTE_CACHE: EnvironmentVars.DL4J_TPU_REMOTE_CACHE,
    SystemProperties.CACHE_TIER: EnvironmentVars.DL4J_TPU_CACHE_TIER,
    SystemProperties.XLA_CACHE: EnvironmentVars.DL4J_TPU_XLA_CACHE,
    SystemProperties.WARMUP_THREADS: EnvironmentVars.DL4J_TPU_WARMUP_THREADS,
    SystemProperties.PAGED_KERNEL: EnvironmentVars.DL4J_TPU_PAGED_KERNEL,
    SystemProperties.FUSED_DEQUANT: EnvironmentVars.DL4J_TPU_FUSED_DEQUANT,
    SystemProperties.INFERENCE_BUCKETING:
        EnvironmentVars.DL4J_TPU_INFERENCE_BUCKETING,
    SystemProperties.INFERENCE_MAX_BATCH:
        EnvironmentVars.DL4J_TPU_INFERENCE_MAX_BATCH,
    SystemProperties.DECODE_SLOTS: EnvironmentVars.DL4J_TPU_DECODE_SLOTS,
    SystemProperties.DECODE_MAX_CTX: EnvironmentVars.DL4J_TPU_DECODE_MAX_CTX,
    SystemProperties.DECODE_MAX_TOKENS:
        EnvironmentVars.DL4J_TPU_DECODE_MAX_TOKENS,
    SystemProperties.KV_BLOCK_SIZE: EnvironmentVars.DL4J_TPU_KV_BLOCK_SIZE,
    SystemProperties.SPEC_DRAFT_K: EnvironmentVars.DL4J_TPU_SPEC_DRAFT_K,
    SystemProperties.PREFIX_CACHE: EnvironmentVars.DL4J_TPU_PREFIX_CACHE,
    SystemProperties.QUANT: EnvironmentVars.DL4J_TPU_QUANT,
    SystemProperties.QUANT_MAX_DIVERGENCE:
        EnvironmentVars.DL4J_TPU_QUANT_MAX_DIVERGENCE,
    SystemProperties.QUANT_MIN_TOP1:
        EnvironmentVars.DL4J_TPU_QUANT_MIN_TOP1,
    SystemProperties.TRAINING_REMAT: EnvironmentVars.DL4J_TPU_REMAT,
    SystemProperties.TRAINING_GRAD_ACCUM: EnvironmentVars.DL4J_TPU_GRAD_ACCUM,
    SystemProperties.TRAINING_ZERO1: EnvironmentVars.DL4J_TPU_ZERO1,
    SystemProperties.METRICS: EnvironmentVars.DL4J_TPU_METRICS,
    SystemProperties.TRACE_BUFFER: EnvironmentVars.DL4J_TPU_TRACE_BUFFER,
    SystemProperties.SERVING_MAX_CONCURRENT:
        EnvironmentVars.DL4J_TPU_SERVING_MAX_CONCURRENT,
    SystemProperties.SERVING_QUEUE_DEPTH:
        EnvironmentVars.DL4J_TPU_SERVING_QUEUE_DEPTH,
    SystemProperties.SERVING_HIGH_WATER:
        EnvironmentVars.DL4J_TPU_SERVING_HIGH_WATER,
    SystemProperties.SERVING_TIMEOUT_S:
        EnvironmentVars.DL4J_TPU_SERVING_TIMEOUT_S,
    SystemProperties.SERVING_DRAIN_TIMEOUT_S:
        EnvironmentVars.DL4J_TPU_SERVING_DRAIN_TIMEOUT_S,
    SystemProperties.SERVING_RETAIN:
        EnvironmentVars.DL4J_TPU_SERVING_RETAIN,
    SystemProperties.SERVING_MANIFEST_DIR:
        EnvironmentVars.DL4J_TPU_SERVING_MANIFEST_DIR,
    SystemProperties.SLO_OBJECTIVE: EnvironmentVars.DL4J_TPU_SLO_OBJECTIVE,
    SystemProperties.SLO_LATENCY_MS: EnvironmentVars.DL4J_TPU_SLO_LATENCY_MS,
    SystemProperties.SLO_WINDOWS: EnvironmentVars.DL4J_TPU_SLO_WINDOWS,
    SystemProperties.SLO_READYZ: EnvironmentVars.DL4J_TPU_SLO_READYZ,
    SystemProperties.REQUEST_RING: EnvironmentVars.DL4J_TPU_REQUEST_RING,
    SystemProperties.DEBUG_ENDPOINTS:
        EnvironmentVars.DL4J_TPU_DEBUG_ENDPOINTS,
    SystemProperties.FAULTS: EnvironmentVars.DL4J_TPU_FAULTS,
    SystemProperties.BREAKER_THRESHOLD:
        EnvironmentVars.DL4J_TPU_BREAKER_THRESHOLD,
    SystemProperties.BREAKER_PROBE_S:
        EnvironmentVars.DL4J_TPU_BREAKER_PROBE_S,
    SystemProperties.AUTO_ROLLBACK: EnvironmentVars.DL4J_TPU_AUTO_ROLLBACK,
    SystemProperties.AUTO_ROLLBACK_OPENS:
        EnvironmentVars.DL4J_TPU_AUTO_ROLLBACK_OPENS,
    SystemProperties.ENGINE_MAX_RESTARTS:
        EnvironmentVars.DL4J_TPU_ENGINE_MAX_RESTARTS,
    SystemProperties.WATCHDOG_FACTOR:
        EnvironmentVars.DL4J_TPU_WATCHDOG_FACTOR,
    SystemProperties.PROFILE_DIR: EnvironmentVars.DL4J_TPU_PROFILE_DIR,
    SystemProperties.FLIGHT_RECORDER_DIR:
        EnvironmentVars.DL4J_TPU_FLIGHT_RECORDER_DIR,
    SystemProperties.FLEET_POLL_S: EnvironmentVars.DL4J_TPU_FLEET_POLL_S,
    SystemProperties.FLEET_RETRIES: EnvironmentVars.DL4J_TPU_FLEET_RETRIES,
    SystemProperties.FLEET_TIMEOUT_S:
        EnvironmentVars.DL4J_TPU_FLEET_TIMEOUT_S,
    SystemProperties.FLEET_RETRY_BUDGET:
        EnvironmentVars.DL4J_TPU_FLEET_RETRY_BUDGET,
    SystemProperties.FLEET_HEDGE_PCTL:
        EnvironmentVars.DL4J_TPU_FLEET_HEDGE_PCTL,
    SystemProperties.FLEET_BROWNOUT_FRAC:
        EnvironmentVars.DL4J_TPU_FLEET_BROWNOUT_FRAC,
    SystemProperties.FLEET_DEFAULT_PRIORITY:
        EnvironmentVars.DL4J_TPU_FLEET_DEFAULT_PRIORITY,
    SystemProperties.FLEET_AGG_RETENTION_S:
        EnvironmentVars.DL4J_TPU_FLEET_AGG_RETENTION_S,
    SystemProperties.FLEET_AGG_MAX_SAMPLES:
        EnvironmentVars.DL4J_TPU_FLEET_AGG_MAX_SAMPLES,
}

_DEFAULTS = {
    SystemProperties.DTYPE: "float32",
    SystemProperties.DEBUG: "0",
    SystemProperties.VERBOSE: "0",
    SystemProperties.MATMUL_PRECISION: "default",
    SystemProperties.NAN_PANIC: "0",
    SystemProperties.INF_PANIC: "0",
    SystemProperties.PROFILING: "0",
    SystemProperties.EAGER_JIT: "1",
    SystemProperties.HOME: "~/.deeplearning4j_tpu",
    SystemProperties.LOCK_CHECK: "0",
    SystemProperties.LOG_INITIALIZATION: "1",
    SystemProperties.CACHE_DIR: "~/.cache/deeplearning4j_tpu",
    SystemProperties.CACHE_MAX_BYTES: str(2 << 30),  # 2 GiB
    SystemProperties.REMOTE_CACHE: "",  # no shared store by default
    SystemProperties.CACHE_TIER: "auto",
    SystemProperties.XLA_CACHE: "auto",
    SystemProperties.WARMUP_THREADS: "0",  # 0 = auto
    SystemProperties.PAGED_KERNEL: "auto",
    SystemProperties.FUSED_DEQUANT: "auto",
    SystemProperties.INFERENCE_BUCKETING: "1",
    SystemProperties.INFERENCE_MAX_BATCH: "128",
    SystemProperties.DECODE_SLOTS: "8",
    SystemProperties.DECODE_MAX_CTX: "256",
    SystemProperties.DECODE_MAX_TOKENS: "128",
    SystemProperties.PREFIX_CACHE: "1",
    SystemProperties.QUANT: "",            # "" = quantized deploys opt-in
    SystemProperties.QUANT_MAX_DIVERGENCE: "0.25",
    SystemProperties.QUANT_MIN_TOP1: "0.99",
    SystemProperties.TRAINING_REMAT: "none",
    SystemProperties.TRAINING_GRAD_ACCUM: "1",
    SystemProperties.TRAINING_ZERO1: "0",
    SystemProperties.METRICS: "1",
    SystemProperties.TRACE_BUFFER: "16384",
    SystemProperties.SERVING_MAX_CONCURRENT: "8",
    SystemProperties.SERVING_QUEUE_DEPTH: "64",
    SystemProperties.SERVING_HIGH_WATER: "0",  # 0 = auto (3/4 of queue)
    SystemProperties.SERVING_TIMEOUT_S: "30",
    SystemProperties.SERVING_DRAIN_TIMEOUT_S: "30",
    SystemProperties.SERVING_RETAIN: "2",
    SystemProperties.SERVING_MANIFEST_DIR: "",  # "" = <cache_dir>/manifests
    SystemProperties.SLO_OBJECTIVE: "0.999",
    SystemProperties.SLO_LATENCY_MS: "0",      # 0 = deadline-hit-rate only
    SystemProperties.SLO_WINDOWS: "300:14.4,3600:6",
    SystemProperties.SLO_READYZ: "1",
    SystemProperties.REQUEST_RING: "256",
    SystemProperties.DEBUG_ENDPOINTS: "1",
    SystemProperties.FAULTS: "",               # "" = no injection (prod)
    SystemProperties.BREAKER_THRESHOLD: "5",
    SystemProperties.BREAKER_PROBE_S: "1",
    SystemProperties.AUTO_ROLLBACK: "0",
    SystemProperties.AUTO_ROLLBACK_OPENS: "2",
    SystemProperties.ENGINE_MAX_RESTARTS: "5",
    SystemProperties.WATCHDOG_FACTOR: "3",
    SystemProperties.PROFILE_DIR: "",          # "" = <cache_dir>/profiles
    SystemProperties.FLIGHT_RECORDER_DIR: "",  # "" = <cache_dir>/flight
    SystemProperties.FLEET_POLL_S: "2.0",
    SystemProperties.FLEET_RETRIES: "1",
    SystemProperties.FLEET_TIMEOUT_S: "30.0",
    SystemProperties.FLEET_RETRY_BUDGET: "0.2",
    SystemProperties.FLEET_HEDGE_PCTL: "95",
    SystemProperties.FLEET_BROWNOUT_FRAC: "0.5",
    SystemProperties.FLEET_DEFAULT_PRIORITY: "5",
    SystemProperties.FLEET_AGG_RETENTION_S: "600",
    SystemProperties.FLEET_AGG_MAX_SAMPLES: "512",
}


class Environment:
    """Runtime config singleton (reference Nd4j.getEnvironment() /
    sd::Environment). Resolution order: programmatic set > env var >
    default."""

    _instance = None
    _lock = threading.Lock()

    def __init__(self):
        self._overrides: Dict[str, str] = {}
        self._compile_lock = threading.Lock()
        self._compile_keys: set = set()
        self._compile_count = 0
        self._compile_listeners: list = []
        self._listener_errors_logged: set = set()

    @classmethod
    def get(cls) -> "Environment":
        if cls._instance is None:
            with cls._lock:
                if cls._instance is None:
                    cls._instance = Environment()
        return cls._instance

    # -- layered property resolution --------------------------------------
    def property(self, key: str, default: Optional[str] = None) -> Optional[str]:
        if key in self._overrides:
            return self._overrides[key]
        env_names = _ENV_FOR_PROP.get(key) or ()
        if isinstance(env_names, str):
            env_names = (env_names,)
        for env_name in env_names:
            if env_name in os.environ:
                return os.environ[env_name]
        return _DEFAULTS.get(key, default)

    def set_property(self, key: str, value: Any):
        self._overrides[key] = str(value)
        if key == SystemProperties.MATMUL_PRECISION:
            self._apply_matmul_precision(str(value))
        return self

    def property_override(self, key: str) -> Optional[str]:
        """The programmatic override for `key`, or None when the value
        resolves from the env var / default layers (lets callers save and
        faithfully restore a property around a scoped change)."""
        return self._overrides.get(key)

    def clear_property(self, key: str):
        """Drop a programmatic override, re-exposing env var/default."""
        self._overrides.pop(key, None)
        return self

    # -- reference Environment getters ------------------------------------
    def is_debug(self) -> bool:
        return self.property(SystemProperties.DEBUG) not in ("0", "false",
                                                             None)

    def is_verbose(self) -> bool:
        return self.property(SystemProperties.VERBOSE) not in ("0", "false",
                                                               None)

    def set_debug(self, v: bool):
        return self.set_property(SystemProperties.DEBUG, "1" if v else "0")

    def set_verbose(self, v: bool):
        return self.set_property(SystemProperties.VERBOSE, "1" if v else "0")

    def max_threads(self) -> int:
        v = self.property(SystemProperties.MAX_THREADS)
        return int(v) if v else os.cpu_count() or 1

    def default_float_dtype(self) -> str:
        return self.property(SystemProperties.DTYPE)

    def set_default_float_dtype(self, dtype: str):
        return self.set_property(SystemProperties.DTYPE, dtype)

    def matmul_precision(self) -> str:
        return self.property(SystemProperties.MATMUL_PRECISION)

    def _flag(self, key: str) -> bool:
        return self.property(key) not in ("0", "false", "", None)

    def nan_panic(self) -> bool:
        """Halt on NaN outputs (reference OpExecutioner.ProfilingMode)."""
        return self._flag(SystemProperties.NAN_PANIC)

    def inf_panic(self) -> bool:
        return self._flag(SystemProperties.INF_PANIC)

    def profiling_enabled(self) -> bool:
        """Op-level profiling collection (DL4J_TPU_PROFILING)."""
        return self._flag(SystemProperties.PROFILING)

    def eager_jit(self) -> bool:
        """Per-op jit cache for the eager executioner
        (DL4J_TPU_EAGER_JIT, on by default)."""
        return self._flag(SystemProperties.EAGER_JIT)

    def home_dir(self) -> str:
        """Root of user-local artifacts — pretrained model cache etc.
        (``DL4J_TPU_HOME``, default ``~/.deeplearning4j_tpu``)."""
        return os.path.expanduser(
            self.property(SystemProperties.HOME) or "~/.deeplearning4j_tpu")

    def lock_check(self) -> bool:
        """Whether the ``common.locks`` runtime lock-order tracker is
        armed (``DL4J_TPU_LOCK_CHECK``; the tracker itself caches this
        at import — flip at runtime via ``locks.set_lock_check``)."""
        return self._flag(SystemProperties.LOCK_CHECK)

    # -- AOT compile cache (runtime/compile_cache.py) ----------------------
    def cache_dir(self) -> Optional[str]:
        """Root of the persistent executable cache, expanded; None when
        caching is disabled (``DL4J_TPU_CACHE_DIR=""``)."""
        d = self.property(SystemProperties.CACHE_DIR)
        if not d:
            return None
        return os.path.expanduser(d)

    def set_cache_dir(self, d: Optional[str]):
        """Programmatic override; "" or None disables all caching."""
        return self.set_property(SystemProperties.CACHE_DIR, d or "")

    def cache_max_bytes(self) -> int:
        """LRU size cap for the executable store
        (``DL4J_TPU_CACHE_MAX_BYTES``); <= 0 means uncapped."""
        v = self.property(SystemProperties.CACHE_MAX_BYTES)
        try:
            return int(v)
        except (TypeError, ValueError):
            return 2 << 30

    def remote_cache(self) -> Optional[str]:
        """Root of the fleet-shared artifact store, expanded
        (``DL4J_TPU_REMOTE_CACHE`` — typically an NFS/FUSE-mounted
        bucket); None when no shared store is configured (the
        default)."""
        d = self.property(SystemProperties.REMOTE_CACHE)
        if not d:
            return None
        return os.path.expanduser(d)

    def set_remote_cache(self, d: Optional[str]):
        """Programmatic override; "" or None disables the shared store."""
        return self.set_property(SystemProperties.REMOTE_CACHE, d or "")

    def cache_tier(self) -> str:
        """Store-tier policy (``DL4J_TPU_CACHE_TIER``): "auto" (default)
        tiers local+remote when ``DL4J_TPU_REMOTE_CACHE`` is set and is
        plain local otherwise; "local"/"remote"/"tiered" force a layout.
        Anything unrecognized falls back to "auto"."""
        v = str(self.property(SystemProperties.CACHE_TIER) or "auto").lower()
        return v if v in ("auto", "local", "remote", "tiered") else "auto"

    def set_cache_tier(self, tier: Optional[str]):
        """Programmatic override; None restores "auto"."""
        return self.set_property(SystemProperties.CACHE_TIER,
                                 tier or "auto")

    def xla_cache(self) -> str:
        """Policy for the ``jax_compilation_cache_dir`` backstop
        (``DL4J_TPU_XLA_CACHE``): "auto" (default) enables it on
        accelerator backends only — on the CPU backend the raw executable
        store already covers serving-shaped entries, and XLA:CPU
        executables deserialized from jax's persistent cache proved
        unstable under churn (nondeterministic aborts in donated train
        steps mid-suite); "on"/"off" force either way."""
        v = str(self.property(SystemProperties.XLA_CACHE) or "auto").lower()
        return v if v in ("auto", "on", "off") else "auto"

    def warmup_threads(self) -> int:
        """Thread-pool width for InferenceEngine.warmup(); 0 = auto
        (bounded by bucket count and host CPUs)."""
        v = self.property(SystemProperties.WARMUP_THREADS)
        try:
            return max(int(v), 0)
        except (TypeError, ValueError):
            return 0

    # -- kernel policies (kernels/__init__.py) ---------------------------
    def paged_kernel(self) -> str:
        """Policy for the Pallas paged-flash decode kernel
        (``DL4J_TPU_PAGED_KERNEL``): "auto" (default) runs it on
        accelerator backends when the paged KV layout tiles natively
        (``kernels.paged_flash_decode.tileable``) and keeps the XLA
        block-table gather path otherwise; "on" forces the kernel
        everywhere (interpret mode off-accelerator — the token-identity
        test/debug hook); "off" pins the gather path. Evaluated at trace
        time by ``kernels.attention_dispatch``, so flipping it only
        affects executables compiled afterwards."""
        v = str(self.property(SystemProperties.PAGED_KERNEL)
                or "auto").lower()
        return v if v in ("auto", "on", "off") else "auto"

    def set_paged_kernel(self, mode: Optional[str]):
        """Programmatic override; None restores "auto"."""
        return self.set_property(SystemProperties.PAGED_KERNEL,
                                 mode or "auto")

    def fused_dequant(self) -> str:
        """Policy for the Pallas fused int8 dequant-matmul
        (``DL4J_TPU_FUSED_DEQUANT``): "auto" (default) fuses on
        accelerator backends when the weight tiles natively (K and N
        multiples of 128) and falls back to the XLA
        cast-then-``dot`` contraction otherwise; "on" forces the kernel
        everywhere (interpret mode off-accelerator); "off" pins the XLA
        path. Trace-time, like ``paged_kernel``."""
        v = str(self.property(SystemProperties.FUSED_DEQUANT)
                or "auto").lower()
        return v if v in ("auto", "on", "off") else "auto"

    def set_fused_dequant(self, mode: Optional[str]):
        """Programmatic override; None restores "auto"."""
        return self.set_property(SystemProperties.FUSED_DEQUANT,
                                 mode or "auto")

    # -- inference-serving knobs (runtime/inference.py) --------------------
    def inference_bucketing(self) -> bool:
        """Whether batched inference pads the batch dim up to a compiled
        bucket shape (on by default; exact-shape compile when off)."""
        return self.property(SystemProperties.INFERENCE_BUCKETING) not in (
            "0", "false", None)

    def set_inference_bucketing(self, v: bool):
        return self.set_property(SystemProperties.INFERENCE_BUCKETING,
                                 "1" if v else "0")

    def inference_max_batch(self) -> int:
        """Top rung of the default bucket ladder for the direct
        output()/predict() paths."""
        v = self.property(SystemProperties.INFERENCE_MAX_BATCH)
        return int(v) if v else 128

    def set_inference_max_batch(self, n: int):
        return self.set_property(SystemProperties.INFERENCE_MAX_BATCH, int(n))

    # -- generative decode knobs (runtime/generation.py) -------------------
    def decode_slots(self) -> int:
        """Concurrent sequences a DecodeEngine's KV cache holds — the
        continuous-batching width (``DL4J_TPU_DECODE_SLOTS``)."""
        v = self.property(SystemProperties.DECODE_SLOTS)
        try:
            return max(int(v), 1)
        except (TypeError, ValueError):
            return 8

    def set_decode_slots(self, n: int):
        return self.set_property(SystemProperties.DECODE_SLOTS, int(n))

    def decode_max_ctx(self) -> int:
        """Per-sequence context window (prompt + generation) of the
        preallocated KV cache (``DL4J_TPU_DECODE_MAX_CTX``; capped by the
        model's position-embedding table)."""
        v = self.property(SystemProperties.DECODE_MAX_CTX)
        try:
            return max(int(v), 2)
        except (TypeError, ValueError):
            return 256

    def set_decode_max_ctx(self, n: int):
        return self.set_property(SystemProperties.DECODE_MAX_CTX, int(n))

    def decode_max_tokens(self) -> int:
        """Default/maximum generated tokens per request when the caller
        does not pass ``max_tokens`` (``DL4J_TPU_DECODE_MAX_TOKENS``;
        always additionally capped by the slot's remaining context)."""
        v = self.property(SystemProperties.DECODE_MAX_TOKENS)
        try:
            return max(int(v), 1)
        except (TypeError, ValueError):
            return 128

    def set_decode_max_tokens(self, n: int):
        return self.set_property(SystemProperties.DECODE_MAX_TOKENS, int(n))

    def kv_block_size(self) -> int:
        """Rows per KV-cache block of the paged decode cache
        (``DL4J_TPU_KV_BLOCK_SIZE``). A sequence holds
        ``ceil(len/block_size)`` blocks instead of reserving ``max_ctx``
        rows; engines clamp the value to their context window, so
        setting it >= max_ctx reproduces the legacy slab layout."""
        v = self.property(SystemProperties.KV_BLOCK_SIZE)
        try:
            return max(int(v), 1)
        except (TypeError, ValueError):
            return 16

    def set_kv_block_size(self, n: int):
        return self.set_property(SystemProperties.KV_BLOCK_SIZE, int(n))

    def spec_draft_k(self) -> int:
        """Draft tokens proposed per speculative-decoding step
        (``DL4J_TPU_SPEC_DRAFT_K``). 0 (default) disables speculation;
        an engine additionally needs a ``draft_model`` to speculate."""
        v = self.property(SystemProperties.SPEC_DRAFT_K)
        try:
            return max(int(v), 0)
        except (TypeError, ValueError):
            return 0

    def set_spec_draft_k(self, n: int):
        return self.set_property(SystemProperties.SPEC_DRAFT_K, int(n))

    def prefix_cache_enabled(self) -> bool:
        """Whether DecodeEngine content-addresses KV blocks by token
        prefix and reuses them across requests/turns
        (``DL4J_TPU_PREFIX_CACHE``, on by default; greedy output is
        token-identical either way — disable only to reproduce
        cold-prefill timing)."""
        return self.property(SystemProperties.PREFIX_CACHE) not in (
            "0", "false", "off", None)

    def set_prefix_cache(self, v: bool):
        return self.set_property(SystemProperties.PREFIX_CACHE,
                                 "1" if v else "0")

    # -- quantized-serving knobs (quant/, serving/registry.py) -------------
    def quant_mode(self) -> str:
        """Fleet default for ``ModelRegistry.deploy(quantize=None)``:
        "" (off — quantized deploys are per-deploy opt-in), "int8" or
        "fp8" (``DL4J_TPU_QUANT``; truthy spellings map to int8)."""
        v = (self.property(SystemProperties.QUANT) or "").strip().lower()
        if v in ("", "0", "off", "none", "false"):
            return ""
        if v in ("1", "true", "on"):
            return "int8"
        return v

    def set_quant_mode(self, mode: str):
        return self.set_property(SystemProperties.QUANT, mode or "")

    def quant_max_divergence(self) -> float:
        """Divergence-gate budget: max allowed logit abs error of a
        quantized twin vs its full-precision original on the calibration
        batch (``DL4J_TPU_QUANT_MAX_DIVERGENCE``)."""
        v = self.property(SystemProperties.QUANT_MAX_DIVERGENCE)
        try:
            return max(float(v), 0.0)
        except (TypeError, ValueError):
            return 0.25

    def set_quant_max_divergence(self, v: float):
        return self.set_property(SystemProperties.QUANT_MAX_DIVERGENCE,
                                 float(v))

    def quant_min_top1(self) -> float:
        """Divergence-gate floor on top-1 (and per-token, for generative
        models) agreement with the full-precision original
        (``DL4J_TPU_QUANT_MIN_TOP1``)."""
        v = self.property(SystemProperties.QUANT_MIN_TOP1)
        try:
            return min(max(float(v), 0.0), 1.0)
        except (TypeError, ValueError):
            return 0.99

    def set_quant_min_top1(self, v: float):
        return self.set_property(SystemProperties.QUANT_MIN_TOP1, float(v))

    # -- memory-scaled training knobs (nn/fit_fastpath.py, parallel) -------
    # Fleet-wide defaults; an explicit per-network conf.remat / conf.grad_accum
    # always wins (the conf fields default to "unset", which resolves here).

    def training_remat(self) -> str:
        """Default activation-rematerialization policy for training steps:
        "none" | "layer" | "dots_saveable"."""
        return self.property(SystemProperties.TRAINING_REMAT) or "none"

    def set_training_remat(self, mode: str):
        return self.set_property(SystemProperties.TRAINING_REMAT, mode)

    def training_grad_accum(self) -> int:
        """Default gradient-accumulation factor (micro-batches per optimizer
        step) when a network conf leaves grad_accum unset."""
        v = self.property(SystemProperties.TRAINING_GRAD_ACCUM)
        return max(int(v), 1) if v else 1

    def set_training_grad_accum(self, k: int):
        return self.set_property(SystemProperties.TRAINING_GRAD_ACCUM, int(k))

    def training_zero1(self) -> bool:
        """Default for ParallelWrapper's ZeRO-1 optimizer-state sharding."""
        return self.property(SystemProperties.TRAINING_ZERO1) not in (
            "0", "false", None)

    def set_training_zero1(self, v: bool):
        return self.set_property(SystemProperties.TRAINING_ZERO1,
                                 "1" if v else "0")

    # -- model serving knobs (serving/) ------------------------------------

    def serving_max_concurrent(self) -> int:
        """Per-model concurrent-dispatch limit for the admission
        controller (``DL4J_TPU_SERVING_MAX_CONCURRENT``)."""
        v = self.property(SystemProperties.SERVING_MAX_CONCURRENT)
        try:
            return max(int(v), 1)
        except (TypeError, ValueError):
            return 8

    def serving_queue_depth(self) -> int:
        """Hard bound on requests waiting for a dispatch slot per model
        (``DL4J_TPU_SERVING_QUEUE_DEPTH``); arrivals beyond it shed."""
        v = self.property(SystemProperties.SERVING_QUEUE_DEPTH)
        try:
            return max(int(v), 1)
        except (TypeError, ValueError):
            return 64

    def serving_high_water(self) -> int:
        """Queue depth at which load shedding engages
        (``DL4J_TPU_SERVING_HIGH_WATER``); <= 0 resolves to 3/4 of
        ``serving_queue_depth`` (shed before the hard bound so retried
        requests see headroom)."""
        v = self.property(SystemProperties.SERVING_HIGH_WATER)
        try:
            hw = int(v)
        except (TypeError, ValueError):
            hw = 0
        if hw <= 0:
            hw = max(1, (3 * self.serving_queue_depth()) // 4)
        return hw

    def serving_default_timeout_s(self) -> Optional[float]:
        """Default per-request deadline budget in seconds
        (``DL4J_TPU_SERVING_TIMEOUT_S``); <= 0 means no deadline."""
        v = self.property(SystemProperties.SERVING_TIMEOUT_S)
        try:
            t = float(v)
        except (TypeError, ValueError):
            t = 30.0
        return t if t > 0 else None

    def serving_drain_timeout_s(self) -> float:
        """How long graceful drain waits for in-flight work
        (``DL4J_TPU_SERVING_DRAIN_TIMEOUT_S``)."""
        v = self.property(SystemProperties.SERVING_DRAIN_TIMEOUT_S)
        try:
            return max(float(v), 0.0)
        except (TypeError, ValueError):
            return 30.0

    def serving_retain(self) -> int:
        """Previous model versions the registry keeps warm for rollback
        (``DL4J_TPU_SERVING_RETAIN``)."""
        v = self.property(SystemProperties.SERVING_RETAIN)
        try:
            return max(int(v), 0)
        except (TypeError, ValueError):
            return 2

    def serving_manifest_dir(self) -> Optional[str]:
        """Explicit warmup-manifest directory override
        (``DL4J_TPU_SERVING_MANIFEST_DIR``); None/"" defers to
        ``runtime.compile_cache.serving_manifest_dir`` (defaults under
        the executable cache dir)."""
        d = self.property(SystemProperties.SERVING_MANIFEST_DIR)
        return os.path.expanduser(d) if d else None

    # -- SLO / debug-observability knobs (serving/slo.py, /debug/*) --------

    def slo_objective(self) -> float:
        """Per-model success-rate objective (``DL4J_TPU_SLO_OBJECTIVE``,
        default 0.999): the fraction of served requests that must
        complete OK (and within the latency objective, when one is
        set)."""
        v = self.property(SystemProperties.SLO_OBJECTIVE)
        try:
            obj = float(v)
        except (TypeError, ValueError):
            obj = 0.999
        return min(max(obj, 0.0), 0.999999)

    def slo_latency_s(self) -> Optional[float]:
        """Optional per-request latency objective in seconds
        (``DL4J_TPU_SLO_LATENCY_MS``); <= 0 (default) means only
        deadline misses / errors count against the SLO."""
        v = self.property(SystemProperties.SLO_LATENCY_MS)
        try:
            ms = float(v)
        except (TypeError, ValueError):
            ms = 0.0
        return ms / 1e3 if ms > 0 else None

    def slo_windows(self):
        """Multi-window burn-rate alert policy
        (``DL4J_TPU_SLO_WINDOWS`` = ``"<seconds>:<burn>,..."``, default
        ``300:14.4,3600:6`` — the SRE-workbook fast-burn pair). Returns
        ((window_s, burn_threshold), ...) sorted short-to-long."""
        v = self.property(SystemProperties.SLO_WINDOWS) or ""
        out = []
        for part in v.split(","):
            if ":" not in part:
                continue
            w, b = part.split(":", 1)
            try:
                out.append((float(w), float(b)))
            except ValueError:
                continue
        if not out:
            out = [(300.0, 14.4), (3600.0, 6.0)]
        return tuple(sorted(out))

    def slo_gate_readyz(self) -> bool:
        """Whether a fast-burning SLO flips ``/readyz`` to 503
        (``DL4J_TPU_SLO_READYZ``, on by default) so the load balancer
        stops routing to a replica that is torching its error budget."""
        return self.property(SystemProperties.SLO_READYZ) not in (
            "0", "false", None)

    def request_ring_size(self) -> int:
        """Capacity of the serving recent-requests ring behind
        ``/debug/requests`` and the flight recorder
        (``DL4J_TPU_REQUEST_RING``)."""
        v = self.property(SystemProperties.REQUEST_RING)
        try:
            return max(int(v), 1)
        except (TypeError, ValueError):
            return 256

    def debug_endpoints_enabled(self) -> bool:
        """Whether the ``/debug/*`` endpoint family is served
        (``DL4J_TPU_DEBUG_ENDPOINTS``, on by default — turn off on
        internet-facing deployments)."""
        return self.property(SystemProperties.DEBUG_ENDPOINTS) not in (
            "0", "false", None)

    def profile_dir(self) -> str:
        """Where ``/debug/profile`` captures land
        (``DL4J_TPU_PROFILE_DIR``); defaults under the executable cache
        dir, falling back to the system tmpdir when caching is off."""
        d = self.property(SystemProperties.PROFILE_DIR)
        if d:
            return os.path.expanduser(d)
        base = self.cache_dir()
        if base:
            return os.path.join(base, "profiles")
        import tempfile
        return os.path.join(tempfile.gettempdir(), "dl4j_tpu_profiles")

    def flight_recorder_dir(self) -> Optional[str]:
        """Where SIGTERM/SIGQUIT flight-recorder dumps land
        (``DL4J_TPU_FLIGHT_RECORDER_DIR``); defaults under the
        executable cache dir; None (recorder disabled) when that is off
        and no explicit dir is set."""
        d = self.property(SystemProperties.FLIGHT_RECORDER_DIR)
        if d:
            return os.path.expanduser(d)
        base = self.cache_dir()
        return os.path.join(base, "flight") if base else None

    # -- resilience knobs (common/faults.py, serving/resilience.py) --------

    def faults_spec(self) -> str:
        """Raw fault-injection spec (``DL4J_TPU_FAULTS`` =
        ``"site:kind:rate:seed,..."``); "" (default) = no injection and
        zero overhead at every site."""
        return self.property(SystemProperties.FAULTS) or ""

    def breaker_threshold(self) -> int:
        """Consecutive dispatch failures that open a model-version's
        circuit breaker (``DL4J_TPU_BREAKER_THRESHOLD``)."""
        v = self.property(SystemProperties.BREAKER_THRESHOLD)
        try:
            return max(int(v), 1)
        except (TypeError, ValueError):
            return 5

    def breaker_probe_s(self) -> float:
        """How long an open breaker fails fast before letting one
        half-open probe through (``DL4J_TPU_BREAKER_PROBE_S``)."""
        v = self.property(SystemProperties.BREAKER_PROBE_S)
        try:
            return max(float(v), 0.001)
        except (TypeError, ValueError):
            return 1.0

    def auto_rollback(self) -> bool:
        """Whether a persistently open breaker with a warm parked
        previous version triggers ``ModelRegistry.rollback()``
        (``DL4J_TPU_AUTO_ROLLBACK``, off by default — degraded service
        beats no service, but changing the served version is an operator
        decision until opted in)."""
        return self.property(SystemProperties.AUTO_ROLLBACK) not in (
            "0", "false", None)

    def set_auto_rollback(self, v: bool):
        return self.set_property(SystemProperties.AUTO_ROLLBACK,
                                 "1" if v else "0")

    def auto_rollback_opens(self) -> int:
        """Consecutive breaker opens (open -> probe fails -> reopen)
        that count as "persistently open" for auto-rollback
        (``DL4J_TPU_AUTO_ROLLBACK_OPENS``)."""
        v = self.property(SystemProperties.AUTO_ROLLBACK_OPENS)
        try:
            return max(int(v), 1)
        except (TypeError, ValueError):
            return 2

    def engine_max_restarts(self) -> int:
        """Supervised-restart burst budget for engine worker threads
        (``DL4J_TPU_ENGINE_MAX_RESTARTS``); <= 0 = unbounded. The budget
        covers crash *bursts* — it resets after a healthy minute."""
        v = self.property(SystemProperties.ENGINE_MAX_RESTARTS)
        try:
            return int(v)
        except (TypeError, ValueError):
            return 5

    def watchdog_factor(self) -> float:
        """Dispatch-watchdog budget as a multiple of the default serving
        deadline (``DL4J_TPU_WATCHDOG_FACTOR``): a dispatch stuck past
        ``deadline * factor`` marks its engine unhealthy and flips
        ``/readyz``. <= 0 disables the watchdog."""
        v = self.property(SystemProperties.WATCHDOG_FACTOR)
        try:
            return float(v)
        except (TypeError, ValueError):
            return 3.0

    # -- fleet routing (serving/fleet) -------------------------------------
    def fleet_poll_s(self) -> float:
        """FleetRouter replica-poll interval in seconds
        (``DL4J_TPU_FLEET_POLL_S``): how often each replica's
        ``/readyz`` + ``/metrics.json`` are refreshed for the
        least-loaded score."""
        v = self.property(SystemProperties.FLEET_POLL_S)
        try:
            return max(float(v), 0.05)
        except (TypeError, ValueError):
            return 2.0

    def fleet_retries(self) -> int:
        """Failover retries the router makes on a *different* replica
        after a replica-level failure — 503 / connection refused / timeout
        (``DL4J_TPU_FLEET_RETRIES``)."""
        v = self.property(SystemProperties.FLEET_RETRIES)
        try:
            return max(int(v), 0)
        except (TypeError, ValueError):
            return 1

    def fleet_timeout_s(self) -> float:
        """Per-attempt HTTP timeout for routed requests
        (``DL4J_TPU_FLEET_TIMEOUT_S``)."""
        v = self.property(SystemProperties.FLEET_TIMEOUT_S)
        try:
            return max(float(v), 0.1)
        except (TypeError, ValueError):
            return 30.0

    def fleet_retry_budget(self) -> float:
        """Fleet retry-budget ratio (``DL4J_TPU_FLEET_RETRY_BUDGET``):
        failovers + hedges may add at most this fraction of recent
        primary dispatches on top of the offered load. 0 disables every
        extra dispatch — one request, one attempt."""
        v = self.property(SystemProperties.FLEET_RETRY_BUDGET)
        try:
            return min(max(float(v), 0.0), 1.0)
        except (TypeError, ValueError):
            return 0.2

    def fleet_hedge_pctl(self) -> float:
        """Latency percentile of the router's observed per-model
        dispatch latencies used as the hedge delay
        (``DL4J_TPU_FLEET_HEDGE_PCTL``): an idempotent request still
        unanswered past that percentile gets a second, budgeted attempt
        on a different replica. <= 0 disables hedging."""
        v = self.property(SystemProperties.FLEET_HEDGE_PCTL)
        try:
            return min(float(v), 100.0)
        except (TypeError, ValueError):
            return 95.0

    def fleet_brownout_frac(self) -> float:
        """Ready-capacity fraction below which the fleet front door
        browns out (``DL4J_TPU_FLEET_BROWNOUT_FRAC``): lowest-priority
        traffic is shed first and forwarded deadlines tighten. <= 0
        disables brownout."""
        v = self.property(SystemProperties.FLEET_BROWNOUT_FRAC)
        try:
            return min(max(float(v), 0.0), 1.0)
        except (TypeError, ValueError):
            return 0.5

    def fleet_default_priority(self) -> int:
        """Priority assumed for requests without an ``X-Priority``
        header (``DL4J_TPU_FLEET_DEFAULT_PRIORITY``), clamped to
        [0, 9]; 9 is most important and shed last during brownout."""
        v = self.property(SystemProperties.FLEET_DEFAULT_PRIORITY)
        try:
            return min(max(int(v), 0), 9)
        except (TypeError, ValueError):
            return 5

    def fleet_agg_retention_s(self) -> float:
        """How long the fleet metrics aggregator's in-memory signal
        ring retains scraped autoscaler samples, in seconds
        (``DL4J_TPU_FLEET_AGG_RETENTION_S``)."""
        v = self.property(SystemProperties.FLEET_AGG_RETENTION_S)
        try:
            return max(float(v), 1.0)
        except (TypeError, ValueError):
            return 600.0

    def fleet_agg_max_samples(self) -> int:
        """Hard cap on samples in the aggregator's signal ring
        (``DL4J_TPU_FLEET_AGG_MAX_SAMPLES``) — the bound that holds
        even when a short poll interval outruns the retention window."""
        v = self.property(SystemProperties.FLEET_AGG_MAX_SAMPLES)
        try:
            return max(int(v), 1)
        except (TypeError, ValueError):
            return 512

    # -- telemetry (common/metrics.py, common/tracing.py) ------------------
    def metrics(self):
        """The process-wide MetricsRegistry (DL4J_TPU_METRICS gates all
        instrumentation writes; see `common.metrics.registry`)."""
        from .metrics import registry
        return registry()

    def metrics_enabled(self) -> bool:
        return self.metrics().enabled

    def set_metrics_enabled(self, v: bool):
        self.set_property(SystemProperties.METRICS, "1" if v else "0")
        self.metrics().set_enabled(v)
        return self

    def trace_buffer(self) -> int:
        """Span ring-buffer capacity (DL4J_TPU_TRACE_BUFFER)."""
        v = self.property(SystemProperties.TRACE_BUFFER)
        return int(v) if v else 16384

    # -- recompile observability ------------------------------------------
    # One "compile event" = one new (tag, input-signature) entry entering a
    # jitted-inference cache (runtime.inference.counted_jit). With bucketing
    # on, K distinct request batch sizes must produce at most
    # ceil(log2(max_batch)) + 1 events per network — the invariant bench.py
    # and tests/test_inference_engine.py assert.

    def record_compile(self, key, cache: str = "bypass") -> bool:
        """Register a compile event; returns False if `key` was already
        seen (in-process signature already materialized). New keys notify
        compile listeners and bump the `dl4j_compiles_total` metric,
        labeled by tag kind and AOT-cache outcome (``cache=hit`` means the
        executable was loaded from the persistent store and XLA never
        actually ran — the event still counts one executable
        materialization, which is what the bucket-ladder invariants
        assert)."""
        with self._compile_lock:
            if key in self._compile_keys:
                return False
            self._compile_keys.add(key)
            self._compile_count += 1
            listeners = list(self._compile_listeners)
        try:
            from .metrics import registry
            kind = key[0] if isinstance(key, (tuple, list)) and key else key
            registry().counter(
                "dl4j_compiles_total",
                "Executable materializations recorded by counted_jit",
                labels=("kind", "cache")).labels(
                    kind=str(kind).split(":")[0], cache=cache).inc()
        except Exception:
            pass  # observability must never break the inference path
        for fn in listeners:
            try:
                fn(key)
            except Exception:
                # swallowed so a bad listener can't break serving — but
                # under is_debug(), surface it once per listener
                if self.is_debug() and id(fn) not in \
                        self._listener_errors_logged:
                    self._listener_errors_logged.add(id(fn))
                    import logging
                    logging.getLogger(__name__).exception(
                        "compile listener %r raised (logged once; further "
                        "exceptions from this listener are dropped)", fn)
        return True

    def compile_count(self) -> int:
        return self._compile_count

    def reset_compile_count(self):
        """Zero the counter and key registry. Signatures already resident
        in a live jit cache will NOT re-record afterwards — no XLA compile
        actually happens for them, and the counter reports real compiles."""
        with self._compile_lock:
            self._compile_keys.clear()
            self._compile_count = 0
        return self

    def add_compile_listener(self, fn: Callable[[Any], None]):
        """`fn(key)` is invoked once per new compile event."""
        with self._compile_lock:
            self._compile_listeners.append(fn)
        return self

    def remove_compile_listener(self, fn: Callable[[Any], None]):
        with self._compile_lock:
            if fn in self._compile_listeners:
                self._compile_listeners.remove(fn)
        return self

    def _apply_matmul_precision(self, precision: str):
        """highest = f32 accumulate everywhere (reference "allowed precision
        boost" knob inverted for TPU: bf16 passes are the default)."""
        import jax
        if precision in ("default", "bfloat16", "fastest"):
            jax.config.update("jax_default_matmul_precision", "default")
        elif precision in ("float32", "highest"):
            jax.config.update("jax_default_matmul_precision", "highest")
        elif precision in ("tensorfloat32", "high"):
            jax.config.update("jax_default_matmul_precision", "high")

    # -- device introspection (reference Environment memory getters) ------
    def backend(self) -> str:
        import jax
        return jax.default_backend()

    def num_devices(self) -> int:
        import jax
        return jax.device_count()

    def memory_stats(self) -> Dict[str, int]:
        import jax
        dev = jax.devices()[0]
        stats = getattr(dev, "memory_stats", lambda: None)()
        return dict(stats) if stats else {}


def environment() -> Environment:
    return Environment.get()
