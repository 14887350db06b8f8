"""AST checkers DL101–DL104 (DL105 lives in ``lockgraph.py``).

Each checker is a pure function over one parsed :class:`~.Module`; the
driver in ``__init__.py`` concatenates their findings and applies the
baseline. Checkers are deliberately *syntactic* — they encode the
framework's conventions, not a type system — so every rule documents its
known false-positive guards and the baseline carries the rest.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, Iterator, List, Optional, Set

from . import Finding, Module, PACKAGE_ROOT

#: label keys metric families may use — the bounded-cardinality contract
#: (DL104). Every key here is either a closed enum (kind/cache/outcome/
#: reason/state/good/window/path/site/engine/mode/tier/priority/slo —
#: mode is the quantization storage format, int8|fp8; tier is the
#: artifact-store layer, local|remote; priority is the X-Priority
#: request class, the ten values "0".."9"; slo is the goodput split on
#: ``dl4j_tokens_total``, ok|violated; outcome enums are per-family,
#: e.g. the router dispatch set and the session-affinity pair
#: hit|fallback on ``dl4j_fleet_affinity_total``; kernel is the
#: hand-written-kernel family on ``dl4j_kernel_dispatch_total`` —
#: attention|paged_decode|dequant_matmul — and the streaming flash pass,
#: fwd|dq|dkv, on ``dl4j_flash_tiles_total``, whose kind is
#: computed|skipped; op is the fused Mamba-2 operation, conv_silu|gate_norm,
#: on ``dl4j_ssm_fused_calls_total``, whose kind is fwd|bwd, as is
#: ``dl4j_ssm_scan_passes_total``'s; phase is trace|lower|compile on
#: ``dl4j_compile_phase_seconds_total``), a
#: deploy-bounded identity
#: (model/version/bucket/worker/name/replica — replica is a fleet
#: member's URL, bounded by the router's configured replica set;
#: block/expert — the expert blocks of a model's layer pattern and the
#: experts a chip holds of each, on ``dl4j_moe_expert_tokens_total``), or
#: process identity (the build-info trio). A request-scoped value (trace id, user id, prompt)
#: must ride on exemplars or spans, never on labels.
REGISTERED_LABELS: Set[str] = {
    "block", "bucket", "cache", "engine", "expert", "good", "kernel", "kind",
    "mode", "model", "name", "op", "outcome", "path", "phase", "priority",
    "reason",
    "replica", "site",
    "slo", "state", "tier", "version", "window", "worker", "jax_version",
    "jaxlib_version", "platform",
}

#: callables that stage a Python function for tracing (DL103): a function
#: passed (or decorated) into any of these has its body run under trace,
#: where host syncs stall the device pipeline and host randomness/time
#: freezes into the compiled executable.
_TRACE_ENTRY_ATTRS = {
    "jit", "scan", "while_loop", "fori_loop", "cond", "checkpoint",
    "grad", "value_and_grad", "vmap", "pmap", "remat", "shard_map",
    "named_call", "switch",
}
_TRACE_ENTRY_NAMES = {"counted_jit", "jit", "shard_map", "checkpoint"}

#: modules whose helper wrappers read env vars on behalf of a caller
#: (DL102 treats a literal DL4J_TPU_* first argument as a read)
_ENV_HELPER_NAMES = {"_env_bool", "_env_int", "_env_float", "getenv"}


# ---------------------------------------------------------------------------
# shared AST utilities
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.scan' for nested Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jax_jit(node: ast.AST) -> bool:
    return _dotted(node) in ("jax.jit", "jax.experimental.jit")


class _ScopeVisitor(ast.NodeVisitor):
    """Tracks the enclosing function qualname while walking."""

    def __init__(self):
        self.stack: List[str] = []

    @property
    def qualname(self) -> str:
        return ".".join(self.stack) or "<module>"

    def visit_FunctionDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()


# ---------------------------------------------------------------------------
# DL101 — bare jax.jit outside counted_jit
# ---------------------------------------------------------------------------

class _DL101(_ScopeVisitor):
    def __init__(self, mod: Module):
        super().__init__()
        self.mod = mod
        self.findings: List[Finding] = []

    def _flag(self, node: ast.AST, form: str):
        # the one structural false-positive: counted_jit's own body IS the
        # sanctioned jax.jit call site (it wraps it with the compile
        # counter + AOT store) — everywhere else must call the wrapper
        if "counted_jit" in self.stack:
            return
        self.findings.append(Finding(
            "DL101", self.mod.relpath, node.lineno,
            f"bare {form} in {self.qualname} bypasses the AOT compile "
            "cache, recompile counters and dl4j_compile_seconds — route "
            "through runtime.inference.counted_jit(fn, tag, **jit_kwargs)"))

    def visit_Call(self, node: ast.Call):
        if _is_jax_jit(node.func):
            self._flag(node, "jax.jit(...)")
        elif _dotted(node.func) in ("functools.partial", "partial") \
                and node.args and _is_jax_jit(node.args[0]):
            self._flag(node, "functools.partial(jax.jit, ...)")
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if _is_jax_jit(target):
                self._flag(dec, "@jax.jit")
        super().visit_FunctionDef(node)

    visit_AsyncFunctionDef = visit_FunctionDef


def check_dl101(mod: Module) -> List[Finding]:
    v = _DL101(mod)
    v.visit(mod.tree)
    return v.findings


# ---------------------------------------------------------------------------
# DL102 — os.environ reads of DL4J_TPU_* bypassing Environment
# ---------------------------------------------------------------------------

_DECLARED_ENV: Optional[Set[str]] = None


def declared_env_names() -> Set[str]:
    """Env-var names declared on ``EnvironmentVars`` in
    ``common/environment.py`` — the knob registry DL102 checks reads
    against. Parsed from source (not imported) so the pass works on any
    checkout without importing jax."""
    global _DECLARED_ENV
    if _DECLARED_ENV is None:
        names: Set[str] = set()
        path = os.path.join(PACKAGE_ROOT, "common", "environment.py")
        try:
            with open(path, "r", encoding="utf-8") as f:
                tree = ast.parse(f.read())
        except (OSError, SyntaxError):
            _DECLARED_ENV = set()
            return _DECLARED_ENV
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) \
                    and node.name == "EnvironmentVars":
                for stmt in node.body:
                    if isinstance(stmt, ast.Assign) \
                            and isinstance(stmt.value, ast.Constant) \
                            and isinstance(stmt.value.value, str):
                        names.add(stmt.value.value)
        _DECLARED_ENV = names
    return _DECLARED_ENV


#: the Environment implementation itself is the one sanctioned reader
_DL102_EXEMPT = ("deeplearning4j_tpu/common/environment.py",)


def _env_read_name(node: ast.Call) -> Optional[ast.AST]:
    """The name-expression of an env read call, or None."""
    fn = _dotted(node.func)
    if fn in ("os.environ.get", "os.getenv") and node.args:
        return node.args[0]
    if isinstance(node.func, ast.Name) \
            and node.func.id in _ENV_HELPER_NAMES and node.args:
        return node.args[0]
    return None


def check_dl102(mod: Module) -> List[Finding]:
    if mod.relpath in _DL102_EXEMPT:
        return []
    out: List[Finding] = []
    declared = declared_env_names()

    def flag(node: ast.AST, name: str, how: str):
        extra = ("" if name in declared else
                 " — and the knob is not even declared on "
                 "EnvironmentVars (undocumented)")
        out.append(Finding(
            "DL102", mod.relpath, node.lineno,
            f"{how} of {name!r} bypasses Environment's layered resolution "
            f"(programmatic override > env > default){extra}; read it "
            "through a common.environment.Environment property"))

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Subscript) \
                and _dotted(node.value) == "os.environ":
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str) \
                    and sl.value.startswith("DL4J_TPU_"):
                flag(node, sl.value, "os.environ[...] read")
        elif isinstance(node, ast.Call):
            arg = _env_read_name(node)
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str) \
                    and arg.value.startswith("DL4J_TPU_"):
                flag(node, arg.value,
                     f"{_dotted(node.func) or 'env-helper'} read")
        elif isinstance(node, ast.Compare) \
                and len(node.comparators) == 1 \
                and _dotted(node.comparators[0]) == "os.environ" \
                and isinstance(node.left, ast.Constant) \
                and isinstance(node.left.value, str) \
                and node.left.value.startswith("DL4J_TPU_"):
            flag(node, node.left.value, "membership test against os.environ")
    return out


# ---------------------------------------------------------------------------
# DL103 — host-sync hazards inside traced code
# ---------------------------------------------------------------------------

def _traced_function_nodes(mod: Module) -> List[ast.AST]:
    """Function/lambda nodes whose bodies run under a JAX trace:
    decorated with jit/checkpoint, or passed by name (or inline) into a
    trace entry point (jit, counted_jit, lax.scan/while/fori/cond, grad,
    vmap, shard_map, ...). One module-local level — callees in other
    modules are out of scope by design."""
    defs: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)

    traced: List[ast.AST] = []
    seen = set()

    def mark(node: ast.AST):
        if id(node) not in seen:
            seen.add(id(node))
            traced.append(node)

    def is_trace_entry(func: ast.AST) -> bool:
        d = _dotted(func)
        if d is None:
            return False
        leaf = d.rsplit(".", 1)[-1]
        if "." in d:
            return leaf in _TRACE_ENTRY_ATTRS and (
                d.startswith("jax.") or d.startswith("lax.")
                or ".lax." in d or leaf in ("jit", "checkpoint"))
        return leaf in _TRACE_ENTRY_NAMES

    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if is_trace_entry(target):
                    mark(node)
        elif isinstance(node, ast.Call) and is_trace_entry(node.func):
            for arg in node.args:
                if isinstance(arg, ast.Lambda):
                    mark(arg)
                elif isinstance(arg, ast.Name):
                    for fd in defs.get(arg.id, ()):
                        mark(fd)
    return traced


#: host-callback escapes whose subtrees legitimately run host code
_HOST_ESCAPES = {"jax.debug.callback", "jax.debug.print",
                 "jax.pure_callback", "jax.experimental.io_callback",
                 "io_callback", "pure_callback"}


def _dl103_hazard(node: ast.Call) -> Optional[str]:
    d = _dotted(node.func)
    if isinstance(node.func, ast.Attribute) and node.func.attr == "item" \
            and not node.args:
        return ".item() forces a device->host sync"
    if isinstance(node.func, ast.Name) \
            and node.func.id in ("float", "int", "bool") \
            and len(node.args) == 1 \
            and not isinstance(node.args[0], ast.Constant):
        # static-shape arithmetic is trace-safe: int(x.shape[0]) etc.
        for sub in ast.walk(node.args[0]):
            if isinstance(sub, ast.Attribute) \
                    and sub.attr in ("shape", "ndim", "size", "dtype"):
                return None
        return (f"{node.func.id}() on a traced value forces a "
                "device->host sync")
    if d in ("np.asarray", "np.array", "numpy.asarray", "numpy.array",
             "onp.asarray", "onp.array"):
        return f"{d}() materializes a traced value on the host"
    if d in ("time.time", "time.perf_counter", "time.monotonic",
             "time.sleep"):
        return (f"{d}() runs at trace time — it freezes into the compiled "
                "executable (and re-runs only on retrace)")
    if d is not None and (d.startswith("random.")
                          or d.startswith("np.random.")
                          or d.startswith("numpy.random.")):
        return (f"{d}() draws host randomness at trace time — use "
                "jax.random with an explicit key")
    return None


def check_dl103(mod: Module) -> List[Finding]:
    out: List[Finding] = []
    for fn in _traced_function_nodes(mod):
        name = getattr(fn, "name", "<lambda>")
        skip: Set[int] = set()
        for node in ast.walk(fn):
            if id(node) in skip:
                continue
            if isinstance(node, ast.Call) \
                    and _dotted(node.func) in _HOST_ESCAPES:
                for sub in ast.walk(node):
                    skip.add(id(sub))
                continue
            if isinstance(node, ast.Call):
                why = _dl103_hazard(node)
                if why:
                    out.append(Finding(
                        "DL103", mod.relpath, node.lineno,
                        f"host-sync hazard in traced function "
                        f"'{name}': {why}"))
    return out


# ---------------------------------------------------------------------------
# DL104 — metrics/tracing hygiene
# ---------------------------------------------------------------------------

#: the one module allowed to read the metrics flag (it caches it as
#: MetricsRegistry.enabled — everything else must consult that)
_DL104_METRICS_IMPL = ("deeplearning4j_tpu/common/metrics.py",)

_METRIC_CTORS = {"counter", "gauge", "histogram"}


def check_dl104(mod: Module) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Expr) and isinstance(node.value, ast.Call):
            call = node.value
            fn = call.func
            leaf = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            if leaf == "span":
                out.append(Finding(
                    "DL104", mod.relpath, node.lineno,
                    "span(...) called as a bare statement — the context "
                    "manager never runs, so the span times nothing; use "
                    "`with span(...):`"))
        if not isinstance(node, ast.Call):
            continue
        leaf = node.func.attr if isinstance(node.func, ast.Attribute) \
            else None
        if leaf in _METRIC_CTORS and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            name = node.args[0].value
            if not name.startswith("dl4j_"):
                out.append(Finding(
                    "DL104", mod.relpath, node.lineno,
                    f"metric name {name!r} is outside the dl4j_* "
                    "namespace — all framework series share the prefix "
                    "so dashboards/alerts can scope on it"))
            for kw in node.keywords:
                if kw.arg != "labels" or not isinstance(
                        kw.value, (ast.Tuple, ast.List)):
                    continue
                for elt in kw.value.elts:
                    if isinstance(elt, ast.Constant) \
                            and isinstance(elt.value, str) \
                            and elt.value not in REGISTERED_LABELS:
                        out.append(Finding(
                            "DL104", mod.relpath, node.lineno,
                            f"label key {elt.value!r} on metric {name!r} "
                            "is not in analysis.checkers."
                            "REGISTERED_LABELS — register it (with a "
                            "cardinality bound) or carry the value on an "
                            "exemplar/span instead"))
        if mod.relpath not in _DL104_METRICS_IMPL:
            arg = _env_read_name(node) if isinstance(node, ast.Call) else None
            if isinstance(arg, ast.Constant) \
                    and arg.value == "DL4J_TPU_METRICS":
                out.append(Finding(
                    "DL104", mod.relpath, node.lineno,
                    "private re-read of DL4J_TPU_METRICS — the flag is "
                    "cached once on MetricsRegistry.enabled; check that "
                    "(or registry().enabled) so set_metrics_enabled() "
                    "stays authoritative"))
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def check_module(mod: Module) -> Iterator[Finding]:
    for checker in (check_dl101, check_dl102, check_dl103, check_dl104):
        yield from checker(mod)
