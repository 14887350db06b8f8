"""Shared device-mesh abstraction (training AND serving).

Reference context (SURVEY.md §2.4/§2.5): the reference's distribution stack —
ParallelWrapper replica threads, Spark parameter averaging, Aeron
gradient-sharing mesh (`MeshOrganizer.java`) — is replaced wholesale by ONE
concept: a `jax.sharding.Mesh` with named axes, over which whole programs
are jit-compiled and XLA inserts ICI collectives.

Training axes (the full 5D parallelism vocabulary, all first-class):
  data   — batch sharding (subsumes all four reference DP flavors)
  fsdp   — parameter sharding along data (ZeRO-3 style, optional)
  tensor — tensor/model parallelism (absent in reference; required for BERT MFU)
  seq    — sequence/context parallelism (ring attention)
  pipe   — pipeline stages

Serving uses a 2-D slice of the same vocabulary: a ``(data, model)`` mesh
built by :func:`serving_mesh`, where ``model`` is the serving-side name for
the tensor-parallel axis (params sharded over ``model``, request batches
over ``data``). Both sides import their axis names from this module so
training and serving agree on the vocabulary. On a single chip every
builder degrades gracefully to a (1, 1)-shaped mesh and every spec helper
falls back to replicated — sharding here is an optimization, never a
correctness constraint.

The reference's node-failure remapping (`MeshOrganizer.remapNode`) maps to
JAX distributed-runtime coordination; in-process we expose elastic re-mesh
by rebuilding the Mesh from the live device list.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA, FSDP, TENSOR, SEQ, PIPE = "data", "fsdp", "tensor", "seq", "pipe"
# serving-side name for the tensor/model-parallel axis (SNIPPETS [2] idiom:
# a 2-D ("batch"|"data", "model") mesh with jit inserting the collectives)
MODEL = "model"

from jax import shard_map as _shard_map


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False, **kw):
    """``jax.shard_map`` with ``check_vma`` defaulting to False: there is
    no replication rule for ``pallas_call``, so the checked form rejects
    the flash-ring bodies."""
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=check_vma, **kw)


axis_size = lax.axis_size


@dataclasses.dataclass
class MeshConfig:
    """Declarative mesh shape; -1 on `data` means "all remaining devices"."""
    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    pipe: int = 1

    def resolve(self, n_devices: int) -> Tuple[int, int, int, int, int]:
        fixed = self.fsdp * self.tensor * self.seq * self.pipe
        data = self.data
        if data == -1:
            if n_devices % fixed != 0:
                raise ValueError(f"{n_devices} devices not divisible by "
                                 f"fsdp*tensor*seq*pipe={fixed}")
            data = n_devices // fixed
        if data * fixed != n_devices:
            raise ValueError(f"mesh {data}x{fixed} != {n_devices} devices")
        return (data, self.fsdp, self.tensor, self.seq, self.pipe)


def make_mesh(config: MeshConfig = None, devices: Sequence = None) -> Mesh:
    """Build a named 5-D training Mesh.

    Axis order puts `data` outermost (DCN-friendly) and `tensor`/`seq`
    innermost (highest-bandwidth ICI neighbors) — the standard TPU layout
    recipe: collectives that run every layer (TP allreduce, ring attention
    ppermute) ride the fastest links.
    """
    config = config or MeshConfig()
    devices = list(devices) if devices is not None else jax.devices()
    shape = config.resolve(len(devices))
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, (DATA, FSDP, TENSOR, SEQ, PIPE))


def data_parallel_mesh(devices=None) -> Mesh:
    return make_mesh(MeshConfig(), devices)


def batch_spec() -> P:
    """Batch sharded over data(+fsdp); everything else replicated."""
    return P((DATA, FSDP))


def replicated_spec() -> P:
    return P()


def shard_batch(mesh: Mesh, batch_tree):
    """Place host arrays sharded over the batch axis."""
    sharding = NamedSharding(mesh, batch_spec())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch_tree)


def replicate(mesh: Mesh, tree):
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), tree)


def dp_size(mesh: Mesh) -> int:
    """Size of the data-parallel group (data * fsdp axes)."""
    return int(mesh.shape[DATA] * mesh.shape[FSDP])


def zero1_spec(mesh: Mesh, arr) -> P:
    """ZeRO-1 PartitionSpec for one optimizer-state leaf: leading dim
    sharded over the data-parallel group when divisible, else replicated
    (sharding is an optimization, never a correctness constraint)."""
    n = dp_size(mesh)
    if n > 1 and getattr(arr, "ndim", 0) >= 1 and arr.shape[0] % n == 0:
        return P((DATA, FSDP))
    return P()


def zero1_shardings(mesh: Mesh, tree):
    """NamedSharding tree for an updater-state pytree under ZeRO-1: each
    chip holds 1/dp of every (divisible) state tensor. The updater math
    runs on the shards; GSPMD all-gathers the resulting update where the
    replicated params consume it — the ZeRO-1 recipe, expressed purely as
    sharding annotations on the jitted train step."""
    return jax.tree_util.tree_map(
        lambda a: NamedSharding(mesh, zero1_spec(mesh, a)), tree)


def zero1_place(mesh: Mesh, tree):
    """device_put an updater-state pytree into the ZeRO-1 layout."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, zero1_spec(mesh, a))),
        tree)


def num_devices(mesh: Optional[Mesh] = None) -> int:
    return int(np.prod(mesh.devices.shape)) if mesh is not None \
        else jax.device_count()


def local_mesh_info(mesh: Mesh) -> str:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return f"Mesh({shape}, {mesh.devices.size} devices)"


# ---------------------------------------------------------------------------
# serving meshes: a (data, model) 2-D mesh + naive spec helpers
# ---------------------------------------------------------------------------

def serving_mesh(model_parallel: Optional[int] = None,
                 devices: Sequence = None) -> Mesh:
    """2-D ``(data, model)`` mesh for tensor-parallel serving.

    ``model_parallel`` picks the model-axis size (must divide the device
    count); the default puts every device on the model axis — the (1, N)
    shape the sharded-predict path is verified against. On a single chip
    this degrades to (1, 1) and every spec helper below falls back to
    replicated.
    """
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    m = n if model_parallel is None else int(model_parallel)
    if m < 1 or n % m != 0:
        raise ValueError(
            f"model_parallel={m} must be >= 1 and divide {n} devices")
    dev_array = np.asarray(devices).reshape(n // m, m)
    return Mesh(dev_array, (DATA, MODEL))


def validate_mesh(mesh: Mesh, required: Sequence[str] = (DATA,)) -> Mesh:
    """Reject a mesh missing the axis names the caller is about to use."""
    missing = [a for a in required if a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"mesh axes {tuple(mesh.axis_names)} missing required "
            f"{missing}; build one with serving_mesh()/make_mesh()")
    return mesh


def mesh_shape(mesh: Optional[Mesh]) -> Optional[Dict[str, int]]:
    """``{"data": 1, "model": 8}``-style dict for /v1/models reporting."""
    if mesh is None:
        return None
    return {str(a): int(s) for a, s in zip(mesh.axis_names,
                                           mesh.devices.shape)}


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def spec_fits(arr, spec: P, mesh: Mesh) -> bool:
    """True when ``spec`` legally shards ``arr`` on ``mesh``: rank covers
    the spec and every named dim divides evenly."""
    ndim = getattr(arr, "ndim", 0)
    if len(spec) > ndim:
        return False
    for d, names in enumerate(spec):
        if names is None:
            continue
        names = names if isinstance(names, tuple) else (names,)
        size = 1
        for name in names:
            if name not in mesh.axis_names:
                return False
            size *= int(mesh.shape[name])
        if size > 1 and arr.shape[d] % size != 0:
            return False
    return True


def naive_param_spec(arr, mesh: Mesh, axis: str = MODEL) -> P:
    """Tensor-parallel spec for one param leaf: shard the innermost dim
    divisible by the ``model`` axis, else replicate (the SNIPPETS [3]
    "naive sharding" idiom, flipped to the trailing dim — matmul weights
    split over output features)."""
    size = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    ndim = getattr(arr, "ndim", 0)
    if size > 1 and ndim >= 2:
        for d in range(ndim - 1, -1, -1):
            if arr.shape[d] >= size and arr.shape[d] % size == 0:
                return P(*([None] * d + [axis]))
    return P()


def param_shardings(mesh: Mesh, tree, spec=None):
    """NamedSharding tree for a param pytree.

    ``spec`` may be None (naive per-leaf over the ``model`` axis), a single
    PartitionSpec applied to every leaf it fits (replicated fallback), or a
    pytree of PartitionSpecs matching ``tree``.
    """
    if spec is None:
        return jax.tree_util.tree_map(
            lambda a: NamedSharding(mesh, naive_param_spec(a, mesh)), tree)
    if isinstance(spec, P):
        return jax.tree_util.tree_map(
            lambda a: NamedSharding(
                mesh, spec if spec_fits(a, spec, mesh) else P()), tree)
    return jax.tree_util.tree_map(
        lambda a, s: NamedSharding(
            mesh, s if spec_fits(a, s, mesh) else P()), tree, spec)


def shard_params(mesh: Mesh, tree, spec=None):
    """device_put a param pytree into its serving layout."""
    shardings = param_shardings(mesh, tree, spec)
    return jax.tree_util.tree_map(jax.device_put, tree, shardings)


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Request batches ride the ``data`` axis (replicated when absent)."""
    return NamedSharding(mesh, P(DATA) if DATA in mesh.axis_names else P())


def spec_desc(spec) -> str:
    """Stable JSON-able description of a param_spec deploy kwarg."""
    if spec is None:
        return f"auto({MODEL})"
    if isinstance(spec, P):
        return "P(" + ", ".join(repr(e) for e in spec) + ")"
    leaves = jax.tree_util.tree_leaves(
        spec, is_leaf=lambda s: isinstance(s, P))
    return f"tree[{len(leaves)} specs]"
