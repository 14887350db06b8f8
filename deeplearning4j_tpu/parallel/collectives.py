"""Collective communication primitives.

Reference: the entire `nd4j-parameter-server-parent` Aeron stack — message
chunking (`MessageSplitter`), mesh propagation (`ModelParameterServer:
356-422`), NDArray wire format (`nd4j-aeron/ipc/`) — collapses to XLA
collectives over ICI emitted inside jit/shard_map. These wrappers exist to
(a) give the distributed backend an explicit, documented surface like the
reference's Transport API, and (b) centralize axis-name handling.

All functions must run inside `shard_map`/`pjit` over a Mesh (SPMD); outside
a mapped context they raise, exactly like Aeron sends outside a started
transport.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

AxisName = Union[str, Sequence[str]]


def all_reduce_sum(x, axis: AxisName):
    """Dense gradient allreduce — the TPU answer to threshold-compressed
    gradient sharing (SURVEY.md §2.5: ICI makes dense cheaper)."""
    return lax.psum(x, axis)


def all_reduce_mean(x, axis: AxisName):
    return lax.pmean(x, axis)


def all_gather(x, axis: AxisName, *, gather_axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: AxisName, *, scatter_axis: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                            tiled=True)


def ppermute_next(x, axis: str, shift: int = 1):
    """Rotate shards around the ring (ring attention's K/V rotation)."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def all_to_all(x, axis: str, split_axis: int, concat_axis: int):
    """DeepSpeed-Ulysses style sequence<->head exchange."""
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    return lax.axis_size(axis)


def broadcast_from(x, axis: str, root: int = 0):
    """Broadcast root's shard to all members of `axis`."""
    n = axis_size(axis)
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis)
