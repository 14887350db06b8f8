"""Shared flat-Adam update for the model-level train steps.

The model modules (bert / bert-pipeline / bert-QA / seq2seq) all use the
same (u, m)-lists optimizer state layout; this is the single
tree_flatten -> adam_updater -> tree_unflatten pass they share.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common.tracing import model_scope
from ..ops import updater_ops


def adam_apply(params, grads, opt_state, learning_rate, iteration,
               cast_f32: bool = True):
    """One Adam step over a pytree. opt_state = (u_list, m_list) aligned
    with tree_leaves(params). With cast_f32, the update math runs in f32
    and the result is cast back to each param's dtype (bf16 masters)."""
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_p = jax.tree_util.tree_flatten(params)[0]
    u, m = opt_state
    new_p, new_u, new_m = [], [], []
    with model_scope("optimizer"):
        for p, g, ui, mi in zip(flat_p, flat_g, u, m):
            g_ = g.astype(jnp.float32) if cast_f32 else g
            upd, u2, m2 = updater_ops.adam_updater(g_, ui, mi,
                                                   lr=learning_rate,
                                                   iteration=iteration)
            if cast_f32:
                new_p.append((p.astype(jnp.float32) - upd).astype(p.dtype))
            else:
                new_p.append(p - upd)
            new_u.append(u2)
            new_m.append(m2)
    return jax.tree_util.tree_unflatten(treedef, new_p), (new_u, new_m)


def adam_init(params):
    flat = jax.tree_util.tree_leaves(params)
    return ([jnp.zeros(p.shape, jnp.float32) for p in flat],
            [jnp.zeros(p.shape, jnp.float32) for p in flat])
