"""Inner optimizer: AdamW (paper §IV: lr 4e-4, weight decay 0.1), pure-pytree,
no external deps. Decoupled weight decay, bias-corrected moments, global-norm clip.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    mu: object
    nu: object
    count: jax.Array


def adamw_init(params, moment_dtype=jnp.float32) -> AdamWState:
    """moment_dtype=bf16 halves optimizer memory (used for the 400B-class dry-run
    fit; f32 default matches the paper's training setup)."""
    zeros = lambda: jax.tree.map(lambda p: jnp.zeros_like(p, dtype=moment_dtype), params)
    return AdamWState(mu=zeros(), nu=zeros(), count=jnp.zeros((), jnp.int32))


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def adamw_update(grads, state: AdamWState, params, lr, *,
                 b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, clip_norm=1.0):
    """Returns (new_params, new_state). lr may be a traced scalar (schedule).
    Runs under the named scope `adamw` (the clip and the update)."""
    with jax.named_scope("adamw"):
        count = state.count + 1
        if clip_norm is not None:
            gn = global_norm(grads)
            scale = jnp.minimum(1.0, clip_norm / (gn + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        mu = jax.tree.map(
            lambda m, g: (b1 * m.astype(jnp.float32)
                          + (1 - b1) * g.astype(jnp.float32)).astype(m.dtype),
            state.mu, grads)
        nu = jax.tree.map(
            lambda v, g: (b2 * v.astype(jnp.float32) + (1 - b2)
                          * jnp.square(g.astype(jnp.float32))).astype(v.dtype),
            state.nu, grads)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)

        def upd(p, m, v):
            mhat = m.astype(jnp.float32) / c1
            vhat = v.astype(jnp.float32) / c2
            step = (mhat / (jnp.sqrt(vhat) + eps)
                    + weight_decay * p.astype(jnp.float32))
            return (p.astype(jnp.float32) - lr * step).astype(p.dtype)

        new_params = jax.tree.map(upd, params, mu, nu)
        return new_params, AdamWState(mu=mu, nu=nu, count=count)
