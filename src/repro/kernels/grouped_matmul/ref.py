"""Pure-jnp oracle: grouped matrix multiplication by `jax.lax.ragged_dot`.

Rows of `lhs` are sorted by group and the groups' sizes count from row 0;
`rhs` holds the groups [group_offset, group_offset + G) of them. Row r of a
held group g is lhs[r] @ rhs[g - group_offset]; every other row is zero.
The groups before and after the held ones become two groups with zero
weights, so one ragged product covers every row."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_matmul_ref(lhs, rhs, group_sizes, group_offset=None):
    """lhs (m, k); rhs (G, k, n); group_sizes (Gt,) int32 with Gt >= G;
    group_offset () int32 or None (0). Returns (m, n) in lhs's dtype,
    accumulated in float32."""
    m, g = lhs.shape[0], rhs.shape[0]
    off = 0 if group_offset is None else group_offset
    held = jax.lax.dynamic_slice_in_dim(group_sizes, off, g)
    before = jnp.sum(jnp.where(jnp.arange(group_sizes.shape[0]) < off,
                               group_sizes, 0))
    sizes = jnp.concatenate([before[None], held,
                             (m - before - jnp.sum(held))[None]])
    zero = jnp.zeros((1,) + rhs.shape[1:], rhs.dtype)
    out = jax.lax.ragged_dot(lhs, jnp.concatenate([zero, rhs, zero]),
                             sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)
