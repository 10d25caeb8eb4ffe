"""Dropless Mixture-of-Experts FFN, with experts held per chip.

The router is `num_experts` wide and picks `top_k` experts per token, whose
gates are the softmax over the chosen logits. Under expert parallelism this
chip holds the experts [first, first + held) (`MoEConfig.expert_shard` of
`expert_shards`); it routes over all of them and computes the part of the
result its own experts give: pairs routed to experts held elsewhere add
nothing here.

Every (token, expert) pair of a held expert is computed; nothing is
dropped and there is no capacity. The T x top_k pairs are sorted so that
the held experts' pairs come first, grouped by expert, in a buffer of
T x min(top_k, held) rows (no token can send more pairs here), and the
three expert matmuls run as grouped matmuls over those rows
(`kernels/grouped_matmul`, whose time follows the rows routed, not the
buffer). The gathers into and out of the sorted order have gathers as
their backward pass too, through the inverse permutation, one (T, D)
gather per choice, so no (T, top_k, D) buffer is made.

Named scopes: `moe_route` (router, top-k, gates, the balance statistics),
`moe_dispatch` (sort and gather), `moe_experts` (the grouped matmuls),
`moe_combine` (gates and the sum back per token).

Expert e's weights are drawn from a key folded with its global index e, so
a share's experts are exactly those rows of the uncut layer. Weights are
stacked (L, held, D, F) and sharded expert-parallel over the `model` mesh
axis in the dry-run (see launch/sharding.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.kernels.grouped_matmul import ops as gmm_ops
from repro.models.layers import dense_init
from repro.telemetry import note_kernel


class MoEOut(NamedTuple):
    y: jax.Array
    # (2, E) float32: the share of the top-k choices each expert got, and
    # its mean router probability; `balance_loss` takes them over layers
    load: jax.Array


def init_moe_params(key, n_layers: int, d_model: int, d_ff: int,
                    moe: MoEConfig, dtype):
    ks = jax.random.split(key, 4)
    held = jnp.arange(moe.first_held, moe.first_held + moe.held)

    def experts(k, shape, fan_in):
        w = jax.vmap(lambda e: dense_init(jax.random.fold_in(k, e), shape,
                                          dtype, fan_in=fan_in))(held)
        return jnp.moveaxis(w, 0, 1)                     # (L, held, ...)

    L, D, F = n_layers, d_model, d_ff
    return {
        "router": dense_init(ks[0], (L, D, moe.num_experts), jnp.float32,
                             fan_in=D),
        "w_gate": experts(ks[1], (L, D, F), D),
        "w_up": experts(ks[2], (L, D, F), D),
        "w_down": experts(ks[3], (L, F, D), F),
    }


def _pair_rows(a, inv, j, k):
    """(T, D): for each token t, row inv[t * k + j] of the sorted rows a,
    or zero where its j-th pair is not among them."""
    r = a.shape[0]
    idx = inv.reshape(-1, k)[:, j]
    return jnp.where((idx < r)[:, None], jnp.take(a, jnp.minimum(idx, r - 1),
                                                  axis=0), 0)


def _sum_pairs(a, inv, k, w=None):
    """(T, D): each token's sum over its k pairs of their sorted rows of a,
    weighted by w (T, k) where given; one (T, D) gather a pair, so no
    (T, k, D) buffer is made."""
    out = 0
    for j in range(k):
        rows = _pair_rows(a, inv, j, k)
        out = out + (rows if w is None else rows * w[:, j:j + 1])
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_sorted(x, sel, inv, k):
    """x (T, D) -> (R, D): row r is token sel[r] // k (pair sel[r])."""
    return x[sel // k]


def _to_sorted_fwd(x, sel, inv, k):
    return _to_sorted(x, sel, inv, k), (sel, inv)


def _to_sorted_bwd(k, res, d):
    sel, inv = res
    return _sum_pairs(d, inv, k), None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(ys, gates, sel, inv, k):
    """(T, D): each token's gate-weighted sum of its pairs' sorted rows ys
    (R, D); a pair not among the R rows adds nothing."""
    return _sum_pairs(ys, inv, k, gates)


def _combine_fwd(ys, gates, sel, inv, k):
    return _combine(ys, gates, sel, inv, k), (ys, gates, sel, inv)


def _combine_bwd(k, res, dy):
    ys, gates, sel, inv = res
    dys = dy[sel // k] * gates.reshape(-1)[sel][:, None]
    dgates = jnp.stack([jnp.sum((_pair_rows(ys, inv, j, k) * dy).astype(
        jnp.float32), axis=-1) for j in range(k)], axis=1)
    return dys, dgates.astype(gates.dtype), None, None


_to_sorted.defvjp(_to_sorted_fwd, _to_sorted_bwd)
_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_ffn(x, lp, moe: MoEConfig, *, impl: str = "auto") -> MoEOut:
    """x: (B, S, D); lp: per-layer slice {router (D, E); w_gate, w_up
    (held, D, F); w_down (held, F, D)}. `impl` picks the grouped matmul's
    path (`kernels.grouped_matmul.ops`)."""
    B, S, D = x.shape
    T = B * S
    E, K, held, first = moe.num_experts, moe.top_k, moe.held, moe.first_held
    R = T * min(K, held)
    xf = x.reshape(T, D)

    with jax.named_scope("moe_route"):
        logits = xf.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        top_logits, top_e = jax.lax.top_k(logits, K)             # (T, K)
        gates = jax.nn.softmax(top_logits, axis=-1)
        chosen = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
        load = jnp.stack([chosen / T,
                          jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)])

    with jax.named_scope("moe_dispatch"):
        # held experts first (local ids 0..held-1), then the rest
        local = ((top_e - first) % E).reshape(T * K)
        order = jnp.argsort(local, stable=True)
        sel = order[:R]
        inv = jnp.zeros((T * K,), jnp.int32).at[order].set(
            jnp.arange(T * K, dtype=jnp.int32))
        sizes = jnp.zeros((E,), jnp.int32).at[local].add(1)[:held]
        xs = _to_sorted(xf, sel, inv, K)                         # (R, D)

    with jax.named_scope("moe_experts"):
        if gmm_ops.uses_kernel(impl):
            note_kernel("expert_matmul")
        mm = functools.partial(gmm_ops.grouped_matmul, group_sizes=sizes,
                               impl=impl)
        h = jax.nn.silu(mm(xs, lp["w_gate"]).astype(jnp.float32)) * \
            mm(xs, lp["w_up"]).astype(jnp.float32)
        ys = mm(h.astype(x.dtype), lp["w_down"])                 # (R, D)

    with jax.named_scope("moe_combine"):
        y = _combine(ys, gates.astype(x.dtype), sel, inv, K)
    return MoEOut(y.reshape(B, S, D), load)


def balance_loss(load, moe: MoEConfig):
    """The load-balancing term over the layers' routers: E times the dot of
    each expert's share of the top-k choices and its mean router
    probability, both averaged over every layer's tokens (the layers' router
    logits taken as one batch), times `load_balance_coef`. load: (L, 2, E)."""
    share, prob = jnp.mean(load, axis=0)
    return moe.load_balance_coef * moe.num_experts * jnp.sum(share * prob)
