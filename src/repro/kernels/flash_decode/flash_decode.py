"""Pallas TPU kernel: one-token (decode) GQA attention over a ring-buffer KV
cache — the serving hot-spot for decode_32k / long_500k.

Per (batch, kv-head): all `group = H/KV` query heads that share the kv head are
processed TOGETHER as a (group, hd) panel so the cache is read from HBM exactly
once per kv head. The grid walks KV-cache blocks SEQUENTIALLY (`arbitrary`)
carrying online-softmax stats (m, l, acc) in VMEM scratch; validity/causality/
window masking is computed from the cache's position map (ring buffers leave
stale or empty slots — masked via kv_pos).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

BLOCK_C = 512
NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref, m_scr, l_scr,
            acc_scr, *, bc, n_c, window, scale):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32) * scale          # (G, hd)
    k = k_ref[...].astype(jnp.float32)                  # (BC, hd)
    v = v_ref[...].astype(jnp.float32)
    kv_pos = pos_ref[...]                               # (1, BC) int32
    qpos = qpos_ref[pl.program_id(0)]

    # f32 matmuls (the TPU default would round operands to bf16), matching
    # the oracle
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=HIGHEST)          # (G, BC)
    valid = (kv_pos >= 0) & (kv_pos <= qpos)
    if window is not None:
        valid &= (qpos - kv_pos) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(p, v, precision=HIGHEST)
    m_scr[...] = m_new

    @pl.when(ci == n_c - 1)
    def _fin():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bc", "interpret"))
def flash_decode_bkv(q, k_cache, v_cache, kv_positions, q_position, *,
                     window=None, bc=BLOCK_C, interpret=False):
    """q: (B, KV, G, hd) — query heads grouped by kv head;
    caches: (B, KV, C, hd); kv_positions: (B, C) int32 (-1 = empty slot);
    q_position: (B,) int32 — each batch lane carries its OWN position map, so
    a slotted serving cache can decode requests at different depths in one
    dispatch. C % bc == 0. Returns (B, KV, G, hd)."""
    B, KV, G, hd = q.shape
    C = k_cache.shape[2]
    bc = min(bc, C)
    n_c = C // bc
    scale = 1.0 / math.sqrt(hd)
    grid = (B, KV, n_c)

    q_spec = pl.BlockSpec((1, 1, G, hd), lambda b, h, c: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, bc, hd), lambda b, h, c: (b, h, c, 0))
    # positions ride as (B, 1, C) so the block's last two dims are (1, bc):
    # a (1, bc) block of a (B, C) array breaks the TPU's 8-sublane tiling
    # whenever B > 1
    pos_spec = pl.BlockSpec((1, 1, bc), lambda b, h, c: (b, 0, c))

    def squeeze(qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref, m, l, acc):
        _kernel(qpos_ref, q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0],
                pos_ref.at[0], o_ref.at[0, 0], m, l, acc,
                bc=bc, n_c=n_c, window=window, scale=scale)

    return pl.pallas_call(
        squeeze,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  q_spec, kv_spec, kv_spec, pos_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode_gqa",
    )(jnp.asarray(q_position, jnp.int32), q, k_cache, v_cache,
      kv_positions[:, None, :])
