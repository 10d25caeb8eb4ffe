"""Pallas TPU kernel: blockwise (flash) GQA attention with causal and
sliding-window masking — the prefill/train compute hot-spot.

TPU-native design (vs the CUDA flash-attention formulation):
  * grid = (batch, q_heads, Sq/BQ) with a `fori_loop` over KV blocks inside the
    kernel; online-softmax stats (m, l) and the accumulator live in VMEM scratch.
  * BQ/BK default to 128 so the q@k^T and p@v contractions are MXU-shaped
    (128 x head_dim x 128); masks are built from iota on the VPU.
  * GQA is handled in the BlockSpec index_map: q head h reads kv head
    h // (H // KV) — no head replication through HBM.
  * causal + window: KV blocks that are fully masked are skipped by clamping the
    loop bounds (lo = first in-window block, hi = q-diagonal block), giving the
    O(S·W) sliding-window complexity rather than O(S²) with masking.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq, bk, sk, causal, window, scale):
    # refs arrive squeezed to (S, hd) via None block dims, so each KV block
    # is one plain ref slice
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * scale                 # (BQ, hd)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    n_kv = sk // bk
    # last kv block that any query in this q block can see
    hi = jnp.minimum(n_kv, (q_start + bq + bk - 1) // bk) if causal else n_kv
    if window is not None:
        # first kv block with any key in-window for the FIRST query of the block
        lo_pos = jnp.maximum(q_start - (window - 1), 0)
        lo = lo_pos // bk
    else:
        lo = 0

    def body(ki, _):
        k_start = ki * bk
        k = k_ref[pl.ds(k_start, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(k_start, bk), :].astype(jnp.float32)
        s = q @ k.T                                            # (BQ, BK)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)              # (BQ, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new
        l_scr[...] = l_new
        return ()

    jax.lax.fori_loop(lo, hi, body, ())
    l = l_scr[...]
    l = jnp.where(l == 0.0, 1.0, l)                            # fully-masked rows
    o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=None,
                         bq=DEFAULT_BQ, bk=DEFAULT_BK, interpret=False):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd). Sq % bq == Sk % bk == 0.
    Returns (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    group = H // KV
    scale = 1.0 / math.sqrt(hd)
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    grid = (B, H, Sq // bq)

    # None block dims squeeze batch/head away inside the kernel
    q_spec = pl.BlockSpec((None, None, bq, hd), lambda b, h, i: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, Sk, hd),
                           lambda b, h, i: (b, h // group, 0, 0))

    kernel = functools.partial(_kernel, bq=bq, bk=bk, sk=Sk, causal=causal,
                               window=window, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="flash_attention_gqa",
    )(q, k, v)
