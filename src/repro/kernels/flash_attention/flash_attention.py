"""Pallas TPU kernels: blockwise (flash) GQA attention with causal and
sliding-window masking, forward and backward — the train/prefill hot-spot.

TPU-native design (vs the CUDA flash-attention formulation):
  * one grid step per (batch, head): the head's whole sequence sits in VMEM
    and the kernel walks its (bq x bk) compute blocks in straight-line code.
    Which blocks are visible, and which of those need a mask, is known at
    trace time from the block's positions, so fully masked blocks are never
    emitted (O(S·W) with a window) and only the blocks that straddle the
    diagonal or the window's edge build a mask;
  * GQA in the BlockSpec index_map: q head h reads kv head h // group — no
    head replication through HBM; the backward's innermost grid axis walks
    the group's q heads and sums their dK/dV into one VMEM accumulator;
  * q, k, v reach the MXU in the dtype they arrive in (bf16 in training)
    with f32 accumulation; softmax statistics, the log-sum-exp residual and
    dS stay f32, and P is rounded to v's dtype before the PV and dV products
    — the numerics of the jnp reference at the TPU's default precision;
  * the forward keeps its running max and sum lane-replicated (bq, 128), so
    they broadcast over a score block by whole-vreg copies;
  * the backward is FlashAttention-2's, fused: the forward writes the
    per-row log-sum-exp `m + log l` (f32, lane-major (1, S) rows), and one
    kernel rebuilds P blockwise from it and D = rowsum(dO * O) and
    accumulates dQ, dK and dV from the same P and dS. It computes in the
    transposed orientation (keys on sublanes, queries on lanes), so the
    residual rows broadcast without a relayout; only dQ's product takes a
    transposed operand.

`flash_attention_bhsd` is differentiable (`jax.custom_vjp`); its residuals
are q, k, v, the output and the log-sum-exp.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
LANES = 128
# the whole-sequence tiles and the unrolled blocks' temporaries take ~40 MiB
# of VMEM at S 4096 (a v5e core has 128 MiB), over the compiler's default
# 16 MiB per kernel
VMEM_LIMIT = 100 * 1024 * 1024
NT = (((1,), (1,)), ((), ()))          # a @ b.T
TN = (((0,), (0,)), ((), ()))          # a.T @ b


def _block(s: int, target: int) -> int:
    """Largest power of two <= target dividing s (s itself below 128)."""
    if s <= LANES:
        return s
    b = target
    while s % b:
        b //= 2
    return b


def block_sizes(s: int) -> tuple[int, int, int]:
    """(forward bq, forward bk, backward block) for a padded sequence of s.
    Tuned on a TPU v5e at s 1024, hd 64 (other head dims take the same
    blocks): the forward takes 512-row query blocks over 256-key blocks,
    the backward square 256 blocks; a long sequence widens them so at most
    8 blocks cover it and the straight-line kernels stay short."""
    wide = max(s // 8, 256)
    return _block(s, max(wide, 512)), _block(s, wide), _block(s, wide)


def padded_len(s: int) -> int:
    """Sequence length the kernels run at: a multiple of 128 (of 8 below)."""
    step = LANES if s > LANES else 8
    return -(-s // step) * step


def _visible(q_start, nq, k_start, nk, causal, window):
    """(any pair visible, some pair masked) for queries [q_start, +nq) and
    keys [k_start, +nk): query q sees key k iff (k <= q if causal) and
    q - k < window."""
    lo = q_start - (k_start + nk - 1)           # least q - k in the block
    hi = q_start + nq - 1 - k_start             # greatest
    seen = (not causal or hi >= 0) and (window is None or lo < window)
    clear = (not causal or lo >= 0) and (window is None or hi < window)
    return seen, not clear


def _mask(q_start, k_start, shape, q_axis, causal, window):
    """Visibility of (query, key) pairs over a block; queries run along
    `q_axis` (0: rows, 1: lanes)."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = jnp.ones(shape, jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    return mask


def _lanes(x, n):
    """A lane-replicated (rows, 128) statistic widened or cut to n lanes."""
    return x[:, :n] if n <= LANES else jnp.tile(x, (1, n // LANES))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, bq, bk, causal, window, scale):
    # refs arrive squeezed: q/o (S, hd), k/v (S, hd), lse (1, S)
    s_len, hd = q_ref.shape
    for q_start in range(0, s_len, bq):
        q = q_ref[q_start:q_start + bq, :]
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        for k_start in range(0, s_len, bk):
            seen, masked = _visible(q_start, bq, k_start, bk, causal, window)
            if not seen:
                continue
            k = k_ref[k_start:k_start + bk, :]
            v = v_ref[k_start:k_start + bk, :]
            s = jax.lax.dot_general(
                q, k, NT, preferred_element_type=jnp.float32) * scale
            if masked:
                # a row with nothing visible yet reads p = 1 here; its first
                # visible key resets l and acc through alpha = 0
                s = jnp.where(_mask(q_start, k_start, (bq, bk), 0, causal,
                                    window), s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, bk))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * _lanes(alpha, hd) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[...] = m_new
        l = l_scr[...]
        o_ref[q_start:q_start + bq, :] = (
            acc_scr[...] / _lanes(l, hd)).astype(o_ref.dtype)
        lse = (m_scr[...] + jnp.log(l))[:, :1]
        lse_ref[:, q_start:q_start + bq] = lse.reshape(1, bq)


def _fwd(q, k, v, *, causal, window, scale, interpret):
    B, H, sq, hd = q.shape
    KV, sk = k.shape[1], k.shape[2]
    group = H // KV
    bq, bk, _ = block_sizes(sq)
    q_spec = pl.BlockSpec((None, None, sq, hd), lambda b, h: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((None, None, sk, hd),
                           lambda b, h: (b, h // group, 0, 0))
    lse_spec = pl.BlockSpec((None, None, 1, sq), lambda b, h: (b, h, 0, 0))
    kernel = functools.partial(_fwd_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dQ, dK, dV in one kernel
# ---------------------------------------------------------------------------


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, dk_blk, dv_blk,
                *, b, causal, window, scale):
    # q/do/dq (S, hd) of one q head of the group, k/v/dk/dv (S, hd),
    # lse/d (1, S); grid axis 2 walks the group's q heads
    g = pl.program_id(2)
    s_len = q_ref.shape[0]

    @pl.when(g == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    dq_scr[...] = jnp.zeros_like(dq_scr)
    for k_start in range(0, s_len, b):
        k = k_ref[k_start:k_start + b, :]
        v = v_ref[k_start:k_start + b, :]
        dk_blk[...] = jnp.zeros_like(dk_blk)
        dv_blk[...] = jnp.zeros_like(dv_blk)
        for q_start in range(0, s_len, b):
            seen, masked = _visible(q_start, b, k_start, b, causal, window)
            if not seen:
                continue
            q = q_ref[q_start:q_start + b, :]
            do = do_ref[q_start:q_start + b, :]
            lse = lse_ref[:, q_start:q_start + b]                # (1, b)
            d = d_ref[:, q_start:q_start + b]
            s_t = jax.lax.dot_general(
                k, q, NT, preferred_element_type=jnp.float32) * scale
            if masked:
                s_t = jnp.where(_mask(q_start, k_start, (b, b), 1, causal,
                                      window), s_t, NEG_INF)
            p_t = jnp.exp(s_t - lse)                              # (keys, q)
            dv_blk[...] += jnp.dot(p_t.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(v, do, NT,
                                       preferred_element_type=jnp.float32)
            ds_t = (p_t * (dp_t - d)).astype(q.dtype)
            dk_blk[...] += jnp.dot(ds_t, q, preferred_element_type=jnp.float32)
            dq_scr[q_start:q_start + b, :] += jax.lax.dot_general(
                ds_t, k, TN, preferred_element_type=jnp.float32)
        dk_scr[k_start:k_start + b, :] += dk_blk[...] * scale
        dv_scr[k_start:k_start + b, :] += dv_blk[...]

    dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(q, k, v, do, lse, d, *, causal, window, scale, interpret):
    B, H, sq, hd = q.shape
    KV, sk = k.shape[1], k.shape[2]
    group = H // KV
    b = block_sizes(sq)[2]
    q_spec = pl.BlockSpec((None, None, sq, hd),
                          lambda b, kv, g: (b, kv * group + g, 0, 0))
    kv_spec = pl.BlockSpec((None, None, sk, hd),
                           lambda b, kv, g: (b, kv, 0, 0))
    row_spec = pl.BlockSpec((None, None, 1, sq),
                            lambda b, kv, g: (b, kv * group + g, 0, 0))
    kernel = functools.partial(_bwd_kernel, b=b, causal=causal, window=window,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(B, KV, group),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((sq, hd), jnp.float32),
                        pltpu.VMEM((sk, hd), jnp.float32),
                        pltpu.VMEM((sk, hd), jnp.float32),
                        pltpu.VMEM((b, hd), jnp.float32),
                        pltpu.VMEM((b, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="flash_attention_bwd",
    )(q, k, v, do, lse, d)


# ---------------------------------------------------------------------------
# differentiable entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(q, k, v, causal, window, scale, interpret):
    return _fwd(q, k, v, causal=causal, window=window, scale=scale,
                interpret=interpret)[0]


def _attention_fwd(q, k, v, causal, window, scale, interpret):
    o, lse = _fwd(q, k, v, causal=causal, window=window, scale=scale,
                  interpret=interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(causal, window, scale, interpret, res, do):
    q, k, v, o, lse = res
    d = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                axis=-1)[:, :, None, :]                         # (B, H, 1, S)
    return _bwd(q, k, v, do, lse, d, causal=causal, window=window,
                scale=scale, interpret=interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=None, scale=None,
                         interpret=False):
    """q: (B, H, S, hd); k, v: (B, KV, S, hd), S a multiple of 128 (or at
    most 128 and a multiple of 8). The scores are q.k * scale (None:
    1/sqrt(hd)). Returns (B, H, S, hd); differentiable in q, k and v."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _attention(q, k, v, causal, window, scale, interpret)
