"""Pallas TPU kernel: chunked RWKV-6 WKV recurrence with matrix-valued head state.

    o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T          S in R^{hd x hd} per (batch, head)

TPU adaptation: grid = (B*H, T/BT) with the time axis SEQUENTIAL; S is a VMEM
scratch (hd x hd f32) carried across time chunks. Within a chunk the per-step
updates are rank-1 outer products (VPU) plus an (1 x hd)@(hd x hd) matvec on the
MXU. hd=64 keeps the state at 16 KiB — far under VMEM. This replaces the CUDA
warp-per-head formulation with a lane-parallel per-head state resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

BLOCK_T = 128


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref, s_scr, *,
            bt, n_t):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        s_scr[...] = s0_ref[...].astype(jnp.float32)

    r = r_ref[...].astype(jnp.float32)          # (BT, hd)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)          # (1, hd)

    def step(t, s):
        kv = k[t][:, None] * v[t][None, :]      # (hd, hd) rank-1
        o = (r[t][None, :] @ (s + u.T * kv))[0]  # (hd,)
        o_ref[t, :] = o.astype(o_ref.dtype)
        return w[t][:, None] * s + kv

    s = jax.lax.fori_loop(0, bt, step, s_scr[...])
    s_scr[...] = s

    @pl.when(ti == n_t - 1)
    def _fin():
        sT_ref[...] = s.astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def wkv_scan_bht(r, k, v, w, u, s0, *, bt=BLOCK_T, interpret=False):
    """r,k,v,w: (BH, T, hd); u: (BH, hd); s0: (BH, hd, hd) f32.
    T % bt == 0. Returns (o: (BH, T, hd), sT: (BH, hd, hd) f32)."""
    BH, T, hd = r.shape
    bt = min(bt, T)
    n_t = T // bt
    grid = (BH, n_t)
    data_spec = pl.BlockSpec((1, bt, hd), lambda b, t: (b, t, 0))
    u_spec = pl.BlockSpec((1, 1, hd), lambda b, t: (b, 0, 0))
    s_spec = pl.BlockSpec((1, hd, hd), lambda b, t: (b, 0, 0))

    def squeeze(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref, s_scr):
        _kernel(r_ref.at[0], k_ref.at[0], v_ref.at[0], w_ref.at[0], u_ref.at[0],
                s0_ref.at[0], o_ref.at[0], sT_ref.at[0], s_scr, bt=bt, n_t=n_t)

    return pl.pallas_call(
        squeeze,
        grid=grid,
        in_specs=[data_spec, data_spec, data_spec, data_spec, u_spec, s_spec],
        out_specs=[data_spec, s_spec],
        out_shape=[jax.ShapeDtypeStruct(r.shape, r.dtype),
                   jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="rwkv6_wkv_scan",
    )(r, k, v, w, u[:, None, :], s0)
