"""Pallas TPU kernel: chunked diagonal linear recurrence (RG-LRU core).

    h_t = a_t * h_{t-1} + b_t        (a, b, h: per-channel)

TPU-native adaptation: instead of a 1-step-per-iteration scan through HBM (T round
trips) or a T-wide associative scan (log T full-tensor passes), the grid walks time
chunks SEQUENTIALLY (`arbitrary` dimension semantics) while channels/batch are
parallel; the running state h lives in a VMEM scratch carried across grid steps.
Within a chunk, the recurrence runs on registers/VMEM with a `fori_loop` over the
chunk's rows — one HBM read of (a, b) and one write of h total.

Blocks: (BT, BD) with BD=128-lane aligned; channel dim is the minor (lane) axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

BLOCK_T = 256
BLOCK_D = 128


def _kernel(a_ref, b_ref, h0_ref, o_ref, h_scr, *, bt):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)      # (BT, BD)
    b = b_ref[...].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + b[t]                 # (BD,)
        o_ref[t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bt, step, h_scr[0])
    h_scr[...] = h[None]


@functools.partial(jax.jit, static_argnames=("bt", "bd", "interpret"))
def lru_scan_btd(a, b, h0, *, bt=BLOCK_T, bd=BLOCK_D, interpret=False):
    """a, b: (B, T, D); h0: (B, D). T % bt == 0, D % bd == 0. Returns h (B, T, D)."""
    B, T, D = a.shape
    bt = min(bt, T)
    bd = min(bd, D)
    grid = (B, D // bd, T // bt)
    data_spec = pl.BlockSpec((1, bt, bd), lambda bi, di, ti: (bi, ti, di))
    h0_spec = pl.BlockSpec((1, 1, bd), lambda bi, di, ti: (bi, 0, di))

    def squeeze(a_ref, b_ref, h0_ref, o_ref, h_scr):
        _kernel(a_ref.at[0], b_ref.at[0], h0_ref.at[0], o_ref.at[0], h_scr, bt=bt)

    return pl.pallas_call(
        squeeze,
        grid=grid,
        in_specs=[data_spec, data_spec, h0_spec],
        out_specs=data_spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rglru_scan",
    )(a, b, h0[:, None, :])
