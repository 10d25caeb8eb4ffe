"""Pallas TPU kernels: grouped matrix multiplication, the expert FFN of a
dropless MoE layer.

The rows of `lhs` come sorted by group: group g covers rows
[starts[g], starts[g] + sizes[g]) and multiplies with its own `rhs[g]`.
Groups are disjoint and in row order, and need not cover every row; a row in
no group comes out zero. That lets one call take several batches' groups
side by side (the regions' vmap folds into the group axis, `ops.py`) with
the rows no group holds between them.

TPU-native design, after JAX's megablox kernels
(`jax.experimental.pallas.ops.tpu.megablox`):
  * the grid walks the (row tile, group) pairs that the groups touch, and
    how many there are is known only on the device: the pairs' group and
    row-tile ids are scalar-prefetched into SMEM and their count is a
    dynamic grid bound. So the time follows the rows grouped, not the
    buffer's size: a buffer sized for the worst case costs nothing for its
    empty rows;
  * a row tile that straddles two groups is visited once for each,
    consecutively, so the output block stays in VMEM between the visits and
    each visit stores only its own group's rows;
  * `expert_gmm` computes lhs @ rhs[g] (or lhs @ rhs[g].T, the backward's
    gradient of lhs), with f32 accumulation over k tiles; `expert_tgmm`
    computes lhs[rows of g].T @ rhs[rows of g] per group, the gradient of
    the weights: a transposed-lhs MXU product, with the rows of other groups
    masked out of both operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

TM = 128                               # rows per tile of expert_gmm
# rows per tile of expert_tgmm: every tile re-reads and re-writes the whole
# (tk, tn) f32 accumulator, so taller tiles amortise it over more rows
TM_T = 256
TILE_CAP = 1536                        # widest k or n tile
# the widest tiles (1536 x 1536 weights, double-buffered, and the f32
# accumulator) take ~30 MiB, over the compiler's default 16 MiB per kernel
VMEM_LIMIT = 64 * 1024 * 1024
NN = (((1,), (0,)), ((), ()))          # a @ b
NT = (((1,), (1,)), ((), ()))          # a @ b.T
TN = (((0,), (0,)), ((), ()))          # a.T @ b


def _tile(dim: int) -> int:
    """The whole of `dim` up to TILE_CAP, else its largest divisor that is
    a multiple of 128 and at most TILE_CAP."""
    if dim <= TILE_CAP or dim % 128:
        return dim
    t = TILE_CAP
    while dim % t:
        t -= 128
    return t


def tile_schedule(starts, sizes, m_tiles: int, tm: int, visit_empty: bool):
    """The grid's (group, row tile) steps: (group_ids, m_tile_ids, steps),
    where step i < steps works on group group_ids[i] over row tile
    m_tile_ids[i]. A group takes the row tiles it touches; an empty group
    takes none, or one with `visit_empty` (its output is then written as
    zero). The arrays are sized for the most steps disjoint groups in row
    order can take; `steps` is at least 1."""
    g = starts.shape[0]
    first = starts // tm
    n = jnp.where(sizes > 0, (starts + sizes + tm - 1) // tm - first,
                  1 if visit_empty else 0)
    bound = m_tiles + 2 * g
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), n,
                           total_repeat_length=bound)
    begin = jnp.cumsum(n) - n
    step = jnp.arange(bound, dtype=jnp.int32)
    m_tile_ids = jnp.clip(first[group_ids] + step - begin[group_ids], 0,
                          m_tiles - 1).astype(jnp.int32)
    return group_ids, m_tile_ids, jnp.maximum(jnp.sum(n), 1)


def _rows_of(m_tile, start, size, shape):
    """Which rows of an m tile (shape[0] rows along axis 0) the group
    [start, start + size) holds."""
    row = m_tile * shape[0] + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= start) & (row < start + size)


def covered(starts, sizes, m: int):
    """(m,) bool: the rows some group holds."""
    edge = jnp.zeros((m + 1,), jnp.int32).at[starts].add(1).at[
        starts + sizes].add(-1)
    return jnp.cumsum(edge)[:m] > 0


# ---------------------------------------------------------------------------
# lhs @ rhs[g]
# ---------------------------------------------------------------------------


def _gmm_kernel(group_ids, m_tile_ids, starts, sizes, lhs_ref, rhs_ref,
                out_ref, acc_ref, *, tiles_k, dims):
    i, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _():
        g = group_ids[i]
        mine = _rows_of(m_tile_ids[i], starts[g], sizes[g], acc_ref.shape)
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def gmm(lhs, rhs, starts, sizes, *, transpose_rhs: bool = False,
        interpret: bool = False):
    """lhs (m, k); rhs (G, k, n), or (G, n, k) with `transpose_rhs`;
    starts, sizes (G,) int32. Returns (m, n) in lhs's dtype: row r of group
    g is lhs[r] @ rhs[g] (@ rhs[g].T), a row in no group is zero."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    pad = -m % TM
    lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tk, tn = _tile(k), _tile(n)
    group_ids, m_tile_ids, steps = tile_schedule(
        starts, sizes, (m + pad) // TM, TM, visit_empty=False)

    def lhs_map(n_i, i, k_i, gids, mids, st, sz):
        return mids[i], k_i

    def rhs_map(n_i, i, k_i, gids, mids, st, sz):
        return (gids[i], n_i, k_i) if transpose_rhs else (gids[i], k_i, n_i)

    def out_map(n_i, i, k_i, gids, mids, st, sz):
        return mids[i], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    kernel = functools.partial(_gmm_kernel, tiles_k=k // tk,
                               dims=NT if transpose_rhs else NN)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m + pad, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, steps, k // tk),
            in_specs=[pl.BlockSpec((TM, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((TM, tn), out_map),
            scratch_shapes=[pltpu.VMEM((TM, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_gmm",
    )(group_ids, m_tile_ids, starts, sizes, lhs, rhs.astype(lhs.dtype))
    return jnp.where(covered(starts, sizes, m)[:, None], out[:m], 0)


# ---------------------------------------------------------------------------
# lhs[rows of g].T @ rhs[rows of g]
# ---------------------------------------------------------------------------


def _tgmm_kernel(group_ids, m_tile_ids, starts, sizes, lhs_ref, rhs_ref,
                 out_ref, acc_ref):
    i, steps = pl.program_id(2), pl.num_programs(2)
    g = group_ids[i]
    prev = group_ids[jnp.maximum(i - 1, 0)]
    nxt = group_ids[jnp.minimum(i + 1, steps - 1)]

    @pl.when((i == 0) | (prev != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def mine(ref):
        x = ref[...]
        keep = _rows_of(m_tile_ids[i], starts[g], sizes[g], x.shape)
        return jnp.where(keep, x.astype(jnp.float32), 0).astype(x.dtype)

    acc_ref[...] += jax.lax.dot_general(mine(lhs_ref), mine(rhs_ref), TN,
                                        preferred_element_type=jnp.float32)

    @pl.when((i == steps - 1) | (nxt != g))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def tgmm(lhs, rhs, starts, sizes, *, out_dtype, interpret: bool = False):
    """lhs (m, k), rhs (m, n), starts, sizes (G,) int32. Returns (G, k, n)
    in `out_dtype`: group g's rows of lhs, transposed, times its rows of
    rhs (zero for an empty group)."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tm = TM_T
    pad = -m % tm
    lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    rhs = jnp.pad(rhs.astype(lhs.dtype), ((0, pad), (0, 0)))
    tk, tn = _tile(k), _tile(n)
    group_ids, m_tile_ids, steps = tile_schedule(
        starts, sizes, (m + pad) // tm, tm, visit_empty=True)

    def lhs_map(n_i, k_i, i, gids, mids, st, sz):
        return mids[i], k_i

    def rhs_map(n_i, k_i, i, gids, mids, st, sz):
        return mids[i], n_i

    def out_map(n_i, k_i, i, gids, mids, st, sz):
        return gids[i], k_i, n_i

    return pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct((starts.shape[0], k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, k // tk, steps),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_tgmm",
    )(group_ids, m_tile_ids, starts, sizes, lhs, rhs)
