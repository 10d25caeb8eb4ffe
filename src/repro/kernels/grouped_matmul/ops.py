"""Public wrapper: megablox's contract (rows sorted by group, the groups'
sizes counted from row 0, `rhs` holding groups [group_offset, group_offset
+ G)), a differentiable kernel path, and batching by folding.

The kernel path's backward pass is the kernels again: the gradient of lhs
is `expert_gmm` against the transposed weights, the gradient of the weights
`expert_tgmm`. Under `vmap` (the trainer's regions) every call folds the
batch into the group axis, B x G groups over B x m rows, so the batch costs
one kernel call and not a loop of B calls."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.kernels import is_cpu
from repro.kernels.grouped_matmul import grouped_matmul as kernel
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref


def uses_kernel(impl: str = "auto") -> bool:
    """Whether `grouped_matmul(..., impl=impl)` takes the Pallas kernel:
    "auto" takes it off the CPU, where the oracle runs instead."""
    if impl not in ("auto", "pallas", "ref"):
        raise ValueError(f"unknown impl {impl!r}; options: auto|pallas|ref")
    return impl == "pallas" or (impl == "auto" and not is_cpu())


def grouped_matmul(lhs, rhs, group_sizes, group_offset=None, *,
                   impl: str = "auto"):
    """lhs (m, k), rows sorted by group; rhs (G, k, n); group_sizes (Gt,)
    int32, Gt >= G, the groups' row counts from row 0 (they may sum to less
    than m); group_offset () int32 or None (0). Row r of group g, for g in
    [group_offset, group_offset + G), is lhs[r] @ rhs[g - group_offset];
    every other row is zero. Returns (m, n) in lhs's dtype with float32
    accumulation, differentiable in lhs and rhs.
    `impl`: "ref" = the ragged_dot oracle; "pallas" = the kernels
    (interpret mode on CPU); "auto" = the kernels off the CPU, the oracle
    on it."""
    if not uses_kernel(impl):
        return grouped_matmul_ref(lhs, rhs, group_sizes, group_offset)
    g = rhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    off = 0 if group_offset is None else group_offset
    sizes = jax.lax.dynamic_slice_in_dim(group_sizes, off, g)
    starts = jax.lax.dynamic_slice_in_dim(ends, off, g) - sizes
    return _gmm(lhs, rhs.astype(lhs.dtype), starts.astype(jnp.int32),
                sizes.astype(jnp.int32), is_cpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm(lhs, rhs, starts, sizes, interpret):
    return _call("gmm", interpret)(lhs, rhs, starts, sizes)


def _gmm_fwd(lhs, rhs, starts, sizes, interpret):
    return _gmm(lhs, rhs, starts, sizes, interpret), (lhs, rhs, starts,
                                                      sizes)


def _gmm_bwd(interpret, res, dout):
    lhs, rhs, starts, sizes = res
    dout = dout.astype(lhs.dtype)
    dlhs = _call("gmm_t", interpret)(dout, rhs, starts, sizes)
    drhs = _call("tgmm", interpret)(lhs, dout, starts, sizes)
    return dlhs, drhs.astype(rhs.dtype), None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@functools.lru_cache(maxsize=None)
def _call(kind: str, interpret: bool):
    """One kernel as `(a, b, starts, sizes) -> out`, whose batching rule
    folds the batch into the group axis: batch element j's rows move to
    [j * m, (j + 1) * m) and its groups' starts with them."""
    if kind == "tgmm":
        def fn(a, b, starts, sizes):
            return kernel.tgmm(a, b, starts, sizes, out_dtype=a.dtype,
                               interpret=interpret)
    else:
        fn = functools.partial(kernel.gmm, transpose_rhs=kind == "gmm_t",
                               interpret=interpret)
    call = custom_batching.custom_vmap(fn)

    @call.def_vmap
    def folded(axis_size, in_batched, a, b, starts, sizes):
        a, b, starts, sizes = (
            x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, batched in zip((a, b, starts, sizes), in_batched))
        m = a.shape[1]
        starts = starts + m * jnp.arange(axis_size, dtype=jnp.int32)[:, None]
        flat = lambda x: x.reshape((-1,) + x.shape[2:])   # noqa: E731
        out = fn(flat(a), flat(b), flat(starts), flat(sizes))
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return call
