"""Compile the main path for a described TPU v5e, at real shapes, with no chip.

The TPU compiler is installed on CPU-only hosts and compiles for a chip that
is described rather than attached (`jax.experimental.topologies`). That
refuses what interpret mode cannot: misaligned tiles, VMEM overflow, a
program that does not fit HBM. Covered here:

  * every Pallas kernel on the train/serve path at paper_150m shapes; each
    compiled program must hold a `tpu_custom_call` (the kernel itself, not an
    interpreted loop);
  * the serve engine's decode step over chip_smoke.py's slot plane, with
    flash_decode;
  * the trainer's segment program (the same `single_step` the trainer
    scans, with the flash attention kernel and its backward pass as on the
    chip) at paper_150m widths with M=4 regions vmapped on one chip, whose
    peak, plus the resident cocodc engine state, must fit 16 GB of HBM.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture, never at import.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import CoCoDCConfig, get_config
from repro.core import engine_state as es
from repro.core.fragments import make_fragmenter
from repro.core.trainer import SegmentRunner, make_single_step
from repro.models import api
from repro.optim import adamw_init

HBM_BYTES = 16e9                    # one TPU v5e chip
M, K = 4, 4                         # regions vmapped on the chip, fragments
SEQ = 1024                          # paper_150m's training sequence
LOCAL_BATCH = 2                     # chip_smoke.py's per-region batch
N_SLOTS, CACHE_LEN = 8, 128         # chip_smoke.py's serve plane
LANES = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def cfg():
    return get_config("paper_150m")


@pytest.fixture(scope="module")
def param_shapes(cfg):
    return jax.eval_shape(lambda k: api.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_cases(cfg, param_shapes):
    """(name, fn, arg shapes) of every Pallas kernel on the path."""
    from repro.kernels.delay_comp.delay_comp import delay_comp_2d
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_bhsd
    from repro.kernels.flash_decode.flash_decode import flash_decode_bkv
    from repro.kernels.outer_update.outer_update import (deliver_2d,
                                                         nesterov_2d)
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    # the embedding, the largest leaf, tiled to rows of LANES
    emb_rows = -(-cfg.vocab * cfg.d_model // LANES)
    frag_rows = make_fragmenter(cfg, param_shapes, K).flat.rows(0)
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    g = nh // nkv
    emb = ((emb_rows, LANES), f32)
    frag = ((frag_rows, LANES), f32)
    stack = ((M, frag_rows, LANES), f32)
    return {
        "delay_comp_2d": (delay_comp_2d, [emb, emb, emb, ((4,), f32)]),
        "outer_nesterov": (nesterov_2d, [frag, frag, frag, ((2,), f32)]),
        "outer_deliver_blend": (
            lambda lo, s, gg, a, sc: deliver_2d(lo, s, gg, a, sc,
                                                mode="blend"),
            [stack, stack, frag, ((M,), f32), ((5,), f32)]),
        "outer_deliver_compensate": (
            lambda lo, s, gg, a, sc: deliver_2d(lo, s, gg, a, sc,
                                                mode="compensate"),
            [stack, stack, frag, ((M,), f32), ((5,), f32)]),
        "flash_decode": (flash_decode_bkv, [
            ((N_SLOTS, nkv, g, hd), bf16),
            ((N_SLOTS, nkv, CACHE_LEN, hd), bf16),
            ((N_SLOTS, nkv, CACHE_LEN, hd), bf16),
            ((N_SLOTS, CACHE_LEN), i32), ((N_SLOTS,), i32)]),
        "flash_attention": (flash_attention_bhsd, [
            ((1, nh, SEQ, hd), bf16), ((1, nkv, SEQ, hd), bf16),
            ((1, nkv, SEQ, hd), bf16)]),
        # the trainer's backward: M regions x LOCAL_BATCH sequences
        "flash_attention_bwd": (_attention_vjp, [
            ((M * LOCAL_BATCH, nh, SEQ, hd), bf16),
            ((M * LOCAL_BATCH, nkv, SEQ, hd), bf16),
            ((M * LOCAL_BATCH, nkv, SEQ, hd), bf16),
            ((M * LOCAL_BATCH, nh, SEQ, hd), bf16)]),
    }


def _attention_vjp(q, k, v, do):
    """dq, dk, dv of flash attention: the forward (for its residuals) and
    the backward kernel."""
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_bhsd
    return jax.vjp(flash_attention_bhsd, q, k, v)[1](do)


KERNELS = ("delay_comp_2d", "outer_nesterov", "outer_deliver_blend",
           "outer_deliver_compensate", "flash_decode", "flash_attention",
           "flash_attention_bwd")
# kernels each compiled program must hold, where it is more than one
KERNEL_NAMES = {"flash_attention_bwd": ("flash_attention_fwd",
                                        "flash_attention_bwd")}


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, cfg, param_shapes, sds):
    fn, args = _kernel_cases(cfg, param_shapes)[name]
    text = _compile(fn, *(sds(s, d) for s, d in args)).as_text()
    assert "tpu_custom_call" in text
    for kernel in KERNEL_NAMES.get(name, ()):
        assert kernel in text, kernel


# bfloat16 is the model's serving dtype; chip_smoke.py also serves at float32
# to compare the flash and ref engines token for token
@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
def test_serve_decode_step_compiles_for_v5e(compute_dtype, cfg, param_shapes,
                                            sds, monkeypatch):
    from repro.kernels.flash_decode import ops as fd_ops
    from repro.serve import ServeEngine
    # the host backend is the CPU, the target is the TPU: lower the kernel,
    # not its interpreter
    monkeypatch.setattr(fd_ops, "is_cpu", lambda: False)
    eng = ServeEngine(dataclasses.replace(cfg, compute_dtype=compute_dtype),
                      param_shapes, n_slots=N_SLOTS, cache_len=CACHE_LEN,
                      attn_impl="flash")
    params, state = (jax.tree.map(lambda a: sds(a.shape, a.dtype), t)
                     for t in (param_shapes, eng.state))
    compiled = eng._decode_fn.fn.lower(params, state).compile()
    assert "tpu_custom_call" in compiled.as_text()        # flash_decode
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < HBM_BYTES


def _nbytes(tree):
    return sum(x.size * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def test_paper_150m_segment_fits_v5e_hbm(cfg, param_shapes, sds,
                                        monkeypatch):
    from repro.kernels.flash_attention import ops as fa_ops
    # the chip's path: the flash kernel with its backward pass, lowered
    # (the host backend is the CPU, which would interpret it)
    monkeypatch.setattr(fa_ops, "is_cpu", lambda: False)
    stack = jax.tree.map(lambda a: sds((M,) + a.shape, a.dtype),
                         param_shapes)
    opt = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                       jax.eval_shape(jax.vmap(adamw_init), stack))
    batch = {k: sds((1, M, LOCAL_BATCH, SEQ), jnp.int32)
             for k in ("tokens", "labels")}
    runner = SegmentRunner(make_single_step(cfg, weight_decay=0.1,
                                            attn_impl="flash"),
                           donate=True)
    compiled = runner._fn.fn.lower(stack, opt, batch,
                                   sds((1,), jnp.float32)).compile()
    assert "flash_attention_bwd" in compiled.as_text()
    ma = compiled.memory_analysis()
    # the carry is donated: params stack + AdamW moments update in place
    assert ma.alias_size_in_bytes >= _nbytes(stack) + _nbytes(opt)
    program = (ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    # resident beside the segment: theta_g, momentum, the in-flight delta
    # and the worker-stacked Algorithm-1 snapshot
    ccfg = CoCoDCConfig(num_workers=M, num_fragments=K)
    frag = make_fragmenter(cfg, param_shapes, K)
    engine = jax.eval_shape(
        lambda s: es.init_state("cocodc", ccfg, s, frag), stack)
    total = program + _nbytes(engine)
    assert total < 0.9 * HBM_BYTES, (program, _nbytes(engine))
