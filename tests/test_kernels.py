"""Per-kernel allclose validation against the pure-jnp oracles (interpret mode on
CPU), sweeping shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.delay_comp.ops import delay_comp, delay_comp_array
from repro.kernels.delay_comp.ref import delay_comp_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.rglru_scan.ops import lru_scan
from repro.kernels.rglru_scan.ref import lru_scan_ref
from repro.kernels.rwkv6_scan.ops import wkv_scan
from repro.models.rwkv6 import wkv_scan_ref

KEY = jax.random.PRNGKey(0)


def rand(i, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.fold_in(KEY, i), shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# delay_comp
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(7,), (128,), (33, 65), (4, 9, 17), (2048,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_delay_comp_matches_ref(shape, dtype):
    tl, tp, tg = (rand(i, shape, dtype) for i in range(3))
    out = delay_comp_array(tl, tp, tg, tau=5.0, lam=0.5, H=100.0, impl="auto")
    ref = delay_comp_ref(tl, tp, tg, tau=5.0, lam=0.5, H=100.0)
    rtol, atol = (3e-2, 3e-2) if dtype == jnp.bfloat16 else (1e-5, 1e-6)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=rtol, atol=atol)


@pytest.mark.parametrize("tau,lam,H,sign", [(1.0, 0.0, 1.0, 1.0),
                                            (5.0, 0.5, 100.0, 1.0),
                                            (3.0, 1.0, 10.0, -1.0)])
def test_delay_comp_param_sweep(tau, lam, H, sign):
    tl, tp, tg = (rand(i, (256,)) for i in range(3))
    out = delay_comp_array(tl, tp, tg, tau=tau, lam=lam, H=H, sign=sign, impl="auto")
    ref = delay_comp_ref(tl, tp, tg, tau=tau, lam=lam, H=H, sign=sign)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_delay_comp_pytree():
    tree = {"a": rand(0, (17,)), "b": [rand(1, (3, 5)), rand(2, (8, 8))]}
    out = delay_comp(tree, tree, tree, tau=5.0, lam=0.5, H=100.0)
    # theta_tl == theta_tp == theta_g  =>  g = 0  =>  out == theta_g
    jax.tree.map(lambda o, t: np.testing.assert_allclose(o, t, rtol=1e-6), out, tree)


def test_delay_comp_lam0_is_raw_drift():
    """lam=0: out = theta_g + (theta_tl - theta_tp) (invariant 2, DESIGN.md §7)."""
    tl, tp, tg = (rand(i, (64,)) for i in range(3))
    out = delay_comp_array(tl, tp, tg, tau=7.0, lam=0.0, H=100.0, impl="ref")
    np.testing.assert_allclose(out, tg + (tl - tp), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,KV,hd,window", [
    (1, 128, 4, 2, 64, None),
    (2, 256, 4, 4, 32, None),
    (1, 256, 4, 1, 64, 64),
    (1, 200, 2, 2, 64, None),      # non-multiple S (padding path)
    (1, 384, 8, 2, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, S, H, KV, hd, window, dtype):
    q = rand(1, (B, S, H, hd), dtype)
    k = rand(2, (B, S, KV, hd), dtype)
    v = rand(3, (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, causal=True, window=window)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_attention_first_token_attends_self():
    q = rand(1, (1, 128, 2, 32))
    k = rand(2, (1, 128, 2, 32))
    v = rand(3, (1, 128, 2, 32))
    out = flash_attention(q, k, v, causal=True)
    # position 0 can only attend itself -> output == v[0]
    np.testing.assert_allclose(np.asarray(out[0, 0, 0]), np.asarray(v[0, 0, 0]),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("B,S,H,KV,hd,window", [
    (1, 256, 4, 4, 64, None),      # H == KV
    (1, 256, 8, 2, 64, None),      # GQA group of 4
    (1, 384, 4, 1, 64, 96),        # GQA + sliding window
    (2, 200, 4, 1, 32, None),      # non-multiple S (padding path)
    (1, 1024, 2, 2, 64, 300),      # window across 512-wide query blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vjp_matches_ref(B, S, H, KV, hd, window, dtype):
    """dq, dk, dv of the kernel's custom VJP against `jax.vjp` of the
    reference, on the same output cotangent."""
    q = rand(1, (B, S, H, hd), dtype)
    k = rand(2, (B, S, KV, hd), dtype)
    v = rand(3, (B, S, KV, hd), dtype)
    do = rand(4, (B, S, H, hd), dtype)
    _, vjp = jax.vjp(lambda *a: flash_attention(*a, causal=True,
                                                window=window), q, k, v)
    _, vjp_ref = jax.vjp(lambda *a: flash_attention_ref(*a, causal=True,
                                                        window=window), q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    for got, want in zip(vjp(do), vjp_ref(do)):
        assert got.dtype == want.dtype
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        scale = np.abs(want).max()
        np.testing.assert_allclose(got / scale, want / scale, rtol=0, atol=tol)


# compute dtype -> (loss rtol, per-leaf gradient gap over its norm): at
# bf16 the kernel rounds P and dS to bf16 at the MXU where the CPU
# reference keeps them f32, a ~1% gradient difference
SINGLE_STEP_TOL = {"float32": (1e-6, 1e-5), "bfloat16": (1e-3, 5e-2)}


@pytest.mark.parametrize("compute_dtype", sorted(SINGLE_STEP_TOL))
def test_single_step_flash_matches_ref(compute_dtype):
    """The trainer's inner step (vmapped over regions, scanned over steps,
    layers under remat) with the flash kernel against the reference. At
    lr 0 the parameters stay put, so the losses of both steps and the AdamW
    first moment (a sum of the two steps' gradients) compare the loss and
    its gradient alone."""
    import dataclasses
    from repro.configs import get_config
    from repro.core.trainer import SegmentRunner, make_single_step
    from repro.models import api
    from repro.optim import adamw_init
    cfg = dataclasses.replace(get_config("paper_150m").reduced(),
                              compute_dtype=compute_dtype)
    loss_tol, grad_tol = SINGLE_STEP_TOL[compute_dtype]
    M, B, S, n = 2, 2, 128, 2
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    stack = jax.tree.map(lambda a: jnp.stack([a] * M), params)
    opt = jax.vmap(adamw_init)(stack)
    tokens = jax.random.randint(KEY, (n, M, B, S + 1), 0, cfg.vocab)
    batch = {"tokens": tokens[..., :-1], "labels": tokens[..., 1:]}
    lrs = jnp.zeros((n,), jnp.float32)
    out = {}
    for impl in ("ref", "flash"):
        runner = SegmentRunner(make_single_step(cfg, weight_decay=0.1,
                                                attn_impl=impl))
        out[impl] = runner(stack, opt, batch, lrs)
        want = 1 if impl == "flash" else 0
        assert runner.telemetry.attention_kernel_layers == want
    (_, o_ref, loss_ref), (_, o_fl, loss_fl) = out["ref"], out["flash"]
    np.testing.assert_allclose(loss_fl, loss_ref, rtol=loss_tol)
    for g_fl, g_ref in zip(jax.tree.leaves(o_fl.mu),
                           jax.tree.leaves(o_ref.mu)):
        gap = float(jnp.linalg.norm(g_fl - g_ref))
        assert gap <= grad_tol * float(jnp.linalg.norm(g_ref)), gap


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,T,D,with_h0,bt,bd", [
    (2, 64, 32, False, 32, 32),
    (1, 300, 130, True, 64, 64),    # padding both axes
    (2, 512, 128, True, 128, 128),
    (1, 8, 8, False, 8, 8),
])
def test_lru_scan_matches_ref(B, T, D, with_h0, bt, bd):
    a = jax.nn.sigmoid(rand(1, (B, T, D)))
    b = rand(2, (B, T, D))
    h0 = rand(3, (B, D)) if with_h0 else None
    out = lru_scan(a, b, h0, bt=bt, bd=bd)
    ref = lru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def test_lru_scan_identity_coeff_is_cumsum():
    B, T, D = 1, 32, 16
    a = jnp.ones((B, T, D))
    b = rand(1, (B, T, D))
    out = lru_scan(a, b, bt=16, bd=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.cumsum(b, axis=1)),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# rwkv6 wkv scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,T,H,hd,with_s0,bt", [
    (1, 32, 2, 16, False, 16),
    (2, 100, 2, 32, True, 32),      # T padding
    (1, 128, 4, 64, True, 64),
])
def test_wkv_scan_matches_ref(B, T, H, hd, with_s0, bt):
    r = rand(1, (B, T, H, hd), scale=0.5)
    k = rand(2, (B, T, H, hd), scale=0.5)
    v = rand(3, (B, T, H, hd), scale=0.5)
    w = jax.nn.sigmoid(rand(4, (B, T, H, hd)))
    u = rand(5, (H, hd), scale=0.1)
    s0 = rand(6, (B, H, hd, hd)) if with_s0 else None
    o, sT = wkv_scan(r, k, v, w, u, s0, bt=bt)
    o_ref, s_ref = wkv_scan_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(s_ref), rtol=1e-4, atol=1e-5)


def test_wkv_state_carry_equals_full_scan():
    """Chunked decode (carry sT) == one full scan: the O(1)-state decode path."""
    B, T, H, hd = 1, 64, 2, 16
    r, k, v = (rand(i, (B, T, H, hd), scale=0.5) for i in (1, 2, 3))
    w = jax.nn.sigmoid(rand(4, (B, T, H, hd)))
    u = rand(5, (H, hd), scale=0.1)
    o_full, s_full = wkv_scan(r, k, v, w, u, bt=32)
    half = T // 2
    o1, s1 = wkv_scan(r[:, :half], k[:, :half], v[:, :half], w[:, :half], u, bt=32)
    o2, s2 = wkv_scan(r[:, half:], k[:, half:], v[:, half:], w[:, half:], u, s1,
                      bt=32)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], axis=1)),
                               np.asarray(o_full), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# fused rms_norm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(7, 64), (2, 33, 128), (300, 256), (1, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rms_norm_matches_ref(shape, dtype):
    from repro.kernels.rms_norm.ops import rms_norm
    from repro.kernels.rms_norm.ref import rms_norm_ref
    x = rand(1, shape, dtype)
    w = rand(2, (shape[-1],), dtype)
    out = rms_norm(x, w)
    ref = rms_norm_ref(x, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash_decode (one-token attention over ring-buffer cache)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,KV,hd,C,pos,window", [
    (2, 4, 2, 64, 128, 100, None),
    (1, 8, 2, 64, 256, 300, 64),     # ring wrapped + sliding window
    (2, 4, 4, 32, 100, 37, None),    # partially-filled cache + C padding
    (1, 2, 1, 64, 64, 63, 32),       # MQA
])
def test_flash_decode_matches_ref(B, H, KV, hd, C, pos, window):
    from repro.kernels.flash_decode.ops import flash_decode
    from repro.kernels.flash_decode.ref import flash_decode_ref
    q = rand(3, (B, H, hd))
    kc = rand(4, (B, C, KV, hd))
    vc = rand(5, (B, C, KV, hd))
    kv_pos = jnp.where(jnp.arange(C) <= pos, jnp.arange(C), -1)
    qpos = jnp.asarray(pos, jnp.int32)
    out = flash_decode(q, kc, vc, kv_pos, qpos, window=window, bc=64)
    ref = flash_decode_ref(q, kc, vc, kv_pos, qpos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_flash_decode_empty_slots_ignored():
    """Slots with kv_pos = -1 must not contribute regardless of their values."""
    from repro.kernels.flash_decode.ops import flash_decode
    B, H, KV, hd, C = 1, 2, 1, 32, 64
    q = rand(1, (B, H, hd))
    kc = rand(2, (B, C, KV, hd))
    vc = rand(3, (B, C, KV, hd))
    kv_pos = jnp.where(jnp.arange(C) < 8, jnp.arange(C), -1)
    qpos = jnp.asarray(7, jnp.int32)
    out1 = flash_decode(q, kc, vc, kv_pos, qpos, bc=32)
    # poison the masked slots
    kc2 = kc.at[:, 8:].set(1e9)
    vc2 = vc.at[:, 8:].set(-1e9)
    out2 = flash_decode(q, kc2, vc2, kv_pos, qpos, bc=32)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)
