"""Per-architecture smoke tests (reduced configs: <=2-3 layers, d_model<=256,
<=4 experts) + decode/forward consistency + family-specific behaviour."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import api

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B=2, S=32):
    k = jax.random.fold_in(KEY, 1)
    batch = {
        "tokens": jax.random.randint(k, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.fold_in(k, 2), (B, S), 0, cfg.vocab),
    }
    if cfg.family == "vlm":
        batch["prefix_emb"] = jax.random.normal(
            jax.random.fold_in(k, 3), (B, cfg.n_prefix_tokens, cfg.prefix_dim),
            jnp.bfloat16)
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.fold_in(k, 3), (B, cfg.n_prefix_tokens, cfg.prefix_dim),
            jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes_no_nan(arch):
    """Deliverable (f): reduced variant, one forward pass, shape + NaN asserts."""
    cfg = get_config(arch).reduced()
    params = api.init_params(cfg, KEY)
    batch = make_batch(cfg)
    h, aux = api.forward(cfg, params, batch, train=False, remat=False)
    B, S = batch["tokens"].shape
    exp_S = S + (cfg.n_prefix_tokens if cfg.family == "vlm" else 0)
    assert h.shape == (B, exp_S, cfg.d_model)
    assert not bool(jnp.any(jnp.isnan(h.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step_no_nan(arch):
    """Deliverable (f): one train step on CPU — loss finite, grads flow."""
    from repro.optim import adamw_init, adamw_update
    cfg = get_config(arch).reduced()
    params = api.init_params(cfg, KEY)
    batch = make_batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: api.loss_fn(cfg, p, batch), has_aux=True)(params)
    assert jnp.isfinite(loss)
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert gn > 0.0
    opt = adamw_init(params)
    new_params, _ = adamw_update(grads, opt, params, 1e-3)
    # params actually moved
    moved = sum(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(new_params),
                                jax.tree.leaves(params)))
    assert moved > 0.0


def _served(cfg):
    """The decode paths apply none of the muP scalars and refuse a model
    that has them (Granite): decode its layers with the scalars at the
    identity."""
    return dataclasses.replace(cfg, embedding_multiplier=1.0,
                               attention_multiplier=None,
                               residual_multiplier=1.0, logits_scaling=1.0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = _served(get_config(arch).reduced())
    params = api.init_params(cfg, KEY)
    B = 2
    cache = api.init_cache(cfg, B, 16)
    toks = jnp.zeros((B,), jnp.int32)
    for _ in range(3):
        logits, cache = api.decode_step(cfg, params, cache, toks)
    assert logits.shape == (B, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert int(cache["pos"]) == 3


def _f32(cfg):
    return dataclasses.replace(cfg, compute_dtype="float32")


@pytest.mark.parametrize("arch", ["qwen3_0_6b", "rwkv6_3b", "recurrentgemma_9b",
                                  "granite_moe_3b_a800m"])
def test_decode_matches_forward(arch):
    """Token-by-token decode reproduces the training forward's next-token logits
    (teacher forcing) — validates cache/ring-buffer/recurrent-state handling.
    The MoE layer is dropless in both; MoE keeps its short prompt."""
    cfg = _served(_f32(get_config(arch).reduced()))
    params = api.init_params(cfg, KEY)
    B, S = 1, 4 if cfg.moe is not None else 12
    toks = jax.random.randint(jax.random.fold_in(KEY, 9), (B, S), 0, cfg.vocab)
    # forward logits at the last position
    h, _ = api.forward(cfg, params, {"tokens": toks}, train=False, remat=False)
    from repro.models import transformer, rwkv6, rglru
    if cfg.family in ("dense", "moe"):
        head = transformer.lm_head_weight(cfg, params)
    else:
        head = params["lm_head"]
    ref_logits = h[:, -1].astype(jnp.float32) @ head.astype(jnp.float32)
    # decode step-by-step
    cache = api.init_cache(cfg, B, S + 4)
    for t in range(S):
        logits, cache = api.decode_step(cfg, params, cache, toks[:, t])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-3, atol=2e-3)


def test_sliding_window_decode_ring_buffer():
    """With window W, decoding past W positions must equal decoding with a full
    cache but masked attention — the ring buffer drops exactly the out-of-window
    entries."""
    cfg = _f32(get_config("llava_next_mistral_7b").reduced())
    W = cfg.attn_window
    assert W is not None
    params = api.init_params(cfg, KEY)
    B, S = 1, W + 8  # decode past the window
    toks = jax.random.randint(jax.random.fold_in(KEY, 5), (B, S), 0, cfg.vocab)
    # ring cache (length W) vs full cache (length S)
    cache_ring = api.init_cache(cfg, B, W)
    cache_full = api.init_cache(cfg, B, S)
    for t in range(S):
        lr, cache_ring = api.decode_step(cfg, params, cache_ring, toks[:, t])
        lf, cache_full = api.decode_step(cfg, params, cache_full, toks[:, t])
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lf), rtol=2e-3, atol=2e-3)


def test_audio_decode_with_cross_attention():
    from repro.models import encdec
    cfg = _f32(get_config("seamless_m4t_large_v2").reduced())
    params = api.init_params(cfg, KEY)
    B, F, S = 1, 8, 6
    frames = jax.random.normal(jax.random.fold_in(KEY, 7), (B, F, cfg.prefix_dim))
    toks = jax.random.randint(jax.random.fold_in(KEY, 8), (B, S), 0, cfg.vocab)
    h, _ = api.forward(cfg, params, {"tokens": toks, "frames": frames},
                       train=False, remat=False)
    ref_logits = h[:, -1].astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    memory = encdec.encode(cfg, params,
                           frames.astype(jnp.float32), train=False, remat=False)
    ck, cv = encdec.prepare_cross_cache(
        cfg, jax.tree.map(lambda a: a, params), memory)
    cache = encdec.init_cache(cfg, B, S + 2, n_frames=F)
    cache["cross_k"], cache["cross_v"] = ck, cv
    for t in range(S):
        logits, cache = api.decode_step(cfg, params, cache, toks[:, t])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-2, atol=2e-2)


def test_moe_routes_to_multiple_experts():
    from repro.models.moe import moe_ffn
    from repro.models import moe as moe_lib
    from repro.configs.base import MoEConfig
    mcfg = MoEConfig(num_experts=4, top_k=2)
    D, F = 32, 64
    lp = jax.tree.map(lambda a: a[0],
                      moe_lib.init_moe_params(KEY, 1, D, F, mcfg, jnp.float32))
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 16, D))
    out = moe_ffn(x, lp, mcfg)
    assert out.y.shape == x.shape
    share, prob = out.load
    # every token's top_k choices are counted, over more than one expert
    np.testing.assert_allclose(float(jnp.sum(share)), mcfg.top_k, rtol=1e-6)
    assert int(jnp.sum(share > 0)) > 1
    np.testing.assert_allclose(float(jnp.sum(prob)), 1.0, rtol=1e-5)
    assert jnp.isfinite(moe_lib.balance_loss(out.load[None], mcfg))


def test_moe_identical_tokens_identical_outputs():
    """Permutation/consistency: same token vector -> same MoE output regardless of
    position (dispatch bookkeeping correctness)."""
    from repro.models.moe import moe_ffn
    from repro.models import moe as moe_lib
    from repro.configs.base import MoEConfig
    mcfg = MoEConfig(num_experts=4, top_k=2)
    D, F = 16, 32
    lp = jax.tree.map(lambda a: a[0],
                      moe_lib.init_moe_params(KEY, 1, D, F, mcfg, jnp.float32))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (D,))
    x = jnp.broadcast_to(v, (1, 8, D))
    out = moe_ffn(x, lp, mcfg)                     # dropless: no capacity
    y = np.asarray(out.y[0])
    for t in range(1, 8):
        np.testing.assert_allclose(y[t], y[0], rtol=1e-4, atol=1e-5)


def test_vlm_prefix_changes_text_logits():
    cfg = _f32(get_config("llava_next_mistral_7b").reduced())
    params = api.init_params(cfg, KEY)
    B, S = 1, 8
    toks = jax.random.randint(jax.random.fold_in(KEY, 6), (B, S), 0, cfg.vocab)
    pe1 = jnp.zeros((B, cfg.n_prefix_tokens, cfg.prefix_dim))
    pe2 = jnp.ones((B, cfg.n_prefix_tokens, cfg.prefix_dim))
    h1, a1 = api.forward(cfg, params, {"tokens": toks, "prefix_emb": pe1},
                         train=False, remat=False)
    h2, _ = api.forward(cfg, params, {"tokens": toks, "prefix_emb": pe2},
                        train=False, remat=False)
    assert a1["n_prefix"] == cfg.n_prefix_tokens
    assert float(jnp.max(jnp.abs(h1[:, -1] - h2[:, -1]))) > 1e-4


def test_unroll_matches_scan():
    """The roofline probe path (unrolled layers) computes the same function."""
    for arch in ["qwen3_0_6b", "rwkv6_3b", "recurrentgemma_9b"]:
        cfg = _f32(get_config(arch).reduced())
        params = api.init_params(cfg, KEY)
        batch = make_batch(cfg, B=1, S=16)
        l1, _ = api.loss_fn(cfg, params, batch, remat=False)
        l2, _ = api.loss_fn(cfg, params, batch, remat=False, unroll=True)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_decode_flash_impl_matches_ref():
    """decode_step(attn_impl='flash') — the flash_decode Pallas kernel wired into
    the production decode path — matches the reference attention."""
    cfg = _f32(get_config("qwen3_0_6b").reduced())
    params = api.init_params(cfg, KEY)
    B = 2
    c1 = api.init_cache(cfg, B, 16)
    c2 = api.init_cache(cfg, B, 16)
    toks = jnp.ones((B,), jnp.int32)
    for _ in range(4):
        l1, c1 = api.decode_step(cfg, params, c1, toks)
        l2, c2 = api.decode_step(cfg, params, c2, toks, attn_impl="flash")
        toks = jnp.argmax(l1, -1).astype(jnp.int32)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("path", ["decode_step", "prefill", "serve_engine"])
def test_scaled_model_is_refused_by_the_serve_paths(path):
    """Granite's muP scalars are applied in training only: the decode and
    prefill paths, and ServeEngine, refuse such a model with a clear error
    instead of decoding a different one."""
    from repro.models import transformer
    from repro.serve import ServeEngine
    cfg = _f32(get_config("granite_moe_3b_a800m").reduced())
    assert not cfg.scalars_are_identity and _served(cfg).scalars_are_identity
    params = api.init_params(cfg, KEY)
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="scalars"):
        if path == "decode_step":
            api.decode_step(cfg, params, api.init_cache(cfg, 1, 8), toks[:, 0])
        elif path == "prefill":
            transformer.prefill(cfg, params, {"tokens": toks})
        else:
            ServeEngine(cfg, params, n_slots=2, cache_len=16)
