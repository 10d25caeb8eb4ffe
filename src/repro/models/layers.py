"""Common model primitives: RMSNorm, RoPE, SwiGLU, attention (GQA / qk-norm /
sliding-window / KV-cache decode), chunked cross-entropy.

Everything is functional: params are plain pytrees of jnp arrays; layer params are
stacked along a leading layer axis so the decoder stacks can `lax.scan` over depth
(O(1)-in-depth compile time — essential for the 126-layer dry-runs).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms / rope / mlp
# ---------------------------------------------------------------------------


def cast_params_for_compute(cfg: ModelConfig, params):
    """AMP policy (paper §IV: bf16 compute, f32 master weights): cast float params to
    the compute dtype at forward entry. Matmul accumulations stay f32 via
    preferred_element_type / explicit f32 islands (norms, softmax, scans)."""
    compute = jnp.dtype(cfg.compute_dtype)

    def cast(a):
        return a.astype(compute) if jnp.issubdtype(a.dtype, jnp.floating) else a

    return jax.tree.map(cast, params)


def rms_norm(x, weight, eps=1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                                    # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32)[..., None, :] * freqs  # (...,S,1,hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def gqa_attention(q, k, v, *, causal: bool, window: Optional[int],
                  q_positions=None, kv_positions=None, kv_mask=None,
                  precision=None, scale=None):
    """Reference GQA attention.

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd). H % KV == 0.
    window: sliding window size (attend to keys with q_pos - k_pos < window).
    kv_mask: (B, Sk) bool validity mask (decode caches / padded encoders).
    precision: of both einsums (None = the backend default, which on TPU
    rounds the f32 softmax probabilities to bf16 for the PV product).
    scale: of the scores q.k (None = 1/sqrt(hd)).
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    group = H // KV
    qh = q.reshape(B, Sq, KV, group, hd)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32),
                        k.astype(jnp.float32),
                        precision=precision) * scale              # (B,KV,g,Sq,Sk)
    if q_positions is None:
        q_positions = jnp.arange(Sq)[None, :]
    if kv_positions is None:
        kv_positions = jnp.arange(Sk)[None, :]
    qp = q_positions[:, None, None, :, None]                       # (B,1,1,Sq,1)
    kp = kv_positions[:, None, None, None, :]                      # (B,1,1,1,Sk)
    mask = jnp.ones((B, 1, 1, Sq, Sk), dtype=bool)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= (qp - kp) < window
    if kv_mask is not None:
        mask &= kv_mask[:, None, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32),
                     precision=precision)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def init_attn_params(key, cfg: ModelConfig, n_layers: int, dtype):
    hd = cfg.resolved_head_dim
    D, H, KV = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (n_layers, D, H * hd), dtype, fan_in=D),
        "wk": dense_init(ks[1], (n_layers, D, KV * hd), dtype, fan_in=D),
        "wv": dense_init(ks[2], (n_layers, D, KV * hd), dtype, fan_in=D),
        "wo": dense_init(ks[3], (n_layers, H * hd, D), dtype, fan_in=H * hd),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((n_layers, hd), dtype)
        p["k_norm"] = jnp.ones((n_layers, hd), dtype)
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((n_layers, H * hd), dtype)
        p["bk"] = jnp.zeros((n_layers, KV * hd), dtype)
        p["bv"] = jnp.zeros((n_layers, KV * hd), dtype)
        p["bo"] = jnp.zeros((n_layers, D), dtype)
    return p


def attn_qkv(x, lp, cfg: ModelConfig, positions, *, rope: bool = True):
    """Project to q/k/v for one layer (lp = per-layer slice of stacked params)."""
    hd = cfg.resolved_head_dim
    q = x @ lp["wq"]
    k = x @ lp["wk"]
    v = x @ lp["wv"]
    if cfg.attn_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = _split_heads(q, cfg.n_heads, hd)
    k = _split_heads(k, cfg.n_kv_heads, hd)
    v = _split_heads(v, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(o, lp, cfg: ModelConfig):
    B, S = o.shape[:2]
    y = o.reshape(B, S, -1) @ lp["wo"]
    if cfg.attn_bias:
        y = y + lp["bo"]
    return y


# ---------------------------------------------------------------------------
# chunked cross-entropy (never materializes the full (B,S,V) logits)
# ---------------------------------------------------------------------------


def chunked_cross_entropy(h, lm_head, labels, *, chunk: int = 512,
                          scale: float = 1.0):
    """h: (B, S, D) final hidden states; lm_head: (D, V); labels: (B, S) int32;
    the logits are (h @ lm_head) * scale, h scaled in float32 first.

    Computes mean token NLL by scanning over sequence chunks so peak memory is
    O(B * chunk * V) instead of O(B * S * V) — the 256k-vocab archs need this.
    """
    B, S, D = h.shape
    chunk = min(chunk, S)
    n = S // chunk
    rem = S - n * chunk

    def chunk_nll(hc, lc):
        hc = hc.astype(jnp.float32)
        if scale != 1.0:
            hc = hc * scale
        logits = (hc @ lm_head.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return lse - gold                                          # (B, chunk)

    def body(carry, xs):
        hc, lc = xs
        return carry + jnp.sum(chunk_nll(hc, lc)), None

    hs = h[:, :n * chunk].reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    ls = labels[:, :n * chunk].reshape(B, n, chunk).transpose(1, 0, 2)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls))
    if rem:
        total = total + jnp.sum(chunk_nll(h[:, n * chunk:], labels[:, n * chunk:]))
    return total / (B * S)
