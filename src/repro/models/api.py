"""Uniform model API: dispatch by cfg.family.

All families implement:
  init_params(cfg, key) -> params
  forward(cfg, params, batch, train=..., ...) -> (h, aux)
  loss_fn(cfg, params, batch, ...) -> (loss, metrics)
  init_cache(cfg, batch_size, cache_len) -> cache
  decode_step(cfg, params, cache, tokens, ...) -> (logits, cache)

Batches are dicts:
  dense/moe/ssm/hybrid: {tokens (B,S), labels (B,S)}
  vlm:   {tokens (B,S_text), prefix_emb (B,P,prefix_dim), labels (B,S_text)}
  audio: {frames (B,F,prefix_dim), tokens (B,S), labels (B,S)}
"""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import encdec, rglru, rwkv6, transformer

_FAMILY_MOD = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": rwkv6,
    "hybrid": rglru,
    "audio": encdec,
}


def family_module(cfg: ModelConfig):
    return _FAMILY_MOD[cfg.family]


def resolve_attn_impl(attn_impl: str = "auto") -> str:
    """"auto" resolves from the backend: the jnp reference on CPU, where
    interpret-mode Pallas is orders slower (and every CPU golden pins the
    reference's numbers), the Pallas kernels on accelerators."""
    if attn_impl == "auto":
        import jax
        attn_impl = "ref" if jax.default_backend() == "cpu" else "flash"
    if attn_impl not in ("ref", "flash"):
        raise ValueError(f"unknown attn_impl {attn_impl!r}")
    return attn_impl


def init_params(cfg: ModelConfig, key):
    return family_module(cfg).init_params(cfg, key)


def loss_fn(cfg: ModelConfig, params, batch, **kw):
    return family_module(cfg).loss_fn(cfg, params, batch, **kw)


def forward(cfg: ModelConfig, params, batch, **kw):
    return family_module(cfg).forward(cfg, params, batch, **kw)


def init_cache(cfg: ModelConfig, batch_size: int, cache_len: int):
    return family_module(cfg).init_cache(cfg, batch_size, cache_len)


def decode_step(cfg: ModelConfig, params, cache, tokens, **kw):
    return family_module(cfg).decode_step(cfg, params, cache, tokens, **kw)


def decode_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    """Effective KV-cache length for a decode shape: ring-buffer bounded by the
    native or long-decode window for windowed archs; full length otherwise."""
    if cfg.family == "ssm":
        return 1  # unused: constant-size state
    win = cfg.attn_window or cfg.long_decode_window
    if cfg.family == "hybrid":
        win = cfg.attn_window
    return min(seq_len, win) if win else seq_len


def batch_shapes(cfg: ModelConfig, shape: InputShape,
                 batch_override: int | None = None) -> Dict[str, Any]:
    """Abstract shapes/dtypes for a training/prefill batch of this arch.
    Returns dict name -> (shape tuple, dtype). Decode shapes are handled by
    cache/token specs in launch/dryrun.py."""
    B = batch_override if batch_override is not None else shape.global_batch
    S = shape.seq_len
    if cfg.family == "vlm":
        P = cfg.n_prefix_tokens
        s_text = max(S - P, 1)
        return {"tokens": ((B, s_text), jnp.int32),
                "prefix_emb": ((B, P, cfg.prefix_dim), jnp.bfloat16),
                "labels": ((B, s_text), jnp.int32)}
    if cfg.family == "audio":
        return {"frames": ((B, cfg.n_prefix_tokens, cfg.prefix_dim), jnp.bfloat16),
                "tokens": ((B, S), jnp.int32),
                "labels": ((B, S), jnp.int32)}
    return {"tokens": ((B, S), jnp.int32), "labels": ((B, S), jnp.int32)}


def param_count(params) -> int:
    import jax
    return sum(p.size for p in jax.tree.leaves(params))
