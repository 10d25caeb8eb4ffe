"""Pure-jnp oracle: GQA attention with causal / sliding-window masking.
Delegates to the shared reference implementation in models/layers.py so the kernel
is validated against exactly what the models use."""
from __future__ import annotations

from repro.models.layers import gqa_attention


def flash_attention_ref(q, k, v, *, causal=True, window=None, scale=None):
    return gqa_attention(q, k, v, causal=causal, window=window, scale=scale)
