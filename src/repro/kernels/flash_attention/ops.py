"""Public wrapper: (B, S, H, hd) layout in/out, padding to block multiples,
interpret-mode on CPU. Differentiable: the kernel path carries its own
backward (flash_attention.py)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import is_cpu
from repro.kernels.flash_attention.flash_attention import (flash_attention_bhsd,
                                                           padded_len)
from repro.kernels.flash_attention.ref import flash_attention_ref


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    impl: str = "auto"):
    """q: (B, S, H, hd); k, v: (B, S, KV, hd) — the models' layout.
    Pads S to the kernel's block multiple and transposes to (B, H, S, hd)
    for the kernel, which picks its block sizes from S. The scores are
    q.k * scale (None: 1/sqrt(hd)).
    `impl`: "ref" = pure-jnp oracle; "auto"/"pallas" = Pallas kernel
    (interpret mode on CPU)."""
    if impl not in ("auto", "pallas", "ref"):
        raise ValueError(f"unknown impl {impl!r}; options: auto|pallas|ref")
    if impl == "ref":
        return flash_attention_ref(q, k, v, causal=causal, window=window,
                                   scale=scale)
    S = q.shape[1]
    if k.shape[1] != S:
        raise ValueError(f"flash path needs as many keys as queries, got "
                         f"{k.shape[1]} keys for {S} queries")
    pad = padded_len(S) - S
    # padded keys sit at positions >= S; causal masking removes them from
    # every real query. Without causality they would need a mask of their own
    if pad and not causal:
        raise ValueError(f"non-causal flash path cannot pad S={S} to "
                         f"{S + pad}")
    qt, kt, vt = (jnp.pad(jnp.moveaxis(x, 2, 1),
                          ((0, 0), (0, 0), (0, pad), (0, 0)))
                  for x in (q, k, v))
    o = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                             scale=scale, interpret=is_cpu())
    return jnp.moveaxis(o[:, :, :S], 1, 2)
