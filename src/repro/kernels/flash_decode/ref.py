"""Oracle: one-token GQA attention over a (ring-buffer) KV cache, via the shared
reference attention. Accepts shared (C,)/() or per-slot (B, C)/(B,) positions,
like the kernel wrapper.

Both matmuls run at f32 precision, as the kernel's do: at the TPU's default
precision the PV product would round the probabilities to bf16, and greedy
decoding at near-flat logits would then pick up the difference."""
from __future__ import annotations

from repro.models.layers import gqa_attention


def flash_decode_ref(q, k_cache, v_cache, kv_positions, q_position, *,
                     window=None):
    """q: (B, H, hd); caches: (B, C, KV, hd); kv_positions: (C,) or (B, C)
    int32 (-1 = empty slot); q_position: () or (B,) int32. Returns (B, H, hd)."""
    import jax
    import jax.numpy as jnp
    B = q.shape[0]
    C = k_cache.shape[1]
    q4 = q[:, None]                                     # (B, 1, H, hd)
    qpos = jnp.asarray(q_position, jnp.int32)
    if qpos.ndim == 0:
        qpos = jnp.broadcast_to(qpos[None], (B,))
    kvpos = jnp.asarray(kv_positions, jnp.int32)
    if kvpos.ndim == 1:
        kvpos = jnp.broadcast_to(kvpos[None], (B, C))
    out = gqa_attention(q4, k_cache, v_cache, causal=True, window=window,
                        q_positions=qpos[:, None], kv_positions=kvpos,
                        kv_mask=kvpos >= 0,
                        precision=jax.lax.Precision.HIGHEST)
    return out[:, 0]
