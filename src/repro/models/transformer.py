"""Decoder-only transformer stack: covers families dense, moe, vlm.

Layer params are stacked (leading L axis) and the stack is a single `lax.scan`
(wrapped in `jax.checkpoint` for training) so compile time and HLO size are O(1) in
depth. The same stack is reused by the enc-dec (audio) family in encdec.py.

API (shared by all families via models/api.py):
  init_params(cfg, key)                         -> params
  forward(cfg, params, batch, train)            -> (h, aux)   h: (B,S,D)
  loss_fn(cfg, params, batch)                   -> (loss, metrics)
  init_cache(cfg, params, batch_size, cache_len)-> cache
  decode_step(cfg, params, cache, tokens, pos)  -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import moe as moe_lib
from repro.models.layers import (apply_rope, attn_out, attn_qkv, chunked_cross_entropy,
                                 dense_init, embed_init, gqa_attention, init_attn_params,
                                 rms_norm, swiglu)
from repro.models.layers import cast_params_for_compute
from repro.telemetry import note_kernel


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key):
    dtype = jnp.dtype(cfg.param_dtype)
    L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab
    ks = jax.random.split(key, 8)
    layers = {
        "attn": init_attn_params(ks[0], cfg, L, dtype),
        "ln1": jnp.ones((L, D), dtype),
        "ln2": jnp.ones((L, D), dtype),
    }
    if cfg.moe is not None:
        layers["moe"] = moe_lib.init_moe_params(ks[1], L, D, F, cfg.moe, dtype)
    else:
        layers["mlp"] = {
            "w_gate": dense_init(ks[2], (L, D, F), dtype, fan_in=D),
            "w_up":   dense_init(ks[3], (L, D, F), dtype, fan_in=D),
            "w_down": dense_init(ks[4], (L, F, D), dtype, fan_in=F),
        }
    params = {
        "embed": embed_init(ks[5], (V, D), dtype),
        "layers": layers,
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[6], (D, V), dtype, fan_in=D)
    if cfg.family == "vlm":
        P = cfg.prefix_dim
        params["projector"] = {
            "w1": dense_init(ks[7], (P, D), dtype, fan_in=P),
            "w2": dense_init(jax.random.fold_in(ks[7], 1), (D, D), dtype, fan_in=D),
        }
    return params


def lm_head_weight(cfg: ModelConfig, params):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _seq_shard(x):
    """Sequence-parallel residual constraint (beyond-paper, §Perf iteration 5):
    shard the residual stream's sequence dim over `model` so GSPMD lowers the
    TP boundary as reduce-scatter + all-gather (half the bytes of the Megatron
    all-reduce) and runs norms/elementwise sequence-sharded."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax._src import mesh as mesh_lib
    mesh = mesh_lib.thread_resources.env.physical_mesh  # the `with mesh:` ctx
    names = getattr(mesh, "axis_names", ()) or ()
    if "model" not in names or x.ndim != 3:
        return x
    size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    if x.shape[1] % size != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(None, "model", None)))


def _layer(cfg: ModelConfig, x, lp, positions, window, attn_impl,
           seq_parallel=False):
    # named scopes `attention` and `mlp` reach every op's HLO metadata
    # (forward, remat recompute and backward), so a device trace can be
    # split by layer
    if seq_parallel:
        x = _seq_shard(x)
    with jax.named_scope("attention"):
        h = rms_norm(x, lp["ln1"], cfg.rms_eps)
        q, k, v = attn_qkv(h, lp["attn"], cfg, positions)
        if attn_impl == "flash":
            from repro.kernels.flash_attention import ops as flash_ops
            note_kernel("attention")
            o = flash_ops.flash_attention(q, k, v, causal=True, window=window)
        else:
            o = gqa_attention(q, k, v, causal=True, window=window,
                              q_positions=positions, kv_positions=positions)
        x = x + attn_out(o, lp["attn"], cfg)
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["ln2"], cfg.rms_eps)
        if cfg.moe is not None:
            out = moe_lib.moe_ffn(h, lp["moe"], cfg.moe)
            return x + out.y, out.aux_loss
        return x + swiglu(h, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                          lp["mlp"]["w_down"]), jnp.zeros((), jnp.float32)


def embed_inputs(cfg: ModelConfig, params, batch):
    """Token (+ vlm prefix) embedding, named scope `embed`. Returns
    (x, positions, n_prefix)."""
    with jax.named_scope("embed"):
        emb = params["embed"]
        x = emb[batch["tokens"]].astype(jnp.dtype(cfg.compute_dtype))
        n_prefix = 0
        if cfg.family == "vlm" and "prefix_emb" in batch:
            pj = params["projector"]
            pe = jax.nn.gelu(batch["prefix_emb"].astype(pj["w1"].dtype)
                             @ pj["w1"]) @ pj["w2"]
            x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
            n_prefix = pe.shape[1]
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        return x, positions, n_prefix


def forward(cfg: ModelConfig, params, batch, *, train: bool = True,
            attn_impl: str = "ref", remat: bool = True, unroll: bool = False,
            seq_parallel: bool = False):
    params = cast_params_for_compute(cfg, params)
    x, positions, n_prefix = embed_inputs(cfg, params, batch)
    window = cfg.attn_window

    def body(carry, lp):
        x = carry
        y, aux = _layer(cfg, x, lp, positions, window, attn_impl,
                        seq_parallel=seq_parallel)
        return y, aux

    if unroll:  # roofline probes: loop bodies visible to HLO cost analysis
        auxs = []
        for l in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[l], params["layers"])
            x, aux = body(x, lp)
            auxs.append(aux)
        auxs = jnp.stack(auxs)
    else:
        body_fn = jax.checkpoint(body) if (train and remat) else body
        x, auxs = jax.lax.scan(body_fn, x, params["layers"])
    with jax.named_scope("head"):
        h = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return h, {"moe_aux": jnp.sum(auxs), "n_prefix": n_prefix}


def loss_fn(cfg: ModelConfig, params, batch, *, attn_impl: str = "ref",
            remat: bool = True, xent_chunk: int = 512, unroll: bool = False,
            seq_parallel: bool = False):
    h, aux = forward(cfg, params, batch, train=True, attn_impl=attn_impl, remat=remat,
                     unroll=unroll, seq_parallel=seq_parallel)
    n_prefix = aux["n_prefix"]
    if n_prefix:
        h = h[:, n_prefix:]
    with jax.named_scope("head"):           # with forward's final norm
        nll = chunked_cross_entropy(h, lm_head_weight(cfg, params),
                                    batch["labels"], chunk=xent_chunk)
    loss = nll + aux["moe_aux"]
    return loss, {"nll": nll, "moe_aux": aux["moe_aux"], "ppl": jnp.exp(nll)}


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch_size: int, cache_len: int):
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, batch_size, cache_len, cfg.n_kv_heads, hd)
    dt = jnp.dtype(cfg.compute_dtype)
    return {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "kv_pos": jnp.full((cache_len,), -1, jnp.int32),  # ring-buffer slot -> position
        "pos": jnp.zeros((), jnp.int32),
    }


def _decode_attention(q, kc, vc, kv_pos, pos, window, attn_impl):
    """One decode token's attention over the ring cache: the flash_decode
    kernel ("flash") or its oracle ("ref"). q: (B, 1, H, hd)."""
    from repro.kernels.flash_decode import ops as fd_ops
    impl = {"flash": "auto", "ref": "ref"}[attn_impl]
    return fd_ops.flash_decode(q[:, 0], kc, vc, kv_pos, pos, window=window,
                               impl=impl)[:, None]


def decode_step(cfg: ModelConfig, params, cache, tokens, *,
                window: Optional[int] = None, attn_impl: str = "ref",
                unroll: bool = False):
    """One-token decode. tokens: (B,) int32. Window falls back to the arch's native
    window; pass cfg.long_decode_window for the long_500k variant."""
    window = window if window is not None else cfg.attn_window
    params = cast_params_for_compute(cfg, params)
    pos = cache["pos"]
    C = cache["k"].shape[2]
    slot = pos % C
    B = tokens.shape[0]
    x = params["embed"][tokens][:, None, :].astype(jnp.dtype(cfg.compute_dtype))
    positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    kv_pos = cache["kv_pos"].at[slot].set(pos)

    def body(x, xs):
        lp, kc, vc = xs
        h = rms_norm(x, lp["ln1"], cfg.rms_eps)
        q, k, v = attn_qkv(h, lp["attn"], cfg, positions)
        kc = jax.lax.dynamic_update_slice(kc, k, (0, slot, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, slot, 0, 0))
        o = _decode_attention(q, kc, vc, kv_pos, pos, window, attn_impl)
        x = x + attn_out(o, lp["attn"], cfg)
        h = rms_norm(x, lp["ln2"], cfg.rms_eps)
        if cfg.moe is not None:
            x = x + moe_lib.moe_ffn(h, lp["moe"], cfg.moe).y
        else:
            x = x + swiglu(h, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                           lp["mlp"]["w_down"])
        return x, (kc, vc)

    if unroll:
        ks_l, vs_l = [], []
        for l in range(cfg.n_layers):
            xs_l = jax.tree.map(lambda a: a[l],
                                (params["layers"], cache["k"], cache["v"]))
            x, (kc, vc) = body(x, xs_l)
            ks_l.append(kc)
            vs_l.append(vc)
        ks, vs = jnp.stack(ks_l), jnp.stack(vs_l)
    else:
        x, (ks, vs) = jax.lax.scan(body, x,
                                   (params["layers"], cache["k"], cache["v"]))
    h = rms_norm(x[:, 0], params["final_norm"], cfg.rms_eps)
    logits = h.astype(jnp.float32) @ lm_head_weight(cfg, params).astype(jnp.float32)
    new_cache = {"k": ks, "v": vs, "kv_pos": kv_pos, "pos": pos + 1}
    return logits, new_cache


def init_slot_cache(cfg: ModelConfig, n_slots: int, cache_len: int):
    """Slot-plane KV cache for continuous-batching serving: unlike `init_cache`
    (one shared position map + scalar clock for a lock-step batch), every slot
    carries its OWN ring-buffer position map and decode position, so the plane
    can hold requests at arbitrary, independent depths."""
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, n_slots, cache_len, cfg.n_kv_heads, hd)
    dt = jnp.dtype(cfg.compute_dtype)
    return {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "kv_pos": jnp.full((n_slots, cache_len), -1, jnp.int32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
    }


def decode_step_slotted(cfg: ModelConfig, params, cache, tokens, *, active,
                        window: Optional[int] = None, attn_impl: str = "ref"):
    """One decode step over the whole slot plane. tokens: (B,) int32 (last
    sampled token per slot); active: (B,) bool. Inactive slots are computed
    (the traced shapes never change with batch composition) but neither write
    their cache row nor advance their position — their writes land on a
    deliberately out-of-bounds column and are dropped."""
    window = window if window is not None else cfg.attn_window
    params = cast_params_for_compute(cfg, params)
    pos = cache["pos"]                                  # (B,)
    C = cache["k"].shape[2]
    B = tokens.shape[0]
    bidx = jnp.arange(B)
    slot = jnp.where(active, pos % C, C)                # C -> dropped scatter
    x = params["embed"][tokens][:, None, :].astype(jnp.dtype(cfg.compute_dtype))
    positions = pos[:, None]                            # (B, 1)
    kv_pos = cache["kv_pos"].at[bidx, slot].set(pos, mode="drop")

    def body(x, xs):
        lp, kc, vc = xs
        h = rms_norm(x, lp["ln1"], cfg.rms_eps)
        q, k, v = attn_qkv(h, lp["attn"], cfg, positions)
        kc = kc.at[bidx, slot].set(k[:, 0], mode="drop")
        vc = vc.at[bidx, slot].set(v[:, 0], mode="drop")
        o = _decode_attention(q, kc, vc, kv_pos, pos, window, attn_impl)
        x = x + attn_out(o, lp["attn"], cfg)
        h = rms_norm(x, lp["ln2"], cfg.rms_eps)
        if cfg.moe is not None:
            x = x + moe_lib.moe_ffn(h, lp["moe"], cfg.moe).y
        else:
            x = x + swiglu(h, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                           lp["mlp"]["w_down"])
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(body, x,
                               (params["layers"], cache["k"], cache["v"]))
    h = rms_norm(x[:, 0], params["final_norm"], cfg.rms_eps)
    logits = h.astype(jnp.float32) @ lm_head_weight(cfg, params).astype(jnp.float32)
    new_cache = {"k": ks, "v": vs, "kv_pos": kv_pos,
                 "pos": pos + active.astype(jnp.int32)}
    return logits, new_cache


def prefill_chunk_slotted(cfg: ModelConfig, params, cache, tokens, slot, start,
                          n_valid, *, window: Optional[int] = None):
    """Prefill ONE fixed-size chunk of ONE slot's prompt into the slot plane.

    tokens: (Pc,) int32 (entries past n_valid ignored); slot/start/n_valid:
    traced scalars (so admission order never retraces). Writes the chunk's K/V
    into the slot's cache row at ring positions start..start+n_valid-1, sets
    cache['pos'][slot] = start + n_valid, and returns (last_logits, cache)
    where last_logits (V,) are the logits at the chunk's last valid token —
    the first-token sampling point when the chunk completes the prompt."""
    window = window if window is not None else cfg.attn_window
    params = cast_params_for_compute(cfg, params)
    C = cache["k"].shape[2]
    Pc = tokens.shape[0]
    ar = jnp.arange(Pc, dtype=jnp.int32)
    positions = (start + ar)[None]                      # (1, Pc)
    valid = ar < n_valid
    widx = jnp.where(valid, (start + ar) % C, C)        # C -> dropped scatter
    x = params["embed"][tokens][None].astype(jnp.dtype(cfg.compute_dtype))
    kv_row = jax.lax.dynamic_slice_in_dim(cache["kv_pos"], slot, 1, axis=0)
    kv_row = kv_row[0].at[widx].set(start + ar, mode="drop")[None]  # (1, C)
    kv_mask = kv_row >= 0

    k_rows = jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, axis=1)
    v_rows = jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, axis=1)

    def body(x, xs):
        lp, kc, vc = xs                                 # kc/vc: (1, C, KV, hd)
        h = rms_norm(x, lp["ln1"], cfg.rms_eps)
        q, k, v = attn_qkv(h, lp["attn"], cfg, positions)
        kc = kc[0].at[widx].set(k[0], mode="drop")[None]
        vc = vc[0].at[widx].set(v[0], mode="drop")[None]
        # chunk queries attend over the updated row: earlier cache content plus
        # the in-chunk prefix, both selected by position (kp <= qp)
        o = gqa_attention(q, kc, vc, causal=True, window=window,
                          q_positions=positions, kv_positions=kv_row,
                          kv_mask=kv_mask)
        x = x + attn_out(o, lp["attn"], cfg)
        h = rms_norm(x, lp["ln2"], cfg.rms_eps)
        if cfg.moe is not None:
            x = x + moe_lib.moe_ffn(h, lp["moe"], cfg.moe).y
        else:
            x = x + swiglu(h, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                           lp["mlp"]["w_down"])
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], k_rows, v_rows))
    last = jnp.clip(n_valid - 1, 0, Pc - 1)
    h_last = jax.lax.dynamic_slice_in_dim(x[0], last, 1, axis=0)[0]  # (D,)
    h_last = rms_norm(h_last, params["final_norm"], cfg.rms_eps)
    logits = h_last.astype(jnp.float32) @ lm_head_weight(cfg, params).astype(
        jnp.float32)
    new_cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], ks, slot, axis=1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], vs, slot, axis=1),
        "kv_pos": jax.lax.dynamic_update_slice_in_dim(cache["kv_pos"], kv_row,
                                                      slot, axis=0),
        "pos": cache["pos"].at[slot].set(start + n_valid),
    }
    return logits, new_cache


def prefill(cfg: ModelConfig, params, batch, *, cache_len: Optional[int] = None):
    """Run the prompt through the stack, returning (last-token logits, cache).
    Requires cache_len >= prompt length (no ring wrap during prefill)."""
    params = cast_params_for_compute(cfg, params)
    x, positions, n_prefix = embed_inputs(cfg, params, batch)
    B, S = x.shape[:2]
    C = cache_len or S
    assert C >= S, "prefill requires cache_len >= prompt length"
    window = cfg.attn_window

    def body(x, lp):
        h = rms_norm(x, lp["ln1"], cfg.rms_eps)
        q, k, v = attn_qkv(h, lp["attn"], cfg, positions)
        o = gqa_attention(q, k, v, causal=True, window=window,
                          q_positions=positions, kv_positions=positions)
        x = x + attn_out(o, lp["attn"], cfg)
        h = rms_norm(x, lp["ln2"], cfg.rms_eps)
        if cfg.moe is not None:
            x = x + moe_lib.moe_ffn(h, lp["moe"], cfg.moe).y
        else:
            x = x + swiglu(h, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                           lp["mlp"]["w_down"])
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    h = rms_norm(x[:, -1], params["final_norm"], cfg.rms_eps)
    logits = h.astype(jnp.float32) @ lm_head_weight(cfg, params).astype(jnp.float32)
    pad = C - S
    hd = cfg.resolved_head_dim
    kc = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    kv_pos = jnp.where(jnp.arange(C) < S, jnp.arange(C), -1).astype(jnp.int32)
    cache = {"k": kc, "v": vc, "kv_pos": kv_pos, "pos": jnp.array(S, jnp.int32)}
    return logits, cache
