"""Serving driver over the continuous-batching engine (`repro.serve`).

    PYTHONPATH=src python -m repro.launch.serve --arch bench_tiny \
        --mode continuous --slots 8 --requests 32 --temperature 0.8

Transformer families (dense/moe) run on `ServeEngine`: slotted KV cache,
chunked prefill interleaved with one jitted decode step over the full slot
plane, requests joining/leaving with zero recompiles. `--mode static` keeps
the old lock-step wave batching as a baseline. SSM / hybrid / audio archs
(no transformer prefill) keep the legacy token-by-token lock-step path —
O(1) state makes that the native path anyway.

Loads params from --ckpt (theta_g of a training run) or random-inits.
Fused-mode checkpoints (`fused_updates=True`) store theta_g as ONE flat
fragment plane — `load_params` rebuilds the run's Fragmenter from checkpoint
meta and unpacks the plane back into the per-leaf pytree.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api, transformer


def _unflatten_theta(cfg, theta, meta):
    """Fused-mode checkpoints serialize theta_g as a flat ``(total_rows,
    LANES)`` f32 fragment plane (engine_state stores every engine buffer
    that way). Rebuild the run's Fragmenter from checkpoint meta and unpack
    the plane into the per-leaf parameter pytree."""
    from repro.core.flatplane import LANES
    from repro.core.fragments import make_fragmenter

    theta = jnp.asarray(theta)
    if theta.ndim != 2 or theta.shape[-1] != LANES:
        raise ValueError(
            f"fused checkpoint theta_g has shape {theta.shape}, expected a "
            f"(total_rows, {LANES}) flat fragment plane")
    shape = jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    frag = make_fragmenter(cfg, shape, int(meta.get("num_fragments", 1)),
                           strategy=meta.get("fragment_strategy", "strided"))
    if frag.flat.total_rows != theta.shape[0]:
        raise ValueError(
            f"flat theta_g has {theta.shape[0]} rows but arch "
            f"{cfg.name!r} with num_fragments={meta.get('num_fragments')} "
            f"strategy={meta.get('fragment_strategy')!r} needs "
            f"{frag.flat.total_rows} — checkpoint/arch mismatch")
    template = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shape)
    return frag.flat.unpack_full(template, theta)


def load_params(cfg, ckpt):
    if ckpt:
        from repro.checkpoint import load_pytree
        state = load_pytree(ckpt)
        if isinstance(state, dict) and state.get("format") == "trainer_state_v1":
            # full-run checkpoint (launch/train --ckpt): consensus model lives
            # in the serialized EngineState
            meta = state.get("meta", {})
            arch = meta.get("arch")
            if arch and arch != cfg.name:
                raise ValueError(f"checkpoint was trained on arch {arch!r}, "
                                 f"serving requested {cfg.name!r}")
            params = state["trainer_state"]["engine"]["theta_g"]
            if meta.get("fused_updates") and not isinstance(params, dict):
                return _unflatten_theta(cfg, params, meta)
        else:
            params = state["theta_g"] if "theta_g" in state else state
        return jax.tree.map(jnp.asarray, params)
    return api.init_params(cfg, jax.random.PRNGKey(0))


def _serve_engine(cfg, params, args):
    """Transformer serving on the slot-plane engine (continuous or static)."""
    from repro.serve import Request, ServeEngine

    rng = np.random.default_rng(args.seed)
    reqs = []
    t = 0.0
    for i in range(args.requests):
        t += float(rng.exponential(1.0 / max(args.rps, 1e-9)))
        P = int(rng.integers(max(2, args.prompt_len // 2), args.prompt_len + 1))
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, size=P).astype(np.int32),
            max_new_tokens=int(rng.integers(max(1, args.gen_len // 2),
                                            args.gen_len + 1)),
            arrival_s=t))

    cache_len = max(args.cache_len,
                    api.decode_cache_len(cfg, args.prompt_len + args.gen_len))
    eng = ServeEngine(cfg, params, n_slots=args.slots, cache_len=cache_len,
                      max_prompt=args.prompt_len,
                      prefill_chunk=args.prefill_chunk, mode=args.mode,
                      temperature=args.temperature, seed=args.seed,
                      attn_impl=args.attn_impl)
    recs = eng.run_trace(reqs)
    s = eng.stats()
    print(f"mode={args.mode} slots={args.slots} completed={s['completed']}"
          f"/{len(reqs)}")
    print(f"  virtual: {s['tok_per_s']:.1f} tok/s  occupancy "
          f"{s['occupancy']:.2f}  ttft p50/p99 {s['ttft_p50_s']*1e3:.0f}/"
          f"{s['ttft_p99_s']*1e3:.0f} ms  tok-latency p99 "
          f"{s['tok_latency_p99_s']*1e3:.1f} ms")
    print(f"  dispatches: {s['decode_dispatches']} decode "
          f"(traced {eng.decode_trace_count()}x), "
          f"{s['prefill_dispatches']} prefill; wall {s['wall_s']:.2f}s")
    for rec in recs[:4]:
        head = rec.tokens[:16]
        print(f"  req{rec.rid}: {head}{'...' if len(rec.tokens) > 16 else ''}")
    return 0


def _serve_lockstep(cfg, params, args):
    """Legacy lock-step path for archs without transformer prefill: batch of
    identical-length prompts, token-by-token through decode_step."""
    B, P, G = args.slots, args.prompt_len, args.gen_len
    key = jax.random.PRNGKey(args.seed)
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab)
    cache_len = api.decode_cache_len(cfg, P + G)
    decode = jax.jit(lambda p, c, t: api.decode_step(cfg, p, c, t))

    t0 = time.time()
    cache = api.init_cache(cfg, B, max(cache_len, P + G))
    for t in range(P):
        logits, cache = decode(params, cache, prompts[:, t])
    t_prefill = time.time() - t0
    print(f"prefill {B}x{P} tokens in {t_prefill:.2f}s "
          f"({B*P/max(t_prefill,1e-9):.0f} tok/s)")

    # a dedicated sampling stream, never the key that generated the prompts
    sample_key = jax.random.fold_in(key, 0x5A17)

    def sample(logits, i):
        k = jax.random.fold_in(sample_key, i)
        if args.temperature <= 0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        return jax.random.categorical(k, logits / args.temperature).astype(
            jnp.int32)

    toks = sample(logits, 0)
    outs = [toks]
    t0 = time.time()
    for i in range(1, G):
        logits, cache = decode(params, cache, toks)
        toks = sample(logits, i)
        outs.append(toks)
    dt = time.time() - t0
    gen = jnp.stack(outs, axis=1)
    print(f"decode {B}x{G} tokens in {dt:.2f}s ({B*G/max(dt,1e-9):.1f} tok/s)")
    for b in range(min(B, 4)):
        print(f"  seq{b}: {list(map(int, gen[b][:16]))}"
              f"{'...' if G > 16 else ''}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "static"])
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slots (batch lanes)")
    ap.add_argument("--cache-len", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rps", type=float, default=4.0,
                    help="mean request arrival rate on the virtual clock")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "ref", "flash"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = load_params(cfg, args.ckpt)
    if cfg.family in ("dense", "moe"):
        return _serve_engine(cfg, params, args)
    return _serve_lockstep(cfg, params, args)


if __name__ == "__main__":
    sys.exit(main())
