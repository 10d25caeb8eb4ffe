#!/usr/bin/env python3
"""Chip smoke: CoCoDC training and serving at paper_150m widths on one TPU.

    python chip_smoke.py              # one TPU chip; exits nonzero without one
    python chip_smoke.py --reduced    # rehearsal at the reduced widths, any
                                      # backend (Pallas kernels interpreted on
                                      # the CPU)

One process, four phases; no phase catches its own failure:

  device   platform, device_kind and count; anything but a TPU exits nonzero
           (unless --reduced)
  kernels  each Pallas kernel on the path, at paper_150m shapes, against its
           ref.py oracle; on the TPU its lowering must hold a tpu_custom_call
           (compiled for the chip, not interpreted)
  train    cocodc, M=4 regions vmapped on the chip, K=4 fragments, through
           repro.api.build_experiment: per-step loss, compile seconds,
           steady seconds per segment (each ends in block_until_ready), peak
           HBM; at least one initiation and one delay-compensated delivery
  serve    the trained consensus model on ServeEngine (continuous, 8 slots),
           flash_decode against the attn_impl="ref" engine, each decode step
           traced exactly once: at the bf16 serving dtype (agreement
           reported), and at f32 activations with f32 matmuls, where the
           greedy tokens must be equal

The last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "paper_150m"
M, K, H, TAU = 4, 4, 8, 2          # regions, fragments, local steps, overlap
TRAIN_STEPS = 16
# The largest per-region batch at seq 1024 whose segment program, plus the
# resident cocodc engine state, stays well inside 16 GB of HBM (compiled for
# v5e: 10.3 GB program + 3.8 GB engine at 2; 12.2 + 3.8 GB at 4).
FULL_LOCAL_BATCH, FULL_SEQ = 2, 1024
N_SLOTS, CACHE_LEN, MAX_PROMPT, PREFILL_CHUNK = 8, 128, 64, 16
N_REQUESTS, NEW_TOKENS = 8, 16


def log(msg: str) -> None:
    print(msg, flush=True)


def phase_device(args):
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    log(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if d.platform != "tpu" and not args.reduced:
        print(f"chip_smoke: needs a TPU, JAX found {d.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    return info


def _check_kernel(name, pallas_fn, ref_fn, inputs, *, rtol, atol, on_tpu):
    """Run one kernel and its oracle on the same inputs and compare."""
    fn = jax.jit(pallas_fn)
    if on_tpu:
        text = fn.lower(*inputs).as_text()
        assert "tpu_custom_call" in text, f"{name}: no tpu_custom_call"
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*inputs))
    dt = time.perf_counter() - t0
    ref = jax.jit(ref_fn)(*inputs)
    outs = out if isinstance(out, (tuple, list)) else (out,)
    refs = ref if isinstance(ref, (tuple, list)) else (ref,)
    err = 0.0
    for o, r in zip(outs, refs, strict=True):
        o = np.asarray(o, np.float32)
        r = np.asarray(r, np.float32)
        np.testing.assert_allclose(o, r, rtol=rtol, atol=atol, err_msg=name)
        err = max(err, float(np.max(np.abs(o - r))))
    shapes = [tuple(x.shape) for x in inputs if hasattr(x, "shape")]
    log(f"[kernels] {name} {shapes} max|pallas-ref|={err:.3e} "
        f"first call {dt:.2f}s")


def phase_kernels(cfg, seq_len, on_tpu):
    from repro.core.fragments import make_fragmenter
    from repro.kernels.delay_comp.ops import delay_comp_array
    from repro.kernels.delay_comp.ref import delay_comp_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    from repro.kernels.flash_decode.ops import flash_decode
    from repro.kernels.flash_decode.ref import flash_decode_ref
    from repro.kernels.outer_update.ops import fused_deliver, outer_nesterov
    from repro.models import api

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    f32 = dict(rtol=1e-5, atol=1e-6, on_tpu=on_tpu)
    bf16 = dict(rtol=3e-2, atol=3e-2, on_tpu=on_tpu)

    def normal(shape, dtype=jnp.float32, scale=0.02):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    # delay_comp: the per-leaf Algorithm-1 kernel on the largest leaf, the
    # embedding (vocab x d_model, tiled to rows of 1024 lanes)
    shape = (cfg.vocab, cfg.d_model)
    tp = normal(shape)
    tl = tp + normal(shape, scale=1e-3)
    tg = tp + normal(shape, scale=1e-3)
    dc = dict(tau=float(TAU), lam=0.5, H=float(H), sign=1.0)
    _check_kernel(
        "delay_comp_2d",
        lambda a, b, c: delay_comp_array(a, b, c, impl="pallas", **dc),
        lambda a, b, c: delay_comp_ref(a, b, c, **dc),
        (tl, tp, tg), **f32)
    del tp, tl, tg

    # outer_update: nesterov and both deliver modes on fragment 0 of the
    # flat plane, worker-stacked over M regions with one region offline
    pshape = jax.eval_shape(lambda k: api.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    rows = make_fragmenter(cfg, pshape, K).flat.rows(0)
    lanes = 1024
    theta, mom, delta = (normal((rows, lanes)) for _ in range(3))
    nest = dict(lr=0.7, mu=0.9)
    _check_kernel(
        "outer_nesterov",
        lambda t, m, d: outer_nesterov(t, m, d, impl="pallas", **nest),
        lambda t, m, d: outer_nesterov(t, m, d, impl="ref", **nest),
        (theta, mom, delta), **f32)
    local = normal((M, rows, lanes))
    snap = local - normal((M, rows, lanes), scale=1e-3)
    avail = jnp.asarray([1.0] * (M - 1) + [0.0], jnp.float32)
    for mode, kw in (("blend", dict(alpha=0.5)),
                     ("compensate", dict(tau=float(TAU), lam=0.5,
                                         H=float(H), sign=1.0))):
        _check_kernel(
            f"outer_deliver_{mode}",
            lambda lo, s, g, a, mode=mode, kw=kw: fused_deliver(
                lo, s, g, a, mode=mode, impl="pallas", **kw),
            lambda lo, s, g, a, mode=mode, kw=kw: fused_deliver(
                lo, s, g, a, mode=mode, impl="ref", **kw),
            (local, snap, theta, avail), **f32)
    del theta, mom, delta, local, snap

    # flash_decode at the serve shapes: every slot at its own depth, the
    # rest of its ring empty (-1)
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = normal((N_SLOTS, nh, hd), jnp.bfloat16, 1.0)
    kc = normal((N_SLOTS, CACHE_LEN, nkv, hd), jnp.bfloat16, 1.0)
    vc = normal((N_SLOTS, CACHE_LEN, nkv, hd), jnp.bfloat16, 1.0)
    depth = np.random.default_rng(0).integers(1, CACHE_LEN + 1, N_SLOTS)
    ar = np.arange(CACHE_LEN)
    kv_pos = jnp.asarray(np.where(ar[None] < depth[:, None], ar[None], -1),
                         jnp.int32)
    q_pos = jnp.asarray(depth - 1, jnp.int32)
    _check_kernel(
        "flash_decode",
        lambda *a: flash_decode(*a, impl="pallas"),
        flash_decode_ref, (q, kc, vc, kv_pos, q_pos), **bf16)

    # flash_attention: one full training sequence, causal
    q, k, v = (normal((1, seq_len, nh, hd), jnp.bfloat16, 1.0)
               for _ in range(3))
    _check_kernel(
        "flash_attention",
        lambda a, b, c: flash_attention(a, b, c, impl="pallas"),
        lambda a, b, c: flash_attention_ref(a, b, c, causal=True),
        (q, k, v), **bf16)


class _TimedRunner:
    """Wraps the trainer's SegmentRunner: each segment ends in
    block_until_ready, and its duration, length, per-step losses and
    whether it compiled a new program are recorded."""

    def __init__(self, runner):
        self.runner = runner
        self.segments = []

    def __call__(self, params_stack, opt_state, batch_seg, lrs):
        traces = self.runner.trace_count
        t0 = time.perf_counter()
        out = jax.block_until_ready(
            self.runner(params_stack, opt_state, batch_seg, lrs))
        dt = time.perf_counter() - t0
        losses = np.asarray(out[2], np.float64).mean(axis=1)
        self.segments.append({"n": int(lrs.shape[0]), "s": dt,
                              "compiled": self.runner.trace_count > traces,
                              "losses": losses.tolist()})
        return out


def phase_train(args, cfg_reduced: bool, local_batch: int, seq_len: int):
    from repro.api import ExperimentSpec, build_experiment
    from repro.api.spec import MethodSpec, ModelRef, RunSpec

    spec = ExperimentSpec(
        name="chip_smoke",
        model=ModelRef(arch=ARCH, reduced=cfg_reduced),
        method=MethodSpec(name="cocodc", num_workers=M, local_steps=H,
                          num_fragments=K, overlap_depth=TAU),
        run=RunSpec(steps=TRAIN_STEPS, seed=args.seed,
                    local_batch=local_batch, seq_len=seq_len,
                    eval_every=TRAIN_STEPS, eval_batch=4))
    t0 = time.perf_counter()
    trainer = build_experiment(spec)
    log(f"[train] built {ARCH}{' (reduced)' if cfg_reduced else ''} "
        f"M={M} K={K} H={H} tau={TAU} local_batch={local_batch} "
        f"seq={seq_len} in {time.perf_counter() - t0:.1f}s")
    timed = _TimedRunner(trainer.segment_runner)
    trainer.segment_runner = timed
    t0 = time.perf_counter()
    hist = trainer.run(eval_every=TRAIN_STEPS, log=log)
    run_s = time.perf_counter() - t0

    losses = [x for seg in timed.segments for x in seg["losses"]]
    for i, loss in enumerate(losses):
        log(f"[train] step {i:2d} loss {loss:.6f}")
    assert len(losses) == TRAIN_STEPS, losses
    assert all(math.isfinite(x) for x in losses), losses
    compile_s = sum(s["s"] for s in timed.segments if s["compiled"])
    steady = [s for s in timed.segments if not s["compiled"]]
    log(f"[train] segments (steps, seconds, compiled): "
        f"{[(s['n'], round(s['s'], 4), s['compiled']) for s in timed.segments]}")
    log(f"[train] compile segments {compile_s:.2f}s "
        f"({sum(s['compiled'] for s in timed.segments)} programs)")
    if steady:
        st = sum(s["s"] for s in steady)
        ns = sum(s["n"] for s in steady)
        tok = ns * M * local_batch * seq_len
        log(f"[train] steady: {len(steady)} segments, {ns} steps in "
            f"{st:.4f}s = {st / len(steady):.4f} s/segment, "
            f"{st / ns:.4f} s/step, {tok / st:.1f} tokens/s")
    else:
        log("[train] steady: no segment ran without compiling")

    eng = trainer.engine
    last_sync = np.asarray(eng.state.last_sync)
    delivered = int(np.sum(last_sync >= 0))
    log(f"[train] n_syncs={eng.n_syncs} deliveries_applied={delivered} "
        f"in_flight={len(eng.pending)} eval_nll={hist[-1]['nll']:.6f} "
        f"run {run_s:.1f}s")
    assert eng.n_syncs >= 1, "no initiation"
    assert delivered >= 1, "no delay-compensated delivery"
    assert math.isfinite(hist[-1]["nll"])
    stats = jax.devices()[0].memory_stats()
    if stats:
        log(f"[train] peak_bytes_in_use={stats['peak_bytes_in_use']} "
            f"({stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB)")
    return trainer.mcfg, eng.theta_g


def _serve(cfg, params, reqs, impl, seed, label):
    """All of `reqs` through one continuous-mode ServeEngine; rid -> tokens."""
    from repro.serve import ServeEngine

    eng = ServeEngine(cfg, params, n_slots=N_SLOTS, cache_len=CACHE_LEN,
                      max_prompt=MAX_PROMPT, prefill_chunk=PREFILL_CHUNK,
                      mode="continuous", attn_impl=impl, seed=seed)
    recs = eng.run_trace(reqs)
    tokens = {r.rid: r.tokens for r in recs}
    log(f"[serve] {label} {impl}: {len(recs)} requests, "
        f"{sum(len(t) for t in tokens.values())} tokens, "
        f"{eng.n_decode_dispatches} decode / {eng.n_prefill_dispatches} "
        f"prefill dispatches, decode traced {eng.decode_trace_count()}x, "
        f"wall {eng.wall_s:.2f}s (compile included)")
    assert len(recs) == len(reqs)
    assert all(len(t) == NEW_TOKENS for t in tokens.values())
    assert eng.decode_trace_count() == 1, eng.decode_trace_count()
    return tokens


def _agreement(label, a, b):
    """Requests whose greedy tokens agree; logs where the others split."""
    for rid in sorted(a):
        split = next((i for i, (x, y) in enumerate(zip(a[rid], b[rid]))
                      if x != y), None)
        if split is not None:
            log(f"[serve] {label} req{rid} splits at new token {split}")
    same = sum(a[r] == b[r] for r in a)
    log(f"[serve] {label} flash_decode == ref greedy tokens: "
        f"{same}/{len(a)}")
    return same


def phase_serve(cfg, params, seed: int):
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=int(rng.integers(16, 65))
                                        ).astype(np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i in range(N_REQUESTS)]
    # the serving dtype (bf16 activations and KV cache). Not compared
    # token for token: the flash and ref engines are two XLA programs that
    # round bf16 activations differently, and at the near-flat logits of a
    # 16-step model greedy argmax flips on that rounding
    bf16 = {impl: _serve(cfg, params, reqs, impl, seed, "bf16")
            for impl in ("flash", "ref")}
    _agreement("bf16", bf16["flash"], bf16["ref"])
    # f32 activations and f32 matmuls: the engines differ only in the
    # attention implementation, so their greedy tokens must be equal
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        f32 = {impl: _serve(cfg32, params, reqs, impl, seed, "f32")
               for impl in ("flash", "ref")}
    for rid in sorted(f32["flash"]):
        log(f"[serve] f32 req{rid}: {f32['flash'][rid]}")
    same = _agreement("f32", f32["flash"], f32["ref"])
    assert same == N_REQUESTS, (f32["flash"], f32["ref"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reduced", action="store_true",
                    help="rehearse at the reduced widths on any backend")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    info = phase_device(args)
    log(f"[device] compilation cache: {cache_dir}")
    on_tpu = info["platform"] == "tpu"

    from repro.api.build import resolve_model
    from repro.api import ExperimentSpec
    from repro.api.spec import ModelRef
    cfg = resolve_model(ExperimentSpec(model=ModelRef(arch=ARCH,
                                                      reduced=args.reduced)))
    local_batch, seq_len = (2, 64) if args.reduced else (FULL_LOCAL_BATCH,
                                                         FULL_SEQ)
    log(f"[device] model {cfg.name}: {cfg.n_layers}x{cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab}")

    t0 = time.perf_counter()
    phase_kernels(cfg, seq_len, on_tpu)
    log(f"[kernels] done in {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    cfg, theta_g = phase_train(args, args.reduced, local_batch, seq_len)
    log(f"[train] done in {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    phase_serve(cfg, theta_g, args.seed)
    log(f"[serve] done in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
