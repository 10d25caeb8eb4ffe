"""End-to-end cross-region training driver.

    PYTHONPATH=src python -m repro.launch.train --arch paper_150m --method cocodc \
        --steps 400 --workers 4 --local-batch 4 --seq-len 64

Every run is defined by a declarative `ExperimentSpec` (repro.api): the CLI
flags map onto spec fields, `--spec path.json` launches from a saved spec
(explicit flags override its fields), and `--print-spec` emits the composed
spec as JSON without training — feed it back via `--spec` to reproduce the
run bitwise. The trainer itself is always constructed through
`repro.api.build_experiment`.

Runs the full stack: synthetic non-IID per-worker data -> worker-stacked inner
AdamW -> protocol engine (any registered sync method) -> periodic
consensus-model eval -> checkpoint.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

from repro.api import ExperimentSpec, build_experiment, registered_methods
from repro.core.network import MESH_PROFILES, SCENARIOS
from repro.launch.compile_cache import enable_compile_cache


def spec_from_args(args) -> ExperimentSpec:
    """Map CLI flags onto an ExperimentSpec. With --spec, the file is the
    base and explicitly-passed flags override its fields; without, the spec
    dataclass defaults are the CLI defaults. (Every flag defaults to None =
    "not passed"; boolean flags are three-state — `--x` / `--no-x` / unset —
    so a spec-file boolean can be cleared from the CLI, e.g.
    `--spec routed.json --method streaming --no-adaptive-resync`.)"""
    spec = (ExperimentSpec.from_json_file(args.spec) if args.spec
            else ExperimentSpec())

    def over(obj, **kw):
        kw = {k: v for k, v in kw.items() if v is not None}
        return dataclasses.replace(obj, **kw) if kw else obj

    model = over(spec.model, arch=args.arch, reduced=args.reduced)
    ext = over(spec.method.extensions,
               fragment_strategy=args.fragment_strategy,
               link_pricing=args.link_pricing,
               adaptive_resync=args.adaptive_resync,
               wire_codec=args.wire_codec,
               codec_block=args.codec_block,
               codec_error_feedback=args.codec_error_feedback,
               fused_updates=args.fused_updates)
    method = over(spec.method, name=args.method, num_workers=args.workers,
                  local_steps=args.H, num_fragments=args.fragments,
                  overlap_depth=args.tau, comp_lambda=args.comp_lambda,
                  net_utilization=args.gamma, mixing_alpha=args.alpha)
    method = dataclasses.replace(method, extensions=ext)
    network = over(spec.network, topology=args.topology, mesh=args.mesh,
                   mesh_seed=args.mesh_seed, dynamics=args.dynamics,
                   step_time_s=args.step_time, routing=args.routing,
                   hub_failover=args.hub_failover,
                   channel_scheduler=args.channel_scheduler,
                   multipath_k=args.multipath_k,
                   concurrent_collectives=args.concurrent_collectives)
    run = over(spec.run, steps=args.steps, seed=args.seed, inner_lr=args.lr,
               local_batch=args.local_batch, seq_len=args.seq_len,
               eval_every=args.eval_every, ckpt_every=args.ckpt_every,
               engine_impl=args.engine_impl, loop=args.loop)
    return dataclasses.replace(spec, model=model, method=method,
                               network=network, run=run)


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="Cross-region training driver. Flag defaults are the "
                    "ExperimentSpec defaults (shown in --print-spec); with "
                    "--spec, flags you pass explicitly override the file.")
    ap.add_argument("--spec", default=None,
                    help="launch from a saved ExperimentSpec JSON "
                         "(experiments/specs/*.json); explicit flags override")
    ap.add_argument("--print-spec", action="store_true",
                    help="print the composed ExperimentSpec as JSON and exit "
                         "(feed it back via --spec to reproduce the run)")
    ap.add_argument("--arch", default=None, help="architecture config id "
                    "(default paper_150m)")
    ap.add_argument("--reduced", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="use the reduced smoke variant of the arch (CPU-friendly)")
    ap.add_argument("--method", default=None,
                    choices=sorted(registered_methods()),
                    help="registered sync method (default cocodc)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--H", type=int, default=None, help="local steps per round")
    ap.add_argument("--fragments", type=int, default=None)
    ap.add_argument("--tau", type=int, default=None)
    ap.add_argument("--comp-lambda", type=float, default=None)
    ap.add_argument("--gamma", type=float, default=None)
    ap.add_argument("--alpha", type=float, default=None)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--local-batch", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--eval-every", type=int, default=None)
    ap.add_argument("--topology", default=None, choices=sorted(SCENARIOS),
                    help="heterogeneous WAN scenario (default: calibrated "
                         "symmetric paper network)")
    ap.add_argument("--mesh", default=None, choices=sorted(MESH_PROFILES),
                    help="generated N-region mesh profile (N = --workers); "
                         "mutually exclusive with --topology")
    ap.add_argument("--mesh-seed", type=int, default=None,
                    help="seed for --mesh generation and --dynamics draws")
    ap.add_argument("--dynamics", default=None,
                    help="time-varying link dynamics spec, e.g. "
                         "'diurnal:period=240:depth=0.6,hub_failure:start=100:"
                         "dur=50,jitter:frac=0.05' (see "
                         "repro.core.network.parse_dynamics)")
    ap.add_argument("--fragment-strategy", default=None,
                    choices=["", "strided", "contiguous", "skewed"],
                    help="model fragmentation strategy ('' = strided)")
    ap.add_argument("--step-time", type=float, default=None,
                    help="T_c seconds per local step for --topology/--mesh "
                         "scenarios")
    ap.add_argument("--engine-impl", default=None, choices=["jit", "host"],
                    help="jitted EngineState transitions vs eager host path")
    ap.add_argument("--loop", default=None, choices=["segment", "per_step"],
                    help="segment-scanned execution engine (one lax.scan "
                         "dispatch per inter-event segment) vs the legacy "
                         "one-dispatch-per-step loop")
    ap.add_argument("--link-pricing", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="Algorithm-2 link-aware fragment pricing (R_p/T_s,p)")
    ap.add_argument("--routing", default=None,
                    choices=["static", "routed"],
                    help="routed communication plans: every collective runs "
                         "over deterministic multi-hop min-cost routes "
                         "computed against the CURRENT link state, re-planned "
                         "at each dynamics edge (static = fixed "
                         "ring/hierarchical formulas)")
    ap.add_argument("--hub-failover", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="with --routing routed: re-elect the next-best-"
                         "connected region as hub while the declared hub's "
                         "links are out (restored on recovery); fully dark "
                         "regions drop out of the collective")
    ap.add_argument("--channel-scheduler", default=None,
                    choices=["serial", "fairshare"],
                    help="WAN traffic plane: serial = fixed channel queue "
                         "(bitwise-pinned default); fairshare = max-min "
                         "water-filling bandwidth sharing over all in-flight "
                         "transfers (links as shared resources)")
    ap.add_argument("--multipath-k", default=None, type=int,
                    help="with --routing routed: split each logical link's "
                         "payload across up to k edge-disjoint min-cost "
                         "paths (inverse-cost byte shares; default 1)")
    ap.add_argument("--concurrent-collectives", default=None, type=int,
                    help="serial scheduler's WAN channel pool size "
                         "(explicit topologies/meshes only; default 1)")
    ap.add_argument("--adaptive-resync", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="re-derive Eq. 9's target sync count N (and Eq. "
                         "10's h) each outer round from measured transfer "
                         "durations (cocodc)")
    ap.add_argument("--wire-codec", default=None,
                    choices=["none", "int8", "int4"],
                    help="quantize pseudo-gradient deltas before the WAN "
                         "(per-block absmax, kernels/delta_codec); none "
                         "keeps the raw f32/sync_dtype wire bitwise")
    ap.add_argument("--codec-block", default=None, type=int,
                    help="elements per quantization block (one f32 scale "
                         "ships per block; default 256)")
    ap.add_argument("--codec-error-feedback", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="keep quantization residuals locally and fold them "
                         "into the next initiation of the same elements "
                         "(EF-SGD; default on)")
    ap.add_argument("--fused-updates", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="route protocol transitions through the flat "
                         "fragment plane + fused outer-update kernels (one "
                         "Pallas dispatch per fragment per stage; default "
                         "off = per-leaf path, bitwise vs prior releases)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="atomically checkpoint the FULL run state to --ckpt "
                         "every N steps (segment boundaries)")
    ap.add_argument("--resume", default=None,
                    help="checkpoint to resume from: a trainer_state_v1 "
                         "checkpoint restores the full run (exact trajectory); "
                         "a legacy dict restores theta_g/momentum only")
    ap.add_argument("--stop-at", type=int, default=None,
                    help="pause the run at this absolute step (the LR schedule "
                         "still spans the spec's steps); checkpoint with "
                         "--ckpt and continue later with --resume")
    ap.add_argument("--history-out", default=None)
    return ap


def main(argv=None):
    ap = make_parser()
    args = ap.parse_args(argv)
    try:
        spec = spec_from_args(args).validate()
    except (ValueError, OSError) as e:
        ap.error(str(e))
    if args.print_spec:
        print(spec.to_json())
        return 0
    if spec.run.ckpt_every and not args.ckpt:
        ap.error("--ckpt-every requires --ckpt (nowhere to save)")

    enable_compile_cache()
    trainer = build_experiment(spec)
    if args.resume:
        from repro.checkpoint import load_pytree
        from repro.core.trainer import CKPT_FORMAT
        state = load_pytree(args.resume)
        if isinstance(state, dict) and state.get("format") == CKPT_FORMAT:
            trainer.restore_checkpoint(args.resume, state=state)
            print(f"resumed full run state from {args.resume} "
                  f"(step {trainer.step}, wall {trainer.engine.wall_clock:.0f}s)")
        else:
            # legacy partial checkpoint: consensus model + outer momentum only
            import jax
            import jax.numpy as jnp
            trainer.engine.theta_g = jax.tree.map(
                lambda a, b: b.astype(a.dtype) if hasattr(b, "astype") else b,
                trainer.engine.theta_g, state["theta_g"])
            trainer.engine.momentum = jax.tree.map(
                lambda a, b: b.astype(a.dtype) if hasattr(b, "astype") else b,
                trainer.engine.momentum, state["momentum"])
            # workers restart from the restored consensus
            trainer.params_stack = jax.tree.map(
                lambda g: jnp.broadcast_to(
                    g[None], (trainer.ccfg.num_workers,) + g.shape).copy(),
                trainer.engine.theta_g)
            print(f"resumed (legacy: theta_g/momentum only) from {args.resume} "
                  f"(step {state.get('step')})")
    t0 = time.time()
    hist = trainer.run(steps=args.stop_at, eval_every=spec.run.eval_every,
                       log=lambda s: print(s, flush=True),
                       ckpt_path=args.ckpt, ckpt_every=spec.run.ckpt_every)
    dt = time.time() - t0
    stats = trainer.engine.stats()
    link_stats = trainer.engine.link_stats()
    print(f"done in {dt:.1f}s host-time; simulated wall {stats['wall_clock_s']:.0f}s;"
          f" comm hidden {stats['overlap_ratio']*100:.0f}%", flush=True)
    print(f"host counters: {trainer.telemetry.line()}", flush=True)
    if stats.get("stall_seconds"):
        print(f"dynamic links: stalled {stats['stall_seconds']:.1f}s "
              f"({stats['stall_fraction']*100:.0f}% of WAN time), "
              f"{int(stats['n_retries'])} outage retries", flush=True)
    if spec.network.routing == "routed":
        print(f"routed planner: {int(stats['reroutes'])} reroutes, "
              f"{int(stats['hub_elections'])} hub elections", flush=True)
    if spec.network.channel_scheduler == "fairshare" or \
            spec.network.multipath_k > 1:
        print(f"traffic plane ({spec.network.channel_scheduler}): transfer "
              f"sojourn mean {stats['transfer_mean_s']:.2f}s "
              f"p95 {stats['transfer_p95_s']:.2f}s, "
              f"{int(stats['multipath_splits'])} multipath splits", flush=True)
    if link_stats["links"]:
        print("per-link WAN traffic:", flush=True)
        for link, rec in sorted(link_stats["links"].items()):
            print(f"  {link:32s} {rec['bytes']/1e9:9.3f} GB "
                  f"busy {rec['busy_seconds']:8.1f}s "
                  f"({rec['busy_fraction']*100:4.1f}%)", flush=True)
        print(f"  busiest link: {link_stats['busiest_link']}", flush=True)
    if args.ckpt:
        trainer.save_checkpoint(args.ckpt)
        print(f"checkpoint (full run state) -> {args.ckpt}")
    if args.history_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.history_out)),
                    exist_ok=True)
        with open(args.history_out, "w") as f:
            json.dump({"args": vars(args), "spec": spec.to_dict(),
                       "history": hist, "stats": stats,
                       "link_stats": link_stats}, f, indent=1)
        print(f"history -> {args.history_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
