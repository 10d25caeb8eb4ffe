#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of `BENCHMARK.json`'s `workloads`; its configuration,
traffic mix, per-layer metrics and the limits of its `correct` are data
files under this directory, found by name (`registry.py`). The run exits
non-zero and prints no result unless JAX sees a TPU of a kind in
`peaks.json`, as many chips as the cell asks for.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics with
`--trace 0`, its per-layer metrics with `--trace 1`), `device`, with
`--trace 1` a `breakdown` (the device ops with most time, and the idle gaps
by the program's `repro.*` span open in each), and last `checks`, each
compared number with its limit. Earlier lines carry counts and simulated
seconds, labelled as such.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, Optional, Sequence  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import device, program_trace  # noqa: E402
from benchmarks.chip.registry import Registry  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class Context:
    cfg: Dict
    mix: Dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    kernels: tuple
    counter: Any
    model: Any                        # the reference model module
    log: Callable[[str], None] = log
    t_start: float = T_START
    break_program: Optional[Callable] = None
    control: bool = False

    def window(self) -> program_trace.Window:
        return program_trace.Window(self.trace, self.kernels)

    def memory_peak(self) -> int:
        return device.memory_peak_bytes(self.chips)


def judge(numbers: Dict[str, float], limits: Dict):
    """Each compared number beside its limit (0 where the cell's limits
    file names none), and whether every number is within its limit."""
    checks = {}
    for name, value in numbers.items():
        limit = float(limits[name]["limit"]) if name in limits else 0.0
        checks[name] = {"value": value, "limit": limit}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             registry: Optional[Registry] = None, require_chip: bool = True,
             break_program: Optional[Callable] = None,
             control: bool = False, extra_metrics: Sequence[str] = (),
             details: Optional[Dict] = None) -> Dict:
    """One run of a cell; returns the result object. `require_chip=False`
    and `break_program` exist for the tests, which drive a run on the CPU
    with the timed path broken underneath; `control` adds the readings of
    the control and of the planted faults (`control.py`), each judged
    against the cell's limits, under `"control"`; the benchmark's own runs
    never compute them. With `trace`, `extra_metrics` names readers to run
    besides the cell's own per-layer metrics, and `details`, where given,
    receives what they read: the run's `record`, the `trace` reduction and
    the `end_to_end` numbers."""
    import jax
    reg = registry or Registry()
    cell = reg.workload(workload)
    cfg, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    limits = reg.limits(workload)
    e2e = reg.metrics_of(workload, "end_to_end")
    per_layer = reg.metrics_of(workload, "per_layer")
    if require_chip:
        info = device.guard(int(cell["chips"]))
    else:
        d = jax.devices()[0]
        info = {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}
    names = [m["name"] for m in per_layer]
    names += [n for n in extra_metrics if n not in names]
    readers = {n: reg.metric(n) for n in names} if trace else {}
    kernels = tuple(k for mod in readers.values()
                    for k in getattr(mod, "KERNELS", ()))
    model = reg.model(cfg)
    ctx = Context(cfg=cfg, mix=mix, seed=int(seed), seconds=float(seconds),
                  trace=bool(trace), chips=int(cell["chips"]),
                  kernels=kernels, counter=device.CompileCounter(),
                  model=model, break_program=break_program, control=control)
    if mix["kind"] != "train":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    from benchmarks.chip import train_cell
    res = train_cell.run(ctx)

    units = {m["name"]: m["unit"] for m in e2e + per_layer}
    metrics = {}
    if not trace:
        for m in e2e:
            metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    dev = {**info, "memory_peak_bytes": res["memory_peak_bytes"]}
    breakdown = None
    if trace:
        win = res["window"]
        red = win.reduction
        ctx_run = {"record": res["record"], "trace": red, "config": cfg,
                   "mix": mix, "dims": model.dims(cfg),
                   "peaks": device.peaks_for(info["kind"])
                   if require_chip else None,
                   "end_to_end": res["end_to_end"]}
        if details is not None:
            details.update(ctx_run)
        for name, mod in readers.items():
            v = mod.read(ctx_run)
            if v is not None:
                metrics[name] = {"value": v, "unit": units.get(name, "")}
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps_program"][:10]}
        log(f"[trace] programs {json.dumps(red['programs'])}")
        log(f"[trace] kernels {json.dumps(red['kernels'])}")
        program_trace.log_window(win, res["record"]["steps"], log)

    checks, correct = judge(res["numbers"], limits)
    correct = correct and res["failed"] == 0
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if res.get("control") is not None:
        out["control"] = {}
        for name, numbers in res["control"].items():
            c_checks, c_correct = judge(numbers, limits)
            out["control"][name] = {"correct": c_correct, "checks": c_checks}
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    device.enable_compile_cache(ROOT)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except device.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
