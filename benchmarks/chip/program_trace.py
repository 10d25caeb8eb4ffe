#!/usr/bin/env python3
"""The program's own spans, counters and named scopes, read from one traced
window of a training cell.

    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n> \
        --seconds <s>

`run.py` reduces a trace by program, by kernel and by the harness's own
`bench.*` spans. The trainer now writes its own: host spans `repro.*`
(`repro.telemetry.span`), host counters (`trainer.telemetry`) and the named
scopes `embed`, `attention`, `mlp`, `head` and `adamw` in the segment
program's op metadata. This module reads them:

- `reduce_program` gives `scopes`, the device seconds and op count of the
  `run_segment` ops in the window by scope, each split into `forward`,
  `remat` and `backward` (`other` holds the ops under none), and
  `idle_gaps_program`, the idle seconds by the innermost `repro.*` span
  open at each gap's midpoint;
- the command runs one cell once with the trace on, through
  `train_cell.run` as `run.py` does, and prints both, the counters'
  difference across the window (`telemetry`) and the readers of the five
  metrics that read them, beside the cell's own per-layer metrics.

A TPU op event carries its HLO instruction's text without the metadata. Its
`op_name` (where a fusion carries its root's) is looked up in the HLO proto
of its program, which the trace keeps in its `/host:metadata` plane under
the program's module name, the name of the `XLA Modules` event around the
op (`program_op_names`).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import collections  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import weakref  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Iterator, Mapping, Optional, Sequence, Tuple  # noqa

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import traces  # noqa: E402

SPAN_PREFIX = "repro."
SCOPES = ("embed", "attention", "mlp", "head", "adamw")
READERS = ("attention_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
           "adamw_ms_per_step", "boundary_ms_per_segment")
SEGMENT_PROGRAM = "run_segment"
METADATA_PLANE = "/host:metadata"
_SCOPE_TOKEN = re.compile(r"(?:^|[/(;])(" + "|".join(SCOPES)
                          + r")(?=$|[/);])")


def _varint(b: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for every other wire type."""
    b = memoryview(b)
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _first(msg, field: int, default=b""):
    return next((v for f, v in _fields(msg) if f == field), default)


def program_key(module_name: str) -> str:
    """`jit_run_segment(1084...)` -> `1084...`: the program id that names a
    module event and its HLO proto alike (the whole name where none)."""
    m = re.search(r"\((-?\d+)\)$", module_name)
    return m.group(1) if m else module_name


def _text(msg, field: int) -> str:
    return bytes(_first(msg, field)).decode()


def program_op_names(xplane: bytes) -> Dict[str, Dict[str, str]]:
    """{program key: {HLO instruction: op_name}} of every program in a
    trace, from the HLO protos of its `/host:metadata` plane.

    Field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4 and
    .stat_metadata 5 (map entries: key 1, value 2); XEventMetadata.name 2,
    .stats 5; XStatMetadata.name 2; XStat.metadata_id 1, .bytes_value 6
    (an HloProto); HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(xplane):
        if f != 1 or _text(plane, 2) != METADATA_PLANE:
            continue
        hlo_stat = {_first(entry, 1, 0) for f2, entry in _fields(plane)
                    if f2 == 5 and _text(_first(entry, 2), 2) == "Hlo Proto"}
        for f2, entry in _fields(plane):
            if f2 != 4:
                continue
            meta = _first(entry, 2)
            names = out.setdefault(program_key(_text(meta, 2)), {})
            for f3, stat in _fields(meta):
                if f3 != 5 or _first(stat, 1, 0) not in hlo_stat:
                    continue
                proto = _first(stat, 6)
                for f4, comp in _fields(_first(proto, 1)):
                    if f4 != 3:
                        continue
                    for f5, ins in _fields(comp):
                        if f5 == 2:
                            op = _text(_first(ins, 7), 2)
                            if op:
                                names[_text(ins, 1)] = op
    return out


class Capture(traces.Capture):
    """`traces.Capture` that also keeps `op_names`, the programs' op_names
    (`program_op_names`), read before the trace is deleted."""

    def __exit__(self, *exc):
        import jax
        try:
            jax.profiler.stop_trace()
            paths = glob.glob(f"{self._dir}/**/*.xplane.pb", recursive=True)
            if exc[0] is None:
                if not paths:
                    raise RuntimeError("the profiler wrote no trace")
                self.events = traces.load_xplane(paths[0])
                self.op_names = program_op_names(Path(paths[0]).read_bytes())
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


def scope_of(op_name: str) -> str:
    """The innermost of `SCOPES` in an op_name, or `other`."""
    found = _SCOPE_TOKEN.findall(op_name.split(";")[0])
    return found[-1] if found else "other"


def pass_of(op_name: str) -> str:
    """`backward` under a transpose, `remat` for the recompute inside it,
    else `forward` (the optimizer included)."""
    if "transpose(" not in op_name:
        return "forward"
    return "remat" if "rematted_computation" in op_name else "backward"


def _instruction(op_event_name: str) -> str:
    """`%fusion.633 = f32[4]{0} fusion(%a), ...` -> `fusion.633`."""
    return op_event_name.split(" = ")[0].lstrip("%")


def _span_at(spans: Sequence[traces.Event], starts: Sequence[float],
             t: float) -> str:
    """Innermost program span open at host time t (the latest started),
    or `host.other`; `spans` sorted by start, `starts` theirs."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[i].end_ns >= t:
            return spans[i].name[len(SPAN_PREFIX):]
    return "host.other"


def reduce_program(events: Sequence[traces.Event],
                   window_ns: Tuple[float, float],
                   op_names: Mapping[str, Mapping[str, str]]) -> Dict:
    """`scopes` and `idle_gaps_program` of one traced window (see the module
    docstring), and `other_ops`, the op_names (or, with none, the
    instructions) that take most of `other`. Ops count where they start inside the window and inside a
    `run_segment` module event of their chip; loop and call containers are
    left out, their bodies' ops counted. `op_names` maps a module event's
    `program_key` to its instructions' op_names."""
    lo, hi = window_ns
    chips = sorted({e.plane for e in events
                    if traces.DEVICE_PLANE.match(e.plane)})
    ops = [e for e in events if e.plane in chips and e.line == traces.OPS_LINE]
    segs = collections.defaultdict(list)
    for e in events:
        if e.plane in chips and e.line == traces.MODULES_LINE and \
                traces.program_name(e.name) == SEGMENT_PROGRAM:
            segs[e.plane].append((e.start_ns, e.end_ns, e.name))
    for iv in segs.values():
        iv.sort()
    starts = {c: [s for s, _, _ in iv] for c, iv in segs.items()}

    scopes: Dict[str, Dict[str, float]] = {}
    other = collections.Counter()
    for e in ops:
        if not lo <= e.start_ns < hi or \
                traces._op_key(e.name) in traces.CONTAINERS:
            continue
        i = bisect.bisect_right(starts.get(e.plane, []), e.start_ns) - 1
        if i < 0 or e.start_ns >= segs[e.plane][i][1]:
            continue                    # not inside a segment program
        module = program_key(segs[e.plane][i][2])
        name = op_names.get(module, {}).get(_instruction(e.name), "")
        d = scopes.setdefault(scope_of(name), {
            "s": 0.0, "n": 0, "forward": 0.0, "remat": 0.0, "backward": 0.0})
        d["s"] += e.dur_ns / 1e9
        d["n"] += 1
        d[pass_of(name)] += e.dur_ns / 1e9
        if scope_of(name) == "other":
            other[name or _instruction(e.name)] += e.dur_ns / 1e9

    spans = sorted((e for e in events if not traces.DEVICE_PLANE.match(e.plane)
                    and e.name.startswith(SPAN_PREFIX)),
                   key=lambda e: e.start_ns)
    span_starts = [e.start_ns for e in spans]
    gaps = collections.Counter()
    for chip in chips:
        iv = traces._clip(traces._union(
            (e.start_ns, e.end_ns) for e in ops if e.plane == chip), lo, hi)
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps[_span_at(spans, span_starts, (gs + ge) / 2)] += \
                    (ge - gs) / len(chips)
    return {"scopes": scopes,
            "other_ops": [[k, v] for k, v in other.most_common(10)],
            "idle_gaps_program": [[k, v / 1e9] for k, v in gaps.most_common()]}


class Window:
    """The cell's measured window with the profiler on: the standard
    reduction plus `reduce_program`'s keys, and `telemetry`, the difference
    of the trainer's counters across the window (None for a trainer that
    keeps none)."""

    def __init__(self, kernels, trainer_ref):
        self.kernels = tuple(kernels)
        self.trainer_ref = trainer_ref
        self.reduction: Optional[Dict] = None
        self.telemetry: Optional[Dict] = None
        self.segment_programs: Dict[str, bool] = {}   # name: HLO found
        self._cap = Capture()
        self._span = None
        self._before = None

    def _counters(self):
        tr = self.trainer_ref()
        return getattr(tr, "telemetry", None) if tr is not None else None

    def __enter__(self):
        c = self._counters()
        self._before = c.snapshot() if c is not None else None
        self._cap.__enter__()
        self._span = traces.host_span("window")
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        c = self._counters()
        if c is not None and exc[0] is None:
            from repro.telemetry import difference
            self.telemetry = difference(c.snapshot(), self._before)
        self._cap.__exit__(*exc)       # reads the trace, deletes its files
        if exc[0] is None:
            ev = self._cap.events
            window = traces.window_from_spans(ev, "window")
            self.reduction = traces.reduce_events(ev, window, self.kernels)
            self.reduction.update(
                reduce_program(ev, window, self._cap.op_names))
            self.segment_programs = {
                e.name: program_key(e.name) in self._cap.op_names
                for e in ev if e.line == traces.MODULES_LINE and
                traces.program_name(e.name) == SEGMENT_PROGRAM}
        return False


def split_line(scopes: Dict, steps: int) -> str:
    """ms per inner step by scope, split forward/remat/backward."""
    parts = []
    for name, d in sorted(scopes.items(), key=lambda kv: -kv[1]["s"]):
        ms = lambda s: 1e3 * s / max(1, steps)       # noqa: E731
        parts.append(f"{name} {ms(d['s']):.2f} (fwd {ms(d['forward']):.2f} "
                     f"remat {ms(d['remat']):.2f} bwd {ms(d['backward']):.2f})"
                     f" n={d['n']}")
    return "; ".join(parts)


def run_traced(workload: str, seed: int, seconds: float,
               require_chip: bool = True, registry=None) -> Dict:
    """One traced run of a training cell, read as the module docstring
    says. Returns the result object, which ends the command's output."""
    import jax
    from benchmarks.chip import device, run, train_cell
    from benchmarks.chip.registry import Registry
    reg = registry or Registry()
    cell = reg.workload(workload)
    cfg, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    if mix["kind"] != "train":
        raise ValueError(f"{workload} is not a training cell")
    if require_chip:
        info = device.guard(int(cell["chips"]))
    else:
        d = jax.devices()[0]
        info = {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}
    names = [m["name"] for m in reg.metrics_of(workload, "per_layer")]
    names += [n for n in READERS if n not in names]
    readers = {n: reg.metric(n) for n in names}
    kernels = tuple(k for mod in readers.values()
                    for k in getattr(mod, "KERNELS", ()))
    held = {"trainer": lambda: None}

    def hold(trainer):
        held["trainer"] = weakref.ref(trainer)

    class Context(run.Context):
        def window(self):
            return Window(self.kernels, held["trainer"])

    ctx = Context(cfg=cfg, mix=mix, seed=int(seed), seconds=float(seconds),
                  trace=True, chips=int(cell["chips"]), kernels=kernels,
                  counter=device.CompileCounter(), t_start=T_START,
                  break_program=hold)
    res = train_cell.run(ctx)
    win, rec = res["window"], dict(res["record"])
    red = win.reduction
    if win.telemetry is not None:
        rec["telemetry"] = win.telemetry
    view = {"record": rec, "trace": red, "config": cfg, "mix": mix,
            "peaks": device.peaks_for(info["kind"]) if require_chip else None,
            "end_to_end": res["end_to_end"]}
    metrics = {}
    for name, mod in readers.items():
        v = mod.read(view)
        if v is not None:
            metrics[name] = v
    steps = rec["steps"]
    run.log(f"[trace] segment programs with their HLO op_names in the "
            f"trace: {json.dumps(win.segment_programs)}")
    run.log(f"[trace] scopes, ms per inner step: "
            f"{split_line(red['scopes'], steps)}")
    if set(red["scopes"]) == {"other"}:
        # JAX's persistent cache key leaves op metadata out: an executable
        # compiled from a program without the scopes is loaded as it is
        run.log("[trace] no op under a named scope: were the segment "
                "programs loaded from a compile cache filled by a program "
                "without them?")
    seg = red["programs"].get(SEGMENT_PROGRAM, {}).get("s", 0.0)
    in_scopes = sum(d["s"] for d in red["scopes"].values())
    run.log(f"[trace] ops under no scope, s: {json.dumps(red['other_ops'])}")
    run.log(f"[trace] scope op time {in_scopes:.4f} s of {seg:.4f} s of "
            f"segment program; idle by program span "
            f"{json.dumps(red['idle_gaps_program'])}")
    if win.telemetry is not None:
        t = win.telemetry
        run.log(f"[train] telemetry {json.dumps(t)}; host syncs "
                f"{sum(t['host_syncs'].values())} by reason "
                f"{json.dumps(t['host_syncs'])}")
    checks, correct = run.judge(res["numbers"], reg.limits(workload))
    return {"correct": bool(correct and res["failed"] == 0),
            "attempted": int(res["attempted"]), "failed": int(res["failed"]),
            "end_to_end": res["end_to_end"], "metrics": metrics,
            "device": {**info, "memory_peak_bytes": res["memory_peak_bytes"],
                       "busy_s": red["busy_s"], "window_s": red["window_s"]},
            "breakdown": {"device_ops": red["device_ops"],
                          "idle_gaps": red["idle_gaps"],
                          "idle_gaps_program": red["idle_gaps_program"],
                          "scopes": red["scopes"]},
            "telemetry": rec.get("telemetry"), "checks": checks}


def main(argv=None) -> int:
    from benchmarks.chip import device
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    device.enable_compile_cache(ROOT)
    try:
        out = run_traced(args.workload, args.seed, args.seconds)
    except device.NoChip as e:
        print(f"program_trace.py: {e}", file=sys.stderr, flush=True)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
