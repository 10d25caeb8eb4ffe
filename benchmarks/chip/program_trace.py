#!/usr/bin/env python3
"""The program's own spans, counters and named scopes, read from a traced
window.

    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n> \
        --seconds <s>

The trainer writes host spans `repro.*` (`repro.telemetry.span`), host
counters (`trainer.telemetry`) and the named scopes `embed`, `attention`,
`mlp`, `head` and `adamw` in the segment program's op metadata. This module
reads the spans and scopes of every traced run (`run.py`'s window is this
`Window`):

- `reduce_program` gives `scopes`, the device seconds and op count of the
  `run_segment` ops in the window by scope, each split into `forward`,
  `remat` and `backward` (`other` holds the ops under none), and
  `idle_gaps_program`, the idle seconds by the innermost `repro.*` span
  open at each gap's midpoint;
- the command runs one cell once with the trace on, through
  `run.run_cell`, and prints the readers of `READERS` beside the cell's own
  per-layer metrics, with the scopes, the idle seconds by program span and
  the counters' difference across the window (`telemetry`, which
  `train_cell` puts into every run's record).

A TPU op event carries its HLO instruction's text without the metadata. Its
`op_name` (where a fusion carries its root's) is looked up in the HLO proto
of its program, which the trace keeps in its `/host:metadata` plane under
the program's module name, the name of the `XLA Modules` event around the
op (`program_op_names`).
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import re
import sys
from pathlib import Path
from typing import Dict, Iterator, Mapping, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import traces  # noqa: E402

SPAN_PREFIX = "repro."
SCOPES = ("embed", "attention", "mlp", "head", "adamw")
READERS = ("attention_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
           "adamw_ms_per_step", "boundary_ms_per_segment",
           "flash_attention_fwd_roofline", "flash_attention_bwd_roofline")
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

    def read(self, path: str) -> None:
        super().read(path)
        self.op_names = program_op_names(Path(path).read_bytes())


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
    """A run's measured window. With tracing on: a profiler capture around
    it (`Capture`), a host span `bench.window` marking its bounds on the
    trace's clock, and `reduction`, `traces.reduce_events`'s numbers plus
    `reduce_program`'s; `segment_programs` says for each segment program
    in the trace whether its HLO op_names were found."""

    def __init__(self, trace: bool, kernels=()):
        self.trace = trace
        self.kernels = tuple(kernels)
        self.reduction: Optional[Dict] = None
        self.segment_programs: Dict[str, bool] = {}
        self._cap = Capture()
        self._span = None

    def __enter__(self):
        if self.trace:
            self._cap.__enter__()
            self._span = traces.host_span("window")
            self._span.__enter__()
        return self

    def __exit__(self, *exc):
        if not self.trace:
            return False
        self._span.__exit__(*exc)
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


def log_window(win: Window, steps: int, log) -> None:
    """The lines a traced window adds to a run's log: the segment programs
    found with their op_names, the scopes, the ops under none, and the idle
    seconds by program span."""
    red = win.reduction
    log(f"[trace] segment programs with their HLO op_names in the "
        f"trace: {json.dumps(win.segment_programs)}")
    log(f"[trace] scopes, ms per inner step: "
        f"{split_line(red['scopes'], steps)}")
    if set(red["scopes"]) == {"other"}:
        # JAX's persistent cache key leaves op metadata out: an executable
        # compiled from a program without the scopes is loaded as it is
        log("[trace] no op under a named scope: were the segment "
            "programs loaded from a compile cache filled by a program "
            "without them?")
    seg = red["programs"].get(SEGMENT_PROGRAM, {}).get("s", 0.0)
    in_scopes = sum(d["s"] for d in red["scopes"].values())
    log(f"[trace] ops under no scope, s: {json.dumps(red['other_ops'])}")
    log(f"[trace] scope op time {in_scopes:.4f} s of {seg:.4f} s of "
        f"segment program; idle by program span "
        f"{json.dumps(red['idle_gaps_program'])}")


def run_traced(workload: str, seed: int, seconds: float,
               require_chip: bool = True, registry=None) -> Dict:
    """One traced run of a cell through `run.run_cell`, with the readers of
    `READERS` besides the cell's own per-layer metrics. Returns the result
    object, which ends the command's output: the run's line with each
    metric's bare value, the end-to-end numbers, the scopes and the idle
    seconds by program span in `breakdown`, and `telemetry`."""
    from benchmarks.chip import run
    seen: Dict = {}
    out = run.run_cell(workload, seed, seconds, True, registry=registry,
                       require_chip=require_chip, extra_metrics=READERS,
                       details=seen)
    red = seen["trace"]
    return {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "end_to_end": seen["end_to_end"],
            "metrics": {k: m["value"] for k, m in out["metrics"].items()},
            "device": out["device"],
            "breakdown": {**out["breakdown"], "scopes": red["scopes"]},
            "telemetry": seen["record"].get("telemetry"),
            "checks": out["checks"]}


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
