"""Profiler capture and the reduction from a device trace to numbers.

`capture` records one window with `jax.profiler` into a temporary directory
outside the checkout, reads the `.xplane.pb` back with `ProfileData` and
deletes the directory. What is kept is a flat list of events:

    Event(plane, line, name, start_ns, dur_ns)

`reduce_events` turns that list into the numbers the per-layer readers use:
device busy time (the union of the op intervals on each chip, averaged over
chips), the traced window, device time and count per program (module events,
keyed by the jitted function's name), device time and count per kernel name,
the ops that took most time (loop and call containers left out, their
bodies' ops counted), and the idle gaps attributed to the host span
that was open at the gap's midpoint.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."          # the harness's window span


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def program_name(module_event_name: str) -> str:
    """`jit_run_segment(42)` -> `run_segment`: the jitted function's name."""
    name = module_event_name.split("(")[0]
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def load_xplane(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


class Capture:
    """Trace one window: `with Capture() as cap: ...` then `cap.events`.
    The trace directory lives under the system's temporary directory and is
    removed once read, so nothing of it stays in the checkout."""

    def __init__(self):
        self.events: List[Event] = []
        self._dir: Optional[str] = None

    def __enter__(self):
        import jax
        self._dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # no per-call Python events
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        try:
            jax.profiler.stop_trace()
            paths = glob.glob(f"{self._dir}/**/*.xplane.pb", recursive=True)
            if exc[0] is None:
                if not paths:
                    raise RuntimeError("the profiler wrote no trace")
                self.read(paths[0])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False

    def read(self, path: str) -> None:
        """Keep what is needed of the trace file before it is deleted."""
        self.events = load_xplane(path)


@contextlib.contextmanager
def host_span(name: str):
    """A host span in the profiler's own trace (no cost when not tracing)."""
    import jax
    with jax.profiler.TraceAnnotation(HOST_SPAN_PREFIX + name):
        yield


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if min(e, hi) > max(s, lo)]


CONTAINERS = ("while", "conditional", "call")


def _op_key(name: str) -> str:
    """`%fusion.633 = (f32[...]) fusion(...)` -> `fusion`: the HLO
    instruction's name without its number."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[.:]\d+$", "", name)


def reduce_events(events: Sequence[Event], window_ns: Tuple[float, float],
                  kernels: Sequence[str] = ()) -> Dict:
    """The numbers of one traced window [window_ns).

    busy_s: union of op intervals per chip, inside the window, averaged over
    chips. programs: {name: {"s", "n"}} from module events that start in the
    window, summed over chips. kernels: {name: {"s", "n"}} for op events whose
    instruction name contains one of `kernels`. device_ops: the 10 op names
    with most device time. idle_gaps: idle seconds (averaged over chips) by
    the host span open at each gap's midpoint, 10 largest."""
    lo, hi = window_ns
    chips = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    ops = [e for e in events if e.plane in chips and e.line == OPS_LINE]
    mods = [e for e in events if e.plane in chips and e.line == MODULES_LINE]
    spans = sorted((e for e in events if not DEVICE_PLANE.match(e.plane)
                    and e.name.startswith(HOST_SPAN_PREFIX)),
                   key=lambda e: e.start_ns)

    busy_ns, gaps = 0.0, collections.Counter()
    for chip in chips:
        iv = _clip(_union((e.start_ns, e.end_ns) for e in ops
                          if e.plane == chip), lo, hi)
        busy_ns += sum(e - s for s, e in iv)
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps[_span_at(spans, (gs + ge) / 2)] += (ge - gs) / len(chips)

    programs: Dict[str, Dict[str, float]] = {}
    for e in mods:
        if lo <= e.start_ns < hi:
            p = programs.setdefault(program_name(e.name), {"s": 0.0, "n": 0})
            p["s"] += e.dur_ns / 1e9
            p["n"] += 1
    found: Dict[str, Dict[str, float]] = {}
    by_op = collections.Counter()
    for e in ops:
        if not lo <= e.start_ns < hi:
            continue
        key = _op_key(e.name)
        if key not in CONTAINERS:          # their bodies' ops are counted
            by_op[key] += e.dur_ns / 1e9
        instruction = e.name.split(" = ")[0]   # not its operands' names
        for k in kernels:
            if k in instruction:
                f = found.setdefault(k, {"s": 0.0, "n": 0})
                f["s"] += e.dur_ns / 1e9
                f["n"] += 1
    n = max(1, len(chips))
    return {
        "chips": len(chips),
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "programs": programs,
        "kernels": found,
        "device_ops": [[k, v] for k, v in by_op.most_common(10)],
        "idle_gaps": [[k, v / 1e9] for k, v in gaps.most_common(10)],
    }


def _span_at(spans: Sequence[Event], t: float) -> str:
    """Innermost harness span open at host time t, or `host.other`."""
    best = None
    for e in spans:
        if e.start_ns > t:
            break
        if e.end_ns >= t and (best is None or e.start_ns >= best.start_ns):
            best = e
    return best.name[len(HOST_SPAN_PREFIX):] if best else "host.other"


def window_from_spans(events: Sequence[Event], name: str) -> Tuple[float, float]:
    """[start, end) of the harness's window span `name` on the trace clock."""
    for e in events:
        if e.name == HOST_SPAN_PREFIX + name:
            return e.start_ns, e.end_ns
    raise RuntimeError(f"no span {HOST_SPAN_PREFIX + name} in the trace")
