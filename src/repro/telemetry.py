"""Host spans and counters of the training path.

`span(name, **ids)` is a `jax.profiler.TraceAnnotation` named
`repro.<name>` whose ids (a segment's `t0` and `n`, a fragment, its
`t_init`) ride along as event stats. It is written into the profiler's own
trace, on the clock of the device's ops, so a gap in which the device has
nothing to do can be named by the span the host was in. With no profiler
running it costs one small object.

`Counters` holds plain host integers and `perf_counter` seconds: segments,
inner steps, scan dispatches, protocol initiations and deliveries, blocking
device-to-host reads by reason, and `boundary_s`, the host's time from the
return of one segment's last blocking read to the next segment's first scan
dispatch, while the device has nothing queued. `snapshot()` and
`difference()` give the counts of an interval. `attention_kernel_layers`
is counted at trace time: each compiled segment program whose layer body
took the Pallas attention kernel (`kernel_notes`, `note_kernel`).

Both are always on. Neither reaches `history` or a checkpoint: timings there
would break the bitwise kill-and-resume contract.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import time
from typing import Any, Dict, Iterator, Optional, Set

import jax

PREFIX = "repro."


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span `repro.<name>` carrying `ids` as its event stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


_notes: contextvars.ContextVar[Optional[Set[str]]] = contextvars.ContextVar(
    "repro_kernel_notes", default=None)


@contextlib.contextmanager
def kernel_notes() -> Iterator[Set[str]]:
    """Collect the names that `note_kernel` records while a program is
    traced inside this block."""
    notes: Set[str] = set()
    token = _notes.set(notes)
    try:
        yield notes
    finally:
        _notes.reset(token)


def note_kernel(name: str) -> None:
    """Record, at trace time, that a layer took the Pallas kernel `name`."""
    notes = _notes.get()
    if notes is not None:
        notes.add(name)


@dataclasses.dataclass
class Counters:
    """Host counters of one trainer, shared by its segment runner and its
    protocol engine."""
    segments: int = 0
    steps: int = 0
    scan_dispatches: int = 0
    initiations: int = 0
    deliveries: int = 0
    boundary_s: float = 0.0
    attention_kernel_layers: int = 0
    host_syncs: Dict[str, int] = dataclasses.field(default_factory=dict)
    _last_read: Optional[float] = dataclasses.field(default=None, init=False,
                                                    repr=False)

    def read(self, reason: str, x: Any) -> Any:
        """Copy `x` (an array or a pytree of arrays) to the host. This BLOCKS
        until the device has computed it; every such read on the training
        path goes through here, counted under `reason` and spanned as
        `repro.sync.<reason>`."""
        with span("sync." + reason):
            out = jax.device_get(x)
        self.host_syncs[reason] = self.host_syncs.get(reason, 0) + 1
        self._last_read = time.perf_counter()
        return out

    def scan_dispatched(self) -> None:
        """Count one scan dispatch; the first after a blocking read closes
        the host's boundary interval."""
        self.scan_dispatches += 1
        if self._last_read is not None:
            self.boundary_s += time.perf_counter() - self._last_read
            self._last_read = None

    def snapshot(self) -> Dict[str, Any]:
        return {"segments": self.segments, "steps": self.steps,
                "scan_dispatches": self.scan_dispatches,
                "initiations": self.initiations,
                "deliveries": self.deliveries,
                "boundary_s": self.boundary_s,
                "attention_kernel_layers": self.attention_kernel_layers,
                "host_syncs": dict(self.host_syncs)}

    def line(self) -> str:
        """One-line summary for an operator's log."""
        syncs = self.host_syncs
        by_reason = ", ".join(f"{r} {n}" for r, n in sorted(syncs.items()))
        return (f"{self.segments} segments, {self.steps} steps, "
                f"{self.scan_dispatches} scan dispatches, "
                f"{sum(syncs.values())} host syncs ({by_reason}), "
                f"{self.initiations} initiations, {self.deliveries} "
                f"deliveries, boundary {self.boundary_s:.3f} s host time, "
                f"attention_kernel_layers {self.attention_kernel_layers}")


def difference(after: Dict[str, Any], before: Dict[str, Any]
               ) -> Dict[str, Any]:
    """The counts of the interval between two `snapshot()`s."""
    out: Dict[str, Any] = {}
    for key, value in after.items():
        if isinstance(value, dict):
            out[key] = {r: n - before[key].get(r, 0) for r, n in value.items()
                        if n != before[key].get(r, 0)}
        else:
            out[key] = value - before[key]
    return out
