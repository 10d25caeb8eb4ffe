"""The measurement guard, the table of peaks and the device record.

A run measures a TPU or nothing: another platform, fewer chips than the cell
asks for, or a `device_kind` missing from `peaks.json` is an error, never a
fall-back to the CPU.
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """The run found no accelerator it may measure."""


def peaks_for(kind: str, path: Path = PEAKS_FILE) -> Dict:
    table = json.loads(Path(path).read_text())
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in {path.name} "
                     f"(known: {sorted(table)})")
    return table[kind]


def guard(chips: int) -> Dict:
    """Platform, kind and count of the devices JAX sees; raises NoChip
    unless they are at least `chips` TPUs of a kind in the table."""
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if d.platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {d.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks_for(d.device_kind)
    return info


def memory_peak_bytes(n_chips: int) -> int:
    """Peak bytes in use on the fullest of the first `n_chips` devices."""
    import jax
    peak = 0
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at `$JAX_COMPILATION_CACHE_DIR`
    when set, else at the fixed `<checkout>/.jax_cache`; every program is
    kept, however small or fast to compile, so a warm run loads them all."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts programs lowered and compiled from the moment it is armed, via
    JAX's monitoring events: a lowering happens for every program a call
    has not run before in this process (loaded from the persistent cache or
    not), a backend compile for every cache miss."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.lowered = 0
        self.compiled = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if not self.armed:
            return
        if name == self.LOWER:
            self.lowered += 1
        elif name == self.COMPILE:
            self.compiled += 1

    def arm(self):
        self.lowered = self.compiled = 0
        self.armed = True

    def disarm(self):
        self.armed = False
