"""The device the run is on: its record, its memory peak, compiles, and peaks.

`CompileLog` and `peak_bytes` follow `chip_smoke.py` in the repository root.
"""
from __future__ import annotations

import json
import threading
from pathlib import Path

import jax

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def device_record() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def require_chips(chips: int) -> dict:
    rec = device_record()
    if rec["platform"] in ("cpu",):
        raise NoAccelerator(f"needs an accelerator; JAX found "
                            f"{rec['platform']} ({rec['kind']})")
    if rec["count"] < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips; JAX found "
                            f"{rec['count']}")
    return rec


def peak_bytes():
    """Largest `peak_bytes_in_use` over the devices (None where the backend
    does not report it)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def peaks_for(kind: str, table: Path = PEAKS) -> dict:
    """The published peaks of a `device_kind`; an unknown kind is an error."""
    rows = json.loads(table.read_text())["devices"]
    if kind not in rows:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(rows)}")
    return rows[kind]


class CompileLog:
    """Counts XLA compiles (loads from the persistent cache included) and
    their seconds, from JAX's monitoring events."""

    def __init__(self):
        self._lock = threading.Lock()
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.count, self.seconds, self.cache_hits
