"""Device traces: taking one at step boundaries, and reducing it to numbers.

A traced run starts JAX's profiler before one engine step and stops it after
another, with the host span `chipbench.window` around the steps in between.
`summarize` reads the `.xplane.pb` the profiler wrote (with nothing but
`jax.profiler.ProfileData`) and returns, for that window:

* `busy_s`: the union of the intervals in which an operation or a compiled
  program ran on the device, averaged over the devices traced; `window_s`:
  the window's length;
* `op_seconds` / `module_seconds`: device time per operation (its HLO name,
  `paged_decode.6`; a Pallas kernel's is its kernel name) and per compiled
  program (`jit__decode_greedy`), with how many times each ran;
* `gaps`: the device's idle intervals, longest first, each named by the
  benchmark's host span (`chipbench.step`, `.submit`, `.sleep`) that overlaps
  it most, or `host` where none does.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from dataclasses import dataclass, field

import jax

from chipbench.drivers import CLOCK

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Tracer:
    """Starts the profiler before the first step at least `start_after`
    seconds into the window and stops it after the first step that ends
    `seconds` after the start."""

    def __init__(self, directory: str, t0: float, start_after: float,
                 seconds: float):
        self.directory = directory
        self.t0, self.start_after, self.seconds = t0, start_after, seconds
        self.start = self.stop = None
        self._span = None

    def before_step(self, drv):
        if self.start is None and CLOCK() - self.t0 >= self.start_after:
            shutil.rmtree(self.directory, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # host spans, not every call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.directory, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(WINDOW)
            self._span.__enter__()
            self.start = CLOCK()

    def after_step(self, drv):
        if (self.start is not None and self.stop is None
                and CLOCK() - self.start >= self.seconds):
            self.stop = CLOCK()
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def close(self):
        """Stop a trace the window ended before its time was up."""
        if self.start is not None and self.stop is None:
            self.stop = CLOCK()
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def steps(self, drv):
        """Step records that lie inside the traced window."""
        return [r for r in drv.steps
                if self.start <= r.start and r.end <= self.stop]

    def path(self) -> str:
        found = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.directory}")
        return max(found, key=os.path.getmtime)


@dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    op_seconds: dict = field(default_factory=dict)
    op_count: dict = field(default_factory=dict)
    module_seconds: dict = field(default_factory=dict)
    module_count: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)

    @staticmethod
    def _match(seconds, count, patterns):
        rx = re.compile("|".join(patterns))
        hit = [n for n in seconds if rx.search(n)]
        return (sum(seconds[n] for n in hit), sum(count[n] for n in hit))

    def ops(self, *patterns):
        """(seconds, count) of the operations whose name matches any
        pattern."""
        return self._match(self.op_seconds, self.op_count, patterns)

    def modules(self, *patterns):
        """(seconds, count) of the compiled programs whose name matches."""
        return self._match(self.module_seconds, self.module_count, patterns)

    def top_ops(self, n=10):
        return sorted(([k, v] for k, v in self.op_seconds.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n=10):
        return [[name, sec] for name, sec in self.gaps[:n]]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


#: control-flow operations whose span holds other operations' spans: they
#: count towards busy time, but their names would count the time twice
CONTAINERS = ("while", "conditional", "call")


def _is_device(plane) -> bool:
    """A device plane with compiled programs on it (the profiler also
    writes empty planes under /device: names)."""
    return plane.name.startswith("/device:") and any(
        line.name == MODULES_LINE and len(list(line.events))
        for line in plane.lines)


def op_name(event_name: str) -> str:
    """`%paged_decode.6 = (...) custom-call(...)` -> `paged_decode.6`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def summarize(path: str) -> Summary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, window = [], None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith("chipbench."):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name[10:]))
        elif _is_device(plane):
            devices.append(plane)
    if window is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    w0, w1 = window
    summ = Summary(window_s=(w1 - w0) * 1e-9, busy_s=0.0,
                   devices=len(devices))
    busy_all = []
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ops = line.name == OPS_LINE
            secs = summ.op_seconds if ops else summ.module_seconds
            count = summ.op_count if ops else summ.module_count
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= w0 or s >= w1:
                    continue
                s, e = max(s, w0), min(e, w1)
                name = op_name(ev.name) if ops else ev.name.split("(")[0]
                intervals.append((s, e))
                if ops and name.split(".")[0] in CONTAINERS:
                    continue
                secs[name] = secs.get(name, 0.0) + (e - s) * 1e-9
                count[name] = count.get(name, 0) + 1
        busy = _union(intervals)
        summ.busy_s += sum(e - s for s, e in busy) * 1e-9 / len(devices)
        busy_all.append(busy)
    if busy_all:
        summ.gaps = _name_gaps(busy_all[0], (w0, w1), spans)
    return summ


def _name_gaps(busy, window, spans):
    """Idle intervals of one device, longest first, named by the host span
    that overlaps each most."""
    w0, w1 = window
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = []
    for s, e in gaps:
        best, name = 0, "host"
        for hs, he, hn in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, hn
        named.append((name, (e - s) * 1e-9))
    return sorted(named, key=lambda g: -g[1])
