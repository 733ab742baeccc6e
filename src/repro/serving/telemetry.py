"""Spans and counters of one serving engine: where the host's time goes.

Each `ServingEngine` owns one `Telemetry`.  The engine opens a span around
each phase of its host work (admission, page-table growth, the decode
dispatch, the wait for its tokens, commit, the DSG pattern rewrite); the
names, the nesting and which spans are device waits are listed in
docs/serving.md, "Spans and counters".  A span goes to two places:

* a bounded in-memory ring of the last `CAPACITY` spans, stamped with
  `time.perf_counter` (the clock of the engine's `Request` stamps), read in
  process with `spans(t0, t1)`;
* while a profiler trace is being taken, a `jax.profiler.TraceAnnotation`,
  so that the span lies on the profiler's host plane, on one timeline with
  the device's operations (TensorBoard or Perfetto show both).

`counters` holds running integer sums (the DSG runtime's FLOP model).

Recording is always on and costs about a microsecond a span with the
profiler off; spans are per phase, never per lane.  Only the engine's worker
thread writes to its recorder, so it takes no lock.  `recorders()` lists
every live recorder, each engine's among them, for a reader with no handle
to the engines.
"""
from __future__ import annotations

import collections
import time
import weakref
from typing import NamedTuple

import jax

from repro.analysis.contracts import owned_by, runs_on

#: spans the ring keeps: over 6,000 decode steps at ten spans a step
CAPACITY = 65_536

_LIVE = weakref.WeakSet()
_clock = time.perf_counter
_Annotation = jax.profiler.TraceAnnotation
_tracing = _Annotation.is_enabled      # a profiler trace is being taken


def recorders() -> list:
    """Every recorder still alive."""
    return list(_LIVE)


class Span(NamedTuple):
    name: str
    t0: float                 # time.perf_counter at entry
    t1: float                 # ... at exit
    parent: int               # sid of the enclosing span, -1 at the top
    attrs: dict
    sid: int                  # this recorder's sequence number

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def self_seconds(spans) -> dict:
    """{sid: the span's length less its children's}, over `spans`."""
    own = {s.sid: s.seconds for s in spans}
    for s in spans:
        if s.parent in own:
            own[s.parent] -= s.seconds
    return own


class _Open:
    """The context manager `Telemetry.span` returns; after exit it keeps
    `t0`, `t1` and `seconds`.  Entry and exit are the recorder's hot path,
    so they touch its state directly."""
    __slots__ = ("_tel", "_note", "name", "attrs", "parent", "sid", "t0",
                 "t1")

    def __init__(self, tel, name, attrs):
        self._tel, self.name, self.attrs = tel, name, attrs

    def __enter__(self):
        if _tracing():
            # no kwargs: JAX would write them into the event's name
            self._note = _Annotation(self.name)
            self._note.__enter__()
        else:
            self._note = None
        tel = self._tel
        self.parent = tel._open
        self.sid = tel._open = tel._next
        tel._next += 1
        self.t0 = _clock()
        return self

    def __exit__(self, typ, val, tb):
        self.t1 = t1 = _clock()
        tel = self._tel
        tel._open = self.parent
        tel._ring.append((self.name, self.t0, t1, self.parent, self.attrs,
                          self.sid))
        if self._note is not None:
            self._note.__exit__(typ, val, tb)
        return False

    def set(self, **attrs):
        """Attributes known only once the work is done."""
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@owned_by("worker", "_open", "_next", "counters")
class Telemetry:
    """One engine's spans and counters (see the module docstring)."""

    def __init__(self):
        # (name, t0, t1, parent, attrs, sid) tuples; `spans` makes them
        # Spans
        self._ring = collections.deque(maxlen=CAPACITY)
        self._open = -1             # sid of the innermost open span
        self._next = 0
        self.counters = collections.Counter()
        _LIVE.add(self)

    def span(self, name: str, **attrs) -> _Open:
        return _Open(self, name, attrs)

    @runs_on("worker")
    def record(self, name: str, t0: float, t1: float, **attrs):
        """A span known only after the fact (a request's wait in the
        queue): in memory only, at the top of the tree."""
        self._ring.append((name, t0, t1, -1, attrs, self._next))
        self._next += 1

    @runs_on("worker")
    def count(self, name: str, n: int = 1):
        self.counters[name] += n

    @runs_on("worker")
    def reset_counters(self, prefix: str):
        for k in [k for k in self.counters if k.startswith(prefix)]:
            del self.counters[k]

    def spans(self, t0: float = float("-inf"), t1: float = float("inf"),
              name: str = None) -> list:
        """Spans that lie inside [t0, t1], oldest first; of one name if
        given."""
        return [Span(*s) for s in list(self._ring)
                if t0 <= s[1] and s[2] <= t1
                and (name is None or s[0] == name)]
