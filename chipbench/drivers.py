"""Drivers: how a mix's requests reach the engine, and what the host sees.

Both drivers call only the engine's public surface (`submit`, `step`,
`queue_depth`, `busy_slots`) and stamp every observation on the benchmark's
own clock, after each `step` returns: the first token of a request is visible
when its output first grows, and it is finished when its status turns `ok`.
Each host phase runs under a `TraceAnnotation` (`chipbench.submit`,
`chipbench.step`, `chipbench.sleep`), so a traced run can name what the host
was doing in each device idle gap.

Each step that emitted tokens leaves a record for the work counts: the tokens
emitted (one per active lane), the keys those lanes attended, the lanes that
DSG re-scores, and the prompts admitted.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np

CLOCK = time.perf_counter


@dataclass
class Seen:
    """What the host saw of one request."""
    spec: object
    req: object
    due: float                 # absolute due time (open loop), else send
    sent: float
    first: float = None
    finish: float = None
    n_seen: int = 0


@dataclass
class StepRecord:
    start: float
    end: float
    lanes: int = 0             # tokens emitted = lanes that decoded
    depth_sum: int = 0         # keys attended by those lanes
    refresh_lanes: int = 0     # lanes due for a DSG re-score this step
    admits: int = 0
    prompt_lens: list = field(default_factory=list)


class Driver:
    """Submits requests, steps the engine and records what it sees."""

    def __init__(self, engine, traffic, make_request, refresh_interval=0):
        self.engine = engine
        self.traffic = traffic
        self.make_request = make_request
        self.refresh = refresh_interval
        self.live = {}
        self.done = []
        self.steps = []
        self.lateness = []
        self.tracer = None          # Tracer of a traced run
        self.selections = None      # SelectionLog of a DSG engine

    def submit(self, spec, due: float):
        now = CLOCK()
        req = self.make_request(spec)
        self.live[spec.uid] = Seen(spec, req, due=due, sent=now)
        self.lateness.append(now - due)
        self.engine.submit(req)

    def busy(self) -> bool:
        return self.engine.queue_depth() > 0 or self.engine.busy_slots() > 0

    def step(self):
        if self.tracer is not None:
            self.tracer.before_step(self)
        t0 = CLOCK()
        with jax.profiler.TraceAnnotation("chipbench.step"):
            self.engine.step()
        t1 = CLOCK()
        if self.selections is not None:
            self.selections.settle()
        rec = StepRecord(t0, t1)
        for uid in list(self.live):
            s = self.live[uid]
            n = len(s.req.output)
            if n > s.n_seen:
                if s.first is None:
                    s.first = t1
                    rec.admits += 1
                    rec.prompt_lens.append(len(s.spec.prompt))
                rec.lanes += n - s.n_seen
                rec.depth_sum += len(s.spec.prompt) + n
                if self.refresh and n % self.refresh == 0:
                    rec.refresh_lanes += 1
                s.n_seen = n
            if s.req.status != "pending":
                s.finish = t1
                self.done.append(self.live.pop(uid))
        self.steps.append(rec)
        if self.tracer is not None:
            self.tracer.after_step(self)

    def everyone(self):
        return self.done + list(self.live.values())


class SelectionLog:
    """Every group selection that a DSG engine's runtime writes for a lane,
    kept for the check: at admission (from the prompt's last token) and at
    each refresh (from the token just decoded).  Per request uid, a list of
    (position whose scores chose it, first position it serves, kept groups
    as an (L, G) mask).  It wraps the runtime's pattern writer on this one
    engine; the host copies a lane's index rows when they are written,
    which the runtime does on the host in any case."""

    def __init__(self, engine):
        self.engine = engine
        self.by_uid = {}
        self._admitted = []
        rt = engine.dsg_rt
        write = rt._write_rows
        cols = np.arange(rt.n_groups)

        def logged(lane, scores, seed_ema):
            write(lane, scores, seed_ema)
            valid = cols[None, :] < rt.counts[:, lane, None]
            kept = np.zeros((rt.n_layers, rt.n_groups), bool)
            kept[np.nonzero(valid)[0], rt.idx[:, lane][valid]] = True
            if seed_ema:            # admission: the lane's request is set later
                self._admitted.append((lane, kept))
            else:
                slot = engine.slots[lane]
                self.by_uid.setdefault(slot.req.uid, []).append(
                    (slot.pos - 1, slot.pos, kept))

        rt._write_rows = logged

    def settle(self):
        """After a step: file its admissions under the requests now in
        their lanes."""
        for lane, kept in self._admitted:
            req = self.engine.slots[lane].req
            if req is not None:
                src = len(req.prompt) - 1
                self.by_uid.setdefault(req.uid, []).insert(0, (src, src, kept))
        self._admitted.clear()


def run_backlog(drv: Driver, seconds: float, depth: int):
    """Offline batch: keep at least `depth` requests queued, step for
    `seconds`; the window ends at the first step boundary past it."""
    i = 0
    t0 = CLOCK()
    end = t0 + seconds
    while True:
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            while drv.engine.queue_depth() < depth:
                drv.submit(drv.traffic.spec(i), CLOCK())
                i += 1
        drv.step()
        if CLOCK() >= end:
            break
    return t0, CLOCK()


def run_open_loop(drv: Driver, seconds: float, drain_s: float):
    """Open loop: request i is due at its offset from the window's start and
    is sent as soon as the loop gets to it; arrivals stop when the window
    closes, then the window's requests drain for at most `drain_s`."""
    t0 = CLOCK()
    close = t0 + seconds
    nxt = drv.traffic.spec(0)
    while CLOCK() < close:
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            while nxt is not None and t0 + nxt.due <= CLOCK():
                if nxt.due < seconds:
                    drv.submit(nxt, t0 + nxt.due)
                    nxt = drv.traffic.spec(nxt.uid + 1)
                else:
                    nxt = None
        if drv.busy():
            drv.step()
        else:
            with jax.profiler.TraceAnnotation("chipbench.sleep"):
                until = min(close, t0 + nxt.due) if nxt is not None else close
                time.sleep(max(0.0, min(until - CLOCK(), 0.005)))
    t_close = CLOCK()
    while drv.busy() and CLOCK() < t_close + drain_s:
        drv.step()
    return t0, t_close
