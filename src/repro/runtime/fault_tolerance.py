"""Fault-tolerance runtime: checkpoint/restart loop, straggler monitor,
failure injection for tests, and the serving-grade chaos injector.

At 1000+ nodes the mean time between node failures drops below the length
of a training run; the loop here implements the standard contract:
  * every step is resumable: (params, dsg, opt, data cursor) all live in
    the checkpoint; the data pipeline is a pure function of step, so
    replaying from step k is bit-exact;
  * failures (device loss, preemption, host OOM) surface as exceptions
    from the step call -> restore from the newest complete checkpoint and
    continue (bounded retries per step to avoid crash loops);
  * a straggler monitor records per-step wall time and flags outliers
    (> factor x rolling median) — on a real fleet this feeds the scheduler
    (hot-swap of the slow host); here it logs and counts, and tests verify
    detection on injected delays.

The serving side applies the same replay contract to replica death
instead of host preemption: `ServingFaultInjector` deterministically
kills / delays / poisons a serving replica at a chosen engine step, and
the Router's failover (serving/router.py, docs/fault_tolerance.md)
replays the reclaimed requests from their prompts on healthy replicas —
bit-identical at temperature 0 because each replica is solo-
deterministic.  benchmarks/bench_router_faults.py gates exactly that.
"""
from __future__ import annotations

import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

log = logging.getLogger("repro.runtime")


class StragglerMonitor:
    def __init__(self, window: int = 32, factor: float = 1.5):
        self.times = deque(maxlen=window)
        self.factor = factor
        self.flagged = []

    def record(self, step: int, seconds: float) -> bool:
        """Returns True if this step is a straggler."""
        is_straggler = False
        if len(self.times) >= 8:
            xs = sorted(self.times)
            median = xs[len(xs) // 2]
            if seconds > self.factor * median:
                self.flagged.append((step, seconds, median))
                is_straggler = True
                log.warning("straggler: step %d took %.3fs (median %.3fs)",
                            step, seconds, median)
        self.times.append(seconds)
        return is_straggler


@dataclass
class FaultInjector:
    """Deterministic failure injection for tests: raises at given steps."""
    fail_at: tuple = ()
    exc: type = RuntimeError
    _fired: set = field(default_factory=set)

    def maybe_fail(self, step: int):
        if step in self.fail_at and step not in self._fired:
            self._fired.add(step)
            raise self.exc(f"injected failure at step {step}")


# -- serving chaos harness ---------------------------------------------------

#: Token value a "poison" fault writes over a lane's last emitted token —
#: obviously out-of-vocab so a poisoned stream that survives failover
#: (instead of being replayed from the prompt) cannot pass a bitwise
#: stream-equality gate by accident.
POISON_TOKEN = -7

FAULT_KINDS = ("kill", "delay", "poison")


class InjectedFault(RuntimeError):
    """The injected replica-crash exception: what a real device loss /
    worker OOM surfaces as, minus the flakiness."""


@dataclass(frozen=True)
class ReplicaFault:
    """One deterministic serving fault: when replica `replica`'s engine
    reaches step `step`, do `kind`.  "Reaches" means the first step
    boundary whose counter is AT OR PAST `step`: with a fused decode
    chunk (ServingEngine decode_chunk > 1) the counter advances by up to
    a whole chunk per boundary, so an exact-match key landing mid-chunk
    would never fire — the fault instead lands on the next chunk
    boundary, which is also the only place the engine can contain it.

      kill   — raise InjectedFault at the step boundary, before the
               step's tokens land (the clean worker-death case);
      delay  — sleep `delay_s` inside the step (a straggler; trips the
               router's stall timeout when one is configured);
      poison — overwrite the last emitted token of every stream still
               in progress with POISON_TOKEN, then raise (the dirty death: failover
               must discard the partial output and replay from the
               prompt, or the corruption survives into the stream).
    """
    replica: int
    step: int
    kind: str = "kill"
    delay_s: float = 0.05

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"expected one of {FAULT_KINDS}")
        if self.replica < 0 or self.step < 0:
            raise ValueError(f"replica/step must be >= 0 "
                             f"(got {self.replica}/{self.step})")


class ServingFaultInjector:
    """Serving-grade FaultInjector: deterministic faults keyed on
    (replica index, engine step), each firing exactly ONCE even across
    replica restarts or benchmark repeats (until `reset()` re-arms).

    `attach(engines)` stamps each engine's `replica_index` and installs
    the injector as its `fault_injector`; the engine calls
    `on_step(engine)` at every step boundary (`ServingEngine.step()`,
    `begin_step()`).  Attach AFTER warmup:
    warmup resets step counters, so a fault keyed on an early step would
    otherwise fire inside the compile pass."""

    def __init__(self, faults: Sequence[ReplicaFault]):
        self.faults: List[ReplicaFault] = [
            f if isinstance(f, ReplicaFault) else ReplicaFault(*f)
            for f in faults]
        self._fired: set = set()
        self.log: List[dict] = []      # faults that actually fired

    def attach(self, engines) -> None:
        for r, eng in enumerate(engines):
            eng.replica_index = r
            eng.fault_injector = self

    def detach(self, engines) -> None:
        for eng in engines:
            if eng.fault_injector is self:
                eng.fault_injector = None

    def reset(self) -> None:
        """Re-arm every fault (benchmark repeats)."""
        self._fired.clear()
        self.log.clear()

    def on_step(self, eng) -> None:
        for k, f in enumerate(self.faults):
            # >= (not ==): a chunked engine's counter jumps by up to
            # decode_chunk per boundary, so a mid-chunk key fires at the
            # first boundary past it instead of being skipped forever
            if (k in self._fired or f.replica != eng.replica_index
                    or eng.steps < f.step):
                continue
            self._fired.add(k)
            self.log.append({"replica": f.replica, "step": f.step,
                             "kind": f.kind})
            if f.kind == "delay":
                time.sleep(f.delay_s)
                continue
            if f.kind == "poison":
                # streams still in progress: a complete one is done
                # already while its lane awaits the commit of its last
                # decode
                for slot in eng.slots:
                    r = slot.req
                    if r is not None and r.output and r.status == "pending":
                        r.output[-1] = POISON_TOKEN
            raise InjectedFault(
                f"injected {f.kind} at replica {f.replica} "
                f"step {f.step}")


def run_with_restarts(*, step_fn: Callable, state, make_batch: Callable,
                      ckpt, total_steps: int, start_step: int = 0,
                      ckpt_every: int = 20, max_retries: int = 3,
                      injector: Optional[FaultInjector] = None,
                      on_step: Optional[Callable] = None,
                      monitor: Optional[StragglerMonitor] = None):
    """Fault-tolerant training loop.

    step_fn(state, batch) -> (state, metrics).  ckpt: CheckpointManager.
    Restores and replays on any exception, up to max_retries per step.
    Returns (state, history)."""
    monitor = monitor or StragglerMonitor()
    history = []
    step = start_step
    retries = 0
    while step < total_steps:
        try:
            t0 = time.perf_counter()
            if injector is not None:
                injector.maybe_fail(step)
            batch = make_batch(step)
            state, metrics = step_fn(state, batch)
            dt = time.perf_counter() - t0
            monitor.record(step, dt)
            history.append({"step": step, "seconds": dt, **{
                k: float(v) for k, v in metrics.items()}})
            if on_step is not None:
                on_step(step, state, metrics)
            if ckpt is not None and (step + 1) % ckpt_every == 0:
                ckpt.save_async(step + 1, state, meta={"step": step + 1})
            step += 1
            retries = 0
        except Exception as e:                      # noqa: BLE001
            retries += 1
            log.error("step %d failed (%s); retry %d/%d", step, e,
                      retries, max_retries)
            if retries > max_retries:
                raise
            if ckpt is not None:
                restored, rstep, _ = ckpt.restore(state)
                if restored is not None:
                    state = restored
                    step = rstep
                    log.info("restored from checkpoint at step %d", rstep)
    if ckpt is not None:
        ckpt.wait()
    return state, history
