"""Queue-depth-aware front-end router over per-replica serving engines.

At pod scale the slot logic of `ServingEngine` runs once per
data-parallel replica group (the ROADMAP's multi-replica item): every
replica owns a full copy of the serving state — its params view, its
cache backend (dense or paged, with its own BlockAllocator), and its
PRNG stream — and a front-end `Router` decides which replica each
incoming `Request` lands on.  Like Dynasparse's runtime rebalancing work
as dynamic sparsity shifts per-input cost, routing reacts to LIVE state
(queue depth, free lanes, free cache pages), not a static assignment:

  * round_robin  — static cyclic assignment; dispatches unconditionally.
                   The baseline every policy is benchmarked against, and
                   the strawman: it cannot see that one replica drew all
                   the expensive requests.
  * least_queue  — pull-based: a request is dispatched only when some
                   replica has an uncommitted free lane (free_slots >
                   queue_depth), to the replica with the least
                   outstanding work (queued + resident requests).
                   Work-conserving under skewed traffic — fast replicas
                   drain their lanes and pull more work while a slow
                   replica keeps grinding its long generations
                   (benchmarks/bench_router.py gates the speedup).
  * least_pages  — admission-safe: dispatch only to a replica whose
                   cache backend can reserve the request's worst-case
                   page count RIGHT NOW (ServingEngine.can_admit_request),
                   preferring the replica with the most unreserved free
                   pages.  A dispatched request is therefore admitted on
                   the replica's very next step — per-replica admission
                   deferral never triggers (tests/test_router.py pins
                   this).

Requests a policy declines to place wait in the router's own FIFO queue
and are re-offered every step; policies never reorder the queue, so
dispatch is FIFO onto whichever replica the policy picks.

Determinism: each replica is solo-deterministic (greedy decode under
per-row DRS selection is bit-identical to a solo run regardless of lane
or co-residents — pinned since PR 1), so the MERGED result dict keyed by
request uid is invariant to the replica count, the routing policy, AND
the executor under temperature=0.  Sampling draws from per-replica PRNG
streams (replica r seeds at `seed + r`; replica 0 matches a bare
engine), so sampled streams are reproducible for a fixed replica count +
policy under the lockstep executors, but not across configurations (and
not at all under the free-running threaded executor, where placement
follows live timing).

HOW replicas run is a pluggable executor (serving/parallel_exec.py,
`exec_mode=`): "sequential" steps them in-process one after another and
`makespan_seconds()` MODELS the data-parallel wall clock from the
slowest replica's busy time (PR 4's record-then-model discipline);
"threaded" free-runs one worker thread per replica and "sharded" fuses
the replica group into one vmapped device step — under both,
`makespan_seconds()` is the MEASURED wall clock.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Union

import jax

from repro.analysis.contracts import owned_by, runs_on
from repro.serving.parallel_exec import (EXEC_MODES, ReplicaFailure,
                                         get_executor)
from repro.serving.scheduler import Request, ServingEngine

POLICIES = ("round_robin", "least_queue", "least_pages")

HEALTH_STATES = ("healthy", "suspect", "dead")


@dataclass(frozen=True)
class FaultToleranceConfig:
    """Failover policy for a Router (docs/fault_tolerance.md).

    Passing any config (even the defaults) OPTS IN to fault tolerance:
    replica failures are contained (reclaim + re-dispatch) instead of
    re-raised, and undispatchable requests finish with an explicit
    `failed`/`timed_out` status instead of raising the stall error.
    `Router(fault_tolerance=None)` — the default — keeps the historical
    fail-fast behavior bit-for-bit.

      max_replica_restarts — how many times a failed replica is returned
          to service before it is marked DEAD for good (0 = first
          failure is fatal to the replica).
      max_retries — per-request re-dispatch budget: a request reclaimed
          from a failed replica more than this many times finishes with
          status "failed" instead of being requeued.
      stall_timeout_s — threaded executor only: a replica whose worker
          makes no step progress for this long is marked SUSPECT and
          asked to abort at its next step boundary (None = no stall
          detection; lockstep executors step in-process and cannot
          stall-detect themselves).
    """
    max_replica_restarts: int = 1
    max_retries: int = 2
    stall_timeout_s: Optional[float] = None

    def __post_init__(self):
        if self.max_replica_restarts < 0:
            raise ValueError(f"max_replica_restarts must be >= 0 "
                             f"(got {self.max_replica_restarts})")
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0 "
                             f"(got {self.max_retries})")
        if self.stall_timeout_s is not None and self.stall_timeout_s <= 0:
            raise ValueError(f"stall_timeout_s must be positive or None "
                             f"(got {self.stall_timeout_s})")


def as_ft_config(ft) -> Optional[FaultToleranceConfig]:
    """None | True | dict | FaultToleranceConfig -> config or None."""
    if ft is None or isinstance(ft, FaultToleranceConfig):
        return ft
    if ft is True:
        return FaultToleranceConfig()
    if isinstance(ft, dict):
        return FaultToleranceConfig(**ft)
    raise ValueError(f"fault_tolerance must be None, True, a dict, or a "
                     f"FaultToleranceConfig (got {ft!r})")


@dataclass
class ReplicaHealth:
    """Per-replica health state machine: HEALTHY -> SUSPECT -> DEAD.

    HEALTHY replicas are routable.  SUSPECT is the transient stall-
    timeout state: the replica's worker stopped making progress, the
    router has asked its engine to abort, and the abort will surface as
    a failure at the next step boundary — policies already skip it.
    A failure consumes one restart from `max_replica_restarts`; within
    budget the replica returns to HEALTHY (engines stay warm, so a
    restart is just reclaim + re-mark), beyond it the replica is DEAD
    and never routed to again.  `events` records every transition as
    (from_state, to_state, reason) for tests and post-mortems."""
    state: str = "healthy"
    restarts: int = 0                       # restarts consumed so far
    failures: List[str] = field(default_factory=list)
    events: List[tuple] = field(default_factory=list)


class RoutePolicy:
    """Pluggable routing policy: `select` returns the replica index to
    dispatch `req` to, or None to leave it queued at the router until the
    next step (deferral).  Policies read replica introspection only
    (queue_depth/free_slots/free_pages/can_admit_request) — they never
    mutate engine state."""

    name = "abstract"

    def select(self, router: "Router", req: Request) -> Optional[int]:
        raise NotImplementedError


class RoundRobin(RoutePolicy):
    """Static cyclic assignment, blind to load; never defers while a
    routable (healthy) replica exists — unhealthy replicas are skipped,
    keeping the cadence over the survivors."""

    name = "round_robin"

    def __init__(self):
        self._next = 0

    def select(self, router, req):
        n = len(router.replicas)
        for _ in range(n):
            r = self._next % n
            self._next += 1
            if router.routable(r):
                return r
        return None                    # every replica unhealthy: defer


class LeastQueue(RoutePolicy):
    """Least outstanding work (queued + resident requests) among replicas
    with an uncommitted free lane; ties break on the lowest index so
    dispatch is deterministic."""

    name = "least_queue"

    def select(self, router, req):
        best, best_score = None, None
        for i, eng in enumerate(router.replicas):
            if not router.routable(i):
                continue                   # unhealthy: never dispatch into it
            if eng.free_slots() <= eng.queue_depth():
                continue                   # every free lane already spoken for
            score = eng.queue_depth() + eng.busy_slots()
            if best_score is None or score < best_score:
                best, best_score = i, score
        return best


class LeastPages(RoutePolicy):
    """Most unreserved free cache pages among replicas that can admit the
    request immediately (free lane AND the backend can cover its
    worst-case page reservation).  Dispatch-to-admission is atomic from
    the replica's point of view — its internal deferral path never runs.
    Requires an empty replica queue so a second dispatch cannot ride on
    pages the first one is about to reserve."""

    name = "least_pages"

    def select(self, router, req):
        best, best_pages = None, None
        for i, eng in enumerate(router.replicas):
            if not router.routable(i):
                continue
            if eng.queue_depth() or not eng.can_admit_request(req):
                continue
            pages = eng.free_pages()
            if best_pages is None or pages > best_pages:
                best, best_pages = i, pages
        return best


def get_policy(name: Union[str, RoutePolicy]) -> RoutePolicy:
    """Factory: policy name -> fresh policy instance (round_robin carries
    a cursor, so instances are per-router).  Objects with a `select`
    method pass through."""
    if hasattr(name, "select"):
        return name
    if name == "round_robin":
        return RoundRobin()
    if name == "least_queue":
        return LeastQueue()
    if name == "least_pages":
        return LeastPages()
    raise ValueError(f"unknown route policy {name!r}; "
                     f"expected one of {POLICIES}")


@owned_by("router", "queue", "dispatch_log", "steps", "health", "failed",
          "fail_log")
class Router:
    """Front-end over N independent `ServingEngine` replicas.

    Construction mirrors `ServingEngine` — `**engine_kw` is forwarded to
    every replica (`n_slots`, `max_seq`, `prompt_bucket`,
    `cache_backend`, `page_size`, `cache_tokens`, ...).  `cache_backend`
    must be a name, not a backend instance: a `PagedBackend` manages one
    live handle, so each replica builds its own.  `param_views` optionally
    supplies one params pytree per replica (e.g. per-device placements of
    the same weights); by default all replicas share the caller's pytree —
    data-parallel replicas hold identical weights either way.

    `exec_mode` picks how the replica group executes
    (serving/parallel_exec.py): "sequential" (default, PR 4's stepped
    in-process behavior, modeled makespan), "threaded" (one free-running
    worker thread per replica, measured makespan), or "sharded" (one
    vmapped device step over the stacked replica group, measured
    makespan; `mesh=` optionally lays the stack over a `replicas` mesh
    axis).  Under "threaded", when multiple local devices exist and no
    `param_views` are given, each replica's params are placed on its own
    device (`jax.local_devices()[r % n]`) so replica steps overlap on
    real hardware instead of queueing on one device.

    `fault_tolerance` (None | True | dict | FaultToleranceConfig) opts
    into per-replica health tracking (healthy/suspect/dead), restart
    budgets, deterministic failover (reclaimed requests replay from
    their prompts on survivors — bitwise identical at temperature 0),
    per-request deadlines, and bounded retries; `None` (default) keeps
    the historical fail-fast contract.  See docs/fault_tolerance.md.

    Drive it exactly like an engine:

        router = Router(cfg, params, dsg, n_replicas=4,
                        policy="least_queue", n_slots=4)
        for r in requests: router.submit(r)
        done = router.run()        # {uid: Request}, replica-count AND
                                   # executor invariant at temperature=0
    """

    def __init__(self, cfg, params, dsg, *, n_replicas: int = 1,
                 policy: Union[str, RoutePolicy] = "least_queue",
                 param_views: Optional[Sequence] = None, seed: int = 0,
                 exec_mode: str = "sequential", mesh=None,
                 fault_tolerance=None, **engine_kw):
        if n_replicas < 1:
            raise ValueError("router needs at least one replica")
        if hasattr(engine_kw.get("cache_backend"), "make"):
            raise ValueError(
                "pass cache_backend by name: backend instances manage one "
                "live handle and cannot be shared across replicas")
        if param_views is not None and len(param_views) != n_replicas:
            raise ValueError(f"param_views must supply one params pytree "
                             f"per replica ({n_replicas})")
        if exec_mode not in EXEC_MODES:
            # executor instances are bound to THEIR engines; the router
            # builds its own, so it only takes mode names (swap
            # router.executor after construction for custom strategies)
            raise ValueError(f"unknown exec mode {exec_mode!r}; "
                             f"expected one of {EXEC_MODES}")
        self.policy = get_policy(policy)
        dsg_views = [dsg] * n_replicas
        if (exec_mode == "threaded" and param_views is None
                and jax.local_device_count() > 1):
            # data-parallel placement: replica r's weights (and therefore
            # its jitted steps — computation follows committed inputs)
            # live on device r, so worker threads overlap on hardware
            devs = jax.local_devices()
            param_views = [jax.device_put(params, devs[r % len(devs)])
                           for r in range(n_replicas)]
            if dsg is not None:
                dsg_views = [jax.device_put(dsg, devs[r % len(devs)])
                             for r in range(n_replicas)]
        self.engines: List[ServingEngine] = [
            ServingEngine(cfg,
                          param_views[r] if param_views is not None
                          else params,
                          dsg_views[r], seed=seed + r, **engine_kw)
            for r in range(n_replicas)]
        self.executor = get_executor(exec_mode, self.engines, mesh=mesh)
        # the dispatch + introspection surface policies see: executor-
        # owned proxies (attribute access forwards to the engines)
        self.replicas = self.executor.proxies
        self.queue: collections.deque = collections.deque()
        self.dispatch_log: List[tuple] = []     # (uid, replica index)
        self.steps = 0
        # fault tolerance (docs/fault_tolerance.md): None keeps the
        # historical fail-fast behavior — failures re-raise, stalls raise
        self.ft = as_ft_config(fault_tolerance)
        self.health = [ReplicaHealth() for _ in range(n_replicas)]
        self.failed: Dict[int, Request] = {}    # failed/timed_out, by uid
        self.fail_log: List[tuple] = []         # (uid, status, reason)
        for r, eng in enumerate(self.engines):
            eng.replica_index = r               # failure attribution

    # -- request flow --------------------------------------------------------

    @runs_on("router")
    def submit(self, req: Request):
        req.submitted = req.submitted or time.perf_counter()
        self.queue.append(req)

    def routable(self, i: int) -> bool:
        """Whether policies may dispatch to replica `i`.  Without fault
        tolerance health is never mutated, so every replica stays
        routable and policies behave exactly as before."""
        return self.health[i].state == "healthy"

    @runs_on("router")
    def _dispatch(self):
        """Offer the queue head to the policy until it defers (FIFO:
        requests are never dispatched around a deferred head)."""
        while self.queue:
            r = self.policy.select(self, self.queue[0])
            if r is None:
                return
            req = self.queue.popleft()
            self.replicas[r].submit(req)
            self.dispatch_log.append((req.uid, r))

    @runs_on("router")
    def step(self):
        """One lockstep router tick: dispatch what the policy will place,
        then have the executor advance every replica that has work one
        step (per-replica time lands in the executor's busy_seconds).
        Free-running executors have no tick — drive them with
        run()/drain()."""
        if not self.executor.lockstep:
            raise RuntimeError(
                f"executor {self.executor.name!r} free-runs replicas from "
                f"worker threads; drive it with run() or drain(), not "
                f"step()")
        self._expire_deadlines()
        self._dispatch()
        active = [i for i, eng in enumerate(self.engines)
                  if self.executor.has_work(eng)]
        if active:
            try:
                self.executor.step_all(active)
            except ReplicaFailure as err:
                # fault tolerance off: re-raise (str(err) carries the
                # cause message, so callers matching on it still work)
                if not self._handle_replica_failure(err):
                    raise
        elif self.queue:
            # every replica is idle yet the policy still defers the head:
            # retirements can never free what it is waiting for (e.g. a
            # paged pool smaller than one request's reservation) — the
            # router analogue of the engine's stalled-admission error
            if self.ft is not None:
                self._fail_undispatchable()
            else:
                raise RuntimeError(
                    f"router stalled: {len(self.queue)} queued request(s) "
                    f"undispatchable by policy {self.policy.name!r} while "
                    f"all replicas are idle; raise cache_tokens or lower "
                    f"max_new/prompt_bucket")
        self.steps += 1

    # -- fault tolerance (docs/fault_tolerance.md) ---------------------------

    @runs_on("router")
    def _transition(self, i: int, state: str, reason: str):
        h = self.health[i]
        h.events.append((h.state, state, reason))
        h.state = state

    @runs_on("router")
    def _finish_failed(self, req: Request, status: str, reason: str):
        """Terminal non-ok completion: the request surfaces in done()
        with an explicit status instead of hanging the drain loop."""
        req.status = status
        req.finished = time.perf_counter()
        self.failed[req.uid] = req
        self.fail_log.append((req.uid, status, reason))

    @runs_on("router")
    def _expire_deadlines(self):
        """Fail out router-queued requests whose deadline passed.  A
        request already admitted to a lane is never interrupted — it
        either completes (cheaper than eviction this close to done) or
        gets its deadline re-checked at reclaim time after a failure."""
        if self.ft is None:
            return
        now = time.perf_counter()
        expired = [r for r in self.queue
                   if r.deadline_s is not None
                   and now - r.submitted > r.deadline_s]
        for req in expired:
            self.queue.remove(req)
            self._finish_failed(req, "timed_out",
                                f"deadline {req.deadline_s}s expired in "
                                f"router queue")

    @runs_on("router")
    def _handle_replica_failure(self, err: ReplicaFailure) -> bool:
        """Contain one replica failure; False when fault tolerance is
        off (the caller re-raises)."""
        if self.ft is None:
            return False
        self._on_replica_failure(err.index, err.cause)
        return True

    @runs_on("router")
    def _on_replica_failure(self, i: int, cause: BaseException):
        """The failover sequence: reclaim the failed replica's queued +
        in-flight requests (pages/lanes freed via ServingEngine.reset),
        decide the replica's fate against its restart budget, and requeue
        the reclaimed requests at the FRONT of the router queue (they
        were dispatched first; FIFO order is preserved).  Each reclaimed
        request replays FROM ITS PROMPT: the partial output is discarded,
        so at temperature 0 the re-decoded stream is bit-identical to an
        uninterrupted run — the paper's determinism property is what
        makes failover this cheap."""
        h = self.health[i]
        h.failures.append(str(cause))
        reclaimed = self.engines[i].reset()
        if h.restarts < self.ft.max_replica_restarts:
            h.restarts += 1
            self._transition(
                i, "healthy",
                f"restarted ({h.restarts}/{self.ft.max_replica_restarts})"
                f" after: {cause}")
        else:
            self._transition(i, "dead",
                             f"restart budget exhausted after: {cause}")
        now = time.perf_counter()
        # reversed so appendleft lands them at the head in reclaim order
        for req in reversed(reclaimed):
            req.retries += 1
            req.output.clear()           # replay from the prompt
            req.started = 0.0
            req.first_token = 0.0        # TTFT re-stamps on the survivor
            if (req.deadline_s is not None
                    and now - req.submitted > req.deadline_s):
                self._finish_failed(req, "timed_out",
                                    f"deadline {req.deadline_s}s expired "
                                    f"during failover from replica {i}")
            elif req.retries > self.ft.max_retries:
                self._finish_failed(req, "failed",
                                    f"retry budget exhausted "
                                    f"({self.ft.max_retries}) after "
                                    f"replica {i} failed")
            else:
                self.queue.appendleft(req)

    @runs_on("router")
    def _on_replica_stall(self, i: int):
        """Stall-timeout containment (threaded executor): the worker is
        stuck inside a step and cannot be killed safely, so mark the
        replica SUSPECT (policies stop routing to it) and ask its engine
        to abort — the EngineAborted raise at the next step boundary
        funnels into the standard failure path.  A worker wedged forever
        inside a single device call never reaches that boundary; its
        requests stay lost until process restart (documented limit)."""
        if self.ft is None or self.health[i].state != "healthy":
            return
        timeout = self.ft.stall_timeout_s
        self._transition(i, "suspect",
                         f"no step progress for {timeout}s")
        self.engines[i].abort = True

    @runs_on("router")
    def _fail_undispatchable(self):
        """Graceful degradation: every replica is idle yet the policy
        still defers — retirements can never unblock the head.  With no
        routable replica left every queued request fails; otherwise only
        the head does (the next head may be placeable)."""
        if not any(self.routable(i) for i in range(len(self.engines))):
            while self.queue:
                self._finish_failed(self.queue.popleft(), "failed",
                                    "no routable replica (all dead)")
        elif self.queue:
            self._finish_failed(self.queue.popleft(), "failed",
                                f"undispatchable by policy "
                                f"{self.policy.name!r} with all replicas "
                                f"idle")

    @runs_on("router")
    def reset_health(self):
        """Revive every replica (benchmark/test repeats after a chaos
        run): health back to HEALTHY, failure/event/fail logs cleared.
        Engines keep their compiled callables — reviving is free."""
        for h in self.health:
            h.state = "healthy"
            h.restarts = 0
            h.failures.clear()
            h.events.clear()
        self.failed.clear()
        self.fail_log.clear()

    def _busy(self) -> bool:
        return bool(self.queue) or any(
            self.executor.has_work(eng) for eng in self.engines)

    def run(self, max_steps: int = 10_000) -> Dict[int, Request]:
        """Drive every submitted request to completion and return the
        merged `{uid: Request}` results.  Lockstep executors are ticked
        through `step()`; free-running executors own the loop via
        `executor.drive()`."""
        if self.executor.lockstep:
            while self._busy() and self.steps < max_steps:
                self.step()
        elif self._busy():
            self.executor.drive(self, max_steps)
        return self.done()

    def drain(self, max_steps: int = 10_000) -> Dict[int, Request]:
        """Finish every in-flight and queued request (no new submissions
        assumed): dispatches the remaining router queue and steps every
        replica until its lanes retire — run() under its retirement-
        draining name, as on the engine."""
        return self.run(max_steps=max_steps)

    def done(self) -> Dict[int, Request]:
        """Merged completed requests across replicas, keyed by uid — the
        replica-count-invariant result surface (uids must be unique
        across the submitted set).  Includes requests the fault-tolerance
        layer finished with status "failed"/"timed_out": every submitted
        request surfaces exactly once, check `req.status`."""
        out: Dict[int, Request] = dict(self.failed)
        for eng in self.engines:
            out.update(eng.done)
        return out

    def close(self):
        """Release executor resources (the threaded executor's worker
        threads).  Safe to call more than once; the router remains
        usable — workers restart at the next run()."""
        self.executor.close()

    # -- introspection / stats ----------------------------------------------

    def queue_depth(self) -> int:
        """Router-level queue only; per-replica queues are the replicas'."""
        return len(self.queue)

    @property
    def busy_seconds(self) -> List[float]:
        """Per-replica accumulated stepping time (executor-owned)."""
        return self.executor.busy_seconds

    def makespan_seconds(self) -> float:
        """The data-parallel wall clock.  MEASURED (executor wall time)
        when the live executor truly overlaps replicas (threaded,
        sharded); otherwise MODELED as the slowest replica's accumulated
        busy time — under the sequential executor replicas are stepped
        one after another in-process, so the max busy time is what N
        truly parallel replicas would take."""
        if self.executor.measured:
            return self.executor.wall_seconds
        return max(self.executor.busy_seconds)

    def throughput(self) -> float:
        """Merged end-to-end tok/s (first admission -> last finish across
        all replicas); raises ValueError before any request finishes,
        matching ServingEngine.throughput()."""
        done = self.done()
        if not done:
            raise ValueError(
                "throughput() needs at least one finished request; "
                "run the router (or drain()) before reading stats")
        toks = sum(len(r.output) for r in done.values())
        t0 = min(r.started or r.submitted for r in done.values())
        t1 = max(r.finished for r in done.values())
        return toks / max(t1 - t0, 1e-9)

    @runs_on("router")
    def reset_counters(self):
        """Zero timing/step counters after warmup so measured windows are
        steady-state (the router analogue of warmup_engine's reset)."""
        self.steps = 0
        self.executor.reset_timing()
        self.dispatch_log.clear()

    def replica_stats(self) -> List[dict]:
        """Per-replica snapshot: executor busy time plus the engine's own
        step/token/queue counters — what bench_router and serve.py
        report."""
        return [{
            "replica": i,
            "busy_s": self.executor.busy_seconds[i],
            "steps": eng.steps,
            "decode_tokens": eng.decode_tokens,
            "finished": len(eng.done),
            "queue_depth": eng.queue_depth(),
            "free_slots": eng.free_slots(),
            "free_pages": eng.free_pages(),
            "health": self.health[i].state,
            "restarts": self.health[i].restarts,
        } for i, eng in enumerate(self.engines)]
