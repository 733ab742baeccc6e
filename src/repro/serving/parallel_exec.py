"""Replica executors: how N serving replicas actually run.

PR 4's Router modeled the data-parallel makespan — replicas were stepped
one after another in-process and the slowest replica's accumulated busy
time stood in for the parallel wall clock.  This module makes the
execution strategy a pluggable choice (the ROADMAP's "real parallel
replica execution" item), the same move Dynasparse makes when it maps
dynamic-sparsity work onto parallel hardware at runtime instead of
simulating the schedule:

  * sequential — PR 4's behavior bit-for-bit: replicas step in index
                 order inside the router tick, per-replica busy time is
                 recorded, and `Router.makespan_seconds()` stays the
                 MODELED number (max busy time).  The reference executor
                 every other mode is differentially tested against.
  * threaded   — one free-running worker thread per replica: each worker
                 drives its own engine's jitted prefill/decode steps
                 (dispatch overlaps device work; JAX releases the GIL
                 inside compiled calls) while the router thread keeps
                 dispatching queued requests against live introspection.
                 Makespan switches to MEASURED wall clock.
  * sharded    — replica steps fuse into ONE device dispatch: per-replica
                 decode operands and KV caches are stacked along a
                 leading replica axis and a single vmapped decode step
                 runs the whole replica group (optionally laid out over a
                 `replicas` mesh axis from `parallel/sharding.py`, so on
                 a multi-device platform each stacked slice lives on its
                 own device).  Makespan is MEASURED wall clock.

Determinism: at `temperature=0` under per-row DRS selection the merged
uid-keyed result stream is invariant to the executor choice — requests
are dispatched whole and every replica is solo-deterministic, so WHERE
and WHEN a request decodes never changes WHAT it decodes
(tests/test_parallel_exec.py pins {sequential, threaded} x {dense,
paged} x {1,2,3} replicas bitwise).  What the threaded executor gives up
is placement reproducibility for SAMPLED traffic: dispatch decisions
react to live timing, so `temperature>0` streams are only reproducible
under the lockstep executors.

The router dispatches against executor-owned `ReplicaProxy` objects, not
engines: a proxy forwards introspection reads (queue_depth / free_slots
/ free_pages / ...) and routes `submit` through the executor so worker
threads are woken when work lands.  Direct engine access stays available
as `proxy.engine` (and `Router.engines`) for warmup and stats code that
runs while no drive is in flight.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import (CheckedCondition, GuardedDeque,
                                      GuardedDict, GuardedList, locked_by,
                                      owned_by, runs_on, tsan_enabled)
from repro.serving import scheduler as sched

EXEC_MODES = ("sequential", "threaded", "sharded")


class ReplicaFailure(RuntimeError):
    """A replica's engine raised during a step.

    Carries WHICH replica (`index`) and the original exception (`cause`)
    so the router's fault-tolerance layer can contain the failure — mark
    the replica, reclaim its requests (serving/router.py).  str() embeds
    the cause message, so fail-fast callers that match on the original
    text (e.g. "engine stalled") keep working when fault tolerance is
    off and the wrapper re-raises."""

    def __init__(self, index: int, cause: BaseException):
        super().__init__(f"replica {index} failed: {cause}")
        self.index = index
        self.cause = cause


class ReplicaProxy:
    """Executor-owned handle for one replica.

    The router's policies and dispatch path talk to proxies only:
    attribute reads and writes forward to the underlying `ServingEngine`
    (so the whole introspection surface — `queue_depth()`,
    `free_slots()`, `free_pages()`, `can_admit_request()`, ... — works
    unchanged), while `submit` routes through the executor, which is
    what lets the threaded executor wake the replica's worker the moment
    work is dispatched to it."""

    __slots__ = ("_executor", "index")

    def __init__(self, executor: "ReplicaExecutor", index: int):
        object.__setattr__(self, "_executor", executor)
        object.__setattr__(self, "index", index)

    @property
    def engine(self):
        """The wrapped ServingEngine (direct access for warmup/stats)."""
        return self._executor.engines[self.index]

    def submit(self, req):
        """Dispatch `req` to this replica through the executor."""
        self._executor.dispatch(self.index, req)

    def __getattr__(self, name):
        return getattr(self._executor.engines[self.index], name)

    def __setattr__(self, name, value):
        setattr(self._executor.engines[self.index], name, value)

    def __repr__(self):
        return (f"ReplicaProxy({self.index}, "
                f"executor={self._executor.name!r})")


class ReplicaExecutor:
    """How a router's replica group executes.

    Concrete executors implement either the lockstep protocol
    (`lockstep=True`: the router tick calls `step_all(indices)` and every
    named replica advances exactly one step before the tick returns) or
    the free-running protocol (`lockstep=False`: `drive(router,
    max_steps)` owns the whole run loop — workers step their replicas
    whenever they have work while the router thread dispatches).

    Timing contract: `busy_seconds[i]` accumulates replica i's stepping
    time; `wall_seconds` accumulates real elapsed time across
    `step_all`/`drive` calls.  `measured` tells the router which number
    `makespan_seconds()` should trust — the modeled max-busy-time for
    the sequential executor, the measured wall clock once replicas truly
    overlap.  `Router.reset_counters()` calls `reset_timing()` after
    warmup so measured windows are steady-state.
    """

    name = "abstract"
    lockstep = True
    #: True when replicas genuinely overlap, so wall_seconds (not the
    #: modeled max busy time) is the data-parallel makespan.
    measured = False

    def __init__(self, engines: Sequence):
        self.engines = list(engines)
        self.proxies = [ReplicaProxy(self, i)
                        for i in range(len(self.engines))]
        self.busy_seconds = [0.0] * len(self.engines)
        self.wall_seconds = 0.0

    # -- dispatch ------------------------------------------------------------

    def dispatch(self, index: int, req):
        """Hand `req` to replica `index` (called from the router thread,
        between ticks for lockstep executors)."""
        self.engines[index].submit(req)

    # -- execution -----------------------------------------------------------

    def step_all(self, indices: Sequence[int]):
        """Advance every replica in `indices` one step (lockstep only)."""
        raise NotImplementedError

    def drive(self, router, max_steps: int):
        """Run the router's whole drain loop (free-running only)."""
        raise NotImplementedError(
            f"{self.name!r} is a lockstep executor; the router drives it "
            f"through step_all()")

    # -- bookkeeping ---------------------------------------------------------

    def reset_timing(self):
        self.busy_seconds = [0.0] * len(self.engines)
        self.wall_seconds = 0.0

    def warm(self, sample: bool = False):
        """Pre-compile any executor-owned jitted callables (the engines'
        own are warmed by workload.warmup_engine).  No-op by default —
        only the sharded executor compiles beyond the engines."""

    def close(self):
        """Release executor resources (worker threads).  Idempotent."""

    @staticmethod
    def has_work(eng) -> bool:
        """Whether an engine has anything left to step: queued requests
        or a resident (non-free) lane.  THE busy predicate — the router
        and every executor share it."""
        return bool(eng.queue) or any(not s.free for s in eng.slots)


class SequentialExecutor(ReplicaExecutor):
    """PR 4's in-process behavior, bit-for-bit: replicas step one after
    another in replica-index order inside the router tick.  Makespan
    stays MODELED (slowest replica's accumulated busy time) — stepping
    is serialized, so wall clock would hide the data-parallel win."""

    name = "sequential"
    lockstep = True
    measured = False

    def step_all(self, indices):
        t0 = time.perf_counter()
        try:
            for i in indices:
                ti = time.perf_counter()
                try:
                    # each engine's step touches only its own state (its
                    # plan, dispatch and commits), so a failure here never
                    # corrupts a sibling replica's state — replicas after
                    # `i` simply skip this tick, which lockstep never
                    # promised anyway
                    self.engines[i].step()
                except BaseException as e:
                    raise ReplicaFailure(i, e) from e
                finally:
                    self.busy_seconds[i] += time.perf_counter() - ti
        finally:
            self.wall_seconds += time.perf_counter() - t0


@locked_by("_cond", "_idle", "_errors", "busy_seconds", "_stop",
           "_progress")
@owned_by("router", "_threads", "wall_seconds")
class ThreadedExecutor(ReplicaExecutor):
    """One free-running worker thread per replica.

    Workers step their own engine whenever it has work (each engine owns
    its jitted callables, and JAX releases the GIL inside compiled
    dispatches, so one replica's host-side scheduling overlaps another's
    device work).  The router thread stays the only dispatcher: it
    re-offers the queue head to the policy against live introspection
    and `dispatch()` wakes the chosen replica's worker.  There is no
    per-tick barrier — a replica draining light requests never waits for
    a sibling grinding a heavy generation.

    Consequences, both pinned by tests/test_parallel_exec.py: greedy
    (`temperature=0`) merged streams are bitwise identical to the
    sequential executor (placement never changes content), and
    `makespan_seconds()` is the MEASURED wall clock of the drive loop.
    Sampled streams are NOT reproducible across runs (placement depends
    on live timing — the engine's per-(step, lane) PRNG schedule sees
    different admission steps), which is the documented trade.

    Worker threads are daemons, started lazily at the first `drive()`
    and parked between runs; call `close()` to join them (long-lived
    apps), or let process exit reap them (tests, benchmarks).
    """

    name = "threaded"
    lockstep = False
    measured = True
    # router safety-net poll: worker -> router wakes ride a sticky Event
    # (set() is never lost, unlike a notify that fires while the router
    # is mid-dispatch), so this only bounds recovery from a crashed
    # worker or an external submit
    _POLL_S = 0.1

    def __init__(self, engines):
        super().__init__(engines)
        # REPRO_TSAN=1 (read once here, like REPRO_INTERPRET at trace
        # time): the Condition learns who holds it and the annotated
        # mutable state asserts the lock/owner discipline on every
        # mutation — the tier-1 suite doubles as a thread sanitizer
        self._tsan = tsan_enabled()
        self._cond = (CheckedCondition() if self._tsan
                      else threading.Condition(threading.RLock()))
        self._router_wake = threading.Event()
        self._idle = [True] * len(self.engines)
        self._errors: List[ReplicaFailure] = []
        # per-replica monotonic stamp of the last completed step — the
        # drive loop's stall-timeout detector compares against it while
        # a worker is busy (fault_tolerance.stall_timeout_s)
        self._progress = [time.perf_counter()] * len(self.engines)
        self._stop = False
        self._threads: Optional[List[threading.Thread]] = None
        if self._tsan:
            self._idle = GuardedList(self._idle, cond=self._cond,
                                     label="ThreadedExecutor._idle")
            self._errors = GuardedList(cond=self._cond,
                                       label="ThreadedExecutor._errors")
            self._progress = GuardedList(
                self._progress, cond=self._cond,
                label="ThreadedExecutor._progress")
            self.busy_seconds = GuardedList(
                self.busy_seconds, cond=self._cond,
                label="ThreadedExecutor.busy_seconds")
            for i, eng in enumerate(self.engines):
                eng.queue = GuardedDeque(eng.queue, cond=self._cond,
                                         label=f"engines[{i}].queue")
                eng.done = GuardedDict(eng.done, cond=self._cond,
                                       label=f"engines[{i}].done")

    def _own_engine(self, i: int, thread):
        """TSAN bookkeeping: resolve the 'worker' role for replica `i` to
        a live thread (claim) or back to quiescent (None — anyone may
        mutate, e.g. warmup/stats on the main thread between drives)."""
        if not self._tsan:
            return
        eng = self.engines[i]
        for obj in (eng.queue, eng.done):
            set_owner = getattr(obj, "set_owner", None)
            if set_owner is not None:
                set_owner(thread)

    # -- dispatch ------------------------------------------------------------

    @runs_on("router")
    def dispatch(self, index, req):
        with self._cond:
            self.engines[index].submit(req)
            self._cond.notify_all()

    # -- worker protocol -----------------------------------------------------

    @runs_on("router")
    def _ensure_threads(self):
        """Start (or re-staff) one worker per replica.  A worker exits
        when its engine raises (the error re-raises in drive), so a
        later run() must replace dead workers; parked live workers are
        kept."""
        with self._cond:
            old = self._threads or [None] * len(self.engines)
            if all(t is not None and t.is_alive() for t in old):
                return
            if not any(t is not None and t.is_alive() for t in old):
                self._stop = False   # fully stopped: safe to restart
            if self._stop:
                return               # close() timed out on a live worker
            self._threads = []
            for i in range(len(self.engines)):
                t = old[i]
                if t is None or not t.is_alive():
                    # start() under the lock is safe: the worker's first
                    # action is to acquire the cond, so it just blocks
                    # until we release
                    t = threading.Thread(target=self._worker, args=(i,),
                                         daemon=True, name=f"replica-{i}")
                    t.start()
                self._threads.append(t)

    @runs_on("worker")
    def _worker(self, i: int):
        eng = self.engines[i]
        while True:
            with self._cond:
                self._own_engine(i, None)     # parked: engine quiescent
                while not self._stop and not self.has_work(eng):
                    self._idle[i] = True
                    self._router_wake.set()
                    self._cond.wait()
                if self._stop:
                    return
                self._idle[i] = False
                # fresh stall clock at the idle->busy transition: the
                # stamp would otherwise date from the last completed
                # step, and a worker woken after a long idle would be
                # falsely suspected before its first step finishes
                self._progress[i] = time.perf_counter()
                self._own_engine(i, threading.current_thread())
            while True:                      # step outside the lock
                done0 = len(eng.done)
                queued0 = len(eng.queue)
                free0 = eng.free_slots()
                t0 = time.perf_counter()
                try:
                    eng.step()
                except BaseException as e:   # surfaced by the drive loop
                    with self._cond:
                        self._errors.append(ReplicaFailure(i, e))
                        self._idle[i] = True
                        self._own_engine(i, None)
                        self._router_wake.set()
                    return
                dt = time.perf_counter() - t0
                with self._cond:
                    # makespan code reads busy_seconds while workers run;
                    # an unlocked += is a lost-update race between the
                    # read-modify-write and reset_timing's rebind
                    self.busy_seconds[i] += dt
                    self._progress[i] = time.perf_counter()
                # wake the router only on events a policy can act on — a
                # request finished, a retirement freed a lane (a step
                # after its stream finished, when a decode was in flight),
                # or an admission drained this replica's queue.  Signaling
                # every step would have the router thread and N workers
                # convoying; the sticky Event keeps even an
                # inconveniently-timed wake from being lost.
                if (len(eng.done) != done0 or eng.free_slots() > free0
                        or len(eng.queue) < queued0):
                    self._router_wake.set()
                # observe close() promptly even while work remains —
                # engines are always between steps here, so stopping is
                # state-safe
                if self._stop or not self.has_work(eng):
                    break                    # outer loop parks under lock

    # -- drive ---------------------------------------------------------------

    @runs_on("router")
    def drive(self, router, max_steps: int):
        """Drain the router: dispatch from this (the router's) thread,
        let workers free-run, return when no queued or resident work is
        left.  Worker exceptions re-raise — unless the router opted into
        fault tolerance, in which case they are contained (reclaim +
        re-dispatch, serving/router.py) and the dead worker is
        restaffed; likewise the router-stall error (all workers parked,
        policy still defers the head) degrades to explicit per-request
        failure instead of raising.  With `stall_timeout_s` set, a busy
        worker making no step progress gets its replica marked SUSPECT
        and its engine aborted at the next step boundary."""
        self._ensure_threads()
        ft = getattr(router, "ft", None)
        t0 = time.perf_counter()
        try:
            with self._cond:
                now = time.perf_counter()
                for i in range(len(self.engines)):
                    self._progress[i] = now    # stall clock starts now
                self._cond.notify_all()      # work may predate the drive
            while router.steps < max_steps:
                with self._cond:             # dispatch + parked check are
                    if self._errors:         # atomic vs worker parking
                        err = self._errors.pop(0)
                        if not router._handle_replica_failure(err):
                            raise err
                        # contained: restaff the dead worker (a restarted
                        # replica needs one; a DEAD replica's worker just
                        # parks — routable() keeps it starved) and give
                        # the revived replica a fresh stall clock
                        self._progress[err.index] = time.perf_counter()
                        self._ensure_threads()
                        self._cond.notify_all()
                        router.steps += 1
                        continue
                    if ft is not None and ft.stall_timeout_s is not None:
                        now = time.perf_counter()
                        for i in range(len(self.engines)):
                            if (not self._idle[i]
                                    and now - self._progress[i]
                                    > ft.stall_timeout_s):
                                router._on_replica_stall(i)
                    router._expire_deadlines()
                    router._dispatch()       # safe: RLock is re-entrant
                    all_parked = (all(self._idle) and
                                  not any(self.has_work(e)
                                          for e in self.engines))
                    if all_parked and not router.queue:
                        return               # drained
                    if all_parked and router.queue:
                        if ft is not None:
                            router._fail_undispatchable()
                            router.steps += 1
                            continue
                        raise RuntimeError(
                            f"router stalled: {len(router.queue)} queued "
                            f"request(s) undispatchable by policy "
                            f"{router.policy.name!r} while all replicas "
                            f"are idle; raise cache_tokens or lower "
                            f"max_new/prompt_bucket")
                    router.steps += 1
                # wait OUTSIDE the lock: the sticky Event means a wake
                # that fires between the check and the wait still lands
                self._router_wake.wait(timeout=self._POLL_S)
                self._router_wake.clear()
            # step budget exhausted with work left: stop the workers so
            # the snapshot run() returns is stable (the lockstep
            # executors also stop stepping at the cap); the next run()
            # restarts fresh workers
            self.close()
        finally:
            self.wall_seconds += time.perf_counter() - t0

    @runs_on("router")
    def reset_timing(self):
        """Base behavior under the lock; under TSAN rebinding replaced
        the guarded busy_seconds with a plain list, so re-wrap."""
        with self._cond:
            super().reset_timing()
            if self._tsan:
                self.busy_seconds = GuardedList(
                    self.busy_seconds, cond=self._cond,
                    label="ThreadedExecutor.busy_seconds")

    @runs_on("router")
    def close(self):
        """Idempotent shutdown: signal every worker, join each with a
        bounded timeout, and RAISE naming the workers that failed to
        exit instead of silently leaking their threads.  A straggler is
        a worker stuck inside a single step (device call); _stop stays
        set so it exits at its next step boundary rather than
        resurrecting — call close() again to confirm the shutdown."""
        with self._cond:
            threads = self._threads or ()
            if not threads:
                return                   # already closed: no-op
            self._stop = True
            self._cond.notify_all()
        for t in threads:
            t.join(timeout=5.0)
        stuck = [t.name for t in threads if t.is_alive()]
        if stuck:
            # restarting now could put two workers on one engine, so the
            # executor stays in the stopped state until the straggler
            # exits (a later _ensure_threads checks _stop)
            raise RuntimeError(
                f"ThreadedExecutor.close(): worker thread(s) "
                f"{', '.join(stuck)} did not exit within 5s (stuck "
                f"inside a step); they exit at their next step boundary "
                f"— call close() again to confirm shutdown")
        with self._cond:
            self._threads = None
            self._stop = False


class ShardedExecutor(ReplicaExecutor):
    """One device dispatch for the whole replica group.

    Each lockstep tick runs the host half of every active replica's step
    (`ServingEngine.begin_step()`), stacks each replica's decode operands
    (`ServingEngine.decode_operands`, KV cache included) along a leading
    replica axis, executes ONE jitted+vmapped decode program
    (`scheduler.make_decode_fns` — the engines' own callables, vmapped),
    then unstacks and commits per replica (`ServingEngine.commit`).
    With a mesh carrying a `replicas` axis (see
    `parallel.sharding.replica_mesh`) the stacked operands are laid out
    over that axis, so each replica's slice lives — and computes — on
    its own device; without a mesh the vmapped step still collapses N
    dispatches into one, which is the win when host dispatch dominates
    (many small replicas).

    Every tick batches the FULL replica group: engines with no active
    work that tick ride along on their idle plan
    (`ServingEngine.idle_plan`: the writes land where nothing reads
    them), so the group step compiles one program per sampling mode
    instead of one per active-subset size, and `warm()` can pre-compile
    them (warmup_router calls it).

    Scope and cost, honestly: admission prefills still run per-replica
    on the host between ticks, and stack/unstack touches every cache
    byte per tick (on a sharded mesh the slices are device-local so the
    reshuffle does not cross devices).  This executor is the scaling
    skeleton for replica groups on real device meshes; the threaded
    executor is the general-purpose parallel choice.
    """

    name = "sharded"
    lockstep = True
    measured = True

    def __init__(self, engines, mesh=None):
        super().__init__(engines)
        if any(getattr(e, "dsg_rt", None) is not None for e in engines):
            raise NotImplementedError(
                "sharded executor batches the dense-FFN decode step; "
                "DSG-serving engines dispatch the CSR/refresh variants "
                "inside ServingEngine.step() — use exec_mode "
                "'sequential' or 'threaded' with dsg_serving")
        self.mesh = mesh
        self._sharding = None
        if mesh is not None:
            if "replicas" not in mesh.axis_names:
                raise ValueError(
                    "sharded executor needs a mesh with a 'replicas' "
                    f"axis (parallel.sharding.replica_mesh), got axes "
                    f"{mesh.axis_names}")
            if self.engines and len(self.engines) % mesh.shape["replicas"]:
                raise ValueError(
                    f"{len(self.engines)} replicas do not divide the "
                    f"mesh's replicas axis ({mesh.shape['replicas']})")
            from jax.sharding import NamedSharding

            from repro.parallel.sharding import replica_stack_spec
            self._sharding = NamedSharding(mesh, replica_stack_spec())
        e0 = self.engines[0]
        if any(e.decode_chunk != e0.decode_chunk for e in self.engines):
            raise ValueError(
                "sharded executor needs a homogeneous decode_chunk "
                "across replicas (the group step is one compiled "
                "variant): got "
                f"{[e.decode_chunk for e in self.engines]}")
        self.chunk = e0.decode_chunk
        shared_p = all(e.params is e0.params for e in self.engines)
        shared_d = all(e.dsg is e0.dsg for e in self.engines)
        p_ax = None if shared_p else 0
        d_ax = None if shared_d else 0
        # params/dsg are immutable across ticks — stack per-replica views
        # ONCE here; only the caches restack per tick
        self._params_in = (e0.params if shared_p
                           else jax.tree_util.tree_map(
                               lambda *ls: self._stack(list(ls)),
                               *[e.params for e in self.engines]))
        self._dsg_in = (e0.dsg if shared_d
                        else jax.tree_util.tree_map(
                            lambda *ls: self._stack(list(ls)),
                            *[e.dsg for e in self.engines]))
        # the group programs, indexed by `sample`: the engine's callables
        # vmapped over every operand `decode_operands` stacks on the
        # replica axis, the stacked caches donated as the engines donate
        # theirs
        greedy, sample = sched.make_decode_fns(e0.cfg, chunk=self.chunk,
                                               max_seq=e0.max_seq)
        idle = e0.idle_plan()
        axes = [(p_ax, d_ax) + (0,) * len(e0.decode_operands(idle, s))
                for s in (False, True)]
        self._jit_group = (
            jax.jit(jax.vmap(greedy, in_axes=axes[0]), donate_argnums=(3,)),
            jax.jit(jax.vmap(sample, in_axes=axes[1]), donate_argnums=(3,)))
        # begin-phase failures deferred past the group step (one raise
        # per tick keeps sibling replicas consistent; see step_all)
        self._pending_failures: List[ReplicaFailure] = []

    def _stack(self, leaves):
        x = jnp.stack(leaves)
        if self._sharding is not None:
            x = jax.device_put(x, self._sharding)
        return x

    def _group_step(self, plans, sample: bool):
        """One vmapped decode over the full group's stacked operands;
        returns the host outputs stacked over replicas (all but the cache
        and the scores), the stacked output caches, and the wall time."""
        t0 = time.perf_counter()
        tok, caches, *ops = jax.tree_util.tree_map(
            lambda *ls: self._stack(list(ls)),
            *[e.decode_operands(p, sample)
              for e, p in zip(self.engines, plans)])
        *outs, caches, _ = self._jit_group[sample](
            self._params_in, self._dsg_in, tok, caches, *ops)
        outs = [np.asarray(o) for o in outs]     # one device sync per tick
        return outs, caches, time.perf_counter() - t0

    def step_all(self, indices):
        t0 = time.perf_counter()
        if self._pending_failures:       # deferred from the previous tick
            raise self._pending_failures.pop(0)
        idx = set(indices)
        plans, real, failures = [], [], []
        for i, eng in enumerate(self.engines):
            plan = None
            if i in idx:
                try:
                    plan = eng.begin_step()
                except BaseException as e:
                    # siblings that already began this tick have emitted
                    # tokens — finish the group step WITHOUT the failed
                    # replica (it rides its idle plan) and raise after
                    # commit, so no sibling double-emits on the retry
                    failures.append(ReplicaFailure(i, e))
            if plan is not None:
                real.append(i)
            plans.append(plan if plan is not None else eng.idle_plan())
        if not real:
            self.wall_seconds += time.perf_counter() - t0
            if failures:
                self._pending_failures.extend(failures[1:])
                raise failures[0]
            return
        outs, out, _ = self._group_step(plans,
                                        any(p.sample for p in plans))
        wall = time.perf_counter() - t0
        share = wall / len(real)
        for i, plan in enumerate(plans):
            # rebinding is uniform: idle riders only got scratch
            # scribbles in regions the next admission overwrites
            self.engines[i].cache = jax.tree_util.tree_map(
                lambda x: x[i], out)
            if i in idx and plan.active:
                # decode_seconds gets an equal share of the fused
                # dispatch; busy_seconds gets the full wall (the replica
                # was co-busy for all of it) — makespan uses
                # wall_seconds either way
                self.engines[i].commit(plan, [o[i] for o in outs], share)
                self.busy_seconds[i] += wall
        self.wall_seconds += wall
        if failures:
            self._pending_failures.extend(failures[1:])
            raise failures[0]

    def warm(self, sample: bool = False):
        """Pre-compile the group step (the executor analogue of
        `ServingEngine.warm_decode`; warmup_router calls it so no vmapped
        compile lands inside a measured window) on every engine's idle
        plan, whose writes nothing reads."""
        plans = [e.idle_plan() for e in self.engines]
        for do_sample in (False, True)[:1 + sample]:
            _, out, _ = self._group_step(plans, do_sample)
            for i in range(len(self.engines)):
                self.engines[i].cache = jax.tree_util.tree_map(
                    lambda x: x[i], out)


def get_executor(mode, engines, *, mesh=None) -> ReplicaExecutor:
    """Executor factory: name -> fresh executor over `engines`.  Objects
    already implementing the executor protocol pass through (custom
    strategies, e.g. a process pool)."""
    if isinstance(mode, ReplicaExecutor):
        return mode
    if mode == "sequential":
        return SequentialExecutor(engines)
    if mode == "threaded":
        return ThreadedExecutor(engines)
    if mode == "sharded":
        return ShardedExecutor(engines, mesh=mesh)
    raise ValueError(f"unknown exec mode {mode!r}; "
                     f"expected one of {EXEC_MODES}")
