"""Synthetic serving workloads + engine measurement harness.

Mixed-length traffic is where overlap admission earns its keep: short and
long prompts (and short and long generations) interleave, and every lane a
retirement frees refills at the next step.  The CPU benchmarks and
`launch/serve.py --workload mixed` drive the engine through this module so
the numbers agree.

Open-loop evaluation (docs/serving.md): closed-loop drains measure the
system at its own pace — every retirement immediately frees capacity for
the next request, so queueing delay never appears.  Production traffic
arrives on ITS schedule; `poisson_arrivals`/`trace_arrivals` +
`run_open_loop` submit requests at wall-clock offsets regardless of
engine state, and `latency_stats` splits the user-visible latency into
TTFT (submit -> first token) and TPOT (steady-state inter-token) —
the two numbers serving SLOs are written against.  SCENARIOS holds the
mixed-tenant presets (chat / batch / long_context);
`shared_prefix_requests` builds the overlapping-prefix traffic the
copy-on-write paged backend dedupes (bench_prefix_sharing.py).
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.runtime.fault_tolerance import ServingFaultInjector
from repro.serving.router import Router
from repro.serving.scheduler import Request, ServingEngine


def mixed_requests(vocab: int, n_requests: int, *, seed: int = 0,
                   prompt_range=(8, 192), max_new_range=(8, 64),
                   eos_id=None, temperature: float = 0.0,
                   top_p: float = 1.0) -> List[Request]:
    """Mixed-length synthetic traffic: uniform prompt lengths and
    generation budgets over the given ranges."""
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n_requests):
        plen = int(rng.integers(prompt_range[0], prompt_range[1] + 1))
        max_new = int(rng.integers(max_new_range[0], max_new_range[1] + 1))
        prompt = rng.integers(0, vocab, plen, dtype=np.int32)
        reqs.append(Request(uid=uid, prompt=prompt, max_new=max_new,
                            eos_id=eos_id, temperature=temperature,
                            top_p=top_p))
    return reqs


def skewed_requests(vocab: int, n_requests: int, *, period: int = 2,
                    seed: int = 0,
                    heavy_prompt=(96, 160), heavy_new=(40, 56),
                    light_prompt=(8, 24), light_new=(2, 4),
                    eos_id=None) -> List[Request]:
    """Skewed mixed traffic: every `period`-th request is HEAVY (long
    prompt, long generation), the rest are light.  With `period` equal to
    the replica count, static round-robin routing funnels every heavy
    request onto one replica — the hash-collision pathology bursty
    production traffic hits — while queue-depth-aware routing spreads
    them by live load (benchmarks/bench_router.py measures the gap)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n_requests):
        pr, nr = ((heavy_prompt, heavy_new) if uid % period == 0
                  else (light_prompt, light_new))
        plen = int(rng.integers(pr[0], pr[1] + 1))
        reqs.append(Request(uid=uid,
                            prompt=rng.integers(0, vocab, plen,
                                                dtype=np.int32),
                            max_new=int(rng.integers(nr[0], nr[1] + 1)),
                            eos_id=eos_id))
    return reqs


#: Mixed-tenant scenario presets (docs/serving.md): the three canonical
#: production traffic shapes.  Ranges are in tokens, sized for the smoke
#: model's default engine limits (max_seq 384, prompt_bucket 256).
SCENARIOS = {
    "chat": dict(prompt_range=(8, 48), max_new_range=(16, 48)),
    "batch": dict(prompt_range=(48, 128), max_new_range=(32, 64)),
    "long_context": dict(prompt_range=(128, 256), max_new_range=(8, 24)),
}


def scenario_requests(scenario: str, vocab: int, n_requests: int, *,
                      seed: int = 0, eos_id=None, temperature: float = 0.0,
                      top_p: float = 1.0) -> List[Request]:
    """Single-tenant traffic drawn from a SCENARIOS preset."""
    try:
        preset = SCENARIOS[scenario]
    except KeyError:
        raise ValueError(f"unknown scenario {scenario!r}; expected one of "
                         f"{sorted(SCENARIOS)}") from None
    return mixed_requests(vocab, n_requests, seed=seed, eos_id=eos_id,
                          temperature=temperature, top_p=top_p, **preset)


def mixed_tenant_requests(vocab: int, n_requests: int, *,
                          scenarios=("chat", "batch", "long_context"),
                          seed: int = 0, eos_id=None,
                          temperature: float = 0.0,
                          top_p: float = 1.0) -> List[Request]:
    """Interleaved multi-tenant traffic: request uid i draws its shape
    from scenarios[i % len(scenarios)], so every scheduling window sees
    all tenants at once — the heterogeneity that makes open-loop TTFT
    tails interesting (a long-context prefill ahead of a chat turn)."""
    presets = []
    for s in scenarios:
        if s not in SCENARIOS:
            raise ValueError(f"unknown scenario {s!r}; expected one of "
                             f"{sorted(SCENARIOS)}")
        presets.append(SCENARIOS[s])
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n_requests):
        p = presets[uid % len(presets)]
        plen = int(rng.integers(p["prompt_range"][0],
                                p["prompt_range"][1] + 1))
        max_new = int(rng.integers(p["max_new_range"][0],
                                   p["max_new_range"][1] + 1))
        reqs.append(Request(uid=uid,
                            prompt=rng.integers(0, vocab, plen,
                                                dtype=np.int32),
                            max_new=max_new, eos_id=eos_id,
                            temperature=temperature, top_p=top_p))
    return reqs


def shared_prefix_requests(vocab: int, n_requests: int, *,
                           prompt_len: int = 24, prefix_len: int = 16,
                           max_new: int = 8, seed: int = 0, eos_id=None,
                           temperature: float = 0.0,
                           top_p: float = 1.0) -> List[Request]:
    """Overlapping-prefix traffic: every prompt is `prompt_len` tokens,
    the first `prefix_len` identical (a shared system prompt), the tail
    unique per request.  prompt_len is FIXED on purpose: the engine
    right-aligns prompts into their bucket, so only identically padded
    rows produce identical page bytes — equal-length prompts are the
    shape on which prefix sharing (kv_cache.PagedBackend) can dedupe."""
    if not 0 <= prefix_len <= prompt_len:
        raise ValueError(f"need 0 <= prefix_len ({prefix_len}) <= "
                         f"prompt_len ({prompt_len})")
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, prefix_len, dtype=np.int32)
    reqs = []
    for uid in range(n_requests):
        suffix = rng.integers(0, vocab, prompt_len - prefix_len,
                              dtype=np.int32)
        reqs.append(Request(uid=uid,
                            prompt=np.concatenate([prefix, suffix]),
                            max_new=max_new, eos_id=eos_id,
                            temperature=temperature, top_p=top_p))
    return reqs


# ---------------------------------------------------------------------------
# open-loop arrivals
# ---------------------------------------------------------------------------

def poisson_arrivals(n: int, rate_rps: float, *, seed: int = 0,
                     start: float = 0.0) -> np.ndarray:
    """Cumulative arrival offsets (seconds) of a Poisson process at
    `rate_rps` requests/second — the standard open-loop arrival model
    (memoryless gaps, bursts included)."""
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be positive, got {rate_rps}")
    rng = np.random.default_rng(seed)
    return start + np.cumsum(rng.exponential(1.0 / rate_rps, n))


def trace_arrivals(inter_arrival_s, *, start: float = 0.0) -> np.ndarray:
    """Cumulative arrival offsets from recorded inter-arrival gaps — the
    replay-a-production-trace arrival model."""
    gaps = np.asarray(inter_arrival_s, dtype=float)
    if gaps.ndim != 1:
        raise ValueError("inter_arrival_s must be a 1-D gap sequence")
    if (gaps < 0).any():
        raise ValueError("inter-arrival gaps must be non-negative")
    return start + np.cumsum(gaps)


def run_open_loop(runner, requests: List[Request], arrivals,
                  *, max_steps: int = 200_000) -> Dict[int, Request]:
    """Drive `runner` (a ServingEngine, or a Router on a lockstep
    executor) open-loop: request i is submitted at wall-clock offset
    arrivals[i] whether or not the system has capacity — queueing delay
    lands in TTFT, exactly as a user would see it.  Between arrivals the
    loop steps the runner if it has work, else sleeps until the next
    arrival.  Returns the merged {uid: Request} results.

    Free-running executors own their drive loop and cannot interleave
    timed submissions with ticks, so they are rejected — open-loop
    measurement needs the tick under this loop's control."""
    arrivals = np.asarray(arrivals, dtype=float)
    if len(arrivals) != len(requests):
        raise ValueError(f"{len(requests)} requests but {len(arrivals)} "
                         f"arrival offsets")
    if len(arrivals) > 1 and (np.diff(arrivals) < 0).any():
        raise ValueError("arrival offsets must be non-decreasing")
    is_router = isinstance(runner, Router)
    if is_router and not runner.executor.lockstep:
        raise ValueError(
            f"open-loop driving needs a lockstep runner; executor "
            f"{runner.executor.name!r} free-runs its replicas")
    if is_router:
        busy = runner._busy
    else:
        busy = lambda: bool(runner.queue) or any(      # noqa: E731
            not s.free for s in runner.slots)
    t0 = time.perf_counter()
    i, steps = 0, 0
    while i < len(requests) or busy():
        now = time.perf_counter() - t0
        while i < len(requests) and arrivals[i] <= now:
            runner.submit(requests[i])
            i += 1
        if busy():
            runner.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"open-loop run exceeded max_steps={max_steps} with "
                    f"{i}/{len(requests)} submitted")
        elif i < len(requests):
            # idle: sleep toward the next arrival (capped so a long gap
            # still polls, keeping the loop responsive to clock skew)
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
    return runner.done() if is_router else dict(runner.done)


def warm_temp_for(requests, warm_temp: float = 0.0) -> float:
    """The warmup temperature a request list needs: any request with
    temperature > 0 means the sampling decode/admission variants must be
    pre-compiled, or the first sampled request to arrive lands a jit
    compile inside the measured window.  Callers that know their traffic
    should pass it to warmup_engine/warmup_router via `requests=` (which
    routes through here) instead of hand-picking warm_temp."""
    return max((r.temperature for r in requests), default=warm_temp)


def warmup_engine(eng: ServingEngine, vocab: int,
                  warm_temp: float = 0.0, max_steps: int = 100_000,
                  requests=None):
    """Compile every shape a measured window can hit, then reset the
    engine's counters: one throwaway admission per prompt bucket (the
    prefill variants + the decode step), the sampling decode/admission
    variants when the traffic samples (same compiled shapes for any
    temperature > 0), and every decode program the engine can dispatch
    (ServingEngine.warm_decode; a DSG engine's CSR widths reach beyond
    what a short warm-up's traffic crosses).  Pass the workload's
    `requests` so the sampling variants are warmed exactly when the
    traffic needs them."""
    if requests is not None:
        warm_temp = warm_temp_for(requests, warm_temp)
    rng = np.random.default_rng(12345)
    for i, b in enumerate(eng.buckets):
        eng.submit(Request(uid=-1 - i,
                           prompt=rng.integers(0, vocab, b, dtype=np.int32),
                           max_new=2, temperature=warm_temp))
    if warm_temp > 0:    # mixed traffic also hits the greedy-only step
        eng.submit(Request(uid=-1 - len(eng.buckets),
                           prompt=rng.integers(0, vocab, eng.buckets[0],
                                               dtype=np.int32),
                           max_new=2))
    eng.run(max_steps=max_steps)
    eng.warm_decode(sample=warm_temp > 0)
    eng.done.clear()
    eng.steps = 0
    eng.decode_seconds = 0.0
    eng.decode_tokens = 0


def warmup_router(router: Router, vocab: int, warm_temp: float = 0.0,
                  max_steps: int = 100_000, requests=None):
    """Warm EVERY replica's prefill buckets and decode programs (each replica owns its own jitted callables — nothing is shared), then
    zero the router's timing counters so measured makespans are
    steady-state.  Engines are warmed directly (not through the
    executor), which is safe while no run is in flight; the executor's
    own jitted callables (the sharded group step) are warmed through
    `executor.warm()`.  Pass `requests` to derive warm_temp from the
    actual traffic (see warm_temp_for)."""
    if requests is not None:
        warm_temp = warm_temp_for(requests, warm_temp)
    for eng in router.engines:
        warmup_engine(eng, vocab, warm_temp, max_steps=max_steps)
    router.executor.warm(sample=warm_temp > 0)
    router.reset_counters()


def latency_stats(done: Dict[int, Request]) -> Dict[str, float]:
    """p50/p95 end-to-end latency (submit -> finish) over requests that
    finished OK, split into the two SLO components: TTFT (submit ->
    first emitted token, the queueing + prefill wait a user stares at)
    and TPOT (steady-state seconds per token after the first — the
    streaming rate).  Failed/timed-out requests are counted separately,
    NOT folded into the percentiles: a timed-out request's finish stamp
    is exactly its deadline, so including it reports the SLO ceiling as
    an observed latency and quietly flattens p95 toward the deadline.

    TTFT needs the engine's `first_token` stamp (requests recorded
    before PR 10 carry 0.0) and TPOT additionally needs >= 2 output
    tokens; when no ok request qualifies the respective keys are
    OMITTED rather than reported as an impossible 0.0.

    Raises ValueError when no request finished ok: a silent 0.0
    percentile reads as an impossibly fast pipeline in dashboards —
    same contract as ServingEngine.throughput() (PR 4)."""
    if not done:
        raise ValueError(
            "latency_stats() needs at least one finished request; "
            "drive the engine/router before reading latency percentiles")
    ok = [r for r in done.values() if r.status == "ok"]
    if not ok:
        raise ValueError(
            "latency_stats() needs at least one request with status "
            f"'ok' (got {len(done)} finished, all failed/timed_out); "
            "completion latency of a request that never completed is "
            "not a percentile")
    lat = np.array(sorted(r.finished - r.submitted for r in ok))
    stats = {"p50_s": float(np.percentile(lat, 50)),
             "p95_s": float(np.percentile(lat, 95)),
             "ok_requests": len(ok),
             "failed_requests": sum(r.status == "failed"
                                    for r in done.values()),
             "timed_out_requests": sum(r.status == "timed_out"
                                       for r in done.values())}
    ttft = np.array(sorted(r.first_token - r.submitted for r in ok
                           if r.first_token > 0.0))
    if len(ttft):
        stats["ttft_p50_s"] = float(np.percentile(ttft, 50))
        stats["ttft_p95_s"] = float(np.percentile(ttft, 95))
    tpot = np.array(sorted((r.finished - r.first_token)
                           / (len(r.output) - 1) for r in ok
                           if r.first_token > 0.0 and len(r.output) > 1))
    if len(tpot):
        stats["tpot_p50_s"] = float(np.percentile(tpot, 50))
        stats["tpot_p95_s"] = float(np.percentile(tpot, 95))
    return stats


def run_workload(cfg, params, dsg, requests: List[Request], *,
                 n_slots: int = 4, max_seq: int = 384, prompt_bucket: int = 256,
                 cache_backend: str = "dense", page_size: int = 16,
                 cache_tokens=None, seed: int = 0, replicas: int = 1,
                 route_policy: str = "least_queue",
                 exec_mode: str = "sequential", dsg_serving=None,
                 fault_tolerance=None, faults=None,
                 decode_chunk: int = 1, prefix_sharing: bool = False,
                 max_steps: int = 100_000) -> Dict[str, float]:
    """Run the request list through one engine (replicas=1, the historical
    path) or a Router over `replicas` engines; returns throughput/latency
    stats.  Warmup triggers every jit compile on every replica first so
    the measurement is steady-state.

    `exec_mode` picks the replica executor (serving/parallel_exec.py):
    "sequential" steps replicas in-process, "threaded" free-runs one
    worker thread per replica, "sharded" fuses the group into one
    vmapped device step.  `dsg_serving` (None | True | DSGServingConfig)
    turns on the serving-side DSG sparsity runtime per engine
    (serving/dsg_runtime.py; every replica owns its own pattern state).
    Router runs add `makespan_s` — MODELED
    data-parallel wall clock (slowest replica's busy time) under the
    sequential executor, MEASURED wall clock under the parallel ones
    (`makespan_measured` records which) — and `parallel_tok_per_s`
    (tokens / makespan) to the stats.

    Fault tolerance (docs/fault_tolerance.md): `fault_tolerance` (None |
    True | dict | FaultToleranceConfig) opts the Router into failure
    containment; `faults` (a ReplicaFault list or a ServingFaultInjector,
    runtime/fault_tolerance.py) injects deterministic chaos — attached
    AFTER warmup so step-keyed faults never fire inside the compile
    pass.  Injecting faults auto-enables default fault tolerance (an
    uncontained kill would just crash the run) and forces the Router
    path.  Chaos runs add failed/timed_out/replica_health stats."""
    engine_kw = dict(n_slots=n_slots, max_seq=max_seq,
                     prompt_bucket=prompt_bucket,
                     cache_backend=cache_backend, page_size=page_size,
                     cache_tokens=cache_tokens, dsg_serving=dsg_serving,
                     decode_chunk=decode_chunk,
                     prefix_sharing=prefix_sharing)
    if faults is not None and fault_tolerance is None:
        fault_tolerance = True
    if (replicas == 1 and exec_mode == "sequential"
            and fault_tolerance is None):
        eng = ServingEngine(cfg, params, dsg, seed=seed, **engine_kw)
        warmup_engine(eng, cfg.vocab, max_steps=max_steps,
                      requests=requests)
        runner, stepper = eng, eng
    else:
        runner = Router(cfg, params, dsg, n_replicas=replicas,
                        policy=route_policy, exec_mode=exec_mode,
                        seed=seed, fault_tolerance=fault_tolerance,
                        **engine_kw)
        warmup_router(runner, cfg.vocab, max_steps=max_steps,
                      requests=requests)
        stepper = None

    injector = None
    if faults is not None:
        injector = (faults if hasattr(faults, "on_step")
                    else ServingFaultInjector(faults))
        injector.attach(runner.engines)
    for r in requests:
        runner.submit(r)
    try:
        t0 = time.perf_counter()
        done = runner.run(max_steps=max_steps)
        wall = time.perf_counter() - t0
    finally:
        if stepper is None:
            # release executor worker threads even when the run raises
            # (e.g. a stalled router) — engines would otherwise stay
            # pinned by parked daemon threads
            runner.close()
    toks = sum(len(r.output) for r in done.values())
    stats = {
        "cache_backend": cache_backend,
        "replicas": replicas,
        "decode_chunk": decode_chunk,
        "prefix_sharing": prefix_sharing,
        "requests": len(done),
        "tokens": toks,
        "truncated": sum(r.truncated for r in done.values()),
        "wall_s": wall,
        "tok_per_s": toks / max(wall, 1e-9),
        # raises on an empty result set instead of reporting 0.0
        # percentiles — a measured workload that finished nothing is an
        # error, not a very fast run
        **latency_stats(done),
    }
    if stepper is not None:
        stats.update({
            "cache_bytes": int(stepper.backend.resident_bytes(stepper.cache)),
            # decode_tok_per_s() raises before any token decodes, but a
            # request can finish on its admission token alone (max_new=1)
            # with zero decode steps — that run is legal, so guard
            "decode_tok_per_s": stepper.decode_tok_per_s()
                                if stepper.decode_tokens else 0.0,
            "steps": stepper.steps,
        })
        if prefix_sharing:
            stats.update({
                "prefill_cache_hits": stepper.prefill_cache_hits,
                "shared_page_hits": stepper.backend.shared_page_hits,
                "cow_copies": stepper.backend.cow_copies,
                "peak_live_pages": stepper.backend.allocator.peak_live,
            })
    else:
        stats.update({
            "route_policy": runner.policy.name,
            "exec_mode": runner.executor.name,
            "cache_bytes": sum(int(e.backend.resident_bytes(e.cache))
                               for e in runner.engines),
            "decode_tok_per_s": sum(e.decode_tokens
                                    for e in runner.engines)
                                / max(sum(e.decode_seconds
                                          for e in runner.engines), 1e-9),
            # total engine decode steps (what serve.py prints); one router
            # tick steps up to `replicas` engines, reported separately
            "steps": sum(e.steps for e in runner.engines),
            "router_steps": runner.steps,
            "makespan_s": runner.makespan_seconds(),
            "makespan_measured": runner.executor.measured,
            "parallel_tok_per_s": toks / max(runner.makespan_seconds(),
                                             1e-9),
            "per_replica": runner.replica_stats(),
        })
        if runner.ft is not None:
            stats.update({
                "completed_ok": sum(r.status == "ok"
                                    for r in done.values()),
                "failed": sum(r.status == "failed"
                              for r in done.values()),
                "timed_out": sum(r.status == "timed_out"
                                 for r in done.values()),
                "retries": sum(r.retries for r in done.values()),
                "replica_health": [h.state for h in runner.health],
                "faults_fired": (len(injector.log)
                                 if injector is not None else 0),
            })
    return stats
