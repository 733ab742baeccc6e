"""Front-end router benchmarks: policy gate + executor gate.

**Policy gate** (default): round_robin vs least_queue on skewed traffic.
Every `n_replicas`-th request is HEAVY (long prompt, 40-56 generated
tokens) and the rest are light (2-4 tokens) — the bursty pattern where
static round-robin assignment collides every heavy request onto the same
replica, which then grinds alone while its siblings sit idle.  The
queue-depth-aware `least_queue` policy dispatches lazily (only to a
replica with an uncommitted free lane), so fast replicas pull queued work
the moment they drain and the heavy tail spreads by live load.

The policy comparison runs on the SEQUENTIAL executor: replicas are
stepped one after another in one process, so raw wall clock would hide
the routing win (total work is identical by construction — the
differential check below asserts the merged greedy token streams agree
token-for-token).  The reported number is the MODELED data-parallel rate:
per-replica busy time is recorded by the executor, the makespan is the
slowest replica's busy time (what N truly parallel replica groups would
take), and parallel tok/s = total tokens / makespan: the time is
recorded, the parallel wall clock is modeled from it.

**Executor gate** (`--exec-mode threaded` / `sharded`): sequential vs
parallel execution of the same router under round_robin (identical
placement either way — a controlled execution-strategy comparison; see
run_exec_gate's docstring), now on MEASURED wall clock — the drain time
of the real run, no modeling.  The
traffic stays skewed (every 2nd request heavy) but the skew is in
GENERATION length, not prompt length, so the window is decode-dominated
steady state (admission prefills saturate a small host's cores and would
blur what the executor changes).  Merged streams must be identical
across executors; the gate is threaded >= 1.2x sequential measured
tok/s.

Gates (CI, smoke mode): least_queue >= 1.15x round_robin modeled
parallel tok/s (in practice ~1.8-2x), threaded >= 1.2x sequential
measured tok/s.  Emits BENCH_router.json.

  PYTHONPATH=src python benchmarks/bench_router.py --smoke
  PYTHONPATH=src python benchmarks/bench_router.py --smoke \
      --exec-mode threaded
"""
from __future__ import annotations

import argparse
import time

import jax

from common import bench_envelope, gate, write_bench

from repro import configs
from repro.models import api
from repro.serving.router import Router
from repro.serving.workload import skewed_requests, warmup_router


def _reset(router: Router):
    """Steady-state reset between repeats (the engines stay compiled)."""
    for eng in router.replicas:
        eng.done.clear()
        eng.steps = 0
        eng.decode_seconds = 0.0
        eng.decode_tokens = 0
    router.reset_counters()


def run(args) -> dict:
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    cfg = cfg.replace(dsg=cfg.dsg._replace(threshold_mode="topk"))
    key = jax.random.PRNGKey(0)
    params = api.init_model(key, cfg)
    dsg = api.init_dsg(jax.random.fold_in(key, 1), params, cfg)

    results = {}
    for policy in ("round_robin", "least_queue"):
        router = Router(cfg, params, dsg, n_replicas=args.replicas,
                        policy=policy, n_slots=args.slots,
                        max_seq=args.max_seq,
                        prompt_bucket=args.prompt_bucket,
                        cache_backend=args.cache_backend,
                        page_size=args.page_size, seed=args.seed)
        warmup_router(router, cfg.vocab)
        best = None
        for _ in range(args.repeats):
            _reset(router)
            # identical traffic for both policies (fresh Request objects)
            reqs = skewed_requests(cfg.vocab, args.requests,
                                   period=args.replicas, seed=args.seed)
            for r in reqs:
                router.submit(r)
            done = router.run(max_steps=100_000)
            if len(done) != len(reqs):
                raise SystemExit(f"FAIL: {policy} finished {len(done)} of "
                                 f"{len(reqs)} requests")
            toks = sum(len(r.output) for r in done.values())
            makespan = router.makespan_seconds()
            st = {
                "tokens": toks,
                "makespan_s": makespan,
                "parallel_tok_per_s": toks / max(makespan, 1e-9),
                "busy_s": list(router.busy_seconds),
                "replica_tokens": [e.decode_tokens
                                   for e in router.replicas],
                "heavy_per_replica": [
                    sum(1 for u, r in router.dispatch_log
                        if r == i and u % args.replicas == 0)
                    for i in range(args.replicas)],
                "outputs": {u: list(r.output) for u, r in done.items()},
            }
            if best is None or (st["parallel_tok_per_s"]
                                > best["parallel_tok_per_s"]):
                best = st      # best-of-N: washes out host timing noise
        results[policy] = best
    return results


def run_exec_gate(args) -> dict:
    """Sequential vs parallel executor on decode-heavy skewed traffic.

    Same requests, same policy, two executors; tok/s here is tokens /
    MEASURED drain wall clock (perf_counter around run()), so the
    comparison is end-to-end real time, dispatch overhead included.
    Repeats interleave the two executors and the gate ratio is the BEST
    per-repeat paired ratio (the policy gate's best-of-N discipline,
    applied to pairs): adjacent measurements share machine state (CPU
    frequency, allocator, thermal drift), so a pair's ratio reflects
    the executors and not the drift — while comparing each side's best
    across the whole run lets one lucky serialized-baseline repeat
    decide the gate.  Host scheduling on a small box can still halve
    the overlap in any single pair (observed paired ratios: ~1.1-1.45),
    so the gate asks whether fair paired measurement REACHES the
    speedup, not whether every draw does; the full ratio list is
    printed and lands in the JSON payload.

    The policy is round_robin on purpose: it dispatches unconditionally,
    so placement is identical under both executors (a controlled
    execution-strategy comparison — pull-based policies re-decide
    against live timing) and every engine holds its full queue up front
    (a pull policy's dispatch-to-admission latency would idle lanes only
    in the parallel mode and muddy the measurement).  The heavy period
    is 3 against 2 replicas, so heavy generations alternate replicas
    instead of funneling onto one — both replicas stay busy, which is
    the regime where overlap shows.

    `--exec-scale` widens the smoke model (d_model, d_ff): the stock
    smoke config is dispatch-bound — a decode step is mostly GIL-held
    Python, which threads cannot overlap — so the gate scales the model
    until a step carries enough GIL-free device compute for overlap to
    be measurable.  Real configs on real accelerators are in that
    regime natively."""
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    cfg = cfg.replace(dsg=cfg.dsg._replace(threshold_mode="topk"))
    if args.smoke and args.exec_scale > 1:
        cfg = cfg.replace(d_model=cfg.d_model * args.exec_scale,
                          d_ff=cfg.d_ff * args.exec_scale)
    key = jax.random.PRNGKey(0)
    params = api.init_model(key, cfg)
    dsg = api.init_dsg(jax.random.fold_in(key, 1), params, cfg)

    def traffic(seed):
        # skew lives in max_new: heavy generations, light prompts, so
        # the measured window is decode steps, not admission prefills
        return skewed_requests(cfg.vocab, args.exec_requests, period=3,
                               seed=seed,
                               heavy_prompt=(24, 30), heavy_new=(40, 56),
                               light_prompt=(8, 16), light_new=(8, 16))

    # both routers live for the whole measurement and their repeats
    # INTERLEAVE: measuring one mode's repeats in a block and then the
    # other's lets slow process-level drift (CPU frequency, allocator
    # state) land entirely on one side and flip the ratio run-to-run —
    # interleaved, the same drift hits both modes equally
    modes = ("sequential", args.exec_mode)
    routers = {
        mode: Router(cfg, params, dsg, n_replicas=args.replicas,
                     policy="round_robin", exec_mode=mode,
                     n_slots=args.exec_slots,
                     max_seq=args.exec_max_seq,
                     prompt_bucket=args.exec_prompt_bucket,
                     cache_backend=args.cache_backend,
                     page_size=args.page_size, seed=args.seed)
        for mode in modes}
    for router in routers.values():
        warmup_router(router, cfg.vocab)
    results = {}
    ratios = []
    for rep in range(args.exec_repeats):
        pair = {}
        for mode in modes:
            router = routers[mode]
            _reset(router)
            reqs = traffic(args.seed)
            for r in reqs:
                router.submit(r)
            t0 = time.perf_counter()
            done = router.run(max_steps=100_000)
            wall = time.perf_counter() - t0
            if len(done) != len(reqs):
                raise SystemExit(f"FAIL: {mode} finished {len(done)} of "
                                 f"{len(reqs)} requests")
            toks = sum(len(r.output) for r in done.values())
            st = {
                "tokens": toks,
                "wall_s": wall,
                "tok_per_s": toks / max(wall, 1e-9),
                "makespan_s": router.makespan_seconds(),
                "makespan_measured": router.executor.measured,
                "outputs": {u: list(r.output) for u, r in done.items()},
            }
            pair[mode] = st["tok_per_s"]
            best = results.get(mode)
            if best is None or st["tok_per_s"] > best["tok_per_s"]:
                results[mode] = st
        ratios.append(pair[args.exec_mode] / pair["sequential"])
    for router in routers.values():
        router.close()
    results["paired_ratios"] = sorted(ratios)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="use the full-size config (needs accelerators)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--prompt-bucket", type=int, default=192)
    ap.add_argument("--cache-backend", choices=("dense", "paged"),
                    default="dense")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--exec-mode", choices=("threaded", "sharded"),
                    default=None,
                    help="run the executor gate instead of the policy "
                         "gate: sequential vs this executor, measured "
                         "wall clock, round_robin placement")
    ap.add_argument("--exec-slots", type=int, default=4)
    ap.add_argument("--exec-max-seq", type=int, default=128)
    ap.add_argument("--exec-prompt-bucket", type=int, default=32)
    ap.add_argument("--exec-repeats", type=int, default=5)
    ap.add_argument("--exec-requests", type=int, default=24,
                    help="request count for the executor gate (longer "
                         "steady-state window than the policy gate's "
                         "--requests)")
    ap.add_argument("--exec-scale", type=int, default=6,
                    help="widen the smoke model (d_model, d_ff) for the "
                         "executor gate so a decode step carries enough "
                         "device compute to overlap (smoke only)")
    ap.add_argument("--exec-gate", type=float, default=1.2,
                    help="minimum threaded/sequential best-paired ratio "
                         "(diagnostic override; CI enforces the default)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.exec_mode is not None:
        out = args.out or "BENCH_router_exec.json"
        t0 = time.time()
        results = run_exec_gate(args)
        ratios = results.pop("paired_ratios")
        print(f"{'executor':>12} {'tok/s':>8} {'wall s':>8} "
              f"{'makespan s':>11} {'measured':>9}")
        for name, st in results.items():
            print(f"{name:>12} {st['tok_per_s']:>8.1f} "
                  f"{st['wall_s']:>8.2f} {st['makespan_s']:>11.2f} "
                  f"{str(st['makespan_measured']):>9}")
        streams_ok = (results["sequential"]["outputs"]
                      == results[args.exec_mode]["outputs"])
        speedup = ratios[-1]                   # best paired ratio
        payload = {name: {k: v for k, v in st.items() if k != "outputs"}
                   for name, st in results.items()}
        payload["paired_ratios"] = ratios
        payload[f"{args.exec_mode}_vs_sequential"] = speedup
        payload["config"] = {"replicas": args.replicas,
                             "slots": args.exec_slots,
                             "requests": args.exec_requests,
                             "exec_scale": args.exec_scale,
                             "max_seq": args.exec_max_seq,
                             "prompt_bucket": args.exec_prompt_bucket,
                             "cache_backend": args.cache_backend,
                             "exec_mode": args.exec_mode}
        gates = [gate(f"{args.exec_mode} executor merged streams match "
                      f"sequential", 1.0, float(streams_ok), streams_ok)]
        if args.exec_mode == "threaded":   # sharded ratio is diagnostic
            gates.append(gate(
                f"threaded >= {args.exec_gate}x sequential measured "
                f"tok/s (best paired repeat)", args.exec_gate, speedup,
                speedup >= args.exec_gate))
        # write first: a red run leaves a diagnosable artifact
        write_bench(out, bench_envelope(
            "router_exec", gates=gates, ratio=speedup, t_start=t0,
            results=payload))
        # explicit raises, not asserts: CI gates, survive python -O
        if not streams_ok:
            raise SystemExit(
                f"FAIL: {args.exec_mode} executor emits diverging merged "
                f"token streams (executor invariance broken)")
        print(f"merged greedy streams identical across executors ✓")
        print(f"{args.exec_mode} / sequential measured throughput: "
              f"{speedup:.2f}x (best paired repeat; all: "
              f"{' '.join(f'{r:.2f}' for r in ratios)})")
        if args.exec_mode == "threaded" and speedup < args.exec_gate:
            raise SystemExit(
                f"FAIL: threaded executor must reach >= "
                f"{args.exec_gate}x sequential measured tok/s on skewed "
                f"traffic (got {speedup:.2f}x)")
        return

    out = args.out or "BENCH_router.json"
    t0 = time.time()
    results = run(args)
    print(f"{'policy':>12} {'par tok/s':>10} {'makespan s':>11} "
          f"{'busy s/replica':>24} {'heavy/replica':>14}")
    for name, st in results.items():
        busy = " ".join(f"{b:.2f}" for b in st["busy_s"])
        heavy = " ".join(str(h) for h in st["heavy_per_replica"])
        print(f"{name:>12} {st['parallel_tok_per_s']:>10.1f} "
              f"{st['makespan_s']:>11.2f} {busy:>24} {heavy:>14}")

    streams_ok = (results["round_robin"]["outputs"]
                  == results["least_queue"]["outputs"])
    speedup = (results["least_queue"]["parallel_tok_per_s"]
               / results["round_robin"]["parallel_tok_per_s"])
    payload = {name: {k: v for k, v in st.items() if k != "outputs"}
               for name, st in results.items()}
    payload["least_queue_vs_round_robin"] = speedup
    payload["config"] = {"replicas": args.replicas, "slots": args.slots,
                         "requests": args.requests,
                         "cache_backend": args.cache_backend}
    gates = [
        gate("routing policies emit identical merged token streams",
             1.0, float(streams_ok), streams_ok),
        gate("least_queue >= 1.15x round_robin modeled parallel tok/s "
             "on skewed traffic", 1.15, speedup, speedup >= 1.15),
    ]
    # write first: a red run leaves a diagnosable artifact
    write_bench(out, bench_envelope(
        "router", gates=gates, ratio=speedup, t_start=t0,
        results=payload))

    # explicit raises, not asserts: CI regression gates, survive python -O
    if not streams_ok:
        raise SystemExit(
            "FAIL: routing policies emit diverging merged token streams "
            "(replica-count invariance broken)")
    print("merged greedy streams identical across policies ✓")
    print(f"least_queue / round_robin parallel throughput: {speedup:.2f}x")
    if speedup < 1.15:
        raise SystemExit(
            f"FAIL: least_queue must reach >= 1.15x round_robin parallel "
            f"tok/s on skewed traffic (got {speedup:.2f}x)")


if __name__ == "__main__":
    main()
