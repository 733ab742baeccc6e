"""Copy-on-write shared-prefix paging: page footprint + open-loop SLOs.

Two paged engines run the SAME overlapping-prefix traffic (a 112-token
shared system prompt ahead of a 16-token unique tail, greedy):

  * sharing-off — every admission copies its full prompt into private
    pages (the PR-3 baseline).
  * sharing-on  — admissions map full-page prompt prefixes onto the
    pages earlier requests already wrote (refcount bump, zero prefill
    recompute when the whole chain is resident); writes into a shared
    page copy-on-write.

The memory gate is the allocator's PEAK live page count over paired
interleaved repeats (identical same-seed traffic, peaks reset after
warmup): with 7 of 8 prompt pages shared, sharing must hold the peak
to <= 0.6x the unshared run (measured ~0.45x).  Streams must stay
bitwise identical in every repeat — sharing that drifts is a bug, not
a saving.

The serving gate drives the sharing engine OPEN-LOOP (workload.
run_open_loop): Poisson arrivals are submitted on the wall clock
whether or not capacity exists, so queueing delay lands in TTFT
exactly as a user would see it.  p95 TTFT and p95 TPOT must clear
smoke-model SLOs calibrated ~4x above the quiet-machine numbers —
loose enough for shared CI runners, tight enough to catch a sharing
hot path that recomputes prefills or serializes decode.

Emits BENCH_prefix_sharing.json; CI runs `--smoke` and fails on
stream divergence or a missed gate.

  PYTHONPATH=src python benchmarks/bench_prefix_sharing.py --smoke
"""
from __future__ import annotations

import argparse
import time

import jax

from common import bench_envelope, gate, write_bench

from repro import configs
from repro.models import api
from repro.serving.scheduler import ServingEngine
from repro.serving.workload import (latency_stats, poisson_arrivals,
                                    run_open_loop, shared_prefix_requests,
                                    warmup_engine)

PAGE_RATIO_GATE = 0.6
TTFT_P95_SLO_S = 2.0
TPOT_P95_SLO_S = 0.25


def _engine(cfg, params, dsg, args, sharing):
    return ServingEngine(cfg, params, dsg, n_slots=args.slots,
                         max_seq=args.max_seq,
                         prompt_bucket=args.prompt_bucket,
                         cache_backend="paged", page_size=args.page_size,
                         cache_tokens=args.cache_tokens,
                         prefix_sharing=sharing)


def _traffic(cfg, args, *, seed=None):
    return shared_prefix_requests(
        cfg.vocab, args.requests, prompt_len=args.prompt_len,
        prefix_len=args.prefix_len, max_new=args.max_new,
        seed=args.seed if seed is None else seed)


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    while eng.queue or any(not s.free for s in eng.slots):
        eng.step()
        if eng.steps >= 100_000:    # explicit raise: survives python -O
            raise RuntimeError("engine failed to drain the workload")
    return {r.uid: list(r.output) for r in reqs}


def _measured_run(eng, cfg, args):
    """One steady-state repeat: fresh same-seed traffic, the allocator
    peak reset so it covers exactly this repeat (warmup requests have
    retired, so the index holds only what this repeat registers)."""
    reqs = _traffic(cfg, args)
    eng.steps = 0
    eng.backend.allocator.reset_peak()
    outputs = _drain(eng, reqs)
    be = eng.backend
    return outputs, {"peak_live_pages": be.allocator.peak_live,
                     "shared_page_hits": be.shared_page_hits,
                     "cow_copies": be.cow_copies,
                     "prefill_cache_hits": eng.prefill_cache_hits}


def run(args) -> dict:
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    cfg = cfg.replace(dsg=cfg.dsg._replace(threshold_mode="topk"))
    key = jax.random.PRNGKey(0)
    params = api.init_model(key, cfg)
    dsg = api.init_dsg(jax.random.fold_in(key, 1), params, cfg)

    engines = {"off": _engine(cfg, params, dsg, args, False),
               "on": _engine(cfg, params, dsg, args, True)}
    for eng in engines.values():
        warmup_engine(eng, cfg.vocab, requests=_traffic(cfg, args))

    # -- closed-loop paired repeats: peak pages + stream equality -------
    repeats = {"off": [], "on": []}
    streams = {}
    streams_ok = True
    for _ in range(args.repeats):
        for mode, eng in engines.items():
            outputs, counters = _measured_run(eng, cfg, args)
            repeats[mode].append(counters)
            if mode == "off":
                streams = outputs
            elif outputs != streams:
                streams_ok = False
    ratios = [s["peak_live_pages"] / max(b["peak_live_pages"], 1)
              for b, s in zip(repeats["off"], repeats["on"])]
    page_ratio = min(ratios)     # pages are deterministic; min = best

    # -- open-loop Poisson drive on the sharing engine ------------------
    reqs = _traffic(cfg, args, seed=args.seed + 1)
    arrivals = poisson_arrivals(len(reqs), args.rate_rps, seed=args.seed)
    done = run_open_loop(engines["on"], reqs, arrivals)
    slo = latency_stats(done)

    return {"repeats": {f"sharing-{k}": v for k, v in repeats.items()},
            "paired_page_ratios": ratios,
            "page_ratio": page_ratio,
            "streams_ok": streams_ok,
            "open_loop": {"rate_rps": args.rate_rps,
                          "n_requests": len(reqs), **slo}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="use the full-size config (needs accelerators)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=192)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--cache-tokens", type=int, default=1024)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--prefix-len", type=int, default=112)
    ap.add_argument("--prompt-bucket", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--rate-rps", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_prefix_sharing.json")
    args = ap.parse_args()

    t0 = time.time()
    results = run(args)
    print(f"{'repeat':>7} {'off peak pages':>15} {'on peak pages':>14} "
          f"{'ratio':>7}")
    off = results["repeats"]["sharing-off"]
    on = results["repeats"]["sharing-on"]
    for i, (b, s, r) in enumerate(zip(off, on,
                                      results["paired_page_ratios"])):
        print(f"{i:>7d} {b['peak_live_pages']:>15d} "
              f"{s['peak_live_pages']:>14d} {r:>7.2f}")
    print(f"sharing counters (last repeat): {on[-1]}")

    ratio = results["page_ratio"]
    streams_ok = results["streams_ok"]
    slo = results["open_loop"]
    ttft = slo.get("ttft_p95_s", float("inf"))
    tpot = slo.get("tpot_p95_s", float("inf"))
    print(f"best paired peak-page ratio = {ratio:.2f}x  "
          f"open-loop p95 TTFT = {ttft:.3f}s  p95 TPOT = {tpot:.4f}s")

    gates = [
        gate("sharing-on and sharing-off emit identical streams",
             1.0, float(streams_ok), streams_ok),
        gate(f"shared-prefix resident pages <= {PAGE_RATIO_GATE}x the "
             f"unshared run (best paired repeat)", PAGE_RATIO_GATE,
             ratio, ratio <= PAGE_RATIO_GATE),
        gate(f"open-loop p95 TTFT <= {TTFT_P95_SLO_S}s at "
             f"{slo['rate_rps']} rps", TTFT_P95_SLO_S, ttft,
             ttft <= TTFT_P95_SLO_S),
        gate(f"open-loop p95 TPOT <= {TPOT_P95_SLO_S}s at "
             f"{slo['rate_rps']} rps", TPOT_P95_SLO_S, tpot,
             tpot <= TPOT_P95_SLO_S),
    ]
    # write first: a red run leaves a diagnosable artifact
    write_bench(args.out, bench_envelope(
        "prefix_sharing", gates=gates, ratio=ratio, t_start=t0,
        results=results))

    # explicit raises, not asserts: CI regression gates, survive python -O
    if not streams_ok:
        raise SystemExit("FAIL: prefix sharing diverges from the "
                         "unshared streams")
    print("streams identical with sharing on vs off ✓")
    if ratio > PAGE_RATIO_GATE:
        raise SystemExit(
            f"FAIL: shared-prefix peak pages must be <= "
            f"{PAGE_RATIO_GATE}x the unshared run (got {ratio:.2f}x)")
    if ttft > TTFT_P95_SLO_S or tpot > TPOT_P95_SLO_S:
        raise SystemExit(
            f"FAIL: open-loop SLO missed (p95 TTFT {ttft:.3f}s vs "
            f"{TTFT_P95_SLO_S}s, p95 TPOT {tpot:.4f}s vs "
            f"{TPOT_P95_SLO_S}s)")


if __name__ == "__main__":
    main()
