"""Serving-side DSG sparsity runtime: decode throughput + modeled FFN FLOPs.

Engines run the SAME mixed traffic through the serving DSG runtime
(serving/dsg_runtime.py) with different group-CSR FFN executors
(ModelConfig.dsg_ffn_apply):

  * dense      — masked-dense reference: full FFN matmuls, pattern applied
                 as an expanded mask (core/dsg_linear.swiglu_csr_masked).
                 Spends every FLOP the non-serving stack would; its
                 streams define bitwise-correct.
  * csr-xla    — bounded XLA gather: contracts only the leading
                 active-group bucket of each lane's CSR row.
  * csr-kernel — Pallas CSR walk (kernels/dsg_ffn.dsg_ffn_csr; interpret
                 mode off-TPU, so its latency column is only meaningful
                 on TPU — included for the stream gate).

threshold_mode="topk" keeps lanes computationally independent, so all
executors must agree token-for-token at temperature=0.  Three gates
(explicit raises, survive python -O):

  1. csr-xla (and csr-kernel when run) streams == dense reference, bitwise.
  2. Modeled FFN FLOP reduction (per-lane CSR counts vs dense groups,
     DSGRuntime.record_step) >= --flop-gate; 1.8x at the default
     gamma=0.5 (ideal 2.0x minus refresh/seeding slack).
  3. csr-xla measured decode tok/s >= --tps-gate x the dense reference
     (best paired repeat, interleaved runs) — sparsity must not tax the
     decode hot path.

Emits BENCH_dsg_serving.json in the shared benchmarks/common.py envelope;
CI runs `--smoke` and uploads the artifact.

  PYTHONPATH=src python benchmarks/bench_dsg_serving.py --smoke
"""
from __future__ import annotations

import argparse
import os

import jax

from common import bench_envelope, gate, write_bench

from repro import configs
from repro.models import api
from repro.serving.dsg_runtime import DSGServingConfig
from repro.serving.scheduler import ServingEngine
from repro.serving.workload import mixed_requests, warmup_engine


def _make_engine(cfg, params, dsg, args, apply_mode):
    vcfg = cfg.replace(dsg_ffn_apply=apply_mode)
    eng = ServingEngine(
        vcfg, params, dsg, n_slots=args.slots, max_seq=args.max_seq,
        prompt_bucket=args.prompt_bucket, admission="overlap",
        cache_backend=args.cache_backend, page_size=args.page_size,
        dsg_serving=DSGServingConfig(
            refresh_interval=args.refresh_interval,
            threshold=args.threshold))
    warmup_engine(eng, cfg.vocab)
    eng.telemetry.reset_counters("dsg.")   # FLOP model: measured window only
    return eng


def _drive(eng, cfg, args):
    """One measured pass of the traffic; returns (streams, decode tok/s)
    from the counter deltas so a warmed engine can be re-driven."""
    toks0, secs0 = eng.decode_tokens, eng.decode_seconds
    reqs = mixed_requests(cfg.vocab, args.requests, seed=args.seed,
                          prompt_range=(args.prompt_min, args.prompt_max),
                          max_new_range=(args.gen_min, args.gen_max))
    for r in reqs:
        eng.submit(r)
    done = eng.run(max_steps=100_000)
    if len(done) < len(reqs):
        raise RuntimeError(
            f"engine drained only {len(done)}/{len(reqs)} requests")
    eng.done.clear()
    streams = {r.uid: list(r.output) for r in reqs}
    rate = ((eng.decode_tokens - toks0)
            / max(eng.decode_seconds - secs0, 1e-9))
    return streams, rate


def run(args) -> tuple:
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    # topk: per-lane selection, lanes independent -> bitwise stream gate
    cfg = cfg.replace(dsg=cfg.dsg._replace(gamma=args.gamma,
                                           threshold_mode="topk"))
    key = jax.random.PRNGKey(0)
    params = api.init_model(key, cfg)
    dsg = api.init_dsg(jax.random.fold_in(key, 1), params, cfg)

    # the Pallas walk needs a TPU (or the interpreter, REPRO_INTERPRET=1
    # — stream gate only; interpret latency means nothing)
    run_kernel = (jax.default_backend() == "tpu"
                  or bool(os.environ.get("REPRO_INTERPRET")))
    engines = {"dense": _make_engine(cfg, params, dsg, args, "dense"),
               "csr-xla": _make_engine(cfg, params, dsg, args, "xla")}
    if run_kernel:
        engines["csr-kernel"] = _make_engine(cfg, params, dsg, args,
                                             "kernel")

    # interleaved repeats: dense/sparse pairs share any machine-load
    # drift, the gate takes the best paired ratio (bench_router idiom)
    streams, rates = {}, {name: [] for name in engines}
    for rep in range(args.repeats):
        for name, eng in engines.items():
            if name == "csr-kernel" and rep > 0:
                continue             # stream gate only: one pass suffices
            s, rate = _drive(eng, cfg, args)
            prev = streams.setdefault(name, s)
            if prev != s:
                raise SystemExit(
                    f"FAIL: {name} streams differ across repeats "
                    f"(engine state leaking between runs)")
            rates[name].append(rate)

    results = {name: {"decode_tok_per_s": rates[name],
                      "steps": eng.steps,
                      "requests": args.repeats * args.requests}
               for name, eng in engines.items()}
    results["flop_model"] = engines["csr-xla"].dsg_rt.flop_stats()
    results["config"] = {
        "arch": args.arch, "gamma": args.gamma,
        "threshold": args.threshold,
        "refresh_interval": args.refresh_interval,
        "slots": args.slots, "requests": args.requests,
        "max_seq": args.max_seq, "prompt_bucket": args.prompt_bucket,
        "cache_backend": args.cache_backend, "repeats": args.repeats,
        "backend": jax.default_backend(), "kernel_ran": run_kernel}
    return streams, rates, results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="use the full-size config (needs accelerators)")
    ap.add_argument("--gamma", type=float, default=0.5,
                    help="fraction of neuron groups dropped; the default "
                         "FLOP gate (1.8x) assumes 0.5")
    ap.add_argument("--threshold", choices=("topk", "ema"),
                    default="topk")
    ap.add_argument("--refresh-interval", type=int, default=8)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--prompt-bucket", type=int, default=32)
    ap.add_argument("--prompt-min", type=int, default=4)
    ap.add_argument("--prompt-max", type=int, default=30)
    ap.add_argument("--gen-min", type=int, default=8)
    ap.add_argument("--gen-max", type=int, default=24)
    ap.add_argument("--cache-backend", choices=("dense", "paged"),
                    default="dense")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--flop-gate", type=float, default=1.8,
                    help="minimum modeled FFN FLOP reduction (csr model)")
    ap.add_argument("--tps-gate", type=float, default=0.95,
                    help="minimum csr-xla/dense best-paired decode tok/s")
    ap.add_argument("--out", default="BENCH_dsg_serving.json")
    args = ap.parse_args()

    import time
    t0 = time.time()
    streams, rates, results = run(args)

    print(f"{'executor':>11} {'decode tok/s (per repeat)':>34}")
    for name, rs in rates.items():
        print(f"{name:>11} {' '.join(f'{r:>10.1f}' for r in rs):>34}")
    flop = results["flop_model"]
    print(f"modeled FFN FLOP reduction: csr "
          f"{flop['flop_reduction_csr']:.2f}x, bound "
          f"{flop['flop_reduction_bound']:.2f}x over {flop['steps']} "
          f"steps (pattern overhead {flop['overhead_bytes']} bytes)")

    sparse_names = [n for n in streams if n != "dense"]
    streams_ok = all(streams[n] == streams["dense"] for n in sparse_names)
    paired = [s / d for s, d in zip(rates["csr-xla"], rates["dense"])]
    tps_ratio = max(paired)
    flop_red = flop["flop_reduction_csr"]
    gates = [
        gate("sparse executors match the dense-apply reference streams "
             "bitwise at temperature=0", 1.0, float(streams_ok),
             streams_ok),
        gate(f"modeled FFN FLOP reduction (csr) >= {args.flop_gate}x at "
             f"gamma={args.gamma}", args.flop_gate, flop_red,
             flop_red >= args.flop_gate),
        gate(f"csr-xla decode tok/s >= {args.tps_gate}x dense-apply "
             f"(best paired repeat)", args.tps_gate, tps_ratio,
             tps_ratio >= args.tps_gate),
    ]
    # write first: a red run must leave a diagnosable artifact (the
    # failed gate is recorded with passed=false)
    write_bench(args.out, bench_envelope(
        "dsg_serving", gates=gates, ratio=flop_red, t_start=t0,
        results=results))

    # explicit raises, not asserts: CI gates, survive python -O
    if not streams_ok:
        bad = [n for n in sparse_names if streams[n] != streams["dense"]]
        raise SystemExit(
            f"FAIL: {', '.join(bad)} diverge from the dense-apply "
            f"reference streams (group-CSR executor equivalence broken)")
    print("streams identical across FFN executors ✓")
    if flop_red < args.flop_gate:
        raise SystemExit(
            f"FAIL: modeled FFN FLOP reduction must reach >= "
            f"{args.flop_gate}x at gamma={args.gamma} "
            f"(got {flop_red:.2f}x)")
    print(f"csr-xla / dense decode throughput: {tps_ratio:.2f}x "
          f"(best paired repeat; all: "
          f"{' '.join(f'{r:.2f}' for r in paired)})")
    if tps_ratio < args.tps_gate:
        raise SystemExit(
            f"FAIL: csr-xla decode tok/s must stay >= {args.tps_gate}x "
            f"the dense-apply reference (got {tps_ratio:.2f}x)")


if __name__ == "__main__":
    main()
