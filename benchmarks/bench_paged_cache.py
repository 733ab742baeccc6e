"""Dense vs paged KV-cache backend: resident cache bytes + throughput.

The dense backend reserves the worst-case (L, n_slots, max_seq, Kv, D)
block no matter what traffic looks like; the paged backend
(serving/kv_cache.py) keeps a page pool sized to peak concurrent demand
and maps lanes onto it through a page table, so mixed traffic whose
prompt+generation lengths sit well under max_seq holds far fewer cache
bytes resident.  Both engines run the SAME traffic (threshold_mode="topk"
so lanes are computationally independent) and must produce identical
outputs — the run doubles as an end-to-end equivalence check, which is
why CI runs it with --smoke.

Default shape: max_seq=256 with prompts up to 64 and generations up to 32
(mean prompt+gen well under 96), pool sized to n_slots * (64 + 32) tokens
-> >= 2x fewer resident bytes than dense with zero admission deferrals.

  PYTHONPATH=src python benchmarks/bench_paged_cache.py --smoke
"""
from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.models import api
from repro.serving.scheduler import bucket_sizes
from repro.serving.workload import mixed_requests, run_workload


def run(args) -> dict:
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    # per-row DRS selection: lanes are independent, so dense and paged
    # engines must agree token-for-token (see tests/test_serving_overlap.py)
    cfg = cfg.replace(dsg=cfg.dsg._replace(threshold_mode="topk"))
    key = jax.random.PRNGKey(0)
    params = api.init_model(key, cfg)
    dsg = api.init_dsg(jax.random.fold_in(key, 1), params, cfg)

    # pool covering peak concurrent demand: every lane simultaneously at
    # its largest bucket + generation budget — no admission deferrals, and
    # still a fraction of the dense n_slots * max_seq reservation
    largest = bucket_sizes(args.prompt_bucket, args.max_seq)[-1]
    peak_lane = min(largest + args.gen_max, args.max_seq)
    cache_tokens = args.cache_tokens or args.slots * peak_lane

    results = {}
    for backend in ("dense", "paged"):
        reqs = mixed_requests(
            cfg.vocab, args.requests, seed=args.seed,
            prompt_range=(args.prompt_min, args.prompt_max),
            max_new_range=(args.gen_min, args.gen_max))
        st = run_workload(
            cfg, params, dsg, reqs, n_slots=args.slots, max_seq=args.max_seq,
            prompt_bucket=args.prompt_bucket, cache_backend=backend,
            page_size=args.page_size,
            cache_tokens=cache_tokens if backend == "paged" else None)
        st["outputs"] = {r.uid: list(r.output) for r in reqs}
        results[backend] = st
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="use the full-size config (needs accelerators)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--cache-tokens", type=int, default=None,
                    help="paged pool capacity (default: slots * "
                         "(largest bucket + gen-max), the peak demand)")
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=64)
    ap.add_argument("--prompt-bucket", type=int, default=64)
    ap.add_argument("--gen-min", type=int, default=8)
    ap.add_argument("--gen-max", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    results = run(args)
    print(f"{'backend':>8} {'cache MB':>9} {'tok/s':>9} {'decode tok/s':>13} "
          f"{'steps':>6} {'tokens':>7}")
    for name, st in results.items():
        print(f"{name:>8} {st['cache_bytes'] / 1e6:>9.2f} "
              f"{st['tok_per_s']:>9.1f} {st['decode_tok_per_s']:>13.1f} "
              f"{st['steps']:>6d} {st['tokens']:>7d}")

    # explicit raises, not asserts: these are the CI regression gates and
    # must survive python -O
    if results["dense"]["outputs"] != results["paged"]["outputs"]:
        raise SystemExit(
            "FAIL: paged backend outputs diverge from the dense engine")
    ratio = results["dense"]["cache_bytes"] / results["paged"]["cache_bytes"]
    print(f"resident cache bytes: dense / paged = {ratio:.2f}x")
    if ratio < 2.0:
        raise SystemExit(f"FAIL: paged cache must hold >= 2x fewer resident "
                         f"bytes (got {ratio:.2f}x)")
    print("outputs identical across backends ✓")


if __name__ == "__main__":
    main()
