#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find its knee, one process.

    python chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 4 8 12

Sets up the cell once, then drives its mix at each rate for `--seconds` and
drains.  Per rate: the share of requests due in the window that met both of the
mix's `slo` limits (a request that failed or never finished meets neither), the
95th percentiles, how many were still unfinished when the window closed (a
backlog that grows), and how late the generator ran.  The knee is the highest
rate at which at least 90% met both limits with no growing backlog.  Not part
of a benchmark run; run it on the chip when a rate is set.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None, *, require_chip: bool = True, root: Path = ROOT):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(root), str(root / "src")]
    from chipbench import run
    run.enable_cache(root)
    import numpy as np
    from chipbench import bench, device, drivers, report, traffic
    from chipbench.weights import make_weights
    from repro.serving.scheduler import Request

    cell = bench.resolve(args.workload, root)
    dev = (device.require_chips(cell.chips) if require_chip
           else device.device_record())
    cfg, mix = cell.config, cell.traffic
    w = make_weights(cfg, args.seed)
    eng = run.build_engine(cfg, w, traffic.seed_words(args.seed)[0])

    def make_request(spec):
        return Request(uid=spec.uid, prompt=spec.prompt, max_new=spec.max_new)

    run.warm_up(eng, traffic.Traffic(mix, args.seed, cfg["vocab_size"]),
                make_request)
    slo = mix["slo"]
    for rate in args.rates:
        gen = traffic.Traffic(dict(mix, rate_rps=rate), args.seed,
                              cfg["vocab_size"])
        drv = drivers.Driver(eng, gen, make_request)
        t0, t_close = drivers.run_open_loop(drv, args.seconds, mix["drain_s"])
        end = drivers.CLOCK()
        due = [s for s in drv.everyone() if s.due < t_close]
        ttft = [((s.first if s.first is not None else end) - s.due) * 1e3
                for s in due]
        tpot = [(s.finish - s.first) / (len(s.req.output) - 1) * 1e3
                if s.req.status == "ok" and len(s.req.output) > 1 else 0.0
                for s in due]
        met = [s.req.status == "ok" and a <= slo["ttft_ms"]
               and b <= slo["tpot_ms"] for s, a, b in zip(due, ttft, tpot)]
        print(json.dumps({
            "workload": args.workload, "rate_rps": rate, "due": len(due),
            "met_share": float(np.mean(met)),
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p95_ms": report.p95(ttft),
            "tpot_p95_ms": report.p95([b for b in tpot if b > 0] or [0]),
            "unfinished_at_close": sum(s.finish is None or s.finish > t_close
                                       for s in due),
            "failed": sum(s.req.status != "ok" for s in due),
            "lateness_p95_s": report.p95(drv.lateness),
            "lateness_max_s": max(drv.lateness),
            "drain_s": end - t_close, "device": dev}), flush=True)
        while eng.queue_depth() or eng.busy_slots():
            eng.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
