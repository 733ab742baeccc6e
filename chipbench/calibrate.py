#!/usr/bin/env python3
"""Readings that a cell's limit is set from, many seeds in one process.

    python chipbench/calibrate.py --workload <cell> --seconds <s> --seeds 11 12 ...

For each seed: weights from the seed, the engine, the cell's own mix for
`--seconds` at its own load, then the check's sample of served requests is read
twice: the program's logit gaps against the reference, and the control's (the
reference in int8, reference.py), each put through the decision that a run
makes (`reference.verdict`) against the configuration's limit.  One JSON line
per seed, then a summary line with, for each statistic, the program's largest
reading and the control's smallest, and how many seeds each came out correct
on.  Not part of a benchmark run; run it on the chip when a limit is set.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None, *, require_chip: bool = True, root: Path = ROOT,
         bench_json: dict = None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(root), str(root / "src")]
    from chipbench import run
    run.enable_cache(root)
    import jax
    from chipbench import bench, device, drivers, reference, traffic
    from chipbench.weights import make_weights
    from repro.serving.scheduler import Request

    cell = bench.resolve(args.workload, root, bench_json)
    dev = (device.require_chips(cell.chips) if require_chip
           else device.device_record())
    cfg, mix = cell.config, cell.traffic
    readings = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        w = make_weights(cfg, seed)
        eng = run.build_engine(cfg, w, traffic.seed_words(seed)[0])
        gen = traffic.Traffic(mix, seed, cfg["vocab_size"])

        def make_request(spec):
            return Request(uid=spec.uid, prompt=spec.prompt,
                           max_new=spec.max_new)

        run.warm_up(eng, gen, make_request)
        drv = drivers.Driver(eng, gen, make_request)
        if cfg["dsg"]["enabled"]:
            drv.selections = drivers.SelectionLog(eng)
        if mix["driver"] == "backlog":
            drivers.run_backlog(drv, args.seconds,
                                mix["queue_depth"] * cfg["serving"]["n_slots"])
        else:
            drivers.run_open_loop(drv, args.seconds, mix["drain_s"])
        rows, sels = run.sample_rows(drv, mix["check"], seed)
        drv.engine = None
        del eng, drv
        gc.collect()
        got = reference.compare(cfg, w, rows,
                                n_rows=mix["check"]["max_requests"],
                                control=True, selections=sels)
        del w
        gc.collect()
        rec = {"workload": args.workload, "seed": seed, **got,
               "program_correct": reference.verdict(cfg, rows,
                                                    got["program"])[0],
               "control_correct": reference.verdict(cfg, rows,
                                                    got["control"])[0],
               "rows": len(rows),
               "prompt_lens": [len(p) for p, _ in rows],
               "served": [len(o) for _, o in rows],
               "seconds": time.perf_counter() - t0}
        readings.append(rec)
        print(json.dumps(rec), flush=True)
    summary = {"summary": args.workload, "device": dev, "seeds": args.seeds,
               "limit": cfg["limits"]["mean_logit_gap"],
               "program_correct": sum(r["program_correct"] for r in readings),
               "control_correct": sum(r["control_correct"] for r in readings)}
    for k in readings[0]["program"]:
        summary[k] = {
            "program_max": max(r["program"][k] for r in readings),
            "control_min": min(r["control"][k] for r in readings)}
    print(json.dumps(summary), flush=True)
    jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
