#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is an entry of `BENCHMARK.json`; its
configuration, model module, traffic mix and metric readers are found by name
(bench.py).  One run: make the weights on the device from the seed, build the
serving engine the configuration states, warm up every prompt bucket and
decode variant the mix can reach, then drive the mix for `--seconds` (the
window), and after it check a seeded sample of the served requests against the
plain reference (reference.py).  With `--trace 1` the profiler traces a few
seconds inside the window and the line carries the per-layer metrics instead
of the end-to-end ones.

Without an accelerator, or with fewer chips than the cell asks for, it exits
with code 3 and prints no result.  JAX's persistent compilation cache lives at
`<checkout>/.jax_cache`, so only a checkout's first run of a cell compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache(root: Path) -> Path:
    """JAX's persistent compilation cache at `<root>/.jax_cache`."""
    import jax
    path = root / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def program_config(cfg: dict):
    """The program's ModelConfig for the configuration file, as its model
    module states it."""
    from chipbench import bench
    return bench.model(cfg).program_config(cfg)


def build_engine(cfg: dict, w: dict, seed: int):
    """The serving engine the configuration states, over the weights `w`;
    kernel routes, executor and decode chunk are the program's defaults."""
    import jax
    from repro.models import api
    from repro.serving.dsg_runtime import DSGServingConfig
    from repro.serving.scheduler import ServingEngine
    from chipbench import bench
    pc = program_config(cfg)
    params = bench.model(cfg).program_params(w)
    state, serving = None, None
    if cfg["dsg"]["enabled"]:
        state = jax.jit(lambda r, p: api.refresh_dsg({"r": r}, p, pc))(
            w["r"], params)
        serving = DSGServingConfig(
            refresh_interval=cfg["dsg"]["refresh_interval"],
            threshold=cfg["dsg"]["threshold_mode"])
    s = cfg["serving"]
    return ServingEngine(pc, params, state, n_slots=s["n_slots"],
                         max_seq=s["max_seq"],
                         prompt_bucket=s["prompt_bucket"],
                         cache_backend="paged", page_size=s["page_size"],
                         seed=seed & 0x7FFFFFFF, dsg_serving=serving)


def warm_up(eng, traffic, make_request):
    """Run one request per prompt length the mix can send (each reaches
    its own prefill bucket), then every decode variant the engine can
    dispatch; wait for the device."""
    import jax
    import numpy as np
    from chipbench.traffic import Spec
    rng = np.random.default_rng(0)
    for i, n in enumerate(sorted(set(traffic.prompt_set.tolist()))):
        eng.submit(make_request(Spec(
            uid=-1 - i, max_new=2,
            prompt=rng.integers(0, traffic.vocab, n, dtype=np.int32))))
    while eng.queue_depth() or eng.busy_slots():
        eng.step()
    eng.warm_decode()
    jax.block_until_ready(eng.cache)


def sample_rows(drv, check: dict, seed: int):
    """A sample of the finished requests, drawn from the seed alone: the
    longest (the first by uid among equals), then the rest in the order of
    a key that the seed gives each uid, so two runs of one seed check the
    same requests wherever both finished them.  Returns ([(prompt, served
    tokens)], their DSG selections or None), at most `max_requests`,
    stopping once `min_served_tokens` are in it."""
    import numpy as np
    from chipbench.traffic import seed_words
    done = sorted((s for s in drv.done if s.req.status == "ok"),
                  key=lambda s: s.spec.uid)
    rows = []
    if done:
        longest = max(done, key=lambda s: len(s.spec.prompt)
                      + len(s.req.output))
        rows.append(longest)
        words = seed_words(seed) + [3]
        done = sorted((s for s in done if s is not longest),
                      key=lambda s: np.random.default_rng(
                          words + seed_words(s.spec.uid)).random())
    for r in done:
        if (len(rows) >= check["max_requests"]
                or sum(len(s.req.output) for s in rows)
                >= check["min_served_tokens"]):
            break
        rows.append(r)
    sels = None
    if drv.selections is not None:
        sels = [drv.selections.by_uid.get(s.spec.uid, []) for s in rows]
    return [(np.asarray(s.spec.prompt), list(s.req.output))
            for s in rows], sels


def step_spans(step) -> dict:
    """The engine's own seconds in each of its spans inside one step, summed
    by name, and the step's seconds outside them all (`outside`); empty for
    a program that records no spans."""
    try:
        from repro.serving import telemetry
    except ImportError:
        return {}
    out, covered = {}, 0.0
    for rec in telemetry.recorders():
        spans = rec.spans(step.start, step.end)
        own = telemetry.self_seconds(spans)
        for sp in spans:
            out[sp.name] = out.get(sp.name, 0.0) + own[sp.sid]
            if sp.parent not in own:
                covered += sp.seconds
    out["outside"] = step.end - step.start - covered
    return out


def run_cell(cell, args, dev: dict, log, cache: Path):
    import jax
    from chipbench import device, drivers, reference, report, trace, traffic
    from chipbench.weights import make_weights
    from repro.serving.scheduler import Request

    cfg, mix = cell.config, cell.traffic
    peaks = device.peaks_for(dev["kind"]) if args.trace else {}
    t_setup = time.perf_counter()
    w = make_weights(cfg, args.seed)
    jax.block_until_ready(w)
    t_weights = time.perf_counter()
    eng = build_engine(cfg, w, traffic.seed_words(args.seed)[0])
    gen = traffic.Traffic(mix, args.seed, cfg["vocab_size"])

    def make_request(spec):
        return Request(uid=spec.uid, prompt=spec.prompt, max_new=spec.max_new)

    warm_up(eng, gen, make_request)
    n0, s0, h0 = log.snapshot()
    drv = drivers.Driver(eng, gen, make_request,
                         refresh_interval=(cfg["dsg"].get("refresh_interval", 0)
                                           if cfg["dsg"]["enabled"] else 0))
    if cfg["dsg"]["enabled"]:
        drv.selections = drivers.SelectionLog(eng)
    setup_s = time.perf_counter() - T_START
    tracer = None
    if args.trace:
        tracer = trace.Tracer(str(cell.root / ".chipbench_trace"),
                              drivers.CLOCK(),
                              mix["trace"]["start_after_s"],
                              mix["trace"]["seconds"])
        drv.tracer = tracer
    if mix["driver"] == "backlog":
        depth = mix["queue_depth"] * cfg["serving"]["n_slots"]
        window = drivers.run_backlog(drv, args.seconds, depth)
    else:
        window = drivers.run_open_loop(drv, args.seconds, mix["drain_s"])
    drain_end = drivers.CLOCK()
    n1, s1, h1 = log.snapshot()
    if tracer is not None:
        tracer.close()
    peak = device.peak_bytes()

    ctx = report.Ctx(cfg=cfg, mix=mix, peaks=peaks, setup_s=setup_s,
                     window=window, seen=drv.everyone(), steps=drv.steps,
                     drain_end=drain_end)
    info = {"record": cell.name, "seed": args.seed, "trace": args.trace,
            "setup_s": setup_s, "weights_s": t_weights - t_setup,
            "compile_cache": str(cache),
            "compiles_in_window": n1 - n0, "compile_s_in_window": s1 - s0,
            "cache_hits_in_window": h1 - h0,
            "compiles_total": n1, "cache_hits_total": h1,
            "window_s": ctx.window_s, "steps": len(drv.steps),
            "tokens": sum(r.lanes for r in drv.steps),
            "requests_sent": len(ctx.seen), "requests_done": len(drv.done)}
    if mix["driver"] == "open_loop" and drv.lateness:
        info["lateness_p95_s"] = report.p95(drv.lateness)
        info["lateness_max_s"] = max(drv.lateness)
    longest = max(drv.steps, key=lambda r: r.end - r.start)
    info["longest_step"] = {"s": longest.end - longest.start,
                            "at_s": longest.start - window[0],
                            "lanes": longest.lanes, "admits": longest.admits,
                            "spans": step_spans(longest)}

    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": {**dev, "memory_peak_bytes": peak}}
    if args.trace:
        ctx.trace = trace.summarize(tracer.path())
        ctx.traced_steps = tracer.steps(drv)
        result["device"].update(busy_s=ctx.trace.busy_s,
                                window_s=ctx.trace.window_s)
        result["breakdown"] = {"device_ops": ctx.trace.top_ops(),
                               "idle_gaps": ctx.trace.top_gaps()}
        info["traced_steps"] = len(ctx.traced_steps)
        shutil.rmtree(tracer.directory, ignore_errors=True)
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = cell.reader(m)(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    if mix["driver"] == "open_loop":
        due = ctx.due_in_window()
        result["attempted"] = len(due)
        result["failed"] = sum(s.req.status != "ok" for s in due)
    else:
        result["attempted"] = sum(1 for s in ctx.seen if s.first is not None)
        result["failed"] = sum(s.req.status not in ("ok", "pending")
                               for s in ctx.seen)

    # the check: the engine and its cache go first, so the reference's
    # memory never sets the peak read above
    rows, sels = sample_rows(drv, mix["check"], args.seed)
    drv.engine = None
    del eng, drv
    gc.collect()
    t_check = time.perf_counter()
    got = reference.compare(cfg, w, rows, n_rows=mix["check"]["max_requests"],
                            control=False, selections=sels)
    info["check_s"] = time.perf_counter() - t_check
    info["check_tokens"] = got["tokens"]
    info["check_rows"] = len(rows)
    info.update({f"check_{k}": v for k, v in got["program"].items()})
    result["correct"], result["check"] = reference.verdict(cfg, rows,
                                                          got["program"])
    return result, info


def main(argv=None, *, require_chip: bool = True, root: Path = ROOT,
         bench: dict = None):
    args = parse(argv)
    sys.path[:0] = [str(root), str(root / "src")]
    cache = enable_cache(root)
    from chipbench import bench as bench_mod, device, report
    try:
        cell = bench_mod.resolve(args.workload, root, bench)
    except bench_mod.UnknownModel as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    try:
        dev = (device.require_chips(cell.chips) if require_chip
               else device.device_record())
    except device.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(f"chipbench: {dev['platform']} {dev['kind']} x{dev['count']}",
          file=sys.stderr, flush=True)
    log = device.CompileLog()
    result, info = run_cell(cell, args, dev, log, cache)
    report.emit(result, info)
    return 0


if __name__ == "__main__":
    sys.exit(main())
