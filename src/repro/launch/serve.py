"""Batched serving driver: prefill + decode with DSG active at inference.

The paper extends DSG to inference by keeping the on-the-fly
dimension-reduction search (Appendix C: stored per-sample masks would cost
more memory than they save, so the search stays online).  Two workloads:

  * --workload batch (default): one fixed-shape batch — batched prompt
    prefill -> KV cache -> token-by-token decode, same DSG masks in both
    phases.
  * --workload mixed: continuous batching over mixed-length synthetic
    traffic through the overlap-admission ServingEngine (prompts and
    generation budgets drawn per request; per-slot admission/retirement).
    --cache-backend picks the KV-cache layout (dense worst-case or paged
    with --page-size/--cache-tokens; see serving/kv_cache.py),
    --paged-kernel picks the paged decode executor (Pallas
    kernels/paged_attention.py vs bounded XLA gather), and
    --temperature/--top-p enable in-step nucleus sampling.
    --replicas N runs the traffic through the front-end router
    (serving/router.py) over N per-replica engines with --route-policy
    round_robin / least_queue / least_pages, and --exec-mode picks the
    replica executor (serving/parallel_exec.py): sequential in-process
    stepping reports the MODELED data-parallel makespan (slowest
    replica's busy time), threaded / sharded run the replica group in
    true parallel and report the MEASURED makespan.
    --fault-tolerance opts the router into failure containment
    (docs/fault_tolerance.md: health states, failover, retry budgets;
    tune with --max-replica-restarts/--max-retries/--deadline-s/
    --stall-timeout-s) and --chaos KIND@REPLICA:STEP injects
    deterministic faults (kill/delay/poison) to watch it work.

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
      --smoke --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
      --smoke --workload mixed --requests 16 --slots 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.parallel import context as pctx


def generate(cfg, params, dsg, prompts: jax.Array, gen_tokens: int,
             *, mesh=None, temperature: float = 0.0, seed: int = 0):
    """prompts (B, P) int32 -> generated (B, gen_tokens).  Greedy or
    temperature sampling; decode step is jitted once and reused."""
    b, p_len = prompts.shape
    max_seq = p_len + gen_tokens
    cache = api.make_cache(cfg, b, max_seq)

    with pctx.use_mesh(mesh):
        prefill = jax.jit(lambda pr, dg, inp, c: api.prefill(
            pr, dg, cfg, inp, c))
        decode = jax.jit(lambda pr, dg, tok, st, pos: api.decode_step(
            pr, dg, cfg, tok, st, pos))

        logits, state = prefill(params, dsg, {"tokens": prompts}, cache)
        key = jax.random.PRNGKey(seed)
        out = []
        tok = None
        for i in range(gen_tokens):
            if temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, logits / temperature)
            else:
                tok = jnp.argmax(logits, axis=-1)
            out.append(tok)
            logits, state = decode(params, dsg, tok[:, None].astype(jnp.int32),
                                   state, jnp.int32(p_len + i))
    return jnp.stack(out, axis=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--workload", choices=("batch", "mixed"),
                    default="batch")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--no-dsg", action="store_true")
    ap.add_argument("--gamma", type=float, default=None,
                    help="DSG sparsity: fraction of neuron groups dropped "
                         "(DSGConfig.gamma, in [0, 1); default: the "
                         "arch config's value)")
    ap.add_argument("--dsg-threshold-mode",
                    choices=("topk", "shared", "ema"), default=None,
                    help="DRS threshold mode (DSGConfig.threshold_mode): "
                         "per-row topk, the paper's inter-sample shared "
                         "threshold, or a cross-step EMA")
    ap.add_argument("--dsg-serving", action="store_true",
                    help="mixed workload: serving-side DSG sparsity "
                         "runtime (serving/dsg_runtime.py) — per-lane "
                         "group-CSR patterns drive a sparse FFN decode, "
                         "refreshed every --dsg-refresh-interval tokens")
    ap.add_argument("--dsg-refresh-interval", type=int, default=8,
                    help="emitted tokens between DRS pattern refreshes "
                         "per lane (--dsg-serving)")
    ap.add_argument("--dsg-apply",
                    choices=("auto", "dense", "xla", "kernel"),
                    default="auto",
                    help="group-CSR FFN executor for --dsg-serving "
                         "(ModelConfig.dsg_ffn_apply): masked-dense "
                         "reference, bounded XLA gather, Pallas CSR "
                         "kernel, or auto (kernel on TPU)")
    # mixed-workload knobs
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=384)
    ap.add_argument("--prompt-bucket", type=int, default=256)
    ap.add_argument("--decode-chunk", type=int, default=1,
                    help="decode steps fused into one device dispatch "
                         "(scheduler.make_decode_fns): EOS / "
                         "budget freezing stays on device and the host "
                         "syncs once per chunk instead of per token; "
                         "temperature-0 streams are bitwise-identical "
                         "to --decode-chunk 1 "
                         "(benchmarks/bench_decode_loop.py gates the "
                         "speedup)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel serving replicas behind the "
                         "front-end router (serving/router.py)")
    ap.add_argument("--route-policy",
                    choices=("round_robin", "least_queue", "least_pages"),
                    default="least_queue",
                    help="replica routing policy when --replicas > 1")
    ap.add_argument("--exec-mode",
                    choices=("sequential", "threaded", "sharded"),
                    default="sequential",
                    help="replica executor (serving/parallel_exec.py): "
                         "sequential in-process stepping (modeled "
                         "makespan), threaded worker per replica, or one "
                         "vmapped step over the stacked replica group "
                         "(both: measured makespan)")
    ap.add_argument("--cache-backend", choices=("dense", "paged"),
                    default="dense",
                    help="KV-cache layout (serving/kv_cache.py)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per page for --cache-backend paged")
    ap.add_argument("--cache-tokens", type=int, default=None,
                    help="paged pool capacity in tokens "
                         "(default: slots * max-seq, the dense worst case)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="dedupe identical prompt prefixes onto shared "
                         "refcounted pages with copy-on-write "
                         "(--cache-backend paged only; "
                         "docs/cache_backends.md)")
    ap.add_argument("--paged-kernel", choices=("auto", "kernel", "xla"),
                    default="auto",
                    help="paged decode executor: Pallas kernel "
                         "(kernels/paged_attention.py, interpret on CPU), "
                         "bounded XLA gather, or auto (kernel on TPU)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus mass kept when sampling")
    # fault tolerance + chaos (docs/fault_tolerance.md)
    ap.add_argument("--chaos", action="append", default=[],
                    metavar="KIND@REPLICA:STEP[:SECONDS]",
                    help="mixed workload: inject a deterministic fault "
                         "(runtime/fault_tolerance.py) — kill@1:40 "
                         "raises on replica 1 at engine step 40, "
                         "delay@0:10:0.05 sleeps 0.05s, poison@2:9 "
                         "corrupts resident outputs then raises; "
                         "repeatable; implies --fault-tolerance")
    ap.add_argument("--fault-tolerance", action="store_true",
                    help="opt the router into failure containment "
                         "(serving/router.py FaultToleranceConfig); "
                         "without it a replica failure crashes the run")
    ap.add_argument("--max-replica-restarts", type=int, default=1,
                    help="restarts before a failed replica is marked "
                         "DEAD for good")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="per-request re-dispatch budget after replica "
                         "failures; beyond it the request fails")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline (submit->finish); expired "
                         "queued requests finish with status timed_out")
    ap.add_argument("--stall-timeout-s", type=float, default=None,
                    help="threaded executor: seconds without step "
                         "progress before a replica is marked SUSPECT "
                         "and aborted")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.no_dsg:
        cfg = cfg.replace(dsg=cfg.dsg._replace(enabled=False))
    if args.gamma is not None:
        if not 0.0 <= args.gamma < 1.0:
            ap.error(f"--gamma must be in [0, 1), got {args.gamma}")
        cfg = cfg.replace(dsg=cfg.dsg._replace(gamma=args.gamma))
    if args.dsg_threshold_mode is not None:
        cfg = cfg.replace(dsg=cfg.dsg._replace(
            threshold_mode=args.dsg_threshold_mode))
    if args.dsg_serving and args.no_dsg:
        ap.error("--dsg-serving needs DSG enabled (drop --no-dsg)")
    if args.dsg_serving and args.workload != "mixed":
        ap.error("--dsg-serving is a mixed-workload (serving engine) "
                 "feature; add --workload mixed")
    cfg = cfg.replace(paged_attn_kernel=args.paged_kernel,
                      dsg_ffn_apply=args.dsg_apply)
    key = jax.random.PRNGKey(0)
    params = api.init_model(key, cfg)
    dsg = api.init_dsg(jax.random.fold_in(key, 1), params, cfg)

    if (args.chaos or args.fault_tolerance) and args.workload != "mixed":
        ap.error("--chaos/--fault-tolerance drive the serving engine; "
                 "add --workload mixed")

    if args.workload == "mixed":
        from repro.runtime.fault_tolerance import ReplicaFault
        from repro.serving.dsg_runtime import DSGServingConfig
        from repro.serving.router import FaultToleranceConfig
        from repro.serving.workload import mixed_requests, run_workload

        def _parse_chaos(spec: str) -> ReplicaFault:
            # KIND@REPLICA:STEP[:SECONDS], e.g. kill@1:40, delay@0:10:0.05
            try:
                kind, _, rest = spec.partition("@")
                replica, step, *extra = rest.split(":")
                return ReplicaFault(replica=int(replica), step=int(step),
                                    kind=kind,
                                    delay_s=(float(extra[0]) if extra
                                             else 0.05))
            except ValueError as e:
                ap.error(f"bad --chaos spec {spec!r} "
                         f"(KIND@REPLICA:STEP[:SECONDS]): {e}")

        faults = [_parse_chaos(s) for s in args.chaos] or None
        ft = (FaultToleranceConfig(
            max_replica_restarts=args.max_replica_restarts,
            max_retries=args.max_retries,
            stall_timeout_s=args.stall_timeout_s)
            if (args.fault_tolerance or faults) else None)
        dsg_serving = (DSGServingConfig(
            refresh_interval=args.dsg_refresh_interval)
            if args.dsg_serving else None)
        reqs = mixed_requests(cfg.vocab, args.requests, seed=args.seed,
                              temperature=args.temperature,
                              top_p=args.top_p)
        if args.deadline_s is not None:
            for r in reqs:
                r.deadline_s = args.deadline_s
        stats = run_workload(cfg, params, dsg, reqs, n_slots=args.slots,
                             max_seq=args.max_seq,
                             prompt_bucket=args.prompt_bucket,
                             cache_backend=args.cache_backend,
                             page_size=args.page_size,
                             cache_tokens=args.cache_tokens,
                             replicas=args.replicas,
                             route_policy=args.route_policy,
                             exec_mode=args.exec_mode,
                             dsg_serving=dsg_serving,
                             fault_tolerance=ft, faults=faults,
                             decode_chunk=args.decode_chunk,
                             prefix_sharing=args.prefix_sharing,
                             seed=args.seed)
        tag = stats["cache_backend"]
        if stats.get("prefix_sharing"):
            tag += "/shared"
        if stats["decode_chunk"] > 1:
            tag += f"/chunk{stats['decode_chunk']}"
        if "route_policy" in stats:
            tag += (f"/{stats['replicas']}x {stats['route_policy']}"
                    f"/{stats['exec_mode']}")
        print(f"[{tag}] {stats['requests']} requests, "
              f"{stats['tokens']} tokens in {stats['wall_s']:.2f}s = "
              f"{stats['tok_per_s']:.1f} tok/s "
              f"(decode {stats['decode_tok_per_s']:.1f} tok/s); latency "
              f"p50 {stats['p50_s']:.2f}s p95 {stats['p95_s']:.2f}s "
              f"({stats['steps']} decode steps, "
              f"cache {stats['cache_bytes'] / 1e6:.2f} MB resident, "
              f"{stats['truncated']} truncated)")
        if "makespan_s" in stats:
            kind = ("measured" if stats["makespan_measured"]
                    else "modeled")
            print(f"  {kind} parallel makespan {stats['makespan_s']:.2f}s "
                  f"= {stats['parallel_tok_per_s']:.1f} tok/s across "
                  f"{stats['replicas']} replicas ({stats['exec_mode']})")
        if "replica_health" in stats:
            print(f"  fault tolerance: {stats['completed_ok']} ok, "
                  f"{stats['failed']} failed, {stats['timed_out']} timed "
                  f"out, {stats['retries']} retries, "
                  f"{stats['faults_fired']} fault(s) fired; replica "
                  f"health {stats['replica_health']}")
        if "shared_page_hits" in stats:
            print(f"  prefix sharing: {stats['shared_page_hits']} page "
                  f"hit(s), {stats['cow_copies']} COW cop(ies), "
                  f"{stats['prefill_cache_hits']} prefill replay(s), "
                  f"peak {stats['peak_live_pages']} live pages")
        return

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab,
                                       (args.batch, args.prompt_len),
                                       dtype=np.int32))
    t0 = time.perf_counter()
    toks = generate(cfg, params, dsg, prompts, args.gen)
    dt = time.perf_counter() - t0
    print(f"generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s); "
          f"first row: {np.asarray(toks[0])[:8]}")


if __name__ == "__main__":
    main()
