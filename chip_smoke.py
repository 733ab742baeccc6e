#!/usr/bin/env python3
"""Chip smoke run: the served path at internlm2-1.8b full width on a TPU.

Drives the serving path once through the entry points a user calls
(`serving.workload.run_workload` over `ServingEngine`, and `Router`),
with random weights made from `--seed`, the paged KV cache, and the
Pallas kernels picked by the `auto` routes.  Run it from the checkout
root on a machine with a TPU:

    python chip_smoke.py             # one chip: device, kernels, dense
                                     # serving, DSG serving
    python chip_smoke.py --chips 4   # four chips: 4 threaded replicas
                                     # against 1, and nothing else

Every phase prints one JSON line for the record (requests ok, tokens,
wall seconds, compiles and their seconds, peak device bytes); none of
it is a speed claim.  The last line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Any failure exits non-zero before that line: there is no CPU fallback,
and no phase catches an exception.

The compile cache sits where `JAX_COMPILATION_CACHE_DIR` says, else at
`<checkout>/.jax_cache` (repro.launch.compile_cache).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import threading
import time
from pathlib import Path
from typing import NamedTuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "internlm2-1.8b"
KERNELS = ("paged_decode", "dsg_ffn_csr", "drs_project", "drs_scores")
TOL = 2e-2          # max |kernel - reference| over max(1, max |reference|)
MIN_AGREEMENT = 0.75  # greedy tokens matching a cache-free full forward


class Sizes(NamedTuple):
    """Serving shape of a run: engine lanes and cache, and the traffic."""
    n_slots: int = 8
    max_seq: int = 1024
    prompt_bucket: int = 512
    page_size: int = 16
    n_requests: int = 8
    prompt_range: tuple = (64, 512)
    max_new: int = 32


class CompileLog:
    """Counts XLA compiles (loads from the persistent cache included) and
    their seconds, from JAX's monitoring events."""

    def __init__(self):
        self._lock = threading.Lock()
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.count, self.seconds, self.cache_hits


def peak_bytes():
    """Largest `peak_bytes_in_use` over the devices (None where the
    backend does not report it)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_phase(name, log, fn, *args, **kw):
    """Run one phase and print its record line."""
    n0, s0, h0 = log.snapshot()
    t0 = time.perf_counter()
    record = fn(*args, **kw)
    wall = time.perf_counter() - t0
    n1, s1, h1 = log.snapshot()
    record = {"phase": name, **record, "wall_s": wall,
              "compiles": n1 - n0, "compile_s": s1 - s0,
              "compile_cache_hits": h1 - h0,
              "peak_bytes_in_use": peak_bytes()}
    print(json.dumps(record), flush=True)
    return record


def device_record():
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# configurations, weights and traffic
# ---------------------------------------------------------------------------

def serving_configs(cfg):
    """(dense, dsg) serving configs: the auto kernel routes, and the
    per-lane topk selection at gamma 0.5 for DSG (the serving runtime
    refuses the config's inter-sample `shared` threshold)."""
    cfg = cfg.replace(paged_attn_kernel="auto", dsg_ffn_apply="auto")
    dense = cfg.replace(dsg=cfg.dsg._replace(enabled=False))
    dsg = cfg.replace(dsg=cfg.dsg._replace(enabled=True, gamma=0.5,
                                           threshold_mode="topk"))
    return dense, dsg


def init_params(cfg, seed):
    """Random params from `seed`, made on the device."""
    from repro.models import api
    return jax.jit(lambda k: api.init_model(k, cfg))(jax.random.PRNGKey(seed))


def init_dsg_state(cfg, params, seed):
    """DSG projection and projected search weights for `params`."""
    from repro.models import api
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return jax.jit(lambda k, p: api.init_dsg(k, p, cfg))(key, params)


def make_requests(vocab, sizes, seed):
    """Greedy requests with prompt lengths over sizes.prompt_range, both
    ends included, and sizes.max_new tokens each."""
    from repro.serving.scheduler import Request
    rng = np.random.default_rng(seed)
    lo, hi = sizes.prompt_range
    lens = [lo, hi] + [int(n) for n in
                       rng.integers(lo, hi + 1, sizes.n_requests - 2)]
    return [Request(uid=i, prompt=rng.integers(0, vocab, n, dtype=np.int32),
                    max_new=sizes.max_new)
            for i, n in enumerate(lens)]


def engine_kw(sizes):
    return dict(n_slots=sizes.n_slots, max_seq=sizes.max_seq,
                prompt_bucket=sizes.prompt_bucket, cache_backend="paged",
                page_size=sizes.page_size, decode_chunk=1)


def check_served(reqs, stats, vocab, max_new):
    bad = [(r.uid, r.status, len(r.output)) for r in reqs
           if r.status != "ok" or len(r.output) != max_new]
    assert not bad, f"requests not served ok: {bad}"
    assert stats["truncated"] == 0, f"{stats['truncated']} prompts truncated"
    toks = np.concatenate([np.asarray(r.output) for r in reqs])
    assert ((toks >= 0) & (toks < vocab)).all(), "token ids out of range"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all(), "non-finite kernel output"
    err = float(np.max(np.abs(got - want)))
    scale = max(1.0, float(np.max(np.abs(want))))
    return err, err / scale


def kernel_phase(cfg, sizes, seed):
    """Each served-path kernel once at the config's widths, against its
    plain float32 reference at full matmul precision."""
    from repro.core import dsg_linear as dl, projection
    from repro.kernels import ops, ref
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    b, h, kv, d = sizes.n_slots, cfg.n_heads, cfg.n_kv, cfg.head_dim
    ps, max_pages = sizes.page_size, sizes.max_seq // sizes.page_size
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda k, shape: jax.random.normal(k, shape).astype(dt)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    record, errs = {}, {}

    # paged decode: ragged depths (first row, page edge, last position)
    rng = np.random.default_rng(seed)
    pos = rng.integers(0, sizes.max_seq, b)
    pos[:3] = (0, ps - 1, sizes.max_seq - 1)[:b]
    pos = jnp.asarray(pos, jnp.int32)
    table = jnp.asarray(1 + np.arange(b)[:, None] * max_pages
                        + np.arange(max_pages)[None, :], jnp.int32)
    q = normal(ks[0], (b, h, d))
    kn, vn = normal(ks[1], (b, kv, d)), normal(ks[2], (b, kv, d))
    pool = (b * max_pages + 1, ps, kv, d)
    kp, vp = normal(ks[3], pool), normal(ks[4], pool)
    o, kp2, vp2 = ops.paged_decode_attention(q, kn, vn, kp[None], vp[None],
                                             table, pos, 0,
                                             num_pages=max_pages)
    with jax.default_matmul_precision("highest"):
        ow, kw, vw = jax.jit(ref.paged_decode_ref)(q, kn, vn, kp, vp,
                                                   table, pos)
    errs["paged_decode"] = _err(o, ow)
    assert np.array_equal(np.asarray(kp2[0]), np.asarray(kw)) and \
        np.array_equal(np.asarray(vp2[0]), np.asarray(vw)), \
        "paged_decode pool write-back differs from the reference scatter"

    # group-CSR SwiGLU: ragged per-lane counts over sorted group lists
    blk, dm, f = cfg.dsg.block, cfg.d_model, cfg.d_ff
    g = f // blk
    k_bound = max(1, g // 2)
    p = {k: v.astype(dt) for k, v in
         dl.init_swiglu(ks[5], dm, f).items()}
    x = normal(ks[6], (b, dm))
    idx = np.zeros((b, k_bound), np.int32)
    counts = rng.integers(1, k_bound + 1, b).astype(np.int32)
    for lane in range(b):
        groups = np.sort(rng.choice(g, counts[lane], replace=False))
        idx[lane, :counts[lane]] = groups
    idx, counts = jnp.asarray(idx), jnp.asarray(counts)
    y = ops.dsg_ffn_csr(x, p["w_gate"], p["w_up"], p["w_down"], idx,
                        counts, block=blk)
    with jax.default_matmul_precision("highest"):
        yw = jax.jit(lambda p, x: dl.swiglu_csr_masked(
            p, x[:, None], idx, counts, block=blk)[:, 0])(f32(p), f32(x))
    errs["dsg_ffn_csr"] = _err(y, yw)

    # DRS search: projection, then fused virtual matmul + group relu-sum
    r = projection.make_projection(ks[7], dl.proj_dim(dm, f, cfg.dsg), dm,
                                   dtype=dt)
    fw = projection.project(r, p["w_gate"])
    bm = b if b % 128 else 128
    bf = f if f % 512 else 512
    fx = ops.drs_project(x, r, bm=bm)
    scores = ops.drs_scores(fx, fw, block=blk, bm=bm, bf=bf)
    with jax.default_matmul_precision("highest"):
        fxw = jax.jit(ref.drs_project_ref)(f32(x), f32(r))
        sw = jax.jit(ref.drs_scores_ref, static_argnums=2)(f32(fx),
                                                           f32(fw), blk)
    errs["drs_project"] = _err(fx, fxw)
    errs["drs_scores"] = _err(scores, sw)

    for name, (err, rel) in errs.items():
        record[f"{name}_max_abs_err"] = err
        record[f"{name}_rel_err"] = rel
    bad = {n: rel for n, (_, rel) in errs.items() if rel > TOL}
    assert not bad, f"kernels past the bf16 tolerance {TOL}: {bad}"
    return record


def lowered_kernels(cfg, params, dsg, sizes):
    """Names of the Mosaic kernels in the lowered decode step at the
    serving shapes (the refresh variant when DSG is on), through the
    config's own kernel routes."""
    from repro.core import drs, sparse_mask
    from repro.models import api
    sds = jax.ShapeDtypeStruct
    abstract = lambda t: jax.tree.map(lambda a: sds(a.shape, a.dtype), t)
    b, max_pages = sizes.n_slots, sizes.max_seq // sizes.page_size
    n_pages = b * sizes.max_seq // sizes.page_size + 1
    dt = jax.tree.leaves(params)[0].dtype
    pool = sds((cfg.n_layers, n_pages, sizes.page_size, cfg.n_kv,
                cfg.head_dim), dt)
    view = {"pages_k": pool, "pages_v": pool,
            "page_table": sds((b, max_pages), jnp.int32)}
    tok, pos = sds((b, 1), jnp.int32), sds((b,), jnp.int32)
    csr = None
    if dsg is not None:
        g = cfg.d_ff // cfg.dsg.block
        k = sparse_mask.active_group_bound(
            drs.keep_groups(cfg.d_ff, cfg.dsg.drs_cfg()), g)
        csr = {"idx": sds((cfg.n_layers, b, k), jnp.int32),
               "counts": sds((cfg.n_layers, b), jnp.int32)}

    def step(p, d, t, c, q, s):
        return api.decode_step(p, d, cfg, t, c, q, live_pages=max_pages,
                               ffn_csr=s, collect_drs_scores=s is not None)

    text = jax.jit(step).lower(abstract(params), abstract(dsg), tok, view,
                               pos, csr).as_text()
    assert "tpu_custom_call" in text, "decode step holds no Mosaic kernel"
    return sorted(n for n in KERNELS if f'kernel_name = "{n}"' in text)


def bucket_of(n, sizes):
    from repro.serving.scheduler import bucket_sizes
    return next(bb for bb in bucket_sizes(sizes.prompt_bucket, sizes.max_seq)
                if n <= bb)


def reference_agreement(cfg, params, req, sizes):
    """Share of a served greedy stream that a cache-free full forward over
    the same left-padded row (float32 attention scores) predicts."""
    from repro.models import transformer
    pb = bucket_of(len(req.prompt), sizes)
    row = np.concatenate([np.zeros(pb - len(req.prompt), np.int32),
                          req.prompt, np.asarray(req.output[:-1], np.int32)])
    ref_cfg = cfg.replace(attn_bf16_scores=False)
    logits, _, _ = jax.jit(lambda p, t: transformer.forward(
        p, None, ref_cfg, t))(params, jnp.asarray(row)[None])
    pred = np.asarray(jnp.argmax(logits[0, pb - 1:], axis=-1))
    return float(np.mean(pred == np.asarray(req.output)))


def serving_phase(cfg, params, dsg, sizes, seed, dsg_serving=None):
    """The mixed workload through run_workload on one engine; every
    request must finish ok with its full budget."""
    from repro.serving.workload import run_workload
    reqs = make_requests(cfg.vocab, sizes, seed)
    stats = run_workload(cfg, params, dsg, reqs, seed=seed,
                         dsg_serving=dsg_serving, **engine_kw(sizes))
    check_served(reqs, stats, cfg.vocab, sizes.max_new)
    record = {"requests_ok": sum(r.status == "ok" for r in reqs),
              "requests": len(reqs), "tokens": stats["tokens"],
              "decode_steps": stats["steps"]}
    if dsg is None:
        agree = [reference_agreement(cfg, params, r, sizes)
                 for r in (reqs[0], reqs[1])]        # shortest, longest
        record["reference_agreement"] = agree
        assert min(agree) >= MIN_AGREEMENT, \
            f"served streams disagree with the full forward: {agree}"
    return record


def replica_phase(cfg, params, sizes, seed, n_replicas):
    """`n_replicas` threaded Router replicas, one per device, against one
    replica on the same traffic: temperature-0 streams must match, and
    each replica's params, KV pool and prefill template must sit on its
    own device."""
    from repro.serving.router import Router
    from repro.serving.workload import run_workload, warmup_router
    devs = jax.local_devices()
    assert len(devs) >= n_replicas, \
        f"{n_replicas} replicas need {n_replicas} devices, have {len(devs)}"
    one = make_requests(cfg.vocab, sizes, seed)
    stats = run_workload(cfg, params, None, one, seed=seed,
                         **engine_kw(sizes))
    check_served(one, stats, cfg.vocab, sizes.max_new)
    gc.collect()

    reqs = make_requests(cfg.vocab, sizes, seed)
    router = Router(cfg, params, None, n_replicas=n_replicas,
                    exec_mode="threaded", seed=seed, **engine_kw(sizes))

    def placement():
        for r, eng in enumerate(router.engines):
            for what, tree in (("params", eng.params),
                               ("KV pool", eng.cache.data),
                               ("prefill template", eng._lane0)):
                where = {d for leaf in jax.tree.leaves(tree)
                         for d in leaf.devices()}
                assert where == {devs[r]}, \
                    f"replica {r} {what} on {where}, want {devs[r]}"

    placement()
    try:
        warmup_router(router, cfg.vocab, requests=reqs)
        for q in reqs:
            router.submit(q)
        router.run()
    finally:
        router.close()
    placement()
    served = {r.uid: r for r in reqs}
    check_served(reqs, {"truncated": sum(r.truncated for r in reqs)},
                 cfg.vocab, sizes.max_new)
    diff = [r.uid for r in one if r.output != served[r.uid].output]
    assert not diff, f"streams differ from the 1-replica run: {diff}"
    return {"replicas": n_replicas, "requests_ok": len(reqs),
            "tokens": sum(len(r.output) for r in reqs),
            "streams_equal": True,
            "per_replica_requests": [len(e.done) for e in router.engines],
            "placement": "own device"}


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: device, kernel, dense and DSG phases; "
                         "4: only the 4-replica threaded Router phase "
                         "and its 1-replica comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.dsg_runtime import DSGServingConfig
    cache_dir = enable_compile_cache()
    log = CompileLog()

    dev = device_record()
    print(json.dumps({"phase": "device", **dev, "compile_cache": cache_dir}),
          flush=True)
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev['platform']}")
    if dev["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {dev['count']}")

    sizes = Sizes()
    dense_cfg, dsg_cfg = serving_configs(configs.get_config(ARCH))
    params = init_params(dense_cfg, args.seed)

    if args.chips == 4:
        run_phase("replicas_threaded", log, replica_phase, dense_cfg, params,
                  sizes, args.seed, n_replicas=4)
    else:
        run_phase("kernels", log, kernel_phase, dsg_cfg, sizes, args.seed)
        names = lowered_kernels(dense_cfg, params, None, sizes)
        assert names == ["paged_decode"], names
        run_phase("dense_serving", log, lambda: {
            "kernels": names,
            **serving_phase(dense_cfg, params, None, sizes, args.seed)})
        gc.collect()
        dsg = init_dsg_state(dsg_cfg, params, args.seed)
        names = lowered_kernels(dsg_cfg, params, dsg, sizes)
        assert names == sorted(KERNELS), names
        run_phase("dsg_serving", log, lambda: {
            "kernels": names,
            **serving_phase(dsg_cfg, params, dsg, sizes, args.seed,
                            DSGServingConfig(threshold="topk"))})
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
