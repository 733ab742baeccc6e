"""Shared differential-test harness for the serving stack.

The equivalence surface grown across PRs 1-3 (dense vs paged backends,
XLA vs Pallas decode executors — and now
1 vs N router replicas) all reduces to the same check: drive the same
requests through two configurations and compare the per-request greedy
token streams.  This module is that check, extracted from the copies
that used to live in test_paged_attention.py / test_serving_overlap.py /
test_kv_cache.py:

    parts = make_engine_parts()                  # (cfg, params, dsg)
    reqs  = mixed_traffic(parts[0])              # deterministic traffic
    a = run_and_collect(engine_spec(*parts), reqs)
    b = run_and_collect(engine_spec(*parts, cache_backend="paged",
                                    page_size=8, cache_tokens=80),
                        mixed_traffic(parts[0]))
    assert_streams_equal(a, b)

`run_and_collect` takes an "engine spec" dict (cfg/params/dsg plus any
`ServingEngine` kwargs; add `n_replicas`/`policy` — and optionally
`exec_mode`/`mesh`, forwarded to the replica executor — to run through
the front-end `Router` instead) and returns `{rid: tokens}`.  Traffic
helpers draw from a fixed-seed generator, so two calls with the same
seed produce identical prompts in fresh Request objects — never reuse a
Request across runs; its `output` list is engine state.
"""
import jax
import numpy as np

from repro import configs
from repro.models import api
from repro.serving.router import Router
from repro.serving.scheduler import Request, ServingEngine

SMOKE_ARCH = "internlm2-1.8b"

# The decode_chunk axis every differential suite pins: 1 is the
# classic one-token-per-step loop, 8 is the fused device-resident
# chunk (PR 9).  Parametrizing over this pair catches chunk-boundary
# bugs (commit_chunk early-exit, EOS mid-chunk) in every suite that
# adopts it without bespoke engine setup.
CHUNK_AXIS = (1, 8)


def smoke_cfg(arch: str = SMOKE_ARCH, threshold_mode: str = None):
    """The smoke-model config the serving tests share; threshold_mode
    "topk" (per-row DRS selection) makes lanes computationally
    independent, which every bitwise stream comparison relies on — the
    default "shared" mode couples all lanes to row 0's scores by
    design (the paper's Appendix B inter-sample threshold sharing)."""
    cfg = configs.get_smoke_config(arch)
    if threshold_mode is not None:
        cfg = cfg.replace(dsg=cfg.dsg._replace(
            threshold_mode=threshold_mode))
    return cfg


def make_engine_parts(arch: str = SMOKE_ARCH,
                      threshold_mode: str = "topk"):
    """(cfg, params, dsg) for a smoke model, deterministic across calls."""
    cfg = smoke_cfg(arch, threshold_mode)
    key = jax.random.PRNGKey(0)
    params = api.init_model(key, cfg)
    dsg = api.init_dsg(jax.random.fold_in(key, 1), params, cfg)
    return cfg, params, dsg


def engine_spec(cfg, params, dsg, **engine_kw) -> dict:
    """Bundle model parts + engine kwargs into the spec run_and_collect
    consumes.  Defaults match the historical serving-test engines
    (2 slots, max_seq 64, prompt bucket 32)."""
    spec = {"cfg": cfg, "params": params, "dsg": dsg,
            "n_slots": 2, "max_seq": 64, "prompt_bucket": 32}
    spec.update(engine_kw)
    return spec


def mixed_traffic(cfg, *, seed=23, n=6, temperature: float = 0.0,
                  top_p: float = 1.0):
    """The serving tests' canonical mixed traffic: n requests with
    prompt lengths in [4, 30) and generation budgets in [3, 9), drawn in
    the exact rng order the pre-extraction copies used, so refactored
    tests exercise the same token streams."""
    rng = np.random.default_rng(seed)
    return [Request(uid=u,
                    prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(4, 30)),
                                        dtype=np.int32),
                    max_new=int(rng.integers(3, 9)),
                    temperature=temperature, top_p=top_p)
            for u in range(n)]


def shared_prefix_traffic(cfg, *, seed=29, n=6, prompt_len=24,
                          prefix_len=16, max_new=6,
                          temperature: float = 0.0, top_p: float = 1.0):
    """n requests sharing one random prefix with per-request random
    suffixes — the canonical traffic for prefix-sharing differentials.
    Prompt lengths are FIXED (not mixed): the paged backend only shares
    pages between identical padded rows, so every request must land in
    the same prompt bucket with the prefix at the same offset."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab, prefix_len, dtype=np.int32)
    reqs = []
    for u in range(n):
        suffix = rng.integers(0, cfg.vocab, prompt_len - prefix_len,
                              dtype=np.int32)
        reqs.append(Request(uid=u,
                            prompt=np.concatenate([prefix, suffix]),
                            max_new=max_new, temperature=temperature,
                            top_p=top_p))
    return reqs


def run_and_collect(spec: dict, requests, *, max_steps: int = 400,
                    return_engine: bool = False, faults=None):
    """Run `requests` through the engine (or router) the spec describes
    and return `{rid: tokens}` — every submitted request must finish
    within max_steps.  Set `n_replicas` (and optionally `policy`) in the
    spec to run a Router; otherwise a bare ServingEngine.  With
    return_engine=True, returns (streams, engine_or_router) for
    allocator / counter assertions.  `faults` takes a list of
    ReplicaFault specs to attach via ServingFaultInjector before the
    run (chaos cases; pair with a `fault_tolerance` spec entry)."""
    kw = dict(spec)
    cfg, params, dsg = kw.pop("cfg"), kw.pop("params"), kw.pop("dsg")
    n_replicas = kw.pop("n_replicas", None)
    policy = kw.pop("policy", "least_queue")
    if n_replicas is None:
        eng = ServingEngine(cfg, params, dsg, **kw)
    else:
        eng = Router(cfg, params, dsg, n_replicas=n_replicas,
                     policy=policy, **kw)
    if faults:
        from repro.runtime.fault_tolerance import ServingFaultInjector
        inj = ServingFaultInjector(list(faults))
        inj.attach(eng.engines if n_replicas is not None else [eng])
    for r in requests:
        eng.submit(r)
    try:
        done = eng.run(max_steps=max_steps)
    finally:
        if n_replicas is not None and not return_engine:
            eng.close()
    assert len(done) == len(requests), (
        f"only {len(done)} of {len(requests)} requests finished "
        f"within {max_steps} steps")
    assert all(r.status == "ok" for r in done.values()), (
        "non-ok request in " +
        str({u: r.status for u, r in done.items() if r.status != "ok"}))
    streams = {u: list(r.output) for u, r in done.items()}
    return (streams, eng) if return_engine else streams


def assert_streams_equal(expected: dict, actual: dict, context: str = ""):
    """Per-request token streams must match exactly (uid-keyed, so the
    comparison is permutation-free by construction)."""
    tag = f" [{context}]" if context else ""
    assert set(expected) == set(actual), (
        f"request id sets differ{tag}: "
        f"{sorted(expected)} vs {sorted(actual)}")
    for uid in sorted(expected):
        assert expected[uid] == actual[uid], (
            f"token stream for request {uid} diverges{tag}: "
            f"{expected[uid]} vs {actual[uid]}")
