"""Front-end router over per-replica serving engines (serving/router.py).

The load-bearing invariant: each replica is solo-deterministic under
per-row DRS selection, so merged greedy token streams keyed by request
uid must be IDENTICAL for 1, 2, and 3 replicas, across {dense, paged}
cache backends and {round_robin, least_queue} routing policies — routing
decides only WHERE a request decodes, never WHAT it decodes.  On top of
that: a single-replica router is bit-identical to a bare ServingEngine
(greedy and sampled), and the least_pages policy never dispatches a
request to a replica whose paged pool cannot reserve its worst-case page
count (so per-replica admission deferral never triggers)."""
import numpy as np
import pytest

from harness import (CHUNK_AXIS, assert_streams_equal, engine_spec,
                     make_engine_parts, mixed_traffic, run_and_collect)
from repro.serving.kv_cache import DenseBackend
from repro.serving.router import Router, get_policy
from repro.serving.scheduler import Request, ServingEngine


@pytest.fixture(scope="module")
def engine_parts():
    return make_engine_parts()


_BACKEND_KW = {
    "dense": {},
    # worst-case lane reservation: min(bucket 32 + max_new 8, 64) = 40
    # tokens = 5 pages of 8; 80-token pools hold two lanes per replica
    "paged": {"cache_backend": "paged", "page_size": 8, "cache_tokens": 80},
}

# module-level memo: the 1-replica reference stream per backend, shared
# across the invariance parametrizations so it is computed once
_baseline = {}


def _reference(engine_parts, backend):
    if backend not in _baseline:
        spec = engine_spec(*engine_parts, **_BACKEND_KW[backend])
        _baseline[backend] = run_and_collect(spec,
                                             mixed_traffic(spec["cfg"]))
    return _baseline[backend]


# ---------------------------------------------------------------------------
# construction / policy guards (no engine runs — cheap)
# ---------------------------------------------------------------------------

def test_policy_and_constructor_guards(engine_parts):
    cfg, params, dsg = engine_parts
    with pytest.raises(ValueError):
        get_policy("fastest")
    with pytest.raises(ValueError):
        Router(cfg, params, dsg, n_replicas=0)
    with pytest.raises(ValueError):                 # backend instances are
        Router(cfg, params, dsg, n_replicas=2,      # one-handle objects
               cache_backend=DenseBackend())
    with pytest.raises(ValueError):                 # one view per replica
        Router(cfg, params, dsg, n_replicas=2, param_views=[params])


def test_stats_raise_before_any_finish(engine_parts):
    cfg, params, dsg = engine_parts
    router = Router(cfg, params, dsg, n_replicas=2, n_slots=2, max_seq=64)
    with pytest.raises(ValueError):
        router.throughput()
    assert router.drain() == {}        # nothing queued: drains to nothing


def test_introspection_counters(engine_parts):
    cfg, params, dsg = engine_parts
    eng = ServingEngine(cfg, params, dsg, n_slots=3, max_seq=64,
                        prompt_bucket=32, cache_backend="paged",
                        page_size=8, cache_tokens=80)
    assert eng.queue_depth() == 0 and eng.free_slots() == 3
    assert eng.busy_slots() == 0
    assert eng.free_pages() == eng.backend.allocator.free_pages == 10
    req = Request(uid=0, prompt=np.zeros(12, np.int32), max_new=8)
    eng.submit(req)
    assert eng.queue_depth() == 1
    # bucket_for(12) = 16; min(16 + 8, 64) = 24 tokens -> 3 pages of 8
    assert eng.pages_needed(req) == 3
    assert eng.can_admit_request(req)
    done = eng.drain(max_steps=50)     # retirement draining empties it all
    assert len(done) == 1 and eng.queue_depth() == 0
    assert eng.free_slots() == 3
    assert eng.free_pages() == eng.backend.allocator.free_pages == 10
    dense = ServingEngine(cfg, params, dsg, n_slots=2, max_seq=64,
                          prompt_bucket=32, page_size=8)
    # dense lanes own max_seq stripes: 2 free lanes * 64 / 8 pseudo-pages
    assert dense.free_pages() == 2 * 64 // 8


# ---------------------------------------------------------------------------
# single-replica router == bare engine (bitwise)
# ---------------------------------------------------------------------------

def test_single_replica_router_bit_identical(engine_parts):
    """One replica behind the router runs the same admissions in the same
    order on the same step schedule as a bare engine — greedy AND sampled
    streams (per-(seed, step, lane) keys) must match bit-for-bit."""
    cfg = engine_parts[0]
    bare = run_and_collect(engine_spec(*engine_parts), mixed_traffic(cfg))
    routed = run_and_collect(
        engine_spec(*engine_parts, n_replicas=1, policy="round_robin"),
        mixed_traffic(cfg))
    assert_streams_equal(bare, routed, "1-replica router vs bare engine")

    kw = dict(n=4, temperature=0.8, top_p=0.9)
    bare_s = run_and_collect(engine_spec(*engine_parts, seed=7),
                             mixed_traffic(cfg, **kw))
    routed_s = run_and_collect(
        engine_spec(*engine_parts, seed=7, n_replicas=1,
                    policy="round_robin"),
        mixed_traffic(cfg, **kw))
    assert_streams_equal(bare_s, routed_s, "sampled 1-replica router")


# ---------------------------------------------------------------------------
# replica-count invariance (the acceptance matrix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["dense", "paged"])
@pytest.mark.parametrize("policy", ["round_robin", "least_queue"])
def test_replica_count_invariance(engine_parts, backend, policy):
    """Merged greedy token streams for the same request set are identical
    for 1, 2, and 3 replicas: requests are dispatched whole, every
    replica is solo-deterministic, and results merge by uid (permutation-
    free by construction)."""
    ref = _reference(engine_parts, backend)
    for n in (1, 2, 3):
        spec = engine_spec(*engine_parts, n_replicas=n, policy=policy,
                           **_BACKEND_KW[backend])
        out, router = run_and_collect(spec, mixed_traffic(spec["cfg"]),
                                      max_steps=1000, return_engine=True)
        assert_streams_equal(ref, out, f"{backend}/{policy}/{n} replicas")
        # every request was dispatched exactly once, to a real replica
        uids = [u for u, _ in router.dispatch_log]
        assert sorted(uids) == sorted(ref)
        assert all(0 <= r < n for _, r in router.dispatch_log)


# ---------------------------------------------------------------------------
# least_pages admission safety
# ---------------------------------------------------------------------------

def test_least_pages_never_admits_beyond_reservation(engine_parts):
    """least_pages dispatches only to a replica whose allocator can
    reserve the request's worst-case page count at that instant, so the
    dispatched request is admitted on the replica's very next step:
    per-replica queues never carry a deferred request across a step, and
    the streams still match the reference.  Pools here hold ONE
    reservation each (5 pages of 8 + scratch), forcing the policy to
    defer at the router whenever both replicas are occupied."""
    ref = _reference(engine_parts, "dense")
    cfg, params, dsg = engine_parts
    router = Router(cfg, params, dsg, n_replicas=2, policy="least_pages",
                    n_slots=2, max_seq=64, prompt_bucket=32,
                    cache_backend="paged", page_size=8, cache_tokens=48)
    for r in mixed_traffic(cfg):
        router.submit(r)
    while router._busy():
        before = len(router.dispatch_log)
        router.step()
        # every request dispatched this step was admitted this step —
        # the engine-internal deferral path never ran under least_pages
        for uid, rep in router.dispatch_log[before:]:
            assert router.replicas[rep].queue_depth() == 0, (
                f"request {uid} sat deferred in replica {rep}'s queue")
        assert router.steps < 1000
    out = {u: list(r.output) for u, r in router.done().items()}
    assert_streams_equal(ref, out, "least_pages tiny pools")
    # with single-reservation pools, deferral must actually have happened
    # at the router (6 requests, 2 one-lane-at-a-time replicas)
    assert router.steps > len(router.replicas)


# ---------------------------------------------------------------------------
# decode_chunk axis (harness.CHUNK_AXIS)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", CHUNK_AXIS)
def test_routed_streams_invariant_to_decode_chunk(engine_parts, chunk):
    """The fused decode chunk is a pure batching change: a 2-replica
    router running chunked engines merges the same greedy streams as
    the unchunked bare-engine reference."""
    cfg = engine_parts[0]
    ref = run_and_collect(engine_spec(*engine_parts), mixed_traffic(cfg))
    out = run_and_collect(
        engine_spec(*engine_parts, decode_chunk=chunk, n_replicas=2,
                    policy="round_robin"),
        mixed_traffic(cfg), max_steps=1000)
    assert_streams_equal(ref, out, f"router decode_chunk={chunk}")
