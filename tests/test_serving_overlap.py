"""Overlap-admission isolation: splicing a new prompt into a free slot must
leave resident slots' K/V bytes and outputs bit-identical to a solo run,
and the paged cache backend must reproduce the dense engine exactly over
admit -> decode -> retire -> readmit sequences.

Uses threshold_mode="topk" (per-row DRS selection) so lanes are
computationally independent — the smoke default "shared" mode implements
the paper's Appendix B inter-sample threshold sharing, which deliberately
couples every lane to lane 0's scores; that coupling is a property of the
selection rule, not of the engine's cache surgery, so it is pinned off
here."""
import numpy as np
import pytest

from harness import (assert_streams_equal, engine_spec, make_engine_parts,
                     mixed_traffic, run_and_collect)
from repro.serving.scheduler import Request, ServingEngine


@pytest.fixture(scope="module")
def engine_parts():
    return make_engine_parts()


def _make_engine(cfg, params, dsg):
    return ServingEngine(cfg, params, dsg, n_slots=2, max_seq=64,
                         prompt_bucket=32)


def _solo_output(cfg, params, dsg, req_proto):
    eng = _make_engine(cfg, params, dsg)
    eng.submit(Request(uid=0, prompt=req_proto.prompt,
                       max_new=req_proto.max_new))
    return eng.run(max_steps=200)[0].output


def test_admission_leaves_resident_slot_untouched(engine_parts):
    cfg, params, dsg = engine_parts
    rng = np.random.default_rng(7)
    req_a = Request(uid=0, prompt=rng.integers(0, cfg.vocab, 12,
                                               dtype=np.int32), max_new=10)
    req_b = Request(uid=1, prompt=rng.integers(0, cfg.vocab, 20,
                                               dtype=np.int32), max_new=8)
    solo_a = _solo_output(cfg, params, dsg, req_a)
    solo_b = _solo_output(cfg, params, dsg, req_b)
    assert len(solo_a) == 10 and len(solo_b) == 8

    # mixed run: A decodes alone for 3 steps, then B is admitted into the
    # free slot while A keeps going
    eng = _make_engine(cfg, params, dsg)
    eng.submit(Request(uid=0, prompt=req_a.prompt, max_new=10))
    for _ in range(3):
        eng.step()
    assert len(eng.slots[0].req.output) == 3 and eng.slots[1].free

    lane0_before = {k: np.array(v[:, 0]) for k, v in eng.cache.data.items()}
    eng.submit(Request(uid=1, prompt=req_b.prompt, max_new=8))
    eng._admit()                      # splice B into slot 1, nothing else
    assert not eng.slots[1].free
    # admission performed cache surgery on lane 1 only: lane 0's K/V bytes
    # are bit-identical, lane 1's actually changed
    for k, v in eng.cache.data.items():
        np.testing.assert_array_equal(lane0_before[k], np.array(v[:, 0]))
    assert any(not np.array_equal(np.zeros_like(np.array(v[:, 1])),
                                  np.array(v[:, 1]))
               for v in eng.cache.data.values())

    done = eng.run(max_steps=200)
    # both sequences are bit-identical to their solo runs: admission never
    # perturbed the resident lane, and the per-lane position/RoPE state of
    # the admitted lane is honest despite entering mid-decode
    assert done[0].output == solo_a
    assert done[1].output == solo_b


def test_staggered_stream_matches_solo_runs(engine_parts):
    """Continuous traffic: 6 requests of assorted lengths trickle through 2
    slots; every request's greedy output must equal its solo-run output."""
    cfg, params, dsg = engine_parts
    rng = np.random.default_rng(11)
    reqs = [Request(uid=u, prompt=rng.integers(0, cfg.vocab,
                                               int(rng.integers(4, 30)),
                                               dtype=np.int32),
                    max_new=int(rng.integers(3, 9))) for u in range(6)]
    solo = {r.uid: _solo_output(cfg, params, dsg, r) for r in reqs}

    eng = _make_engine(cfg, params, dsg)
    it = iter(reqs)
    eng.submit(next(it))
    pending = True
    while pending or any(not s.free for s in eng.slots) or eng.queue:
        # drip-feed: submit the next request every other step so admissions
        # land mid-decode, not in a fresh batch
        if pending and eng.steps % 2 == 0:
            nxt = next(it, None)
            if nxt is None:
                pending = False
            else:
                eng.submit(nxt)
        eng.step()
        assert eng.steps < 500
    for r in reqs:
        assert eng.done[r.uid].output == solo[r.uid], r.uid


# ---------------------------------------------------------------------------
# paged backend equivalence (admit -> decode -> retire -> readmit)
# ---------------------------------------------------------------------------

def test_paged_stream_matches_dense_bitwise(engine_parts):
    """6 requests through 2 slots: every lane is retired and readmitted,
    pages are allocated, freed, and reused — and every request's output is
    bit-identical to the dense engine's (same attention shapes, same
    values at positions < pos, everything else masked)."""
    spec = engine_spec(*engine_parts)
    dense_out = run_and_collect(spec, mixed_traffic(spec["cfg"]))
    # worst-case lane reservation: min(bucket 32 + max_new 8, 64) = 40
    # tokens = 5 pages; 2 lanes -> 80-token pool (vs dense 2 * 64 = 128)
    paged_out, paged_eng = run_and_collect(
        engine_spec(*engine_parts, cache_backend="paged", page_size=8,
                    cache_tokens=80),
        mixed_traffic(spec["cfg"]), return_engine=True)
    assert_streams_equal(dense_out, paged_out, "paged vs dense")
    # every page returned to the free list after the stream drains
    alloc = paged_eng.backend.allocator
    assert alloc.free_pages == alloc.n_pages - alloc.reserved


def test_paged_resident_bytes_smaller(engine_parts):
    cfg = engine_parts[0]
    _, dense_eng = run_and_collect(engine_spec(*engine_parts),
                                   mixed_traffic(cfg, n=2),
                                   return_engine=True)
    _, paged_eng = run_and_collect(
        engine_spec(*engine_parts, cache_backend="paged", page_size=8,
                    cache_tokens=80),
        mixed_traffic(cfg, n=2), return_engine=True)
    dense_b = dense_eng.backend.resident_bytes(dense_eng.cache)
    paged_b = paged_eng.backend.resident_bytes(paged_eng.cache)
    assert paged_b < dense_b


def test_paged_matches_dense_under_sampling(engine_parts):
    """Sampling goes through identical logits on both backends, and the
    PRNG key schedule depends only on (engine seed, step, lane) — so
    sampled streams must agree token-for-token too."""
    cfg = engine_parts[0]
    kw = dict(temperature=0.8, top_p=0.9)
    dense_out = run_and_collect(engine_spec(*engine_parts, seed=7),
                                mixed_traffic(cfg, n=4, **kw))
    paged_out = run_and_collect(
        engine_spec(*engine_parts, seed=7, cache_backend="paged",
                    page_size=8, cache_tokens=80),
        mixed_traffic(cfg, n=4, **kw))
    assert_streams_equal(dense_out, paged_out, "sampled paged vs dense")


def test_paged_pool_for_one_lane_defers_admission(engine_parts):
    """A pool that can only hold one request's reservation serialises
    admissions instead of corrupting or crashing: both requests finish
    with their solo outputs."""
    cfg, params, dsg = engine_parts
    reqs = mixed_traffic(cfg, n=2)
    solo = {r.uid: _solo_output(cfg, params, dsg, r) for r in reqs}
    # one lane's reservation is 5 pages of 8; 6 pages can't fit two lanes
    out, eng = run_and_collect(
        engine_spec(*engine_parts, cache_backend="paged", page_size=8,
                    cache_tokens=48),
        mixed_traffic(cfg, n=2), return_engine=True)
    assert_streams_equal(solo, out, "deferred admissions vs solo")
    assert eng.steps > 0
