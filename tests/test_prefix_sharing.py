"""Copy-on-write shared-prefix paging (PR 10).

Layers under test, bottom up:

  * kv_cache.prefix_chain — chained content hashes over page-sized
    blocks of the padded prompt row (the sharing index keys).
  * BlockAllocator — refcounted page lifecycle: alloc/share/free,
    live-only prefix index (entries drop the instant their page's
    refcount reaches zero), double-free and freed-page registration
    rejected.
  * PagedBackend sharing surface — shared_hits leading-run semantics,
    admission arithmetic (sharing_adjustment / can_admit), write-time
    page mapping, and the COW triggers in ensure / ensure_range.
  * ServingEngine / Router differentials (tests/harness.py): sharing
    on vs off produces bitwise-identical greedy streams across
    {dense-reference, paged} x {1, 2} replicas x decode_chunk {1, 8},
    including retire/readmit reuse and a chaos-kill failover.

Sharing only helps when padded prompt rows coincide, so traffic here
uses fixed prompt lengths (see harness.shared_prefix_traffic); the
COW cases use identical prompts in a non-multiple-of-page bucket so a
partial tail page is shared and the first decode write must copy.
"""
import numpy as np
import pytest

from harness import (CHUNK_AXIS, assert_streams_equal, engine_spec,
                     make_engine_parts, run_and_collect,
                     shared_prefix_traffic)
from repro.runtime.fault_tolerance import ReplicaFault
from repro.serving import kv_cache
from repro.serving.kv_cache import (BlockAllocator, OutOfPages,
                                    prefix_chain)

PAGE = 16


@pytest.fixture(scope="module")
def parts():
    return make_engine_parts()     # threshold_mode="topk": lanes independent


def _paged_kw(**extra):
    kw = dict(cache_backend="paged", page_size=PAGE, cache_tokens=256)
    kw.update(extra)
    return kw


# ---------------------------------------------------------------------------
# prefix_chain: content-hash keys
# ---------------------------------------------------------------------------

def test_prefix_chain_is_chained_and_deterministic():
    row = np.arange(48, dtype=np.int32)
    chain = prefix_chain(row, PAGE)
    assert len(chain) == 3 and all(isinstance(k, bytes) for k in chain)
    assert chain == prefix_chain(row.copy(), PAGE)
    # a ragged tail gets its own (shorter-block) key
    assert len(prefix_chain(np.arange(40, dtype=np.int32), PAGE)) == 3
    # chaining: equal blocks at depth i only collide when ALL earlier
    # blocks also match
    other = row.copy()
    other[0] = 999
    diverged = prefix_chain(other, PAGE)
    assert diverged[0] != chain[0]
    assert diverged[1] != chain[1]          # same block 1, different prefix
    assert diverged[2] != chain[2]


def test_prefix_chain_validates_input():
    with pytest.raises(ValueError):
        prefix_chain(np.zeros((2, 4), np.int32), PAGE)
    # non-int32 rows are canonicalised, not rejected: the key hashes
    # int32 bytes regardless of the caller's dtype
    assert (prefix_chain(np.arange(8), PAGE)
            == prefix_chain(np.arange(8, dtype=np.int32), PAGE))


# ---------------------------------------------------------------------------
# BlockAllocator: refcounts + live-only index
# ---------------------------------------------------------------------------

def test_allocator_share_free_lifecycle():
    a = BlockAllocator(8, reserved=1)
    p, q = a.alloc(2)
    assert a.refcount(p) == 1 and a.live_pages == 2
    assert a.share(p) == 2
    free_before = a.free_pages
    a.free([p])                              # rc 2 -> 1: stays live
    assert a.refcount(p) == 1 and a.free_pages == free_before
    a.free([p, q])                           # both hit zero
    assert a.live_pages == 0 and a.free_pages == free_before + 2
    with pytest.raises(ValueError):
        a.free([p])                          # double free
    with pytest.raises(ValueError):
        a.share(p)                           # share of a freed page


def test_allocator_index_is_live_only():
    a = BlockAllocator(4)
    (p,) = a.alloc(1)
    a.register(b"k0", p)
    assert a.lookup(b"k0") == p and a.index_size == 1
    a.register(b"k0", p)                     # idempotent re-register
    assert a.index_size == 1
    a.free([p])
    assert a.lookup(b"k0") is None and a.index_size == 0
    with pytest.raises(ValueError):
        a.register(b"k1", p)                 # freed page can't be indexed
    # a recycled id may be re-registered once it is live again
    pages = a.alloc(a.free_pages)
    assert p in pages
    a.register(b"k2", p)
    assert a.lookup(b"k2") == p


def test_allocator_exhaustion_and_peak():
    a = BlockAllocator(3)
    got = a.alloc(3)
    with pytest.raises(OutOfPages):
        a.alloc(1)
    # sharing never consumes free pages
    a.share(got[0])
    assert a.free_pages == 0 and a.peak_live == 3
    a.free(got + [got[0]])
    a.reset_peak()
    assert a.peak_live == a.live_pages == 0


# ---------------------------------------------------------------------------
# PagedBackend: shared_hits / admission arithmetic / write contracts
# ---------------------------------------------------------------------------

def _mini_backend(**kw):
    from harness import smoke_cfg
    be = kv_cache.get_backend("paged", page_size=4, total_tokens=64,
                              prefix_sharing=True, **kw)
    handle = be.make(smoke_cfg(), n_slots=2, max_seq=16)
    return be, handle


def test_shared_hits_is_a_leading_run():
    be, _ = _mini_backend()
    row = np.arange(12, dtype=np.int32)
    chain = prefix_chain(row, 4)
    assert be.shared_hits(chain) == 0
    pages = be.allocator.alloc(2)
    be.allocator.register(chain[0], pages[0])
    be.allocator.register(chain[2], pages[1])   # hole at depth 1
    assert be.shared_hits(chain) == 1           # stops at the first miss
    assert be.shared_hits(None) == 0


def test_can_admit_accounts_for_sharing():
    be, _ = _mini_backend()
    row = np.arange(8, dtype=np.int32)
    chain = prefix_chain(row, 4)
    base_free = be.allocator.free_pages
    # no sharing context: worst case, pages_for(12) = 3
    assert be.can_admit(12)
    # full-page prefix resident -> one fewer page needed
    pages = be.allocator.alloc(2)
    for k, p in zip(chain, pages):
        be.allocator.register(k, p)
    adj = be.sharing_adjustment(chain, prompt_tokens=8)
    assert adj == -2                            # two full pages resident
    # a ragged prompt charges a +1 COW reserve; with its leading full
    # block resident the hits discount nets the two out
    ragged = prefix_chain(np.arange(6, dtype=np.int32), 4)
    assert be.sharing_adjustment(ragged, prompt_tokens=6) == 0  # +1 -1
    fresh = prefix_chain(np.arange(100, 106, dtype=np.int32), 4)
    assert be.sharing_adjustment(fresh, prompt_tokens=6) == 1   # +1 -0
    be.allocator.free(pages)                    # rc back to zero
    assert be.allocator.free_pages == base_free


def test_write_slot_kv_none_requires_full_coverage():
    be, handle = _mini_backend()
    row = np.arange(8, dtype=np.int32)
    chain = prefix_chain(row, 4)
    with pytest.raises(ValueError, match="slot_kv"):
        be.write(handle, None, 0, n_tokens=8, reserve_tokens=12,
                 chain=chain)


# ---------------------------------------------------------------------------
# engine differentials: sharing on == sharing off == dense reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", CHUNK_AXIS)
@pytest.mark.parametrize("n_replicas", [None, 2])
def test_sharing_streams_bitwise_equal(parts, chunk, n_replicas):
    """Overlapping-prefix traffic: greedy streams with prefix sharing
    on are bitwise identical to both the paged sharing-off run and the
    dense reference, across replica counts and decode chunk sizes."""
    cfg = parts[0]
    rep = {} if n_replicas is None else {"n_replicas": n_replicas,
                                         "policy": "round_robin"}
    ref = run_and_collect(
        engine_spec(*parts, decode_chunk=chunk, **rep),
        shared_prefix_traffic(cfg), max_steps=2000)
    off = run_and_collect(
        engine_spec(*parts, decode_chunk=chunk, **_paged_kw(), **rep),
        shared_prefix_traffic(cfg), max_steps=2000)
    on, eng = run_and_collect(
        engine_spec(*parts, decode_chunk=chunk,
                    **_paged_kw(prefix_sharing=True), **rep),
        shared_prefix_traffic(cfg), max_steps=2000, return_engine=True)
    assert_streams_equal(ref, off, "dense vs paged")
    assert_streams_equal(ref, on, "dense vs paged+sharing")
    backends = ([e.backend for e in eng.engines]
                if n_replicas else [eng.backend])
    assert sum(b.shared_page_hits for b in backends) > 0
    for b in backends:                       # clean drain, empty index
        assert b.allocator.live_pages == 0
        assert b.allocator.index_size == 0
    if n_replicas:
        eng.close()


@pytest.mark.parametrize("chunk", CHUNK_AXIS)
def test_cow_partial_tail_streams_and_counters(parts, chunk):
    """Identical prompts in a 24-token bucket (page_size 16) share a
    partial tail page, so every lane's first decode write lands on a
    shared page and must copy.  Streams stay bitwise equal to the
    sharing-off run and every sharer COWs exactly once."""
    cfg = parts[0]
    n = 5
    reqs = lambda: shared_prefix_traffic(  # noqa: E731
        cfg, n=n, prompt_len=24, prefix_len=24, max_new=6)
    spec = dict(buckets=(24,), decode_chunk=chunk)
    off = run_and_collect(
        engine_spec(*parts, **_paged_kw(), **spec), reqs(),
        max_steps=2000)
    on, eng = run_and_collect(
        engine_spec(*parts, **_paged_kw(prefix_sharing=True), **spec),
        reqs(), max_steps=2000, return_engine=True)
    assert_streams_equal(off, on, f"chunk={chunk}")
    # sharers replay the cached prefill while the registrant's pages
    # are resident, and every holder of the shared tail page COWs it on
    # first decode write — at most once per residency
    assert eng.prefill_cache_hits >= 1
    assert 1 <= eng.backend.cow_copies <= n
    assert eng.backend.shared_page_hits >= eng.prefill_cache_hits
    assert eng.backend.allocator.live_pages == 0


def test_sharing_reduces_peak_pages(parts):
    """The point of the tentpole: resident pages shrink when prompts
    overlap.  Identical 24-token prompts keep only one shared prompt
    copy, so sharing must beat the unshared peak."""
    cfg = parts[0]
    reqs = lambda: shared_prefix_traffic(  # noqa: E731
        cfg, n=6, prompt_len=24, prefix_len=24, max_new=4)
    spec = dict(buckets=(24,), n_slots=3)
    _, off = run_and_collect(
        engine_spec(*parts, **_paged_kw(), **spec), reqs(),
        max_steps=2000, return_engine=True)
    _, on = run_and_collect(
        engine_spec(*parts, **_paged_kw(prefix_sharing=True), **spec),
        reqs(), max_steps=2000, return_engine=True)
    assert on.backend.allocator.peak_live < off.backend.allocator.peak_live


def test_retire_readmit_reuses_and_reclaims(parts):
    """Two waves of identical traffic through one engine: wave 2
    re-registers the (fully reclaimed) pages, shares within the wave,
    and reproduces wave 1's streams bitwise."""
    from repro.serving.scheduler import ServingEngine
    cfg, params, dsg = parts
    eng = ServingEngine(cfg, params, dsg, n_slots=2, max_seq=64,
                        buckets=(24,), cache_backend="paged", page_size=PAGE,
                        cache_tokens=256, prefix_sharing=True)
    wave1 = shared_prefix_traffic(cfg, n=4, prompt_len=24, prefix_len=24,
                                  max_new=6)
    for r in wave1:
        eng.submit(r)
    done1 = dict(eng.run(max_steps=2000))
    assert eng.backend.allocator.live_pages == 0      # full reclaim
    assert eng.backend.allocator.index_size == 0      # index died with rc=0
    hits1 = eng.backend.shared_page_hits
    wave2 = [type(r)(uid=r.uid + 100, prompt=r.prompt.copy(),
                     max_new=r.max_new) for r in wave1]
    for r in wave2:
        eng.submit(r)
    done2 = eng.run(max_steps=2000)
    assert eng.backend.shared_page_hits > hits1       # re-shared after reuse
    for r in wave1:
        assert list(done1[r.uid].output) == list(done2[r.uid + 100].output)
    assert eng.backend.allocator.live_pages == 0


def test_chaos_kill_failover_with_sharing(parts):
    """Replica 1 killed mid-decode with sharing enabled: the dead
    replica's reset decrements (never double-frees) its shared pages,
    and survivors replay the victims to bitwise-equal streams."""
    from repro.serving.router import FaultToleranceConfig
    cfg = parts[0]
    ref = run_and_collect(engine_spec(*parts),
                          shared_prefix_traffic(cfg), max_steps=2000)
    rep = dict(n_replicas=3, policy="round_robin",
               fault_tolerance=FaultToleranceConfig(
                   max_replica_restarts=0, max_retries=3))
    streams, router = run_and_collect(
        engine_spec(*parts, **_paged_kw(prefix_sharing=True), **rep),
        shared_prefix_traffic(cfg), max_steps=8000, return_engine=True,
        faults=[ReplicaFault(replica=1, step=3)])
    try:
        assert router.health[1].state == "dead"
        assert_streams_equal(ref, streams, "chaos+sharing")
        for e in router.engines:             # incl. the dead replica
            assert e.backend.allocator.live_pages == 0
    finally:
        router.close()
