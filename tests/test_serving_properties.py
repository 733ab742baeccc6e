"""Hypothesis property tests for the serving substrate: the paged
backend's BlockAllocator (random alloc/free interleavings never
double-assign a physical page and conserve the free-list count) and the
scheduler's prompt buckets, bucket_sizes (sorted, unique, capped).
These are host-side pure functions — no jit, no device — so hundreds of
examples run in milliseconds."""
import pytest

pytest.importorskip("hypothesis")  # dev-only dep (requirements-dev.txt); skip, don't error
from hypothesis import given, settings, strategies as st

from repro.serving.kv_cache import BlockAllocator, OutOfPages
from repro.serving.scheduler import DEFAULT_BUCKETS, bucket_sizes


# ---------------------------------------------------------------------------
# BlockAllocator
# ---------------------------------------------------------------------------

@settings(max_examples=200)
@given(st.integers(1, 24), st.integers(0, 3),
       st.lists(st.tuples(st.booleans(), st.integers(0, 10)), max_size=50))
def test_allocator_never_double_assigns_and_conserves(alloc_pages,
                                                      reserved, ops):
    """Any interleaving of allocs and frees: handed-out pages are unique,
    never below `reserved`, disjoint from everything currently live, and
    free_pages + live == allocatable at every step.  Requests beyond
    capacity raise OutOfPages and leave the state untouched."""
    n_pages = reserved + alloc_pages
    a = BlockAllocator(n_pages, reserved=reserved)
    live = []                                 # pages we hold, in FIFO order
    for is_alloc, k in ops:
        if is_alloc:
            if k > a.free_pages:
                before = (a.free_pages, sorted(live))
                with pytest.raises(OutOfPages):
                    a.alloc(k)
                assert (a.free_pages, sorted(live)) == before
            else:
                got = a.alloc(k)
                assert len(got) == len(set(got)) == k
                assert all(reserved <= p < n_pages for p in got)
                assert not set(got) & set(live)   # never double-assigned
                live.extend(got)
        elif live:
            take = live[:min(k, len(live))]
            del live[:len(take)]
            if take:
                a.free(take)
        # conservation: every allocatable page is free or held, never both
        assert a.free_pages + len(live) == alloc_pages
        assert len(set(live)) == len(live)


@settings(max_examples=100)
@given(st.integers(1, 16), st.integers(1, 8))
def test_allocator_rejects_double_free_and_foreign(alloc_pages, k):
    a = BlockAllocator(alloc_pages + 1, reserved=1)
    got = a.alloc(min(k, alloc_pages))
    a.free(got)
    with pytest.raises(ValueError):           # double free
        a.free(got[:1])
    with pytest.raises(ValueError):           # reserved id never allocated
        a.free([0])
    assert a.free_pages == alloc_pages


# ---------------------------------------------------------------------------
# BlockAllocator: share / register / free interleavings (prefix sharing)
# ---------------------------------------------------------------------------

# ops: 0=alloc+register, 1=share a random indexed page, 2=free one of our
# refs, 3=free ALL refs on a random held page (retire-style release)
_SHARE_OPS = st.lists(st.tuples(st.integers(0, 3), st.integers(0, 9)),
                      max_size=60)


@settings(max_examples=200)
@given(st.integers(2, 16), _SHARE_OPS)
def test_share_cow_free_interleavings_conserve(alloc_pages, ops):
    """Random share/free interleavings over an indexed allocator: a page
    is free-listed exactly when its refcount hits zero (never while a
    holder remains), total pages are conserved (free + live ==
    allocatable), the index never points at a freed page, and releasing
    a ref twice past zero raises instead of double-freeing."""
    a = BlockAllocator(alloc_pages + 1, reserved=1)
    refs = {}                                 # page -> refs WE hold
    key_of = {}                               # page -> registered key
    n_keys = 0
    for op, pick in ops:
        if op == 0 and a.free_pages:
            (p,) = a.alloc(1)
            assert p not in refs              # free list never lies
            refs[p] = 1
            k = b"key%d" % n_keys
            n_keys += 1
            a.register(k, p)
            key_of[p] = k
        elif op == 1 and refs:
            p = sorted(refs)[pick % len(refs)]
            rc = a.share(p)
            refs[p] += 1
            assert rc == refs[p] == a.refcount(p)
        elif op == 2 and refs:
            p = sorted(refs)[pick % len(refs)]
            refs[p] -= 1
            a.free([p])
            if refs[p] == 0:
                del refs[p]
                assert a.refcount(p) == 0
                assert a.lookup(key_of.pop(p)) is None   # index died with it
                with pytest.raises(ValueError):          # release past zero
                    a.free([p])
                with pytest.raises(ValueError):          # can't share a corpse
                    a.share(p)
        elif op == 3 and refs:
            p = sorted(refs)[pick % len(refs)]
            a.free([p] * refs.pop(p))
            assert a.refcount(p) == 0
            assert a.lookup(key_of.pop(p)) is None
        # invariants, every step:
        assert a.free_pages + len(refs) == alloc_pages   # conservation
        assert a.live_pages == len(refs)
        for p, k in key_of.items():
            assert a.lookup(k) == p                      # index is live-only
        assert a.index_size == len(key_of)


@settings(max_examples=150)
@given(st.integers(1, 8), st.integers(1, 5))
def test_register_is_first_writer_wins_and_live_only(alloc_pages, extra):
    """register() refuses freed pages, keeps the first binding on key
    collision, and lookup of a never-registered key is None."""
    a = BlockAllocator(alloc_pages + 1, reserved=1)
    pages = a.alloc(alloc_pages)
    a.register(b"k", pages[0])
    for p in pages[:extra]:
        a.register(b"k", p)                   # later bindings ignored
    assert a.lookup(b"k") == pages[0]
    assert a.lookup(b"nope") is None
    a.free(pages)
    with pytest.raises(ValueError):
        a.register(b"k2", pages[0])
    assert a.index_size == 0


# ---------------------------------------------------------------------------
# bucket_sizes
# ---------------------------------------------------------------------------

@settings(max_examples=200)
@given(st.integers(1, 512), st.integers(2, 512))
def test_bucket_sizes_capped_sorted_covering(prompt_bucket, max_seq):
    """Prompt buckets are sorted, unique, never exceed the admission cap
    min(prompt_bucket, max_seq - 1) (a full-cache prompt would leave no
    decode headroom), and the largest bucket IS the cap whenever the cap
    is within the default series — so every admissible prompt has a
    bucket that holds it."""
    cap = min(prompt_bucket, max_seq - 1)
    bs = bucket_sizes(prompt_bucket, max_seq)
    assert bs == tuple(sorted(set(bs)))
    assert all(1 <= b <= cap for b in bs)
    assert bs[-1] == min(cap, max(DEFAULT_BUCKETS))
    # monotone in the cap: shrinking prompt_bucket never widens a bucket
    smaller = bucket_sizes(max(prompt_bucket // 2, 1), max_seq)
    assert smaller[-1] <= bs[-1]
