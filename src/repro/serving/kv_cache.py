"""Pluggable KV-cache backends behind a unified `CacheHandle`.

The serving engine used to hard-wire the dense worst-case cache layout
(L, n_slots, Smax, Kv, D) into api.py / attention.py / scheduler.py.  This
module makes the layout a backend choice:

    backend = get_backend("paged", page_size=16, total_tokens=512)
    handle  = backend.make(cfg, n_slots, max_seq)       # opaque CacheHandle
    handle  = backend.write(handle, lane_kv, slot,      # admission splice
                            n_tokens=pb, reserve_tokens=need)
    handle  = backend.ensure(handle, slot, pos)         # growth while decoding
    handle  = backend.free(handle, slot)                # retirement
    data    = backend.view_for_attention(handle)        # pytree for forward()

`CacheHandle` is a registered pytree, so the engine's jitted steps take and
return it directly (buffer donation included); `kind` and `page_size` ride
in the static treedef.

`DenseBackend` keeps today's layout and is the equivalence baseline.
`PagedBackend` stores K/V in fixed-size pages of `page_size` tokens:

    pages_k / pages_v : (L, n_pages, page_size, Kv, D)   physical pool
    page_table        : (n_slots, max_seq // page_size)  int32 logical->physical

A host-side free-list `BlockAllocator` hands out physical pages; lanes
allocate pages as `pos` grows and return them on retirement, so short
requests stop paying worst-case `Smax` memory — the DSG move (exploit
runtime-dynamic sparsity in the data layout instead of a dense worst-case
structure) applied to the serving memory plane.  Physical page 0 is a
reserved scratch page: unallocated page-table entries point at it, so
gathers beyond a lane's depth read defined (masked-out) memory.  Free
lanes never address it during decode — the engine mirrors the donor
lane's page-table row for them, which keeps shared-threshold DRS
deterministic (see decode_view below).

Out-of-pages policy: admission reserves the pages a request could ever
need (`reserve_tokens`, normally `min(prompt_bucket + max_new, max_seq)`)
and `can_admit` gates on free-minus-reserved, so `ensure` growth never
fails mid-decode; a pool smaller than one request's reservation surfaces
as a deferred admission, not silent corruption.

Copy-on-write prefix sharing (PagedBackend(prefix_sharing=True), see
docs/cache_backends.md): the allocator refcounts pages and keeps a
prefix-hash index over prompt token blocks (`prefix_chain`), so an
admission whose padded prompt matches an already-resident prefix maps
the existing pages (refcount bump, no scatter, no fresh allocation)
instead of recomputing them.  The only shared page a decode write can
ever land in is a partial prompt-tail page (growth pages allocated by
`ensure` are never indexed); writing it while its refcount is > 1
triggers copy-on-write into a private page, paid for by one extra
reserved page per admission with a partial tail — so `ensure` stays
infallible.  `free` is release semantics: decrement, return the page to
the free list only at refcount zero, and drop its index entries there —
the index only ever points at live pages.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api, transformer
from repro.serving.telemetry import Telemetry

NULL_PAGE = 0          # reserved scratch page; never handed out

BACKENDS = ("dense", "paged")


class OutOfPages(RuntimeError):
    """The block allocator has fewer free pages than requested."""


@jax.tree_util.register_pytree_node_class
@dataclass
class CacheHandle:
    """Opaque KV-cache pytree + static layout tag.

    `data` holds the device arrays (dense: {'k','v'}; paged:
    {'pages_k','pages_v','page_table'}); `kind`/`page_size` are static
    aux data, so jitted functions can rebuild the handle around updated
    leaves without retracing on layout.
    """
    data: dict
    kind: str = "dense"
    page_size: int = 0

    def tree_flatten(self):
        return (self.data,), (self.kind, self.page_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


# ---------------------------------------------------------------------------
# block allocator (host-side)
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Refcounting free-list allocator over physical page ids
    [reserved, n_pages).

    Page ids below `reserved` are never handed out (id 0 is the paged
    backend's scratch page).  O(1) alloc/free; over-allocation raises
    `OutOfPages`, double-free and foreign ids raise `ValueError`.

    Sharing surface (copy-on-write prefix reuse): `alloc` hands pages
    out at refcount 1, `share` bumps an already-live page, and `free`
    has RELEASE semantics — it decrements and only returns a page to
    the free list at refcount zero, so a fault-path reclaim of a lane
    holding shared pages decrements, never frees, pages other lanes
    still read.  `register`/`lookup` maintain the prefix-hash index
    (content key -> live page); entries drop automatically when their
    page's refcount hits zero, so the index never points at a freed
    page.  `peak_live` is the high-water mark of distinct live pages —
    the resident-page number bench_prefix_sharing.py gates on.
    """

    def __init__(self, n_pages: int, reserved: int = 0):
        if n_pages <= reserved:
            raise ValueError("allocator needs at least one allocatable page")
        self.n_pages = n_pages
        self.reserved = reserved
        self._free = list(range(n_pages - 1, reserved - 1, -1))
        self._rc: dict = {}                # live page -> refcount (>= 1)
        self._index: dict = {}             # prefix key -> live page
        self._page_keys: dict = {}         # live page -> [registered keys]
        self.peak_live = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        """Distinct pages currently allocated (refcounts ignored)."""
        return len(self._rc)

    def reset_peak(self) -> None:
        """Restart the live-page high-water mark at the current
        occupancy (benchmarks call this after warmup)."""
        self.peak_live = len(self._rc)

    def refcount(self, page: int) -> int:
        """Current refcount (0 for pages not live)."""
        return self._rc.get(page, 0)

    def alloc(self, n: int) -> list:
        if n > len(self._free):
            raise OutOfPages(
                f"requested {n} pages, only {len(self._free)} free of "
                f"{self.n_pages - self.reserved}")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._rc[p] = 1
        self.peak_live = max(self.peak_live, len(self._rc))
        return out

    def share(self, page: int) -> int:
        """Add a reference to a live page (a lane mapping an existing
        shared-prefix page); returns the new refcount."""
        if page not in self._rc:
            raise ValueError(f"page {page} is not currently allocated")
        self._rc[page] += 1
        return self._rc[page]

    def free(self, pages) -> None:
        """Release one reference per page: the page returns to the free
        list (and its index entries drop) only when no other holder
        remains."""
        for p in pages:
            if p not in self._rc:
                raise ValueError(f"page {p} is not currently allocated")
            self._rc[p] -= 1
            if self._rc[p]:
                continue
            del self._rc[p]
            for key in self._page_keys.pop(p, ()):
                if self._index.get(key) == p:
                    del self._index[key]
            self._free.append(p)

    # -- prefix-hash index ---------------------------------------------------

    def register(self, key: bytes, page: int) -> None:
        """Publish a live page under a prefix content key so later
        admissions with the same prompt blocks can `share` it.  First
        writer wins: an already-registered key keeps its page (both hold
        identical content; two entries would just split future sharers)."""
        if page not in self._rc:
            raise ValueError(
                f"cannot register freed page {page} in the prefix index")
        if key in self._index:
            return
        self._index[key] = page
        self._page_keys.setdefault(page, []).append(key)

    def lookup(self, key: bytes) -> Optional[int]:
        """The live page registered under `key`, or None.  Entries are
        dropped at free time, so a hit is always safe to `share`."""
        return self._index.get(key)

    @property
    def index_size(self) -> int:
        return len(self._index)


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------

def decode_view(handle: CacheHandle, free_mask=None, donor=None) -> dict:
    """The per-step attention view of a handle (jit-friendly; the serving
    engine calls this inside its jitted decode step).

    No logical (B, Smax, ...) window is ever materialized: the paged view
    is the physical pools + page table exactly as stored, and the
    per-lane depths ride separately as the decode `pos` vector — the
    attention executor (Pallas kernel or bounded XLA gather) walks only
    the pages at or below each lane's depth.

    free_mask/donor: a free paged lane's table row is all NULL — left
    alone it would gather scratch-page junk (nondeterministic row-0
    scores under shared-threshold DRS, since mirrored lanes also scatter
    to one scratch slot and the duplicate-index winner is unspecified).
    Mirroring the donor's page-table row instead makes free lanes exact
    clones of the donor: they read the donor's K/V and re-write the
    donor's own values to the donor's pages (identical duplicates are
    order-independent), so paged decode is deterministic in every
    threshold mode.
    """
    if handle.kind != "paged" or free_mask is None:
        return handle.data
    pt = handle.data["page_table"]
    pt = jnp.where(free_mask[:, None], pt[donor], pt)
    return {**handle.data, "page_table": pt}


class _Backend:
    """Shared backend plumbing: the handle's `data` is always the exact
    pytree `transformer.forward` consumes, and resident bytes are just the
    bytes the handle keeps alive.  `write` and `free` record the spans
    `repro.kv.write` and `repro.kv.free` in `telemetry`, which the serving
    engine replaces with its own recorder."""

    def __init__(self):
        self.telemetry = Telemetry()

    def view_for_attention(self, handle: CacheHandle, free_mask=None,
                           donor=None) -> dict:
        return decode_view(handle, free_mask, donor)

    def resident_bytes(self, handle: CacheHandle) -> int:
        return sum(leaf.nbytes for leaf in jax.tree.leaves(handle.data))

    def ensure_range(self, handle: CacheHandle, slot: int, start: int,
                     stop: int) -> CacheHandle:
        """Grow lane `slot` to cover writes at every position in
        [start, stop) — the fused decode chunk's pre-reservation, where
        `ensure` moves ahead of the device loop because the scanned
        micro-steps cannot grow the page table mid-dispatch.  The caller
        clamps `stop` to the lane's emit budget so the mapping stays
        inside its admission-time page reservation."""
        for pos in range(start, stop):
            handle = self.ensure(handle, slot, pos)
        return handle


def dense_merge(cache: dict, lane_cache: dict, slot) -> dict:
    """Scatter a 1-lane dense cache into lane `slot` of the batched cache.

    Writes the FULL sequence extent of the lane (not just the prompt), so
    stale K/V left behind by a retired request can never leak into the new
    occupant's attention window.  `slot` may be a traced scalar (the
    function is jit-friendly; backends jit it once).
    """
    def upd(c, lane):
        start = (0, slot) + (0,) * (c.ndim - 2)
        return jax.lax.dynamic_update_slice(c, lane.astype(c.dtype), start)
    return jax.tree.map(upd, cache, lane_cache)


class DenseBackend(_Backend):
    """Worst-case dense layout: every cache leaf is (L, n_slots, Smax, ...).

    Admission is a lane-to-lane scatter; `free`/`ensure` are no-ops (each
    lane permanently owns its Smax stripe).
    """

    kind = "dense"
    page_size = 0

    def __init__(self):
        super().__init__()
        self._merge = jax.jit(dense_merge, donate_argnums=(0,))

    def make(self, cfg, n_slots: int, max_seq: int, dtype=None) -> CacheHandle:
        return CacheHandle(api.make_cache(cfg, n_slots, max_seq, dtype),
                           "dense", 0)

    def write(self, handle: CacheHandle, slot_kv: dict, slot,
              n_tokens: Optional[int] = None,
              reserve_tokens: Optional[int] = None,
              chain=None) -> CacheHandle:
        with self.telemetry.span("repro.kv.write"):
            return CacheHandle(self._merge(handle.data, slot_kv, slot),
                               "dense", 0)

    def ensure(self, handle: CacheHandle, slot: int, pos: int) -> CacheHandle:
        return handle

    def free(self, handle: CacheHandle, slot: int) -> CacheHandle:
        with self.telemetry.span("repro.kv.free"):
            return handle

    def can_admit(self, n_tokens: int, chain=None,
                  prompt_tokens: Optional[int] = None) -> bool:
        return True


# ---------------------------------------------------------------------------
# paged backend
# ---------------------------------------------------------------------------

def prefix_chain(tokens: np.ndarray, page_size: int) -> list:
    """Chained content keys for each page of a padded prompt row: key i
    commits to EVERY token in positions [0, min((i+1)*page_size, len)),
    so two prompts share key i iff their padded rows agree on the whole
    prefix through page i — exactly the condition under which page i's
    K/V bytes are identical (page content is a pure function of the
    tokens at and before it).  keyed blake2b, not python hash():
    PYTHONHASHSEED salting would break cross-process determinism.

    The engine hashes the BUCKETED row (left-padding included), so only
    prompts landing in the same bucket with identical padding can share
    — which is also the only case where their page bytes match.
    """
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
    if toks.ndim != 1:
        raise ValueError(f"prefix_chain wants a 1-D token row, "
                         f"got shape {toks.shape}")
    keys, prev = [], b""
    for start in range(0, len(toks), page_size):
        blk = toks[start:start + page_size].tobytes()
        prev = hashlib.blake2b(prev + blk, digest_size=16).digest()
        keys.append(prev)
    return keys


def _paged_merge(pools: dict, lane: dict, pp: jax.Array) -> dict:
    """Scatter the leading `len(pp)` pages of a 1-lane dense cache into the
    physical pages `pp` of the pool (one compile per page count, i.e. per
    prompt bucket).  Freshly allocated pages are fully overwritten, so a
    previous occupant's K/V cannot leak.
    """
    ps = pools["pages_k"].shape[2]
    n_lp = pp.shape[0]

    def upd(pool, lane_leaf):
        l, _, _, kv, d = lane_leaf.shape
        chunks = lane_leaf[:, 0, :n_lp * ps].reshape(l, n_lp, ps, kv, d)
        return pool.at[:, pp].set(chunks.astype(pool.dtype))

    return {"pages_k": upd(pools["pages_k"], lane["k"]),
            "pages_v": upd(pools["pages_v"], lane["v"])}


def _paged_merge_subset(pools: dict, lane: dict, pp: jax.Array,
                        lps: jax.Array, n_lp: int) -> dict:
    """_paged_merge for a shared-prefix admission: scatter only the
    logical pages `lps` (the NON-shared ones) of the lane's first `n_lp`
    pages into physical pages `pp` — shared pages already hold identical
    bytes and must not be rewritten (other lanes read them).  One
    compile per (n_lp, len(lps)) pair."""
    ps = pools["pages_k"].shape[2]

    def upd(pool, lane_leaf):
        l, _, _, kv, d = lane_leaf.shape
        chunks = lane_leaf[:, 0, :n_lp * ps].reshape(l, n_lp, ps, kv, d)
        return pool.at[:, pp].set(chunks[:, lps].astype(pool.dtype))

    return {"pages_k": upd(pools["pages_k"], lane["k"]),
            "pages_v": upd(pools["pages_v"], lane["v"])}


def _page_copy(pools: dict, src: jax.Array, dst: jax.Array) -> dict:
    """Copy one physical page across every layer (the copy half of
    copy-on-write).  src/dst ride as traced scalars — one compile
    total, not one per page id."""
    return {"pages_k": pools["pages_k"].at[:, dst]
            .set(pools["pages_k"][:, src]),
            "pages_v": pools["pages_v"].at[:, dst]
            .set(pools["pages_v"][:, src])}


class PagedBackend(_Backend):
    """Fixed-size pages + per-lane page table + host free-list allocator.

    The pool holds `total_tokens` worth of pages (default: the dense
    worst case `n_slots * max_seq`; size it to expected peak concurrent
    demand to realise the memory saving).  One backend instance manages
    one live handle: the allocator and the host page-table mirror are the
    source of truth, and every mutation returns a handle with a fresh
    device copy of the (tiny) page table.
    """

    kind = "paged"

    def __init__(self, page_size: int = 16,
                 total_tokens: Optional[int] = None,
                 prefix_sharing: bool = False):
        if page_size <= 0:
            raise ValueError("page_size must be positive")
        super().__init__()
        self.page_size = page_size
        self.total_tokens = total_tokens
        self.prefix_sharing = bool(prefix_sharing)
        self.cow_copies = 0             # COW events (test/bench counter)
        self.shared_page_hits = 0       # pages mapped without a scatter
        self.allocator: Optional[BlockAllocator] = None
        self._table: Optional[np.ndarray] = None
        self._resv: Optional[np.ndarray] = None
        self._merge = jax.jit(_paged_merge, donate_argnums=(0,))
        self._merge_subset = jax.jit(_paged_merge_subset,
                                     donate_argnums=(0,),
                                     static_argnums=(4,))
        self._copy_page = jax.jit(_page_copy, donate_argnums=(0,))

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def _device_table(self) -> jax.Array:
        """Device copy of the host page table, made on the pools' device
        (a router replica's own device, not the process default)."""
        with jax.default_device(self._device):
            return jnp.asarray(self._table)

    def make(self, cfg, n_slots: int, max_seq: int, dtype=None) -> CacheHandle:
        if self._table is not None:
            raise RuntimeError("PagedBackend manages one live handle; "
                               "create a fresh backend per engine")
        if cfg.family not in api.DECODER_FAMILIES:
            raise NotImplementedError(
                f"paged KV cache supports decoder families only, "
                f"not {cfg.family!r}")
        if max_seq % self.page_size:
            raise ValueError(f"max_seq={max_seq} must be a multiple of "
                             f"page_size={self.page_size}")
        total = self.total_tokens or n_slots * max_seq
        n_pages = self.pages_for(total) + 1        # +1: scratch page 0
        dt = dtype or api._dtype(cfg)   # same default as the dense cache
        pool = transformer.init_paged_cache(cfg, n_pages, self.page_size, dt)
        self.allocator = BlockAllocator(n_pages, reserved=1)
        self.max_pages = max_seq // self.page_size
        self._table = np.full((n_slots, self.max_pages), NULL_PAGE, np.int32)
        self._resv = np.zeros(n_slots, np.int64)
        # the pools' device: every later page-table push lands beside them
        (self._device,) = pool["k"].devices()
        data = {"pages_k": pool["k"], "pages_v": pool["v"],
                "page_table": self._device_table()}
        return CacheHandle(data, "paged", self.page_size)

    def shared_hits(self, chain: Sequence[bytes]) -> int:
        """Leading run of chain keys with a live indexed page — the pages
        an admission with this prompt chain would map instead of
        allocating.  A chain key can only be resident when every earlier
        one is (all holders map a contiguous leading prefix), so the scan
        stops at the first miss."""
        if not self.prefix_sharing or chain is None:
            return 0
        hits = 0
        for key in chain:
            if self.allocator.lookup(key) is None:
                break
            hits += 1
        return hits

    def sharing_adjustment(self, chain,
                           prompt_tokens: Optional[int]) -> int:
        """Worst-case page-count adjustment for a sharing admission:
        MINUS the full prompt pages already resident (mapped, not
        allocated), PLUS one COW page when the prompt tail only part-
        fills its page — the one shared page a decode write can land in.
        The +1 is charged whether or not the tail is shared YET: the
        registrant's tail can be shared by a LATER admission, and the
        registrant then needs the COW page for its own next write."""
        if not self.prefix_sharing or prompt_tokens is None:
            return 0
        tail = 1 if prompt_tokens % self.page_size else 0
        full = prompt_tokens // self.page_size
        saved = min(self.shared_hits(chain), full) if chain else 0
        return tail - saved

    def can_admit(self, n_tokens: int, chain=None,
                  prompt_tokens: Optional[int] = None) -> bool:
        """True when free-minus-reserved pages cover a request reserving
        `n_tokens`; gating admissions on this makes `ensure` growth (and
        copy-on-write) infallible for already-admitted lanes.  With
        prefix sharing, `chain`/`prompt_tokens` credit the full prompt
        pages already resident and charge the partial-tail COW page —
        the same arithmetic `write` commits to."""
        need = self.pages_for(n_tokens) \
            + self.sharing_adjustment(chain, prompt_tokens)
        return (self.allocator.free_pages - int(self._resv.sum()) >= need)

    def write(self, handle: CacheHandle, slot_kv: Optional[dict], slot: int,
              n_tokens: Optional[int] = None,
              reserve_tokens: Optional[int] = None,
              chain: Optional[Sequence[bytes]] = None) -> CacheHandle:
        """Splice a prefilled 1-lane dense cache into lane `slot`: allocate
        pages covering the first `n_tokens` positions and scatter the
        lane's K/V into them; `reserve_tokens` (>= n_tokens) additionally
        reserves growth pages so later `ensure` calls cannot run out.

        With prefix sharing, `chain` (one prefix_chain key per prompt
        page) maps the leading already-resident run by refcount bump —
        no allocation, no scatter — and registers the freshly written
        pages for later admissions.  When EVERY prompt page is shared the
        caller may pass slot_kv=None (the zero-recompute path: no
        prefill output is needed at all)."""
        if n_tokens is None:
            raise ValueError("paged write needs n_tokens (the prompt extent)")
        self._release(slot)
        n_lp = self.pages_for(n_tokens)
        need = max(self.pages_for(reserve_tokens), n_lp) \
            if reserve_tokens else n_lp
        sharing = self.prefix_sharing and chain is not None
        hits = 0
        if sharing:
            if len(chain) != n_lp:
                raise ValueError(
                    f"chain must carry one key per prompt page "
                    f"({n_lp}), got {len(chain)}")
            hits = self.shared_hits(chain)
        fresh_lps = list(range(hits, n_lp))
        # alloc before share: an OutOfPages raise (admission mis-gated)
        # leaves no dangling refcounts
        pp = self.allocator.alloc(len(fresh_lps))
        for i in range(hits):
            pg = self.allocator.lookup(chain[i])
            self.allocator.share(pg)
            self._table[slot, i] = pg
        self.shared_page_hits += hits
        for lp, pg in zip(fresh_lps, pp):
            self._table[slot, lp] = pg
            if sharing:
                self.allocator.register(chain[lp], pg)
        # reservation: growth pages beyond the prompt extent, plus the
        # partial-tail COW page (see _extra_pages; consumed by _cow)
        tail = 1 if sharing and n_tokens % self.page_size else 0
        self._resv[slot] = need - n_lp + tail
        with self.telemetry.span("repro.kv.write", pages=len(fresh_lps)):
            pools = {"pages_k": handle.data["pages_k"],
                     "pages_v": handle.data["pages_v"]}
            if fresh_lps:
                if slot_kv is None:
                    raise ValueError(
                        f"write(slot_kv=None) needs every prompt page shared "
                        f"({hits} of {n_lp} resident)")
                if hits:
                    pools = self._merge_subset(
                        pools, slot_kv, jnp.asarray(pp, jnp.int32),
                        jnp.asarray(fresh_lps, jnp.int32), n_lp)
                else:
                    pools = self._merge(pools, slot_kv,
                                        jnp.asarray(pp, jnp.int32))
            pools["page_table"] = self._device_table()
            return CacheHandle(pools, "paged", self.page_size)

    def _cow(self, handle: CacheHandle, slot: int, lp: int) -> CacheHandle:
        """Copy-on-write lane `slot`'s logical page `lp` into a private
        physical page: the lane is about to write a page other lanes
        still read.  Copies the page bytes exactly (positions beyond any
        reader's depth are masked junk either way), releases this lane's
        reference on the shared page — never freeing it, other holders
        remain — and spends the lane's reserved COW page."""
        old = int(self._table[slot, lp])
        (new,) = self.allocator.alloc(1)
        self.allocator.free([old])      # rc > 1: decrements, stays live
        self._table[slot, lp] = new
        self._resv[slot] = max(int(self._resv[slot]) - 1, 0)
        self.cow_copies += 1
        pools = self._copy_page(
            {"pages_k": handle.data["pages_k"],
             "pages_v": handle.data["pages_v"]},
            jnp.int32(old), jnp.int32(new))
        pools["page_table"] = self._device_table()
        return CacheHandle(pools, "paged", self.page_size)

    def ensure(self, handle: CacheHandle, slot: int, pos: int) -> CacheHandle:
        """Grow lane `slot` to cover a write at position `pos` (no-op when
        the covering page is already mapped and privately held; a mapped
        page still shared with other lanes is copied-on-write first)."""
        lp = pos // self.page_size
        pg = int(self._table[slot, lp])
        if pg != NULL_PAGE:
            if self.prefix_sharing and self.allocator.refcount(pg) > 1:
                return self._cow(handle, slot, lp)
            return handle
        (pg,) = self.allocator.alloc(1)
        self._table[slot, lp] = pg
        self._resv[slot] = max(int(self._resv[slot]) - 1, 0)
        return CacheHandle({**handle.data,
                            "page_table": self._device_table()},
                           "paged", self.page_size)

    def ensure_range(self, handle: CacheHandle, slot: int, start: int,
                     stop: int) -> CacheHandle:
        """Map every page covering writes in [start, stop), pushing the
        device page table once instead of once per newly-mapped page.
        Shared mapped pages in the range are copied-on-write (the fused
        chunk will write them mid-scan, when the host cannot intervene)."""
        grew = False
        for lp in range(start // self.page_size,
                        (stop - 1) // self.page_size + 1):
            pg = int(self._table[slot, lp])
            if pg != NULL_PAGE:
                if self.prefix_sharing and self.allocator.refcount(pg) > 1:
                    handle = self._cow(handle, slot, lp)
                continue
            (pg,) = self.allocator.alloc(1)
            self._table[slot, lp] = pg
            self._resv[slot] = max(int(self._resv[slot]) - 1, 0)
            grew = True
        if not grew:
            return handle
        return CacheHandle({**handle.data,
                            "page_table": self._device_table()},
                           "paged", self.page_size)

    def free(self, handle: CacheHandle, slot: int) -> CacheHandle:
        """Return lane `slot`'s pages to the free list (retirement)."""
        with self.telemetry.span("repro.kv.free"):
            self._release(slot)
            return CacheHandle({**handle.data,
                                "page_table": self._device_table()},
                               "paged", self.page_size)

    def _release(self, slot: int) -> None:
        pages = [int(p) for p in self._table[slot] if p != NULL_PAGE]
        if pages:
            self.allocator.free(pages)
        self._table[slot] = NULL_PAGE
        self._resv[slot] = 0


def get_backend(name: str, *, page_size: int = 16,
                total_tokens: Optional[int] = None,
                prefix_sharing: bool = False):
    """Factory: "dense" -> DenseBackend, "paged" -> PagedBackend."""
    if name == "dense":
        if prefix_sharing:
            raise ValueError("prefix_sharing needs the paged backend: "
                             "the dense layout has no pages to share")
        return DenseBackend()
    if name == "paged":
        return PagedBackend(page_size=page_size, total_tokens=total_tokens,
                            prefix_sharing=prefix_sharing)
    raise ValueError(f"unknown cache backend {name!r}; "
                     f"expected one of {BACKENDS}")
