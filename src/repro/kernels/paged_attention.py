"""Paged-attention decode (Pallas TPU): fused page-table scatter + a walk
over blocks of each lane's live pages + flash-decode online softmax; and
`mla_decode`, multi-head latent attention's absorbed decode over a pool of
latent rows on the same walk (the end of this file).

Motivation (ROADMAP "Pallas gather kernel for decode"): the XLA paged
decode path gathers the full `max_pages * page_size` logical window
through the page table every step, so a lane 40 tokens deep still
streams the worst-case window from HBM.  The DSG discipline — the
executor must read *only* the activated subset — applies to the serving
memory plane too: per decode step, a lane's live state is exactly the
pages at or below `pos // page_size`.  This kernel walks only those, and
spends no work on the pages past them.

Layout (serving/kv_cache.py PagedBackend, every layer's pool):

    k_pages / v_pages : (L, P, page_size, Kv, D)  stacked page pools
    layer             : () int32                  the layer to attend
    page_table        : (B, max_pages) int32      logical -> physical
    pos               : (B,) int32                per-lane write position
                                                  (== the new token's
                                                  absolute position)

The kernel addresses layer `layer` of the stacked pools in place: the
pools stay in HBM (`memory_space=pl.ANY`), alias the pool outputs, and
every copy in or out names `pool.at[layer, page]`.  The model's layer
scan carries the stacks and hands the kernel its step's layer index, so
no layer's pool is sliced out of the stack or written back into it; the
only pool traffic is the walk's reads and one row written per lane.  A
single layer's pool is the L = 1 case (`pool[None]`, layer 0).

Grid: (B,), one step per lane.  Inside it a loop walks the lane's live
pages in blocks of `pages_per_block` whole pages (about 128 tokens; the
count comes from the pool's shapes and a VMEM budget, clamped to the
page table), so the loop's trip count is the lane's own depth,
`cdiv(pos // ps + 1, pages_per_block)`: no iteration is spent past it.
Each page of a block is one async copy from HBM, a contiguous
(ps, Kv, D) run, into one of two VMEM slots per K and per V; the next
block's copies (the next lane's first block, when this lane ends) start
before this block's wait, so the fetch runs under the compute.  The
block flattens to (pages_per_block * ps * Kv, D) rows and all H query
heads score against them in one matmul; a static head mask keeps each
query head on its own KV head.

  * fused scatter — in the lane's last block, the new token's row is
    stored at row `pos % ps` of its page (logical page `pos // ps`) in
    VMEM before attention reads the block, so the token attends itself
    without a separate XLA scatter pass; that one (Kv, D) row is then
    copied to the pool in HBM.
  * masking convention — row r of logical page j holds absolute
    position t = j * ps + r; valid iff t <= pos (the new token attends
    itself, matching the dense path's `kp <= qp`) and, for sliding
    windows, t > pos - window.  The partial final page's tail (t > pos)
    and a partial block's unfetched slots hold whatever VMEM or the
    pool held — junk is masked by position, and the masked V rows are
    zeroed so junk never reaches the accumulator.

Lanes that share a page-table row (the scheduler mirrors retired lanes
onto a donor lane) write identical rows to the same physical page, so
the order of their writes does not matter, and a mirrored lane that
fetched the page before its donor's write inserts the same row itself —
the same argument that makes the XLA scatter's duplicate-index semantics
safe.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
BLOCK_TOKENS = 128       # tokens a block of the walk aims to hold
VMEM_BUDGET = 8 << 20    # bytes for the blocks' slots and f32 copies


def pages_per_block(page_size: int, kv_heads: int, head_dim: int, dtype,
                    max_pages: int) -> int:
    """Whole pages per step of the page walk: about BLOCK_TOKENS tokens,
    no more than VMEM_BUDGET holds (two slots of the block for K and for
    V in the pool dtype, plus the f32 copies of one K and one V block),
    and no more than the page table's `max_pages`.  It does not depend
    on the lanes' depths, so every step walks the same blocks."""
    page = page_size * kv_heads * head_dim
    per_page = page * (4 * jnp.dtype(dtype).itemsize + 2 * 4)
    n = min(max(1, BLOCK_TOKENS // page_size),
            max(1, VMEM_BUDGET // per_page))
    return max(1, min(n, max_pages))


def walk_blocks(pos, page_size: int, block: int) -> int:
    """Blocks of `block` pages the kernel walks for lanes at `pos` (host
    ints), summed over lanes: each lane walks its pages 0..pos//ps."""
    live = np.asarray(pos) // page_size + 1
    return int((-(-live // block)).sum())


class _Walk:
    """The walk over one lane's live pages that both decode kernels share
    (`paged_decode` and `mla_decode`): grid step b is lane b, its pages
    0..pos//ps copied block by block into two VMEM slots, the next block's
    copies (the next lane's first block, when this lane ends) started
    before this block's wait.

    `pools` pairs each stacked HBM pool with its (2, ppb, ps, ...) VMEM
    slots; `sems` is a DMA semaphore array (2, len(pools)).  Building the
    walk starts lane 0's first block (on grid step 0) and reads the lane's
    position: `pos`, its deepest live page `lp` (clamped to the table's
    `width`), the block count `nb`, the write row `off` in page `lp`,
    `writes` (False only past the table's end) and `end`, the last position
    the walk holds.  `loop` then runs the blocks."""

    def __init__(self, pt_ref, pos_ref, layer_ref, pools, sems, slot_ref, *,
                 ps: int, width: int, ppb: int):
        self.pt_ref, self.pos_ref, self.pools = pt_ref, pos_ref, pools
        self.sems, self.slot_ref, self.ppb = sems, slot_ref, ppb
        self.b = b = pl.program_id(0)
        self.layer = layer_ref[0]
        self.ps, self.width = ps, width

        @pl.when(b == 0)
        def _first():
            slot_ref[0] = 0
            self.block_copies(0, 0, 0, wait=False)

        self.pos = pos = pos_ref[b]
        self.lp = lp = self.last_page(b)
        self.nb = lp // ppb + 1
        self.off = pos % ps
        self.writes = pos // ps == lp     # False only past the table's end
        # last attended position: the lane's own, or the table's end
        self.end = jnp.minimum(pos, (lp + 1) * ps - 1)

    def last_page(self, lane):
        # the lane's deepest live page, clamped to the page table's
        # width: a lane inside the table never clamps; one past it
        # (caller bug) walks the whole table and writes nothing, so no
        # page outside the table is read or written
        return jnp.minimum(self.pos_ref[lane] // self.ps, self.width - 1)

    def block_copies(self, lane, blk, slot, wait):
        """Start, or wait for, the copies of block `blk` of `lane` into
        `slot`: one per live page and pool, each pool on the slot's
        semaphore of its own."""
        first = blk * self.ppb

        def page(i, carry):
            phys = self.pt_ref[lane, first + i]
            for c, (src, buf) in enumerate(self.pools):
                cp = pltpu.make_async_copy(src.at[self.layer, phys],
                                           buf.at[slot, i],
                                           self.sems.at[slot, c])
                cp.wait() if wait else cp.start()
            return carry

        n = jnp.minimum(self.last_page(lane) - first + 1, self.ppb)
        jax.lax.fori_loop(0, n, page, 0)

    def loop(self, attend, init):
        """Walk the lane's blocks: attend(blk, slot, *carry) -> carry runs
        on each block once its copies have landed in `slot`.  Returns the
        last carry and the slot after the last block's (the next lane's
        first); the last block sat in the other one."""
        b, nb = self.b, self.nb

        def body(blk, carry):
            *acc, slot = carry
            nxt = 1 - slot

            @pl.when(blk + 1 < nb)
            def _next_block():
                self.block_copies(b, blk + 1, nxt, wait=False)

            @pl.when((blk + 1 == nb) & (b + 1 < pl.num_programs(0)))
            def _next_lane():
                self.block_copies(b + 1, 0, nxt, wait=False)

            self.block_copies(b, blk, slot, wait=True)
            return (*attend(blk, slot, *acc), nxt)

        *acc, slot = jax.lax.fori_loop(0, nb, body,
                                       (*init, self.slot_ref[0]))
        self.slot_ref[0] = slot
        return acc, slot


def _kernel(pt_ref, pos_ref, layer_ref, q_ref, kn_ref, vn_ref, hm_ref,
            kp_hbm, vp_hbm, o_ref, ko_hbm, vo_hbm, kbuf, vbuf, sems,
            wsems, slot_ref, *, scale: float, ps: int, kv: int,
            window: int, width: int, ppb: int):
    w = _Walk(pt_ref, pos_ref, layer_ref, ((kp_hbm, kbuf), (vp_hbm, vbuf)),
              sems, slot_ref, ps=ps, width=width, ppb=ppb)
    b, layer, pos, lp, off, end = w.b, w.layer, w.pos, w.lp, w.off, w.end
    rows = ppb * ps * kv
    d = q_ref.shape[-1]
    q = q_ref[0].astype(jnp.float32)                     # (H, D)

    def row_writes(slot, i):
        """The copies of the new token's K and V rows, held in page `i`
        of block slot `slot`, to the lane's write page in the pool."""
        phys = pt_ref[b, lp]
        return [pltpu.make_async_copy(buf.at[slot, i, off],
                                      dst.at[layer, phys, off], wsems.at[c])
                for c, (buf, dst) in enumerate(((kbuf, ko_hbm),
                                                (vbuf, vo_hbm)))]

    def attend(blk, slot, m_prev, l_prev, acc):
        @pl.when((blk + 1 == w.nb) & w.writes)
        def _insert():
            # cast to the pool dtype FIRST so the stored and attended
            # values match the XLA scatter
            # (`pool.at[pp, off].set(k_new.astype(pool.dtype))`), then
            # write that one row back to the pool
            i = lp - blk * ppb
            kbuf[slot, i, off] = kn_ref[0].astype(kbuf.dtype)
            vbuf[slot, i, off] = vn_ref[0].astype(vbuf.dtype)
            for cp in row_writes(slot, i):
                cp.start()

        k_t = kbuf[slot].astype(jnp.float32).reshape(rows, d)
        v_t = vbuf[slot].astype(jnp.float32).reshape(rows, d)
        s = jax.lax.dot_general(
            q, k_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, rows)
        # column c holds block row c // Kv of KV head c % Kv; its absolute
        # position blk * ppb * ps + c // Kv is valid iff <= end (and, for
        # sliding windows, > pos - window) -- bounds on c, so no vector
        # division
        base = blk * ppb * ps
        hi = (end - base + 1) * kv
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = (hm_ref[...] != 0) & (col < hi)
        if window > 0:
            valid &= col >= (pos - window - base + 1) * kv
        s = jnp.where(valid, s, NEG)
        # rows past `end` hold junk (a partial page's tail, or slots no
        # page was copied into): zero them so 0 * junk cannot reach acc
        row = jax.lax.broadcasted_iota(jnp.int32, v_t.shape, 0)
        v_t = jnp.where(row < hi, v_t, 0.0)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p, v_t, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    h = q.shape[0]
    init = (jnp.full((h, 1), NEG, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, d), jnp.float32))
    (_, l_run, acc), slot = w.loop(attend, init)

    @pl.when(w.writes)
    def _wait_writes():
        # the last block sat in the slot before `slot`
        for cp in row_writes(1 - slot, lp - (w.nb - 1) * ppb):
            cp.wait()

    o_ref[0] = (acc / jnp.maximum(l_run, 1e-20)).astype(o_ref.dtype)


def paged_decode(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                 k_pages: jax.Array, v_pages: jax.Array,
                 page_table: jax.Array, pos: jax.Array, layer, *,
                 window: int = 0, interpret: bool = False):
    """One fused decode step over the paged KV layout.

    q (B, H, D) — the step's queries (RoPE already applied);
    k_new/v_new (B, Kv, D) — the new token's K/V; k_pages/v_pages
    (L, P, ps, Kv, D) — every layer's physical pools; page_table
    (B, max_pages) int32; pos (B,) int32 per-lane write positions;
    layer () int32 — the layer whose pool is read and written.
    Returns (o (B, H, D), k_pages', v_pages') with the new rows
    scattered into layer `layer` of the pools; every other layer's
    pages come back untouched (the pools are aliased in place).

    Each lane walks its own live pages, 0..pos//ps, in blocks of
    `pages_per_block` pages (from ps, Kv * D, the pool dtype, a VMEM
    budget and the table width); a step costs the lanes' live blocks,
    and no static bound is needed.  The walk is clamped to the page
    table's width: a lane past it (caller bug) reads the whole table
    and writes nothing, so it cannot corrupt the pools.  Every logical
    page 0..pos//ps of each lane must be mapped in the page table (the
    backend's `ensure` guarantees this for live lanes; retired lanes
    must be mirrored onto a live donor row).

    Softmax statistics and the score tile are f32 regardless of
    `attn_bf16_scores`: that flag is an HBM-traffic lever for the XLA
    attention chain, and the kernel's score tile never leaves VMEM — so
    parity with a bf16-scores XLA path is tolerance-level (standard
    flash-kernel numerics), while the f32 path matches bitwise at the
    token-stream level.
    """
    b, h, d = q.shape
    _, _, ps, kv, _ = k_pages.shape
    assert h % kv == 0, f"H={h} not a multiple of Kv={kv}"
    g = h // kv
    max_pages = page_table.shape[1]
    ppb = pages_per_block(ps, kv, d, k_pages.dtype, max_pages)
    # head mask: query head r attends flattened block column c iff c's KV
    # head (c % Kv) is r's group (r // g)
    head_mask = (jnp.arange(ppb * ps * kv)[None, :] % kv
                 == jnp.arange(h)[:, None] // g).astype(jnp.int32)

    lane = lambda bb, pt, pos_, ly: (bb, 0, 0)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,      # page_table, pos, layer
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, d), lane),
            pl.BlockSpec((1, kv, d), lane),
            pl.BlockSpec((1, kv, d), lane),
            pl.BlockSpec((h, ppb * ps * kv), lambda bb, pt, pos_, ly: (0, 0)),
            hbm,
            hbm,
        ],
        out_specs=[pl.BlockSpec((1, h, d), lane), hbm, hbm],
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, kv, d), k_pages.dtype),  # K slots
            pltpu.VMEM((2, ppb, ps, kv, d), v_pages.dtype),  # V slots
            pltpu.SemaphoreType.DMA((2, 2)),    # [slot, K/V] block reads
            pltpu.SemaphoreType.DMA((2,)),      # [K/V] row writes
            pltpu.SMEM((1,), jnp.int32),        # slot of the lane's block 0
        ],
    )
    o, kp, vp = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(d), ps=ps, kv=kv,
                          window=window, width=max_pages, ppb=ppb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, d), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # flat operand indices include the 3 scalar-prefetch args:
        # 7 = k_pages, 8 = v_pages alias pool outputs 1, 2 (in-place)
        input_output_aliases={7: 1, 8: 2},
        # lanes run in order: each lane's walk starts on the block the
        # previous lane prefetched
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode",
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, k_new, v_new, head_mask, k_pages, v_pages)
    return o, kp, vp


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2's MLA), absorbed form
# ---------------------------------------------------------------------------

def _mla_kernel(pt_ref, pos_ref, layer_ref, q_ref, cn_ref, cp_hbm, o_ref,
                co_hbm, cbuf, sems, wsem, slot_ref, *, scale: float,
                ps: int, width: int, ppb: int, lat: int):
    w = _Walk(pt_ref, pos_ref, layer_ref, ((cp_hbm, cbuf),), sems,
              slot_ref, ps=ps, width=width, ppb=ppb)
    rows = ppb * ps
    r = q_ref.shape[-1]
    q = q_ref[0]                                         # (H, R)

    def page_write(slot, i):
        """The copy of page `i` of block slot `slot`, which holds the new
        row, back to the lane's write page in the pool."""
        return pltpu.make_async_copy(
            cbuf.at[slot, i], co_hbm.at[w.layer, pt_ref[w.b, w.lp]],
            wsem.at[0])

    def attend(blk, slot, m_prev, l_prev, acc):
        @pl.when((blk + 1 == w.nb) & w.writes)
        def _insert():
            # a bf16 row is half a packed sublane, too narrow for a store
            # or a copy of its own: the new row goes into its page by a
            # select over the whole page, and the page goes back whole
            i = w.lp - blk * ppb
            page = cbuf[slot, i]
            at = jax.lax.broadcasted_iota(jnp.int32, page.shape, 0) == w.off
            cbuf[slot, i] = jnp.where(at, cn_ref[0], page)
            page_write(slot, i).start()

        c_t = cbuf[slot].reshape(rows, r)
        s = jax.lax.dot_general(
            q, c_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, rows)
        hi = w.end - blk * rows + 1
        valid = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < hi
        s = jnp.where(valid, s, NEG)
        # the first `lat` columns of a row are its value; rows past `end`
        # hold junk: zero them so 0 * junk cannot reach acc
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, lat), 0)
        v_t = jnp.where(row < hi, c_t[:, :lat], jnp.zeros((), c_t.dtype))
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(v_t.dtype), v_t, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    h = q.shape[0]
    init = (jnp.full((h, 1), NEG, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, lat), jnp.float32))
    (_, l_run, acc), slot = w.loop(attend, init)

    @pl.when(w.writes)
    def _wait_write():
        # the last block sat in the slot before `slot`
        page_write(1 - slot, w.lp - (w.nb - 1) * ppb).wait()

    o_ref[0] = (acc / jnp.maximum(l_run, 1e-20)).astype(o_ref.dtype)


def mla_decode(q: jax.Array, c_new: jax.Array, c_pages: jax.Array,
               page_table: jax.Array, pos: jax.Array, layer, *, lat: int,
               scale: float, interpret: bool = False):
    """One decode step of multi-head latent attention over a paged pool of
    latent rows, in the absorbed form.

    q (B, H, R): each head's query against a cached row, `lat` columns of
    q_nope W_UK then the roped q_pe (R = lat + rotary width); c_new (B, R)
    the new token's row, [normalised latent c | roped k_pe]; c_pages
    (L, P, ps, R) every layer's pool of rows, shared by all heads;
    page_table (B, max_pages) int32; pos (B,) int32 write positions;
    layer () int32.  Scores are q . row x `scale`, softmax over the lane's
    rows 0..pos (the new one included), and the output (B, H, lat) is the
    softmax-weighted sum of each row's first `lat` columns (the latent is
    both key and value, stored once).  Returns (o, c_pages') with the new
    row written at `pos` in layer `layer`, in place.

    The walk over each lane's pages is `paged_decode`'s (`_Walk`).  In the
    lane's last block the new row is put into its page in VMEM before the
    block is read, so the token attends itself, and that page is copied
    back to the pool whole: a bf16 row is half of a packed sublane, which
    no store or copy addresses alone.  R must be a multiple of 128, the
    lane tiling of the pool in HBM (models/mla.py pads the rows).
    Products take the pool's dtype with f32 accumulation; softmax
    statistics are f32."""
    b, h, r = q.shape
    ps = c_pages.shape[2]
    max_pages = page_table.shape[1]
    ppb = pages_per_block(ps, 1, r, c_pages.dtype, max_pages)
    lane = lambda bb, pt, pos_, ly: (bb, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,      # page_table, pos, layer
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, r), lane),
                  pl.BlockSpec((1, 1, r), lane),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((1, h, lat), lane),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, r), c_pages.dtype),   # row slots
            pltpu.SemaphoreType.DMA((2, 1)),    # [slot, pool] block reads
            pltpu.SemaphoreType.DMA((1,)),      # the written page
            pltpu.SMEM((1,), jnp.int32),        # slot of the lane's block 0
        ],
    )
    o, cp = pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, ps=ps, width=max_pages,
                          ppb=ppb, lat=lat),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, lat), q.dtype),
                   jax.ShapeDtypeStruct(c_pages.shape, c_pages.dtype)],
        # flat operand 5 = c_pages (after the 3 scalar-prefetch args)
        # aliases pool output 1 (in place)
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mla_decode",
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.astype(c_pages.dtype), c_new.astype(c_pages.dtype)[:, None],
      c_pages)
    return o, cp
