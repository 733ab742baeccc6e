"""Paged-attention decode (Pallas TPU): fused page-table scatter + a walk
over blocks of each lane's live pages + flash-decode online softmax.

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
    on the walk bound, so every bound walks the same blocks."""
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


def _kernel(pt_ref, pos_ref, layer_ref, q_ref, kn_ref, vn_ref, hm_ref,
            kp_hbm, vp_hbm, o_ref, ko_hbm, vo_hbm, kbuf, vbuf, sems,
            wsems, slot_ref, *, scale: float, ps: int, kv: int,
            window: int, walk: int, ppb: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    rows = ppb * ps * kv
    d = q_ref.shape[-1]

    def last_page(lane):
        # the lane's deepest live page, clamped to the walk: a correctly
        # sized walk never clamps; an undersized one (caller bug) walks
        # its first `walk` pages and writes nothing, so no page past the
        # walk is read or written
        return jnp.minimum(pos_ref[lane] // ps, walk - 1)

    def block_copies(lane, blk, slot, wait):
        """Start, or wait for, the copies of block `blk` of `lane` into
        `slot`: one per live page, K and V each on the slot's
        semaphore."""
        first = blk * ppb

        def page(i, carry):
            phys = pt_ref[lane, first + i]
            for c, (src, buf) in enumerate(((kp_hbm, kbuf), (vp_hbm, vbuf))):
                cp = pltpu.make_async_copy(src.at[layer, phys],
                                           buf.at[slot, i], sems.at[slot, c])
                cp.wait() if wait else cp.start()
            return carry

        n = jnp.minimum(last_page(lane) - first + 1, ppb)
        jax.lax.fori_loop(0, n, page, 0)

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        block_copies(0, 0, 0, wait=False)

    pos = pos_ref[b]
    lp = last_page(b)
    nb = lp // ppb + 1
    off = pos % ps
    writes = pos // ps == lp          # False only past an undersized walk
    # last attended position: the lane's own, or its walk's end
    end = jnp.minimum(pos, (lp + 1) * ps - 1)
    q = q_ref[0].astype(jnp.float32)                     # (H, D)

    def row_writes(slot, i):
        """The copies of the new token's K and V rows, held in page `i`
        of block slot `slot`, to the lane's write page in the pool."""
        phys = pt_ref[b, lp]
        return [pltpu.make_async_copy(buf.at[slot, i, off],
                                      dst.at[layer, phys, off], wsems.at[c])
                for c, (buf, dst) in enumerate(((kbuf, ko_hbm),
                                                (vbuf, vo_hbm)))]

    def body(blk, carry):
        m_prev, l_prev, acc, slot = carry
        nxt = 1 - slot

        @pl.when(blk + 1 < nb)
        def _next_block():
            block_copies(b, blk + 1, nxt, wait=False)

        @pl.when((blk + 1 == nb) & (b + 1 < pl.num_programs(0)))
        def _next_lane():
            block_copies(b + 1, 0, nxt, wait=False)

        block_copies(b, blk, slot, wait=True)

        @pl.when((blk + 1 == nb) & writes)
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
        return m_new, l_new, acc, nxt

    h = q.shape[0]
    init = (jnp.full((h, 1), NEG, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, d), jnp.float32), slot_ref[0])
    _, l_run, acc, slot = jax.lax.fori_loop(0, nb, body, init)
    slot_ref[0] = slot

    @pl.when(writes)
    def _wait_writes():
        # the last block sat in the slot before `slot`
        for cp in row_writes(1 - slot, lp - (nb - 1) * ppb):
            cp.wait()

    o_ref[0] = (acc / jnp.maximum(l_run, 1e-20)).astype(o_ref.dtype)


def paged_decode(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                 k_pages: jax.Array, v_pages: jax.Array,
                 page_table: jax.Array, pos: jax.Array, layer, *,
                 window: int = 0, num_pages: int = 0,
                 interpret: bool = False):
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
    whatever `num_pages` is.  num_pages statically bounds the walk (the
    serving scheduler passes its bucketed live-page bound) and must
    cover every lane: num_pages > max(pos) // ps.  A
    lane past an undersized bound cannot corrupt the pools (it reads
    its first num_pages pages and writes nothing), but its attention
    window is truncated and the new token is not persisted — the bound
    is the caller's contract.  Every logical page 0..pos//ps of each
    lane must be mapped in the page table (the backend's `ensure`
    guarantees this for live lanes; retired lanes must be mirrored onto
    a live donor row).

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
    walk = min(num_pages, max_pages) if num_pages else max_pages
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
                          window=window, walk=walk, ppb=ppb),
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
