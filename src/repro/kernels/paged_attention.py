"""Paged-attention decode (Pallas TPU): fused page-table scatter +
depth-bounded page walk + flash-decode online softmax.

Motivation (ROADMAP "Pallas gather kernel for decode"): the XLA paged
decode path gathers the full `max_pages * page_size` logical window
through the page table every step, so a lane 40 tokens deep still
streams the worst-case window from HBM.  The DSG discipline — the
executor must read *only* the activated subset — applies to the serving
memory plane too: per decode step, a lane's live state is exactly the
pages at or below `pos // page_size`.  This kernel walks only those.

Layout (serving/kv_cache.py PagedBackend, every layer's pool):

    k_pages / v_pages : (L, P, page_size, Kv, D)  stacked page pools
    layer             : () int32                  the layer to attend
    page_table        : (B, max_pages) int32      logical -> physical
    pos               : (B,) int32                per-lane write position
                                                  (== the new token's
                                                  absolute position)

The kernel addresses layer `layer` of the stacked pools in place: the
layer index rides as scalar prefetch beside the page table, every pool
block index map leads with it, and the stacked pools alias the pool
outputs.  The model's layer scan carries the stacks and hands the
kernel its step's layer index, so no layer's pool is sliced out of the
stack or written back into it; the only pool traffic is the walk's
reads and one page written per lane.  A single layer's pool is the
L = 1 case (`pool[None]`, layer 0).

Grid: (B, n_pages), page index innermost so the per-lane flash
accumulators carry across the page walk in VMEM scratch.  Each grid cell
takes one whole physical page, every KV head of it: the (ps, Kv, D)
block keeps the pool's last two dims whole, which is what the TPU
compiler asks of a block (last two dims divisible by (8, 128) or equal
to the array's).  The page's rows flatten to (ps * Kv, D) and all H
query heads score against them in one matmul; a static head mask keeps
each query head on its own KV head.  The page table and per-lane depths
ride as scalar prefetch, so BlockSpec index maps resolve
logical->physical page ids before each block fetch:

  * depth bounding — the K/V page index map clamps the logical page at
    the lane's depth, `pt[b, min(j, pos[b] // ps)]`; every grid cell
    past the depth maps to the same physical block as its predecessor,
    and the pipeline's consecutive-identical-index elision skips the
    copy, so pages past the lane's depth are never fetched from HBM.
    `pl.when(j <= pos // ps)` skips their compute as well.
  * fused scatter — the write page (logical page `pos // ps`) is copied
    into the kernel's K/V-pool output block in VMEM and the new token's
    row is stored at row `pos % ps` (the pools are input/output aliased;
    the output index map pins the write page for the whole walk, so
    exactly one page per lane is written back).  Attention reads the
    write page from that block, so it sees the new token without a
    separate XLA scatter pass.
  * masking convention — row r of logical page j holds absolute
    position t = j * ps + r; valid iff t <= pos (the new token attends
    itself, matching the dense path's `kp <= qp`) and, for sliding
    windows, t > pos - window.  The partial final page's tail (t > pos)
    reads whatever the pool holds — junk is masked by position, exactly
    as unwritten dense slots are.

Lanes that share a page-table row (the scheduler mirrors retired lanes
onto a donor lane) scatter identical rows to the same physical page, so
the duplicate write-back is order-independent — the same argument that
makes the XLA scatter's duplicate-index semantics safe.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(pt_ref, pos_ref, layer_ref, q_ref, kn_ref, vn_ref, kp_ref,
            vp_ref, hm_ref, o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr, *,
            scale: float, ps: int, kv: int, window: int, n_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[b]
    lp = pos // ps                   # lane's deepest live logical page
    off = pos % ps                   # new token's row in that page
    # write page clamped to the walk: with a correctly sized walk wp == lp;
    # an undersized walk (caller bug) degrades to an identity write-back
    # of page walk-1 instead of flushing uninitialized VMEM over live K/V
    wp = jnp.minimum(lp, n_pages - 1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j == wp)
    def _scatter():
        # one page write-back per lane: the output index map pins the
        # physical write page across the whole walk
        ko_ref[...] = kp_ref[...]
        vo_ref[...] = vp_ref[...]

        @pl.when(wp == lp)
        def _insert():
            # cast to the pool dtype FIRST so the stored and attended
            # values match the XLA scatter
            # (`pool.at[pp, off].set(k_new.astype(pool.dtype))`)
            ko_ref[0, off] = kn_ref[0].astype(ko_ref.dtype)
            vo_ref[0, off] = vn_ref[0].astype(vo_ref.dtype)

    @pl.when(j <= lp)
    def _compute():
        # the write page is read back from the output block, which holds
        # the new token's row; every other page comes from the pool
        cur = j == lp
        d = q_ref.shape[-1]
        k_t = jnp.where(cur, ko_ref[0].astype(jnp.float32),
                        kp_ref[0].astype(jnp.float32)).reshape(ps * kv, d)
        v_t = jnp.where(cur, vo_ref[0].astype(jnp.float32),
                        vp_ref[0].astype(jnp.float32)).reshape(ps * kv, d)
        q = q_ref[0].astype(jnp.float32)                 # (H, D)
        s = jax.lax.dot_general(
            q, k_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, ps * Kv)
        # column c holds page row c // Kv of KV head c % Kv; its absolute
        # position j * ps + c // Kv is valid iff <= pos (and, for sliding
        # windows, > pos - window) -- bounds on c, so no vector division
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = (hm_ref[...] != 0) & (col < (pos - j * ps + 1) * kv)
        if window > 0:
            valid &= col >= (pos - window - j * ps + 1) * kv
        s = jnp.where(valid, s, NEG)
        m_prev = m_scr[...]                              # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v_t, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)
                    ).astype(o_ref.dtype)


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

    num_pages statically bounds the page walk (the serving scheduler
    passes its bucketed live-page bound so the grid shrinks with actual
    batch depth); it must cover every lane: num_pages > max(pos) // ps.
    An undersized bound cannot corrupt the pools (the write-back page is
    clamped into the walk, degrading to an identity rewrite) but the
    truncated window yields wrong attention output and the new token is
    not persisted — the bound is the caller's contract.  Every logical
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
    walk = min(num_pages, max_pages) if num_pages else max_pages
    # head mask: query head r attends flattened page column c iff c's KV
    # head (c % Kv) is r's group (r // g)
    head_mask = (jnp.arange(ps * kv)[None, :] % kv
                 == jnp.arange(h)[:, None] // g).astype(jnp.int32)

    def page(bb, jj, pt, pos_, ly):
        # depth-clamped physical page: cells past the lane's depth alias
        # their predecessor's block -> the pipeline elides the fetch
        # (pages past `pos` never leave HBM)
        return (ly[0], pt[bb, jnp.minimum(jj, pos_[bb] // ps)], 0, 0, 0)

    def write_page(bb, jj, pt, pos_, ly):
        # write page pinned for the whole walk -> one write-back per lane,
        # flushed when the block index changes (the walk clamp mirrors
        # the kernel's wp, see _kernel)
        return (ly[0], pt[bb, jnp.minimum(pos_[bb] // ps, walk - 1)],
                0, 0, 0)

    lane = lambda bb, jj, pt, pos_, ly: (bb, 0, 0)
    page_block = (None, 1, ps, kv, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,      # page_table, pos, layer
        grid=(b, walk),
        in_specs=[
            pl.BlockSpec((1, h, d), lane),
            pl.BlockSpec((1, kv, d), lane),
            pl.BlockSpec((1, kv, d), lane),
            pl.BlockSpec(page_block, page),
            pl.BlockSpec(page_block, page),
            pl.BlockSpec((h, ps * kv), lambda bb, jj, pt, pos_, ly: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, d), lane),
            pl.BlockSpec(page_block, write_page),
            pl.BlockSpec(page_block, write_page),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),    # running max
            pltpu.VMEM((h, 1), jnp.float32),    # running sum
            pltpu.VMEM((h, d), jnp.float32),    # output accumulator
        ],
    )
    o, kp, vp = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(d), ps=ps, kv=kv,
                          window=window, n_pages=walk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, d), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # flat operand indices include the 3 scalar-prefetch args:
        # 6 = k_pages, 7 = v_pages alias pool outputs 1, 2 (in-place)
        input_output_aliases={6: 1, 7: 2},
        name="paged_decode",
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, k_new, v_new, k_pages, v_pages, head_mask)
    return o, kp, vp
