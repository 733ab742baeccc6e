"""DSG block-sparse SwiGLU FFN — the flagship Pallas TPU kernel.

Realizes the paper's compute saving at MXU granularity: the FFN hidden dim
F is split into 128-wide neuron groups; for each (token-tile, group-block)
cell the kernel consults a tile-level mask and SKIPS the gate/up matmuls,
the SwiGLU, and the down-projection accumulation for masked-out blocks —
the "reorder executions at tile granularity and group non-redundant work"
strategy the paper sketches for GEMM backends (§3.4), here done natively.

Exactness: the tile mask is the OR of the per-token DRS masks over the
token tile; per-token masks are re-applied elementwise inside the kernel,
so the output equals the reference masked FFN bit-for-bit (a block runs if
any token in the tile selected it, and unselected tokens still contribute
zeros).

Grid: (M/bm, F/bf), F innermost so the output tile (bm, d) accumulates in
VMEM across the F pass (sequential revisiting on TPU).  BlockSpecs keep
the working set at bm*d + 2*d*bf + bf*d + bm*bf floats in VMEM — with
bm=bf=128, d<=8192, bf16: about 6.5 MB, comfortably under the 16 MB/core
of v5e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tmask_ref, x_ref, wg_ref, wu_ref, wd_ref, tokmask_ref, o_ref):
    i = pl.program_id(0)
    f_idx = pl.program_id(1)

    @pl.when(f_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(tmask_ref[i, f_idx] > 0)
    def _compute():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        # exact per-token mask within the visited block, expanded to
        # neuron columns by the wrapper
        h = jax.nn.silu(g) * u * tokmask_ref[...]       # (bm, bf)
        o_ref[...] += jnp.dot(h.astype(x.dtype), wd_ref[...],
                              preferred_element_type=jnp.float32
                              ).astype(o_ref.dtype)


def dsg_ffn(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
            token_mask: jax.Array, *, block: int = 128, bm: int = 128,
            bf: int = 128, interpret: bool = False) -> jax.Array:
    """x (M, d), wg/wu (d, F), wd (F, d), token_mask (M, F//block) {0,1}.

    Returns (M, d).  bf must be a multiple of `block`.
    """
    m, d = x.shape
    f = wg.shape[1]
    bm = min(bm, m)
    bf = min(bf, f)
    assert m % bm == 0 and f % bf == 0 and bf % block == 0
    gpb = bf // block                                  # groups per f-block
    mt, ft = m // bm, f // bf

    # tile mask: OR of token masks over each (token-tile, f-block) cell,
    # scalar-prefetched so the skip test is an SMEM read
    tile_mask = token_mask.reshape(mt, bm, ft, gpb).max(axis=(1, 3))
    tile_mask = (tile_mask > 0).astype(jnp.int32)
    # per-token mask at neuron granularity: its (bm, bf) blocks tile like h
    neuron_mask = jnp.repeat(token_mask.astype(jnp.float32), block, axis=1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,      # tile mask
        grid=(mt, ft),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j, tm: (i, 0)),
            pl.BlockSpec((d, bf), lambda i, j, tm: (0, j)),
            pl.BlockSpec((d, bf), lambda i, j, tm: (0, j)),
            pl.BlockSpec((bf, d), lambda i, j, tm: (j, 0)),
            pl.BlockSpec((bm, bf), lambda i, j, tm: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i, j, tm: (i, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        name="dsg_ffn",
        interpret=interpret,
    )(tile_mask, x, wg, wu, wd, neuron_mask)


# ---------------------------------------------------------------------------
# CSR-driven decode variant
# ---------------------------------------------------------------------------

def _csr_kernel(idx_ref, cnt_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    """One (lane, csr-slot) cell: the index maps below already steered the
    gate/up/down weight *blocks* of group idx[b, j] into VMEM, so the body
    is a dense (1, d) x (d, blk) SwiGLU + down-projection, skipped for
    padded slots past the lane's count."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < cnt_ref[b])
    def _compute():
        x = x_ref[0]                                      # (1, d)
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * u                            # (1, blk)
        o_ref[0] += jnp.dot(h.astype(x.dtype), wd_ref[...],
                            preferred_element_type=jnp.float32
                            ).astype(o_ref.dtype)


def dsg_ffn_csr(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
                idx: jax.Array, counts: jax.Array, *, block: int = 128,
                interpret: bool = False) -> jax.Array:
    """Group-CSR SwiGLU decode: walk each lane's active-group index list
    instead of scanning a dense tile mask.

    x (B, d) one token per lane, wg/wu (d, F), wd (F, d),
    idx (B, K) active group indices (core/sparse_mask.py layout: ascending
    per lane, zero-padded past counts), counts (B,) -> (B, d).

    Grid (B, K), K innermost so the (1, d) output row accumulates in VMEM
    across the walk.  x and the output ride as (B, 1, d), so a lane's
    (1, 1, d) block keeps its last two dims whole (the TPU compiler's
    block rule).  The index list is scalar-prefetched (the
    paged-attention page-table idiom): the weight-block index maps read
    `idx[b, j]` directly, so ONLY the kept groups' gate/up/down blocks
    ever leave HBM — weight traffic scales with counts, not F.  Padded
    slots clamp to the last active block (the consecutive-identical-index
    elision skips the re-fetch) and `pl.when` skips their compute."""
    b, d = x.shape
    f = wg.shape[1]
    k = idx.shape[1]
    assert f % block == 0 and k <= f // block

    def _wcol(bb, jj, idx_p, cnt_p):
        # clamp padded slots onto the lane's last active block: identical
        # consecutive indices -> the pipeline elides the HBM fetch
        return idx_p[bb, jnp.minimum(jj, jnp.maximum(cnt_p[bb], 1) - 1)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # idx, counts
        grid=(b, k),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bb, jj, idx_p, cnt_p: (bb, 0, 0)),
            pl.BlockSpec((d, block),
                         lambda bb, jj, idx_p, cnt_p: (0, _wcol(bb, jj, idx_p, cnt_p))),
            pl.BlockSpec((d, block),
                         lambda bb, jj, idx_p, cnt_p: (0, _wcol(bb, jj, idx_p, cnt_p))),
            pl.BlockSpec((block, d),
                         lambda bb, jj, idx_p, cnt_p: (_wcol(bb, jj, idx_p, cnt_p), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d),
                               lambda bb, jj, idx_p, cnt_p: (bb, 0, 0)),
    )
    y = pl.pallas_call(
        _csr_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, d), x.dtype),
        name="dsg_ffn_csr",
        interpret=interpret,
    )(idx.astype(jnp.int32), counts.astype(jnp.int32), x[:, None, :],
      wg, wu, wd)
    return y[:, 0, :]
