"""Grouped SwiGLU over the experts one chip holds (Pallas TPU), and its plain
`jnp` twin.

The served expert layer (models/moe.py, `moe_ffn_dropless`) hands over the
routed rows sorted by held expert:

    x       : (M, d)          rows, expert e's group at [starts[e],
                              starts[e] + sizes[e]); every group starts at
                              a multiple of TILE and the rows between
                              groups are padding
    starts  : (E,) int32      group starts          } scalar prefetch
    sizes   : (E,) int32      group row counts      }
    w_gate, w_up : (L, E, d, f)  every MoE layer's held experts' weights
    w_down       : (L, E, f, d)
    layer        : () int32    the layer whose experts run  (scalar prefetch)

and gets back y (M, d), y[r] = (silu(x[r] W_gate[l, e]) * (x[r] W_up[l, e]))
W_down[l, e] for every row r of group e; padding rows are left unwritten.
The weights are addressed in place in their layer stacks, as the paged
pools are (kernels/paged_attention.py): the model's layer scan passes the
stacks whole with the layer index, so no layer's experts are sliced out
of the stack into a copy before the call.  One layer's weights (E, d, f)
are the L = 1 case.

Grid: (row blocks, E), expert innermost.  Each grid cell takes one held
expert's three matrices whole, so an expert's weights stream from HBM once
per row block (once per call at decode sizes, where one block holds every
row), each as one contiguous DMA, double-buffered so the next expert's
fetch runs under this one's product.  An expert with no rows maps its
weight blocks to those of the expert fetched before it (or, before the
first expert with rows, to that expert's), and the pipeline skips a fetch
whose block index repeats: its weights never leave HBM.  Inside a cell the
group is walked in TILE-row chunks (gate and up products, silu x up, the
down product, fused in VMEM); the trip count is the group's own, so an
empty expert computes nothing.  Products take the weights' dtype and
accumulate in float32, and the SiLU-gated activation is rounded to the
weights' dtype before the down product, as the XLA einsum chain does.

The kernel's name, `moe_experts`, is what a device trace shows for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 16                  # rows of a chunk: one bf16 sublane tile
MAX_BLOCK_ROWS = 1024      # rows held in VMEM at once
VMEM_CAP = 100 << 20       # of the chip's 128 MiB


def _kernel(starts_ref, sizes_ref, wsrc_ref, layer_ref, x_ref, wg_ref,
            wu_ref, wd_ref, o_ref, *, block_rows: int):
    rb = pl.program_id(0)
    e = pl.program_id(1)
    base = rb * block_rows
    lo = jnp.maximum(starts_ref[e], base)
    hi = jnp.minimum(starts_ref[e] + sizes_ref[e], base + block_rows)
    n = (jnp.maximum(hi - lo, 0) + TILE - 1) // TILE

    def chunk(c, carry):
        r0 = pl.multiple_of(lo - base + c * TILE, TILE)
        xs = x_ref[pl.ds(r0, TILE), :]
        g = jnp.dot(xs, wg_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(xs, wu_ref[0, 0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)
        o_ref[pl.ds(r0, TILE), :] = jnp.dot(
            h, wd_ref[0, 0], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)


def weight_source(sizes: jax.Array) -> jax.Array:
    """The expert whose weight blocks grid column e reads: e itself when
    it has rows, else the last expert before it with rows, else the first
    with rows (0 when none has any) -- so an empty expert repeats the
    block index before it and no fetch is made for it."""
    has = sizes > 0
    idx = jnp.arange(sizes.shape[0], dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(has, idx, -1), axis=0)
    return jnp.where(prev >= 0, prev, jnp.argmax(has)).astype(jnp.int32)


def _stacked(w_gate, w_up, w_down, layer):
    if w_gate.ndim == 3:
        return w_gate[None], w_up[None], w_down[None], 0
    return w_gate, w_up, w_down, layer


def moe_experts(x: jax.Array, starts: jax.Array, sizes: jax.Array,
                w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                layer=0, *, interpret: bool = False) -> jax.Array:
    """The grouped SwiGLU of the module docstring: x (M, d) -> (M, d)."""
    w_gate, w_up, w_down, layer = _stacked(w_gate, w_up, w_down, layer)
    m, d = x.shape
    _, n_e, _, f = w_gate.shape
    block_rows = m if m <= MAX_BLOCK_ROWS else MAX_BLOCK_ROWS
    pad = -m % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n_rb = x.shape[0] // block_rows

    def weights(rb, e, st, sz, src, ly):
        return (ly[0], src[e], 0, 0)

    def rows(rb, e, st, sz, src, ly):
        return (rb, 0)

    itemsize = jnp.dtype(w_gate.dtype).itemsize
    need = 2 * (3 * d * f * itemsize + 2 * block_rows * d
                * jnp.dtype(x.dtype).itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # starts, sizes, weight source, layer
        grid=(n_rb, n_e),
        in_specs=[
            pl.BlockSpec((block_rows, d), rows),
            pl.BlockSpec((1, 1, d, f), weights),
            pl.BlockSpec((1, 1, d, f), weights),
            pl.BlockSpec((1, 1, f, d), weights),
        ],
        out_specs=pl.BlockSpec((block_rows, d), rows),
    )
    y = pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(need + (8 << 20), VMEM_CAP))),
        name="moe_experts",
        interpret=interpret,
    )(starts.astype(jnp.int32), sizes.astype(jnp.int32),
      weight_source(sizes.astype(jnp.int32)),
      jnp.reshape(layer, (1,)).astype(jnp.int32), x, w_gate, w_up, w_down)
    return y[:m]


def moe_experts_ref(x: jax.Array, starts: jax.Array, sizes: jax.Array,
                    w_gate: jax.Array, w_up: jax.Array,
                    w_down: jax.Array, layer=0) -> jax.Array:
    """The grouped SwiGLU in plain `jnp`: every held expert over every row,
    each row keeping its own group's result (padding rows read 0)."""
    w_gate, w_up, w_down, layer = _stacked(w_gate, w_up, w_down, layer)
    w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
    r = jnp.arange(x.shape[0])[:, None]
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        g = jnp.dot(x, w_gate[e], preferred_element_type=jnp.float32)
        u = jnp.dot(x, w_up[e], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(w_down.dtype)
        ye = jnp.dot(h, w_down[e], preferred_element_type=jnp.float32)
        inside = (r >= starts[e]) & (r < starts[e] + sizes[e])
        y = jnp.where(inside, ye, y)
    return y.astype(x.dtype)


def grouped_swiglu(x, starts, sizes, w_gate, w_up, w_down, layer=0):
    """The served expert layer's route: the Pallas kernel on a TPU, the
    plain `jnp` product elsewhere (the platform decides; the kernel's
    interpret mode is for its own tests)."""
    if jax.default_backend() == "tpu":
        from repro.kernels import ops
        return ops.moe_experts(x, starts, sizes, w_gate, w_up, w_down, layer)
    return moe_experts_ref(x, starts, sizes, w_gate, w_up, w_down, layer)
