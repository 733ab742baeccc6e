"""DRS Pallas kernels: projection and virtual-score computation.

drs_project: f(X) = X @ R^T — the dimension reduction itself.  On the MXU
the ternary structure of R buys nothing over a dense matmul (DESIGN.md §2),
so the kernel is a straight tiled matmul with k (the projected dim, a
multiple of the 128 lane width by construction in projection.jll_dim).

drs_scores: virtual pre-activations v = f(X) @ f(W), ReLU, and per-group
reduction fused in one pass — the low-dimensional search the paper
substitutes for the full VMM.  The (bm, bf) virtual-activation tile never
leaves VMEM; only the (bm, F/block) group scores are written to HBM —
the kernel's HBM traffic is 1/block of the naive two-op formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _project_kernel(x_ref, rt_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], rt_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def drs_project(x: jax.Array, r: jax.Array, *, bm: int = 128,
                interpret: bool = False) -> jax.Array:
    """x (M, d), r (k, d) -> f(X) (M, k)."""
    m, d = x.shape
    k = r.shape[0]
    bm = min(bm, m)
    assert m % bm == 0
    return pl.pallas_call(
        _project_kernel,
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0)),
                  pl.BlockSpec((d, k), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), x.dtype),
        name="drs_project",
        interpret=interpret,
    )(x, r.T)


def _scores_kernel(fx_ref, fw_ref, o_ref, *, block: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    v = jnp.dot(fx_ref[...], fw_ref[...],
                preferred_element_type=jnp.float32)      # (bm, bf)
    relu = jnp.maximum(v, 0.0)
    gpb = v.shape[1] // block
    # the output block spans every group of the row tile and stays in
    # VMEM across the F pass; this cell fills groups j*gpb .. j*gpb+gpb-1
    # by lane-aligned slice sums placed with a column select
    col = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    out = o_ref[...]
    for c in range(gpb):
        mass = relu[:, c * block:(c + 1) * block].sum(axis=-1, keepdims=True)
        out = jnp.where(col == j * gpb + c, mass.astype(o_ref.dtype), out)
    o_ref[...] = out


def drs_scores(fx: jax.Array, fw: jax.Array, *, block: int = 128,
               bm: int = 128, bf: int = 512,
               interpret: bool = False) -> jax.Array:
    """fx (M, k), fw (k, F) -> group scores (M, F/block).

    Grid (M/bm, F/bf), F innermost.  The (bm, F/block) output block is
    the row tile's whole group axis, so its last two dims satisfy the TPU
    compiler's block rule for any group count."""
    m, k = fx.shape
    f = fw.shape[1]
    bm = min(bm, m)
    bf = min(bf, f)
    assert m % bm == 0 and f % bf == 0 and bf % block == 0
    return pl.pallas_call(
        functools.partial(_scores_kernel, block=block),
        grid=(m // bm, f // bf),
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, bf), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, f // block), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, f // block), jnp.float32),
        name="drs_scores",
        interpret=interpret,
    )(fx, fw)
