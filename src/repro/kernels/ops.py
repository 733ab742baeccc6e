"""jit'd public wrappers around the Pallas kernels.

On the CPU the kernels execute with interpret=True — the kernel body
runs as a traced grid loop, validating logic and BlockSpec indexing.  On
a TPU the same call sites compile natively, always: interpret mode there
would hide the device, so it is refused.

`REPRO_INTERPRET=1` (or `=0`) overrides the backend sniffing on the CPU
only; on a TPU `=1` raises.  The flag is read at trace time: flipping it
after a wrapper has already compiled for a given shape will not retrace
that shape.
"""
from __future__ import annotations

import os
from functools import partial

import jax

from repro.kernels import (drs_search, dsg_ffn, flash_attention as fa,
                           moe_experts as moe_kernel, paged_attention)


def _interpret() -> bool:
    """True when Pallas kernels should run in interpret mode.

    Never on a TPU, where REPRO_INTERPRET=1 raises.  Elsewhere
    REPRO_INTERPRET=1/0 wins when set; otherwise interpret iff the
    default backend is CPU (no Mosaic compiler there)."""
    env = os.environ.get("REPRO_INTERPRET", "")
    backend = jax.default_backend()
    if backend == "tpu":
        if env not in ("", "0"):
            raise RuntimeError(
                f"REPRO_INTERPRET={env} on a TPU backend: the Pallas "
                "kernels compile natively there; interpret mode is for "
                "the CPU only")
        return False
    if env != "":
        return env != "0"
    return backend == "cpu"


@partial(jax.jit, static_argnames=("bm",))
def drs_project(x, r, bm: int = 128):
    return drs_search.drs_project(x, r, bm=bm, interpret=_interpret())


@partial(jax.jit, static_argnames=("block", "bm", "bf"))
def drs_scores(fx, fw, block: int = 128, bm: int = 128, bf: int = 512):
    return drs_search.drs_scores(fx, fw, block=block, bm=bm, bf=bf,
                                 interpret=_interpret())


@partial(jax.jit, static_argnames=("block", "bm", "bf"))
def dsg_ffn_fwd(x, wg, wu, wd, token_mask, block: int = 128,
                bm: int = 128, bf: int = 128):
    return dsg_ffn.dsg_ffn(x, wg, wu, wd, token_mask, block=block,
                           bm=bm, bf=bf, interpret=_interpret())


@partial(jax.jit, static_argnames=("block",))
def dsg_ffn_csr(x, wg, wu, wd, idx, counts, block: int = 128):
    """Group-CSR SwiGLU decode step (kernels/dsg_ffn.dsg_ffn_csr): walk
    each lane's active-group index list — x (B, d), idx (B, K),
    counts (B,) -> (B, d).  K is the static active-group bound
    (core/sparse_mask.active_group_bound)."""
    return dsg_ffn.dsg_ffn_csr(x, wg, wu, wd, idx, counts, block=block,
                               interpret=_interpret())


def dsg_ffn_full(x, wg, wu, wd, r, fw, gamma: float, block: int = 128):
    """End-to-end DSG FFN through the kernels: project -> scores ->
    shared-threshold mask -> block-skip FFN.  Mirrors the pure-JAX
    swiglu_dsg_mask path; used by benchmarks and the kernel parity tests."""
    from repro.core import drs as drs_mod
    fx = drs_project(x, r)
    scores = drs_scores(fx, fw, block=block)
    cfg = drs_mod.DRSConfig(gamma=gamma, block=block, threshold_mode="topk")
    mask, _ = drs_mod.select_mask(scores, fw.shape[1], cfg)
    return dsg_ffn_fwd(x, wg, wu, wd, mask, block=block)


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    return fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=_interpret())


@partial(jax.jit, static_argnames=("window", "num_pages"))
def paged_decode_attention(q, k_new, v_new, k_pages, v_pages, page_table,
                           pos, layer, window: int = 0, num_pages: int = 0):
    """Fused paged decode step (kernels/paged_attention.py): scatter the
    new token's K/V through the page table into layer `layer` of the
    stacked pools, walk each lane's pages at or below its `pos` in
    blocks of whole pages (`paged_attention.pages_per_block`), flash-
    decode online softmax.

    q (B, H, D), k_new/v_new (B, Kv, D), k_pages/v_pages
    (L, P, ps, Kv, D), page_table (B, max_pages), pos (B,), layer ()
    -> (o (B, H, D), k_pages', v_pages').  `num_pages` statically bounds
    the walk (0 = all); it must exceed max(pos) // page_size.  A step
    costs the lanes' live blocks, not the bound."""
    return paged_attention.paged_decode(
        q, k_new, v_new, k_pages, v_pages, page_table, pos, layer,
        window=window, num_pages=num_pages, interpret=_interpret())


@jax.jit
def moe_experts(x, starts, sizes, w_gate, w_up, w_down, layer=0):
    """Grouped SwiGLU over the held experts (kernels/moe_experts.py): x
    (M, d) rows sorted by expert, groups at `starts` of `sizes` rows, the
    experts of layer `layer` of the stacked weights (L, E, ...) -> (M, d)."""
    return moe_kernel.moe_experts(x, starts, sizes, w_gate, w_up, w_down,
                                  layer, interpret=_interpret())
