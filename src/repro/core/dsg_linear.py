"""DSG-sparsified linear/FFN layers — the paper's technique as composable ops.

Three execution modes (DESIGN.md §2, §7):

  * "dense"         — baseline, no DSG.
  * "mask"          — paper-faithful: DRS selects neuron groups per token;
                      the full matmul runs and the mask multiplies the
                      output.  XLA cannot skip dynamic per-token columns, so
                      HLO FLOPs are unchanged — the compute saving at this
                      granularity is realized by the Pallas kernel
                      (kernels/dsg_matmul.py); the *memory* saving (compact
                      stash for backward) is realized here via the masked
                      stash in the custom-vjp path.
  * "gather_shared" — beyond-paper TPU adaptation: one selection shared by
                      all tokens in the (per-device) batch, computed from
                      batch-summed group scores, optionally balanced across
                      `n_chunks` contiguous shard-aligned chunks of the
                      output dim.  The kept weight blocks are gathered once
                      and the matmul shrinks to (1-gamma) of the columns —
                      the FLOP reduction is visible to XLA (and the
                      roofline).

Weights layout: w_gate/w_up are (d, F), w_down is (F, d); the DSG group dim
is F split into G = F/block groups.  Sharding: F dim over the "model" mesh
axis; with n_chunks = number of model shards the gather stays shard-local.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import drs, masks, projection


class DSGConfig(NamedTuple):
    enabled: bool = False
    gamma: float = 0.5            # fraction of neuron groups dropped
    eps: float = 0.5              # JLL epsilon -> projection dim k
    block: int = 128              # neuron-group width
    threshold_mode: str = "topk"  # "topk" | "shared" | "ema"
    score: str = "relu_sum"
    mode: str = "mask"            # "mask" | "gather_shared"
    n_chunks: int = 1             # balanced per-chunk selection (shard-aligned)
    refresh_every: int = 50       # f(W) refresh period (paper: 50)

    def drs_cfg(self) -> drs.DRSConfig:
        return drs.DRSConfig(gamma=self.gamma, block=self.block,
                             threshold_mode=self.threshold_mode,
                             score=self.score)


def proj_dim(d: int, n_out: int, cfg: DSGConfig) -> int:
    return projection.jll_dim(d, n_points=n_out + 1, eps=cfg.eps)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_swiglu(key: jax.Array, d: int, f: int, dtype=jnp.float32) -> dict:
    kg, ku, kd = jax.random.split(key, 3)
    sc_in = 1.0 / math.sqrt(d)
    sc_out = 1.0 / math.sqrt(f)
    return {
        "w_gate": (jax.random.normal(kg, (d, f)) * sc_in).astype(dtype),
        "w_up": (jax.random.normal(ku, (d, f)) * sc_in).astype(dtype),
        "w_down": (jax.random.normal(kd, (f, d)) * sc_out).astype(dtype),
    }


def init_gelu_ffn(key: jax.Array, d: int, f: int, dtype=jnp.float32) -> dict:
    ku, kd = jax.random.split(key)
    return {
        "w_up": (jax.random.normal(ku, (d, f)) / math.sqrt(d)).astype(dtype),
        "w_down": (jax.random.normal(kd, (f, d)) / math.sqrt(f)).astype(dtype),
    }


def init_dsg_state(key: jax.Array, d: int, f: int, cfg: DSGConfig,
                   w_search: jax.Array, dtype=jnp.float32) -> dict:
    """Non-trainable DSG buffers: projection matrix R and projected search
    weights f(W).  f(W) is refreshed every cfg.refresh_every steps by the
    training loop (refresh_fw), matching the paper's amortization."""
    k = proj_dim(d, f, cfg)
    r = projection.make_projection(key, k, d, dtype=dtype)
    fw = projection.project(r, w_search.astype(dtype))
    return {"r": r, "fw": fw}


def refresh_fw(state: dict, w_search: jax.Array) -> dict:
    return {"r": state["r"],
            "fw": projection.project(state["r"], w_search.astype(state["r"].dtype))}


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------

def drs_group_mask(x: jax.Array, state: dict, cfg: DSGConfig) -> jax.Array:
    """Per-token group mask (..., G) from the dimension-reduction search."""
    fx = projection.project_rows(state["r"], x)
    mask, _ = drs.drs_mask(fx, state["fw"], cfg.drs_cfg())
    return masks.freeze(mask)


def shared_topk_indices(x: jax.Array, state: dict, cfg: DSGConfig,
                        f: int) -> jax.Array:
    """Batch-shared selection ("gather_shared"): sum group scores over all
    token rows, then per-chunk top-k so the gather is shard-local and
    load-balanced.  Returns sorted kept-group indices (K',)."""
    fx = projection.project_rows(state["r"], x)
    virtual = jnp.einsum("...k,kn->...n", fx, state["fw"])
    scores = drs.group_scores(virtual, cfg.drs_cfg())
    scores = scores.reshape((-1, scores.shape[-1])).sum(axis=0)  # (G,)
    g = scores.shape[0]
    keep_total = drs.keep_groups(f, cfg.drs_cfg())
    n_chunks = max(1, cfg.n_chunks)
    if g % n_chunks != 0:
        n_chunks = 1
    per_chunk = max(1, keep_total // n_chunks)
    chunked = scores.reshape(n_chunks, g // n_chunks)
    _, local_idx = jax.lax.top_k(chunked, per_chunk)         # (C, kc)
    base = (jnp.arange(n_chunks) * (g // n_chunks))[:, None]
    idx = (local_idx + base).reshape(-1)
    return jax.lax.stop_gradient(jnp.sort(idx))


# ---------------------------------------------------------------------------
# forward paths
# ---------------------------------------------------------------------------

def swiglu_dense(p: dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    h = jax.nn.silu(g) * u
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


def swiglu_dsg_mask(p: dict, x: jax.Array, state: dict,
                    cfg: DSGConfig) -> jax.Array:
    """Paper-faithful per-token masked SwiGLU.  The mask zeroes whole neuron
    groups after the nonlinearity; backward error through w_down rows and
    gate/up columns of dropped groups is exactly zero (Algorithm 1)."""
    mask = drs_group_mask(x, state, cfg)                    # (..., G)
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    h = jax.nn.silu(g) * u
    h = masks.apply_expanded(h, mask, cfg.block)
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


def swiglu_dsg_gather(p: dict, x: jax.Array, state: dict,
                      cfg: DSGConfig) -> jax.Array:
    """Batch-shared gathered SwiGLU: computes only kept groups.

    FLOPs ~ (1-gamma) * dense; weight gather traffic ~ (1-gamma) of the
    weight bytes (HBM-side win too)."""
    d, f = p["w_gate"].shape
    b = cfg.block
    gct = f // b
    idx = shared_topk_indices(x, state, cfg, f)             # (K',)
    # leading-axis gathers: a middle-axis take gets rewritten by XLA into
    # a one-hot dot (observed: +3.5x HLO FLOPs, EXPERIMENTS.md §Perf A5);
    # transposing first keeps it a real gather.
    wg = p["w_gate"].reshape(d, gct, b).transpose(1, 0, 2)[idx]  # (K', d, b)
    wu = p["w_up"].reshape(d, gct, b).transpose(1, 0, 2)[idx]
    wd = p["w_down"].reshape(gct, b, d)[idx]                     # (K', b, d)
    g = jnp.einsum("...d,kdb->...kb", x, wg)
    u = jnp.einsum("...d,kdb->...kb", x, wu)
    h = jax.nn.silu(g) * u
    return jnp.einsum("...kb,kbd->...d", h, wd)


def swiglu_dsg_gather_sharded(p: dict, x: jax.Array, state: dict,
                              cfg: DSGConfig) -> jax.Array:
    """gather_shared under TP (EXPERIMENTS.md §Perf A8): each 'model' shard
    top-ks its LOCAL groups and gathers its LOCAL weight blocks inside
    shard_map — no cross-shard gather (the A5 failure mode: XLA rewrote a
    gather across the sharded F axis into a one-hot dot / weight
    all-gather).  Selection is balanced per shard by construction (the
    n_chunks semantics with chunks == shards), and the FLOP reduction
    ~ (1-gamma) is visible in the compiled HLO."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel import context as pctx

    ctx = pctx.current()
    mesh, ba = ctx.mesh, ctx.ax.batch
    d = p["w_gate"].shape[0]
    blk = cfg.block
    drs_cfg = cfg.drs_cfg()

    def body(x_l, wg, wu, wd, r, fw):
        f_loc = wg.shape[1]
        g_loc = f_loc // blk
        keep = max(1, int((1.0 - cfg.gamma) * g_loc + 0.999999))
        fx = projection.project_rows(r, x_l)
        virtual = jnp.einsum("...k,kn->...n", fx, fw)
        scores = drs.group_scores(virtual, drs_cfg)
        scores = scores.reshape(-1, g_loc).sum(0)              # (G_loc,)
        _, idx = jax.lax.top_k(scores, keep)
        idx = jax.lax.stop_gradient(jnp.sort(idx))
        wg3 = wg.reshape(d, g_loc, blk).transpose(1, 0, 2)[idx]
        wu3 = wu.reshape(d, g_loc, blk).transpose(1, 0, 2)[idx]
        wd3 = wd.reshape(g_loc, blk, d)[idx]
        g = jnp.einsum("...d,kdb->...kb", x_l, wg3)
        u = jnp.einsum("...d,kdb->...kb", x_l, wu3)
        h = jax.nn.silu(g) * u
        y = jnp.einsum("...kb,kbd->...d", h, wd3)
        return jax.lax.psum(y, "model")

    nd = (None,) * (x.ndim - 1)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(ba, *nd), P(None, "model"), P(None, "model"),
                  P("model", None), P(), P(None, "model")),
        out_specs=P(ba, *nd), check_vma=False,
    )(x, p["w_gate"], p["w_up"], p["w_down"], state["r"], state["fw"])


# ---------------------------------------------------------------------------
# group-CSR serving paths (core/sparse_mask.py representation)
# ---------------------------------------------------------------------------

def swiglu_csr_masked(p: dict, x: jax.Array, idx: jax.Array,
                      counts: jax.Array, *, block: int) -> jax.Array:
    """Masked-dense reference for a per-lane CSR selection: expand the
    index list back to a dense group mask and run the full matmuls — zero
    compute saving, the bitwise baseline the gather/kernel paths are
    pinned against.  x (B, S, d), idx (B, K), counts (B,)."""
    from repro.core import sparse_mask
    f = p["w_gate"].shape[1]
    mask = sparse_mask.csr_to_dense(idx, counts, f // block)   # (B, G)
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    h = jax.nn.silu(g) * u
    h = masks.apply_expanded(h, masks.freeze(mask[:, None, :]), block)
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


def swiglu_csr_gather(p: dict, x: jax.Array, idx: jax.Array,
                      counts: jax.Array, *, block: int) -> jax.Array:
    """XLA fallback: contract only the leading K = active-group bound
    blocks per lane (the paged-attention bounded-gather trick — K is a
    static pow2 bucket, so FLOPs scale with the bound, not F).  Per-lane
    patterns force a per-lane weight-block gather (B, K, d, block); the
    CSR Pallas kernel avoids materializing it — this path is the
    non-Mosaic fallback.  Padded slots (>= counts) are zeroed before the
    down-projection, so the result matches swiglu_csr_masked."""
    d, f = p["w_gate"].shape
    b = idx.shape[0]
    k = idx.shape[-1]
    # flat column gather: expand the group list to neuron columns and
    # take along the weights' LAST axis (rows for w_down).  Copy volume
    # is B * K * block columns — it scales with the bound, unlike a
    # transpose-first group gather, whose (d, G, block) -> (G, d, block)
    # shuffle re-copies the FULL weight every decode step.  (Middle-axis
    # takes are still the A5 trap — XLA turns them into one-hot dots.)
    cols = (idx[..., None] * block
            + jnp.arange(block)).reshape(b, k * block)         # (B, KB)
    wg = jnp.take(p["w_gate"], cols, axis=1)                   # (d, B, KB)
    wu = jnp.take(p["w_up"], cols, axis=1)
    wd = jnp.take(p["w_down"], cols, axis=0)                   # (B, KB, d)
    g = jnp.einsum("bsd,dbm->bsm", x, wg)
    u = jnp.einsum("bsd,dbm->bsm", x, wu)
    h = jax.nn.silu(g) * u                                     # (B, S, KB)
    valid = (jnp.arange(k) < counts[:, None]).astype(h.dtype)  # (B, K)
    h = h * jnp.repeat(valid, block, axis=-1)[:, None, :]
    return jnp.einsum("bsm,bmd->bsd", h, wd)


def swiglu_csr(p: dict, x: jax.Array, idx: jax.Array, counts: jax.Array,
               *, block: int, apply: str = "auto") -> jax.Array:
    """Group-CSR SwiGLU dispatch (models/transformer._ffn_apply serving
    path).  `apply`: "dense" masked-dense reference, "xla" bounded
    gather, "kernel" Pallas index-list walk (kernels/dsg_ffn.dsg_ffn_csr,
    decode only: S == 1), "auto" = kernel where Mosaic compiles it."""
    b, s, d = x.shape
    if apply == "auto":
        apply = ("kernel" if jax.default_backend() == "tpu" and s == 1
                 else "xla")
    if apply == "dense":
        return swiglu_csr_masked(p, x, idx, counts, block=block)
    if apply == "xla":
        return swiglu_csr_gather(p, x, idx, counts, block=block)
    if apply != "kernel":
        raise ValueError(f"unknown CSR FFN apply mode {apply!r}")
    if s != 1:
        raise ValueError(
            f"CSR FFN kernel is a decode step (one token per lane), got "
            f"S={s}; use apply='xla' for multi-token rows")
    from repro.kernels import ops
    y = ops.dsg_ffn_csr(x[:, 0], p["w_gate"], p["w_up"], p["w_down"],
                        idx, counts, block=block)
    return y[:, None, :]


def swiglu_ffn(p: dict, x: jax.Array, state: Optional[dict],
               cfg: DSGConfig) -> jax.Array:
    if not cfg.enabled or state is None:
        return swiglu_dense(p, x)
    if cfg.mode == "gather_shared":
        from repro.parallel import context as pctx
        ctx = pctx.current()
        f = p["w_gate"].shape[1]
        if (ctx is not None and ctx.n_model > 1
                and f % (ctx.n_model * cfg.block) == 0):
            return swiglu_dsg_gather_sharded(p, x, state, cfg)
        return swiglu_dsg_gather(p, x, state, cfg)
    return swiglu_dsg_mask(p, x, state, cfg)


def gelu_dense(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, p["w_up"]))
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


def gelu_ffn(p: dict, x: jax.Array, state: Optional[dict],
             cfg: DSGConfig) -> jax.Array:
    """GELU FFN (whisper) with DSG on the up projection."""
    if not cfg.enabled or state is None:
        return gelu_dense(p, x)
    if cfg.mode == "gather_shared":
        d, f = p["w_up"].shape
        b = cfg.block
        idx = shared_topk_indices(x, state, cfg, f)
        wu = p["w_up"].reshape(d, f // b, b)[:, idx]
        wd = p["w_down"].reshape(f // b, b, d)[idx]
        h = jax.nn.gelu(jnp.einsum("...d,dkb->...kb", x, wu))
        return jnp.einsum("...kb,kbd->...d", h, wd)
    mask = drs_group_mask(x, state, cfg)
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, p["w_up"]))
    h = masks.apply_expanded(h, mask, cfg.block)
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


def search_weight(p: dict) -> jax.Array:
    """Which weight the DRS estimates against: the gate path if present
    (SiLU argument decides the activation magnitude), else the up path."""
    return p["w_gate"] if "w_gate" in p else p["w_up"]
