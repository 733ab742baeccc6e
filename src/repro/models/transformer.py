"""Decoder-only transformer assembly (dense / MoE / VLM families).

Layers are stacked (L, ...) pytrees scanned with lax.scan — HLO size is
depth-independent (required for the 512-device dry-run compiles) and remat
wraps the scan body.  A MoE model's leading dense-FFN layers
(`cfg.n_dense_layers`, DeepSeek's first_k_dense_replace) are a second stack,
`params["dense_layers"]`, with a scan of its own ahead of the main one;
`params["layers"]` holds the rest.  Layer indices (the paged pools' first
axis) are global across both.  The DSG state mirrors the main layer stack:
one shared projection R (d -> k) plus per-layer f(W) buffers refreshed by
the training loop every cfg.dsg.refresh_every steps.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.core import dsg_linear as dl
from repro.core import projection
from repro.models import attention as attn
from repro.models import mla
from repro.models import moe as moe_mod
from repro.models.layers import embed_init, norm_apply, norm_init


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key: jax.Array, cfg: ModelConfig, dense: bool = False) -> dict:
    """One layer: attention and the FFN, or MoE for a MoE model unless
    `dense` (a leading dense layer)."""
    ka, kf = jax.random.split(key)
    dt = _dtype(cfg)
    p = {
        "ln_attn": norm_init(cfg.norm, cfg.d_model, dt),
        "attn": (mla.init_mla(ka, cfg, dt) if cfg.is_mla else
                 attn.init_attention(ka, cfg.d_model, cfg.n_heads, cfg.n_kv,
                                     cfg.head_dim, dt)),
        "ln_ffn": norm_init(cfg.norm, cfg.d_model, dt),
    }
    if cfg.is_moe and not dense:
        p["moe"] = moe_mod.init_moe(kf, cfg.d_model, cfg.moe_experts,
                                    cfg.moe_d_ff, cfg.moe_shared, dt,
                                    n_held=cfg.moe_held)
    else:
        p["ffn"] = dl.init_swiglu(kf, cfg.d_model, cfg.d_ff, dt)
    return p


def init_model(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, kl, kh = jax.random.split(key, 3)
    dt = _dtype(cfg)
    layer_keys = jax.random.split(kl, cfg.n_layers - cfg.n_dense_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    p = {
        "embed": embed_init(ke, cfg.vocab, cfg.d_model, dt),
        "layers": layers,
        "ln_final": norm_init(cfg.norm, cfg.d_model, dt),
    }
    if cfg.n_dense_layers:
        dense_keys = jax.random.split(jax.random.fold_in(kl, 1),
                                      cfg.n_dense_layers)
        p["dense_layers"] = jax.vmap(
            lambda k: init_layer(k, cfg, dense=True))(dense_keys)
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(kh, (cfg.d_model, cfg.vocab))
                        / math.sqrt(cfg.d_model)).astype(dt)
    return p


def init_dsg(key: jax.Array, params: dict, cfg: ModelConfig) -> Optional[dict]:
    """DSG buffers: shared R + per-layer f(W) stacks of the main layer
    stack (DESIGN.md §5); leading dense layers run their FFN dense."""
    if not cfg.dsg.enabled:
        return None
    dt = _dtype(cfg)
    if cfg.is_moe:
        fe = cfg.moe_d_ff
        k = dl.proj_dim(cfg.d_model, fe, cfg.dsg)
        r = projection.make_projection(key, k, cfg.d_model, dtype=dt)
        st = {"r": r}
        st["fw_experts"] = jnp.einsum(
            "kd,ledf->lekf", r, params["layers"]["moe"]["w_gate"])
        if cfg.moe_shared > 0:
            st["fw_shared"] = jnp.einsum(
                "kd,ldf->lkf", r, params["layers"]["moe"]["shared"]["w_gate"])
        return st
    k = dl.proj_dim(cfg.d_model, cfg.d_ff, cfg.dsg)
    r = projection.make_projection(key, k, cfg.d_model, dtype=dt)
    fw = jnp.einsum("kd,ldf->lkf", r, params["layers"]["ffn"]["w_gate"])
    return {"r": r, "fw": fw}


def refresh_dsg(dsg: dict, params: dict, cfg: ModelConfig) -> dict:
    """Recompute f(W) from current weights (paper: every 50 steps)."""
    if dsg is None:
        return None
    out = {"r": dsg["r"]}
    if cfg.is_moe:
        out["fw_experts"] = jnp.einsum(
            "kd,ledf->lekf", dsg["r"], params["layers"]["moe"]["w_gate"])
        if "fw_shared" in dsg:
            out["fw_shared"] = jnp.einsum(
                "kd,ldf->lkf", dsg["r"],
                params["layers"]["moe"]["shared"]["w_gate"])
    else:
        out["fw"] = jnp.einsum("kd,ldf->lkf", dsg["r"],
                               params["layers"]["ffn"]["w_gate"])
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _layer_dsg(dsg: Optional[dict], cfg: ModelConfig):
    """Slice the per-layer DSG leaves for scan xs (r stays shared)."""
    if dsg is None:
        return None
    return {k: v for k, v in dsg.items() if k != "r"}


def _ffn_apply(p: dict, dsg_l: Optional[dict], r: Optional[jax.Array],
               x: jax.Array, cfg: ModelConfig, mesh, batch_axes,
               csr_l: Optional[dict] = None,
               count: Optional[jax.Array] = None,
               experts: Optional[dict] = None, moe_layer=None):
    """FFN or MoE with DSG; returns (y, aux, MoE stats or None).

    experts: a served MoE forward's routed-expert stacks (forward); the
    layer then routes dropless over the held experts of MoE layer
    `moe_layer` of them (models/moe.py, `moe_ffn_dropless`) and counts
    the rows of the tokens where `count` holds.

    csr_l: this layer's group-CSR selection {'idx': (B, K),
    'counts': (B,)} from the serving DSG runtime — when present the FFN
    contracts only the listed groups (core/dsg_linear.swiglu_csr: masked
    dense reference, bounded XLA gather, or the CSR Pallas kernel per
    cfg.dsg_ffn_apply) instead of running DRS online per token."""
    if csr_l is not None:
        if "moe" in p:
            raise NotImplementedError(
                "group-CSR serving selection targets the dense-FFN "
                "family; MoE experts are already conditional compute")
        y = dl.swiglu_csr(p["ffn"], x, csr_l["idx"], csr_l["counts"],
                          block=cfg.dsg.block, apply=cfg.dsg_ffn_apply)
        return y, jnp.float32(0.0), None
    if "moe" in p and experts is not None:
        y, stats = moe_mod.moe_ffn_dropless(
            p["moe"], x, top_k=cfg.moe_topk, norm_topk=cfg.moe_norm_topk,
            expert_offset=cfg.moe_expert_offset, count=count,
            experts=experts, layer=moe_layer)
        return y, jnp.float32(0.0), stats
    if "moe" in p:
        dsg_state = None
        if dsg_l is not None:
            dsg_state = {"r": r, "fw_experts": dsg_l["fw_experts"]}
            if "fw_shared" in dsg_l:
                dsg_state["shared"] = {"r": r, "fw": dsg_l["fw_shared"]}
        y, aux = moe_mod.moe_ffn(
            p["moe"], x, n_experts=cfg.moe_experts, top_k=cfg.moe_topk,
            capacity_factor=cfg.moe_capacity_factor, dsg=cfg.dsg,
            dsg_state=dsg_state, mesh=mesh, batch_axes=batch_axes,
            aux_kind=cfg.moe_aux, norm_topk=cfg.moe_norm_topk,
            expert_offset=cfg.moe_expert_offset)
        return y, aux, None
    st = {"r": r, "fw": dsg_l["fw"]} if dsg_l is not None else None
    return dl.swiglu_ffn(p["ffn"], x, st, cfg.dsg), jnp.float32(0.0), None


def _drs_scores(h: jax.Array, r: jax.Array, fw: jax.Array,
                cfg: ModelConfig) -> jax.Array:
    """DRS group scores of the FFN input h (B, S, d) -> (B, S, G), on
    device through the Pallas search kernels (kernels/drs_search.py):
    f(h) = h @ R^T, then fused virtual-matmul + relu-sum group reduce.
    The serving DSG runtime reads these back once per refresh window to
    rewrite its CSR patterns (host bookkeeping lags the kernel, like the
    paged page-table mirror)."""
    from repro.kernels import ops as kernel_ops
    b, s, d = h.shape
    m = b * s
    bm = m if m % 128 else 128          # kernels assert m % bm == 0
    f = fw.shape[-1]
    bf = f if f % 512 else 512
    fx = kernel_ops.drs_project(h.reshape(m, d).astype(r.dtype), r, bm=bm)
    scores = kernel_ops.drs_scores(fx, fw, block=cfg.dsg.block, bm=bm,
                                   bf=bf)
    return scores.reshape(b, s, f // cfg.dsg.block)


def _block(p: dict, dsg_l, r, x, cfg: ModelConfig, q_pos, cache, cache_pos,
           page_table, mesh, batch_axes, csr_l=None,
           collect_scores: bool = False, layer=None, count=None,
           experts=None, moe_layer=None, fresh: bool = False):
    from repro.parallel import context as pctx

    def boundary(t):
        """Perf lever (EXPERIMENTS.md §Perf A1/A3): force the TP branch
        psum to land at the bf16 branch boundary.  A sharding constraint
        alone does NOT do it (partial-sum state is orthogonal to sharding
        and GSPMD defers the all-reduce past the fp32 cast inside the next
        norm — 2x wire bytes); an optimization barrier is a wall the
        partitioner cannot defer a pending reduction across."""
        if cfg.branch_constrain:
            return jax.lax.optimization_barrier(t)
        return t

    if cfg.seq_sharded_residual:
        # Megatron-SP: residual stream (== the remat stash) seq-sharded
        ba = pctx.batch_axes()
        x = pctx.constrain(x, ba, "model", None)
    h = norm_apply(cfg.norm, p["ln_attn"], x, cfg.norm_eps)
    if cfg.is_mla:
        a, new_cache = mla.self_attention(
            p["attn"], h, cfg, q_pos=q_pos, cache=cache, cache_pos=cache_pos,
            page_table=page_table, layer=layer, fresh=fresh)
    else:
        a, new_cache = attn.self_attention(
            p["attn"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            rope_theta=cfg.rope_theta, q_pos=q_pos, causal=True,
            window=cfg.window, cache=cache, cache_pos=cache_pos,
            page_table=page_table, layer=layer,
            paged_kernel=cfg.paged_attn_kernel, shard=cfg.attn_shard,
            bf16_scores=cfg.attn_bf16_scores)
    x = x + boundary(a)
    h = norm_apply(cfg.norm, p["ln_ffn"], x, cfg.norm_eps)
    scores = None
    if collect_scores:
        scores = _drs_scores(h, r, dsg_l["fw"], cfg)
    f, aux, stats = _ffn_apply(p, dsg_l, r, h, cfg, mesh, batch_axes, csr_l,
                               count, experts, moe_layer)
    x = x + boundary(f)
    if cfg.seq_sharded_residual:
        x = pctx.constrain(x, pctx.batch_axes(), "model", None)
    return x, new_cache, aux, scores, stats


def forward(params: dict, dsg: Optional[dict], cfg: ModelConfig,
            tokens: jax.Array, *, prefix_embeds: Optional[jax.Array] = None,
            cache: Optional[dict] = None, pos0=0,
            mesh: Optional[Mesh] = None, batch_axes=None,
            last_only: bool = False, ffn_csr: Optional[dict] = None,
            collect_drs_scores: bool = False,
            moe_count: Optional[jax.Array] = None):
    """tokens (B, S) -> (logits, new_cache, aux_loss)
    [+ drs_scores (L, B, S, G) when collect_drs_scores]
    [+ MoE stats (3,) int32 when moe_count is given to a MoE model].

    ffn_csr: serving DSG selection stacks {'idx': (L, B, K),
    'counts': (L, B)} — per-layer group-CSR patterns scanned alongside
    the layer params; the FFN contracts only the listed groups.
    collect_drs_scores (python-static): additionally return each layer's
    DRS group scores of the FFN input — the serving runtime's refresh
    reads them to rewrite patterns off the measured decode window.

    prefix_embeds (B, P, d): VLM stub patch embeddings, prepended.
    cache: stacked per-layer KV {'k': (L,B,Smax,Kv,D), 'v': ...} for decode
    (an MLA model's: {'c': (L,B,Smax,R)} latent rows, models/mla.py),
    or a paged-backend view {'pages_k': (L,P,ps,Kv,D), 'pages_v': ...,
    'page_table': (B, max_pages)} (MLA: {'pages_c': (L,P,ps,R), ...}; see
    serving/kv_cache.py; the page table is shared by all layers, so it
    rides outside the layer scan).  The
    dense cache is scanned as xs/ys; the paged pools ride the scan's carry
    and each layer's attention addresses them at its layer index, so
    the pools are updated in place and no layer's pool is sliced out of
    the stack and written back (kernels/paged_attention.py).
    pos0: scalar start position, or a per-lane (B,) vector for continuous
    batching (each batch lane decodes at its own depth).
    moe_count (B, S) bool: the tokens a served MoE forward counts (active
    lanes, true prompt tokens); the stats are the sums over the MoE
    layers of the rows routed to held experts, the held experts hit and
    each layer's largest group (models/moe.py, `moe_ffn_dropless`).
    """
    page_table = None
    if cache is not None and "page_table" in cache:
        page_table = cache["page_table"]
        cache = pool_leaves(cache)
    # a prompt written from position 0 (python-static): MLA's prefill
    fresh = isinstance(pos0, int) and pos0 == 0
    x = params["embed"].astype(_dtype(cfg))[tokens]
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    pos0 = jnp.asarray(pos0)
    if pos0.ndim == 1:
        q_pos = pos0[:, None] + jnp.arange(s)      # (B, S) per-lane
    else:
        q_pos = pos0 + jnp.arange(s)               # (S,)

    r = dsg["r"] if dsg is not None else None
    dsg_stack = _layer_dsg(dsg, cfg)
    n_dense = cfg.n_dense_layers
    if n_dense and (ffn_csr is not None or collect_drs_scores):
        raise NotImplementedError(
            "group-CSR selection and DRS scores serve the dense-FFN family")

    # served MoE: the routed experts' weights stay stacked, out of the
    # scanned params, and the expert kernel reads layer i of the stacks in
    # place (kernels/moe_experts.py); a slice per layer would be a copy
    stack, experts = params["layers"], None
    if cache is not None and cfg.is_moe:
        moe = stack["moe"]
        experts = {k: moe[k] for k in ("w_gate", "w_up", "w_down")}
        stack = {**stack, "moe": {k: v for k, v in moe.items()
                                  if k not in experts}}

    def body(carry, scanned):
        xc, pools = carry
        p_l, dsg_l, cache_l, layer, csr_l, i = scanned
        y, new_cache, aux, scores, stats = _block(
            p_l, dsg_l, r, xc, cfg, q_pos,
            cache_l if pools is None else pools, pos0, page_table,
            mesh, batch_axes, csr_l, collect_drs_scores, layer,
            moe_count, experts, i, fresh)
        if pools is None:
            return (y, None), (new_cache, aux, scores, stats)
        return (y, new_cache), (None, aux, scores, stats)

    if cfg.remat and cache is None:
        body = jax.checkpoint(body)

    def scan(carry, stack, dsg_s, first, n, csr, moe):
        """Layers first .. first + n - 1 of the model, one stack."""
        cache_xs = layer_xs = None
        if page_table is not None:
            layer_xs = jnp.arange(first, first + n, dtype=jnp.int32)
        elif cache is not None:
            cache_xs = cache if not n_dense else jax.tree.map(
                lambda a: a[first:first + n], cache)
        local = jnp.arange(n, dtype=jnp.int32) if moe else None
        return jax.lax.scan(body, carry,
                            (stack, dsg_s, cache_xs, layer_xs, csr, local))

    carry = (x, cache if page_table is not None else None)
    if n_dense:
        carry, (dense_cache, _, _, _) = scan(
            carry, params["dense_layers"], None, 0, n_dense, None, False)
    (x, pools), (new_cache, aux, drs_scores, stats) = scan(
        carry, stack, dsg_stack, n_dense, cfg.n_layers - n_dense, ffn_csr,
        experts is not None)
    if page_table is not None:
        new_cache = {**{POOL + k: v for k, v in pools.items()},
                     "page_table": page_table}
    elif cache is not None and n_dense:
        new_cache = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                 dense_cache, new_cache)
    x = norm_apply(cfg.norm, params["ln_final"], x, cfg.norm_eps)
    if last_only:
        x = x[:, -1:]
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(_dtype(cfg))
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    out = (logits, new_cache, jnp.sum(aux))
    if collect_drs_scores:
        out += (drs_scores,)
    if moe_count is not None and cfg.is_moe:
        out += (jnp.sum(stats, axis=0),)
    return out


# ---------------------------------------------------------------------------
# task-level steps
# ---------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = lse - gold
    if mask is not None:
        return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(ce)


def train_loss(params: dict, dsg: Optional[dict], cfg: ModelConfig,
               batch: dict, mesh=None, batch_axes=None) -> jax.Array:
    tokens, labels = batch["tokens"], batch["labels"]
    prefix = batch.get("prefix_embeds")
    logits, _, aux = forward(params, dsg, cfg, tokens, prefix_embeds=prefix,
                             mesh=mesh, batch_axes=batch_axes)
    if prefix is not None:
        logits = logits[:, prefix.shape[1]:]
    return cross_entropy(logits, labels) + 0.01 * aux


#: prefix of a paged view's pool leaves: pool 'k' is the view's 'pages_k'
POOL = "pages_"


def pool_leaves(view: dict) -> dict:
    """The pools of a paged view by their cache names ({'k', 'v'}, or MLA's
    {'c'}), without the page table."""
    return {k[len(POOL):]: v for k, v in view.items() if k.startswith(POOL)}


def cache_row(cfg: ModelConfig) -> dict:
    """Trailing shape of one token's cache entry in each pool leaf: K and
    V (Kv, D) each, or an MLA model's one latent row (R,)."""
    if cfg.is_mla:
        return {"c": (mla.row_width(cfg),)}
    row = (cfg.n_kv, cfg.head_dim)
    return {"k": row, "v": row}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> dict:
    """Dense cache: each leaf (L, batch, max_seq, *row) (`cache_row`)."""
    return {k: jnp.zeros((cfg.n_layers, batch, max_seq) + row, dtype)
            for k, row in cache_row(cfg).items()}


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.float32) -> dict:
    """Physical page pool for the paged KV-cache backend
    (serving/kv_cache.py): each leaf (L, n_pages, page_size, *row) —
    K/V (Kv, D) each, or MLA's one latent row."""
    return {k: jnp.zeros((cfg.n_layers, n_pages, page_size) + row, dtype)
            for k, row in cache_row(cfg).items()}


def prefill(params, dsg, cfg: ModelConfig, tokens, cache,
            prefix_embeds=None, mesh=None, batch_axes=None,
            collect_drs_scores: bool = False, moe_count=None):
    """Prefill the cache with the prompt; returns (last_logits, cache)
    [+ last-token DRS scores (L, B, G) when collect_drs_scores — what the
    serving runtime seeds a lane's CSR pattern from at admission]
    [+ MoE stats (3,) over the tokens where moe_count holds (forward)]."""
    out = forward(params, dsg, cfg, tokens, prefix_embeds=prefix_embeds,
                  cache=cache, pos0=0, mesh=mesh, batch_axes=batch_axes,
                  last_only=True, collect_drs_scores=collect_drs_scores,
                  moe_count=moe_count)
    logits, new_kv = out[0], out[1]
    rest = out[3:]
    if collect_drs_scores:
        rest = (rest[0][:, :, -1],) + rest[1:]
    return (logits[:, -1], new_kv) + rest


def decode_step(params, dsg, cfg: ModelConfig, token, cache, pos,
                mesh=None, batch_axes=None,
                ffn_csr=None, collect_drs_scores: bool = False,
                moe_count=None):
    """One decode step.  token (B, 1), pos scalar or per-lane (B,) vector
    -> (logits (B, V), cache) [+ DRS scores (L, B, G) when
    collect_drs_scores] [+ MoE stats (3,) over the lanes where moe_count
    (B, 1) holds].  ffn_csr: per-layer group-CSR selection stacks (see
    forward)."""
    out = forward(params, dsg, cfg, token, cache=cache, pos0=pos,
                  mesh=mesh, batch_axes=batch_axes,
                  ffn_csr=ffn_csr, collect_drs_scores=collect_drs_scores,
                  moe_count=moe_count)
    rest = out[3:]
    if collect_drs_scores:
        rest = (rest[0][:, :, 0],) + rest[1:]
    return (out[0][:, -1], out[1]) + rest
