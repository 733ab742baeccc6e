"""Mixture-of-Experts FFN: capacity routing for training, dropless routing
over the held experts for serving.

Training dispatch is capacity-based gather/scatter (no (T, E, C) one-hot
einsum — that tensor is quadratically too large at pod scale): tokens get a
slot (expert, position) via a cumulative count, gathered into (E_local, C, d)
buffers, run through the expert matmuls, and scattered back weighted by the
router probability.  Tokens over capacity are dropped (standard Switch/GShard
behavior, capacity_factor controls headroom).

EP: expert weights are sharded over 'model'; the routed-FFN body runs inside
shard_map — every shard processes all of its data-parallel tokens for its
E/model_shards local experts, then a psum over 'model' combines expert
contributions (a token's top-k experts can live on different shards).

DSG composes *inside* each expert (DESIGN.md §3): per-expert f(W) buffers
estimate the expert's gate pre-activations and mask neuron groups — routing
gives coarse dynamic sparsity, DSG adds fine-grained intra-expert sparsity.

Serving (`moe_ffn_dropless`, every forward with a cache) drops no token: a
decode step's free lanes mirror an active lane, and under capacity their
rows would compete with the real ones, so a lane's output would depend on
the rest of the batch.  The router scores all `n_experts` experts in float32
(softmax, top-k, renormalised or not as configured), and every (token,
expert) pair whose expert is held here, `[offset, offset + held)`, becomes
a row of the grouped expert product (kernels/moe_experts.py): rows sorted
by held expert, each group padded to the kernel's row tile.  The static
row bound is tokens x min(top_k, held), so no routing can overflow it.
What the experts held elsewhere would add is left out: this is one chip's
share of an expert-parallel layer, run without its exchange.  The shared
experts are added once, on every chip.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import drs, masks
from repro.core.dsg_linear import (DSGConfig, init_swiglu, swiglu_dense,
                                   swiglu_ffn)
from repro.models.layers import dense_init


def init_moe(key: jax.Array, d: int, n_experts: int, d_ff_e: int,
             n_shared: int, dtype=jnp.float32, n_held: int = 0) -> dict:
    """Router over all `n_experts`; weights of the `n_held` experts this
    chip holds (all when 0)."""
    kr, ke, ks = jax.random.split(key, 3)
    keys = jax.random.split(ke, 3)
    sc_in, sc_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(d_ff_e)
    e = n_held or n_experts
    p = {
        "router": dense_init(kr, (d, n_experts), fan_in=d, dtype=jnp.float32),
        "w_gate": (jax.random.normal(keys[0], (e, d, d_ff_e)) * sc_in).astype(dtype),
        "w_up": (jax.random.normal(keys[1], (e, d, d_ff_e)) * sc_in).astype(dtype),
        "w_down": (jax.random.normal(keys[2], (e, d_ff_e, d)) * sc_out).astype(dtype),
    }
    if n_shared > 0:
        p["shared"] = init_swiglu(ks, d, n_shared * d_ff_e, dtype=dtype)
    return p


def route(logits: jax.Array, top_k: int, norm_topk: bool):
    """Router weights and experts (T, K): top-k of a float32 softmax over
    every expert, renormalised to sum to 1 when `norm_topk`."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)
    if norm_topk:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_w, top_e


def _routed_body(x2d: jax.Array, logits: jax.Array, w_gate: jax.Array,
                 w_up: jax.Array, w_down: jax.Array, e_start: jax.Array,
                 n_experts: int, top_k: int, capacity: int,
                 dsg_fw: Optional[jax.Array], dsg_r: Optional[jax.Array],
                 dsg: DSGConfig, norm_topk: bool = True) -> jax.Array:
    """Per-shard routed-expert compute.  x2d (T, d); expert weights are the
    E_local local experts starting at global index e_start."""
    t, d = x2d.shape
    e_local = w_gate.shape[0]
    top_w, top_e = route(logits, top_k, norm_topk)                 # (T, K)

    flat_e = top_e.reshape(-1)                                     # (T*K,)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), top_k)
    local_e = flat_e - e_start
    is_local = (local_e >= 0) & (local_e < e_local)
    local_e = jnp.where(is_local, local_e, e_local)                # sentinel

    # position of each entry within its expert queue (counts over T*K order)
    onehot = jax.nn.one_hot(local_e, e_local, dtype=jnp.int32)     # (T*K, E_l)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.sum(pos * onehot, axis=-1)                           # (T*K,)
    in_cap = is_local & (pos < capacity)
    slot_e = jnp.where(in_cap, local_e, e_local)                   # drop o.o.b.
    slot_p = jnp.where(in_cap, pos, 0)

    idx_buf = jnp.full((e_local + 1, capacity), t, dtype=jnp.int32)
    idx_buf = idx_buf.at[slot_e, slot_p].set(flat_tok, mode="drop")
    w_buf = jnp.zeros((e_local + 1, capacity), dtype=jnp.float32)
    w_buf = w_buf.at[slot_e, slot_p].set(flat_w, mode="drop")
    idx_buf, w_buf = idx_buf[:e_local], w_buf[:e_local]

    x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
    xg = x_pad[idx_buf]                                            # (E_l, C, d)
    g = jnp.einsum("ecd,edf->ecf", xg, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xg, w_up)
    h = jax.nn.silu(g) * u
    if dsg.enabled and dsg_fw is not None:
        # per-expert DRS: f(X) @ f(W_e) -> group mask over the expert's F dim
        fx = jnp.einsum("ecd,kd->eck", xg, dsg_r)
        virtual = jnp.einsum("eck,ekf->ecf", fx, dsg_fw)
        scores = drs.group_scores(virtual, dsg.drs_cfg())
        mask, _ = drs.select_mask(scores, h.shape[-1], dsg.drs_cfg())
        h = masks.apply_expanded(h, masks.freeze(mask), dsg.block)
    y = jnp.einsum("ecf,efd->ecd", h, w_down)                      # (E_l, C, d)

    out = jnp.zeros((t + 1, d), jnp.float32)
    out = out.at[idx_buf.reshape(-1)].add(
        (y * w_buf[..., None]).reshape(-1, d).astype(jnp.float32))
    return out[:t].astype(x2d.dtype)


def aux_load_balance_loss(logits: jax.Array, top_e: jax.Array,
                          n_experts: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = jnp.mean(probs.reshape(-1, n_experts), axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[..., 0].reshape(-1), n_experts), axis=0)
    return n_experts * jnp.sum(me * ce)


def aux_probs_loss(logits: jax.Array, n_experts: int) -> jax.Array:
    """Sort-free load-balance surrogate: n_E * sum(mean_prob^2) — minimized
    by a uniform router, no top-k/argmax needed (the global top_k in the
    'topk' variant forces the SPMD partitioner to replicate the (T, E)
    probabilities across the data axes: EXPERIMENTS.md §Perf B1)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = jnp.mean(probs.reshape(-1, n_experts), axis=0)
    return n_experts * jnp.sum(me * me)


def moe_ffn(p: dict, x: jax.Array, *, n_experts: int, top_k: int,
            capacity_factor: float, dsg: DSGConfig,
            dsg_state: Optional[dict] = None,
            mesh: Optional[Mesh] = None,
            batch_axes=None, aux_kind: str = "topk",
            norm_topk: bool = True, expert_offset: int = 0) -> tuple:
    """x (B, S, d) -> (y (B, S, d), aux_loss scalar).

    With a mesh carrying a 'model' axis, the routed body runs under
    shard_map (EP); otherwise it runs locally with the experts `p` holds,
    the first of them global expert `expert_offset`.
    """
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    logits = x2d.astype(jnp.float32) @ p["router"]
    if aux_kind == "probs":
        aux = aux_probs_loss(logits, n_experts)
    else:
        _, top_e = jax.lax.top_k(jax.nn.softmax(logits, -1), top_k)
        aux = aux_load_balance_loss(logits, top_e, n_experts)

    dsg_r = dsg_state["r"] if (dsg.enabled and dsg_state) else None
    dsg_fw = dsg_state["fw_experts"] if (dsg.enabled and dsg_state) else None

    use_ep = mesh is not None and "model" in mesh.axis_names and \
        mesh.shape["model"] > 1 and n_experts % mesh.shape["model"] == 0 \
        and p["w_gate"].shape[0] == n_experts
    if use_ep:
        n_shards = mesh.shape["model"]
        e_local = n_experts // n_shards
        t_local = x2d.shape[0] // max(
            1, math.prod(mesh.shape[a] for a in batch_axes or ()))
        capacity = max(1, int(capacity_factor * t_local * top_k / n_experts))

        def body(x_l, lg_l, wg, wu, wd, fw):
            e_start = jax.lax.axis_index("model") * e_local
            out = _routed_body(x_l, lg_l, wg, wu, wd, e_start, n_experts,
                               top_k, capacity, fw, dsg_r, dsg, norm_topk)
            return jax.lax.psum(out, "model")

        bspec = P(batch_axes, None)
        espec = P("model", None, None)
        fw_in = dsg_fw if dsg_fw is not None else \
            jnp.zeros((n_experts, 1, 1), x.dtype)
        y2d = jax.shard_map(
            body, mesh=mesh,
            in_specs=(bspec, bspec, espec, espec, espec, espec),
            out_specs=bspec, check_vma=False,
        )(x2d, logits, p["w_gate"], p["w_up"], p["w_down"], fw_in)
    else:
        capacity = max(1, int(capacity_factor * x2d.shape[0] * top_k
                              / n_experts))
        y2d = _routed_body(x2d, logits, p["w_gate"], p["w_up"], p["w_down"],
                           jnp.int32(expert_offset), n_experts, top_k,
                           capacity, dsg_fw, dsg_r, dsg, norm_topk)

    y = y2d.reshape(b, s, d)
    if "shared" in p:
        sh_state = dsg_state.get("shared") if dsg_state else None
        y = y + swiglu_ffn(p["shared"], x, sh_state, dsg)
    return y, aux


def group_rows(local_e: jax.Array, held: jax.Array, n_held: int, tile: int,
               bound: int):
    """Sorted layout of routed rows for the grouped expert product.

    local_e (R,): each (token, k) pair's held-expert index, valid where
    `held`; at most `bound` pairs are held.  Rows are ordered by held
    expert, and by pair order within one; each expert's group starts at
    a multiple of `tile`.  Returns (pos (R,): a held pair's row, `rows`
    for any other; starts, sizes (n_held,) int32; rows: the static row
    count, which every routing's padded groups fit)."""
    onehot = jax.nn.one_hot(jnp.where(held, local_e, n_held), n_held,
                            dtype=jnp.int32)                    # (R, E_h)
    sizes = onehot.sum(0)
    padded = (sizes + tile - 1) // tile * tile
    starts = jnp.cumsum(padded) - padded
    rank = jnp.sum((jnp.cumsum(onehot, 0) - onehot) * onehot, -1)
    rows = -(-(bound + n_held * (tile - 1)) // tile) * tile
    pos = jnp.where(held, starts[jnp.clip(local_e, 0, n_held - 1)] + rank,
                    rows)
    return pos, starts.astype(jnp.int32), sizes.astype(jnp.int32), rows


def moe_ffn_dropless(p: dict, x: jax.Array, *, top_k: int, norm_topk: bool,
                     expert_offset: int = 0,
                     count: Optional[jax.Array] = None,
                     experts: Optional[dict] = None, layer=0) -> tuple:
    """Dropless routing over the held experts (module docstring); DSG's
    intra-expert masks are a training-path feature and stay off here.

    The experts' weights are `p`'s, or layer `layer` of the stacks
    `experts` {'w_gate': (L, E, d, f), 'w_up', 'w_down'}: the model's
    layer scan passes them whole, so the kernel reads them in place.

    x (B, S, d) -> (y (B, S, d), stats (3,) int32): the rows routed to
    held experts, the held experts that got at least one row, and the
    largest row count of any held expert, over the tokens where `count`
    (B, S) holds (all when None).  It is a least-work count: the rows of
    mirrored lanes and padding, which the kernel computes too, stay out."""
    from repro.kernels import moe_experts as mk
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    t = x2d.shape[0]
    w = experts if experts is not None else p
    n_held = w["w_gate"].shape[-3]
    top_w, top_e = route(x2d.astype(jnp.float32) @ p["router"], top_k,
                         norm_topk)
    local = top_e.reshape(-1) - expert_offset                     # (T*K,)
    held = (local >= 0) & (local < n_held)
    # top-k experts are distinct: a token sends at most min(K, E_h) rows
    pos, starts, sizes, rows = group_rows(local, held, n_held, mk.TILE,
                                          t * min(top_k, n_held))
    tok = jnp.repeat(jnp.arange(t), top_k)
    xs = jnp.zeros((rows, d), x2d.dtype).at[pos].set(x2d[tok], mode="drop")
    ys = mk.grouped_swiglu(xs, starts, sizes, w["w_gate"], w["w_up"],
                           w["w_down"], layer)
    yp = jnp.where(held[:, None], ys[jnp.minimum(pos, rows - 1)], 0.0)
    y = jnp.sum((yp.astype(jnp.float32) * top_w.reshape(-1, 1))
                .reshape(t, top_k, d), axis=1).astype(x.dtype)
    y = y.reshape(b, s, d)
    if "shared" in p:
        y = y + swiglu_dense(p["shared"], x)

    valid = held
    if count is not None:
        valid = held & jnp.repeat(count.reshape(-1), top_k)
    per = jnp.sum(jax.nn.one_hot(jnp.where(valid, local, n_held), n_held,
                                 dtype=jnp.int32), axis=0)
    stats = jnp.stack([per.sum(), (per > 0).sum(), per.max()])
    return y, stats.astype(jnp.int32)
