"""Attention: GQA/MHA + RoPE, KV-cache decode, chunked (flash-style) path.

Sharding modes (DESIGN.md §6):
  * "head" — Megatron-style TP: q/o projections sharded by head over the
    'model' axis (requires n_heads % model_shards == 0); kv projections
    replicated when n_kv < model_shards (small fraction of FLOPs).
  * "seq"  — sequence-parallel self-attention for head counts that do not
    divide the model axis (llama3.2 24H, llama4 40H, llava 56H, whisper
    20H): queries sharded over sequence, KV gathered — works for any head
    count and keeps FLOPs fully partitioned.

The decode KV cache is always sequence-sharded over 'model'
(flash-decode-style split-KV; the softmax reduction over the sharded key
axis becomes a cross-shard LSE combine inserted by SPMD partitioning).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init
from repro.parallel import context as pctx

NEG = -1e30


class AttnParams(NamedTuple):
    wq: jax.Array   # (d, H, hd)
    wk: jax.Array   # (d, Kv, hd)
    wv: jax.Array   # (d, Kv, hd)
    wo: jax.Array   # (H, hd, d)


def init_attention(key: jax.Array, d: int, n_heads: int, n_kv: int,
                   head_dim: int, dtype=jnp.float32) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d, n_heads, head_dim), fan_in=d, dtype=dtype),
        "wk": dense_init(kk, (d, n_kv, head_dim), fan_in=d, dtype=dtype),
        "wv": dense_init(kv, (d, n_kv, head_dim), fan_in=d, dtype=dtype),
        "wo": dense_init(ko, (n_heads, head_dim, d),
                         fan_in=n_heads * head_dim, dtype=dtype),
    }


def _mask(q_pos: jax.Array, kv_pos: jax.Array, causal: bool,
          window: int) -> jax.Array:
    """Boolean validity mask from absolute positions.

    q_pos (S,) -> (S, T); per-lane q_pos (B, S) -> (B, S, T) (continuous
    batching: each lane decodes at its own position)."""
    qp = q_pos[..., :, None]
    kp = kv_pos[None, :]
    shape = jnp.broadcast_shapes(qp.shape, kp.shape)
    m = jnp.broadcast_to(kp >= 0, shape)   # ring-buffer slots not yet written
    if causal:
        m = m & (kp <= qp)
    if window > 0:
        m = m & (kp > qp - window)
    return m


def repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, T, Kv, D) -> (B, T, H, D) by repeating each kv head H/Kv times."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def attend_direct(q: jax.Array, k: jax.Array, v: jax.Array,
                  q_pos: jax.Array, kv_pos: jax.Array,
                  causal: bool, window: int,
                  bf16_scores: bool = False) -> jax.Array:
    """Direct softmax attention; q (B,S,H,D), k/v (B,T,H,D).

    bf16_scores (EXPERIMENTS.md §Perf A6): keep the (B,H,S,T) score and
    probability tensors in bf16 (softmax max/sum statistics in f32) —
    halves the dominant attention HBM traffic; standard flash-kernel
    numerics."""
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d)
    m = _mask(q_pos, kv_pos, causal, window)
    # (S,T) masks broadcast over (B,H); per-lane (B,S,T) masks over H only
    m = m[:, None] if m.ndim == 3 else m[None, None]
    if bf16_scores and q.dtype == jnp.bfloat16:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.bfloat16) * scale
        s = jnp.where(m, s, jnp.bfloat16(NEG))
        mx = jnp.max(s.astype(jnp.float32), axis=-1, keepdims=True)
        p = jnp.exp(s.astype(jnp.float32) - mx)
        p = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(jnp.bfloat16)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return o.astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(m, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def attend_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                   q_pos: jax.Array, kv_pos: jax.Array,
                   causal: bool, window: int,
                   q_chunk: int = 1024, kv_chunk: int = 1024,
                   q_spec=None) -> jax.Array:
    """Flash-style online-softmax attention, double-chunked via lax.scan.

    Keeps the live score tile at (B,H,q_chunk,kv_chunk) — required for the
    32k/500k shapes where the dense (S,T) score matrix cannot exist.
    """
    b, s_len, h, d = q.shape
    t_len = k.shape[1]
    q_chunk = min(q_chunk, s_len)
    kv_chunk = min(kv_chunk, t_len)
    nq, nk = s_len // q_chunk, t_len // kv_chunk
    assert nq * q_chunk == s_len and nk * kv_chunk == t_len, (
        f"chunking must tile exactly: {s_len}/{q_chunk}, {t_len}/{kv_chunk}")

    qc = jnp.moveaxis(q.reshape(b, nq, q_chunk, h, d), 1, 0)
    kc = jnp.moveaxis(k.reshape(b, nk, kv_chunk, h, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nk, kv_chunk, h, d), 1, 0)
    qpc = q_pos.reshape(nq, q_chunk)
    kpc = kv_pos.reshape(nk, kv_chunk)
    scale = 1.0 / math.sqrt(d)

    def q_body(_, qi):
        q_i, qpos_i = qi
        if q_spec is not None:
            # per-chunk sharding constraint (seq/head parallel attention)
            q_i = pctx.constrain(q_i, *q_spec)

        def kv_body(carry, ki):
            k_j, v_j, kpos_j = ki
            m_run, l_run, acc = carry
            s = jnp.einsum("bqhd,bkhd->bhqk", q_i.astype(jnp.float32),
                           k_j.astype(jnp.float32)) * scale
            msk = _mask(qpos_i, kpos_j, causal, window)[None, None]
            s = jnp.where(msk, s, NEG)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None]) * msk
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_j.astype(jnp.float32))
            return (m_new, l_new, acc), None

        init = (jnp.full((b, h, q_chunk), NEG, jnp.float32),
                jnp.zeros((b, h, q_chunk), jnp.float32),
                jnp.zeros((b, h, q_chunk, d), jnp.float32))
        (m_run, l_run, acc), _ = jax.lax.scan(kv_body, init, (kc, vc, kpc))
        out = acc / jnp.maximum(l_run, 1e-20)[..., None]
        return None, jnp.moveaxis(out, 1, 2)          # (b, q_chunk, h, d)

    _, out = jax.lax.scan(q_body, None, (qc, qpc))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s_len, h, d)
    return out.astype(q.dtype)


# --- full layers -------------------------------------------------------------

CHUNK_THRESHOLD = 1 << 24   # S*T above which the chunked path is used


def _use_paged_kernel(mode: str) -> bool:
    """Resolve the paged decode executor: "kernel" forces the Pallas
    kernel (interpret mode on CPU), "xla" forces the whole-table gather
    fallback, "auto" picks the kernel only where Mosaic compiles it."""
    if mode == "auto":
        return jax.default_backend() == "tpu"
    if mode not in ("kernel", "xla"):
        raise ValueError(f"unknown paged_attn_kernel mode {mode!r}")
    return mode == "kernel"


def self_attention(p: dict, x: jax.Array, *, n_heads: int, n_kv: int,
                   rope_theta: float, q_pos: jax.Array,
                   causal: bool = True, window: int = 0,
                   cache: Optional[dict] = None,
                   cache_pos: Optional[jax.Array] = None,
                   cache_kv_pos: Optional[jax.Array] = None,
                   page_table: Optional[jax.Array] = None,
                   layer: Optional[jax.Array] = None,
                   paged_kernel: str = "auto",
                   shard: str = "auto", bf16_scores: bool = False):
    """Self-attention over x (B, S, d).

    Training / prefill: cache=None -> returns (out, new_kv) where new_kv is
    the (B, S, Kv, D) tensors (prefill stores them into the cache).
    Decode: cache={'k','v'} of (B, Smax, Kv, D), cache_pos = write position
    (ring-buffer slot for windowed caches) — a scalar shared by the batch,
    or a per-lane (B,) vector for continuous batching where every slot sits
    at its own depth (q_pos is then (B, S)).  cache_kv_pos = absolute
    positions held by each cache slot (defaults to arange(Smax)) -> returns
    (out, updated_cache).

    Paged decode (serving/kv_cache.py PagedBackend): page_table is the
    per-lane (B, max_pages) int32 map, cache={'k','v'} are every layer's
    physical page pools (L, P, page_size, Kv, D), `layer` is the index of
    this layer's pool in them, and cache_pos carries the per-lane depths;
    the updated stacks come back whole.  Two executors behind
    `paged_kernel` (see _use_paged_kernel), both addressing the stacks in
    place at `layer`:

      * Pallas kernel (kernels/paged_attention.py): fused scatter +
        a per-lane walk over blocks of whole pages + flash decode — each
        lane reads and spends work on only its pages at or below
        `cache_pos`.
      * XLA fallback (the CPU path, and the kernel tests' reference):
        scatter through the page table, then gather the whole table.

    In both, logical positions beyond a lane's depth read junk
    (unallocated rows point at the scratch page) but are masked by
    `kp <= qp` exactly as unwritten dense slots are.  Per-lane
    single-token decode only.
    """
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    rope_pos = q_pos if q_pos.ndim == 2 else q_pos[None, :]
    q = apply_rope(q, rope_pos, rope_theta) if rope_theta > 0 else q
    k_new = (apply_rope(k_new, rope_pos, rope_theta)
             if rope_theta > 0 else k_new)

    paged = page_table is not None
    if cache is None:
        if paged:
            raise NotImplementedError(
                "paged KV cache has no prefill path: prefill runs on a "
                "dense 1-lane cache and is spliced in by the backend")
        k, v = k_new, v_new
        kv_pos = q_pos
    elif paged:
        if s != 1 or jnp.ndim(cache_pos) != 1:
            raise NotImplementedError(
                "paged KV cache supports per-lane single-token decode only")
        if layer is None:
            raise ValueError("paged decode needs the layer index of its "
                             "pool in the stacked pools")
        ps_sz = cache["k"].shape[2]
        if _use_paged_kernel(paged_kernel):
            from repro.kernels import ops as kernel_ops
            o, pk, pv = kernel_ops.paged_decode_attention(
                q[:, 0], k_new[:, 0], v_new[:, 0], cache["k"], cache["v"],
                page_table, cache_pos, layer, window=window)
            out = jnp.einsum("bshk,hkd->bsd", o[:, None], p["wo"])
            # pool sharding is deferred to the kernel's page addressing
            return out, {"k": pk, "v": pv}
        lanes = jnp.arange(b)
        pp = page_table[lanes, cache_pos // ps_sz]
        off = cache_pos % ps_sz
        pk = cache["k"].at[layer, pp, off].set(
            k_new[:, 0].astype(cache["k"].dtype))
        pv = cache["v"].at[layer, pp, off].set(
            v_new[:, 0].astype(cache["v"].dtype))
        t = jnp.arange(page_table.shape[1] * ps_sz)
        k = pk[layer, page_table[:, t // ps_sz], t % ps_sz]
        v = pv[layer, page_table[:, t // ps_sz], t % ps_sz]
        kv_pos = (cache_kv_pos[..., :t.shape[0]]
                  if cache_kv_pos is not None else t)
    elif jnp.ndim(cache_pos) == 1:
        # per-lane scatter: lane i writes its tokens at its own position
        upd = jax.vmap(
            lambda c, n, pp: jax.lax.dynamic_update_slice(c, n, (pp, 0, 0)))
        k = upd(cache["k"], k_new.astype(cache["k"].dtype), cache_pos)
        v = upd(cache["v"], v_new.astype(cache["v"].dtype), cache_pos)
        kv_pos = (cache_kv_pos if cache_kv_pos is not None
                  else jnp.arange(k.shape[1]))
    else:
        k = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, cache_pos, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, cache_pos, 0, 0))
        kv_pos = (cache_kv_pos if cache_kv_pos is not None
                  else jnp.arange(k.shape[1]))

    kf = repeat_kv(k, n_heads)
    vf = repeat_kv(v, n_heads)

    # --- SPMD sharding constraints (DESIGN.md §6) ---
    mode = pctx.resolve_attn_shard(shard, n_heads)
    ba = pctx.batch_axes()
    q_spec = None
    decode = cache is not None and s == 1
    if mode != "none":
        if decode:
            # split-KV decode: cache sequence-sharded over 'model'
            q = pctx.constrain(q, ba, None, None, None)
            kf = pctx.constrain(kf, ba, "model", None, None)
            vf = pctx.constrain(vf, ba, "model", None, None)
        elif mode == "head":
            q_spec = (ba, None, "model", None)
            q = pctx.constrain(q, *q_spec)
            kf = pctx.constrain(kf, ba, None, "model", None)
            vf = pctx.constrain(vf, ba, None, "model", None)
        else:  # seq-parallel: queries sharded over sequence, KV gathered
            q_spec = (ba, "model", None, None)
            q = pctx.constrain(q, *q_spec)
            kf = pctx.constrain(kf, ba, None, None, None)
            vf = pctx.constrain(vf, ba, None, None, None)

    # chunked path only handles batch-shared positions; per-lane decode
    # (q_pos 2-D) is always tiny (s == 1) and never needs it
    if s * kf.shape[1] > CHUNK_THRESHOLD and q_pos.ndim == 1:
        o = attend_chunked(q, kf, vf, q_pos, kv_pos, causal, window,
                           q_spec=q_spec)
    else:
        o = attend_direct(q, kf, vf, q_pos, kv_pos, causal, window,
                          bf16_scores=bf16_scores)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if cache is None:
        return out, {"k": k_new, "v": v_new}
    if paged:
        # the updated pools go back as-is (the page table addresses them);
        # pool sharding is deferred to a sharded variant of the paged
        # decode kernel (kernels/paged_attention.py)
        return out, {"k": pk, "v": pv}
    if mode != "none":
        k = pctx.constrain(k, ba, "model", None, None)
        v = pctx.constrain(v, ba, "model", None, None)
    return out, {"k": k, "v": v}


def cross_attention(p: dict, x: jax.Array, mem_k: jax.Array,
                    mem_v: jax.Array, *, n_heads: int,
                    q_pos: jax.Array) -> jax.Array:
    """Decoder cross-attention over precomputed encoder K/V (B, T, Kv, D)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kf = repeat_kv(mem_k, n_heads)
    vf = repeat_kv(mem_v, n_heads)
    kv_pos = jnp.arange(kf.shape[1])
    s = x.shape[1]
    if s * kf.shape[1] > CHUNK_THRESHOLD:
        o = attend_chunked(q, kf, vf, q_pos, kv_pos, causal=False, window=0)
    else:
        o = attend_direct(q, kf, vf, q_pos, kv_pos, causal=False, window=0)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def memory_kv(p: dict, memory: jax.Array) -> tuple:
    """Encoder-memory K/V for cross-attention (computed once at prefill)."""
    k = jnp.einsum("btd,dhk->bthk", memory, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", memory, p["wv"])
    return k, v
