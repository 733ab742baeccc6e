"""Multi-head latent attention (DeepSeek-V2's MLA), with YaRN RoPE.

Per layer, for the normed input h (B, S, d):

    q            = h W_q                 (H, nope + rope): [q_nope | q_pe]
    [c, k_pe]    = h W_kva               lat + rope; c = RMSNorm_kv(c)
    q_pe, k_pe   get YaRN RoPE; k_pe is one part shared by the H heads
    k_nope[h]    = c W_uk[h],  v[h] = c W_uv[h]       (W_kv_b, split)
    score        = (q_nope . k_nope + q_pe . k_pe) x scale
    out          = softmax(score) v, then W_o

with scale = (nope + rope)^-1/2 x m^2, m YaRN's temperature term.  What a
layer caches per token is one row, [c | k_pe] (`latent_row` wide), shared
by all heads; the rows are padded with zeros to a multiple of 128
(`row_width`), the lane tiling of the pool in HBM, so the decode kernel
copies whole tiles.

Prefill and training use the plain form above over the prompt's tokens.
Decode uses the absorbed form, which reads only the cached rows:

    q_lat[h] = q_nope[h] W_uk[h]^T       (lat)
    score    = [q_lat | q_pe] . [c | k_pe] x scale
    o_lat[h] = sum softmax(score) c,     o[h] = o_lat[h] W_uv[h]

The paged pool is {'c': (L, P, ps, row_width)}; the layer scan carries it
in place as it carries K/V pools, and the Pallas kernel `mla_decode`
(kernels/paged_attention.py) walks each lane's live pages in blocks, as
`paged_decode` does; the XLA fallback (the CPU path) gathers the whole
table.  The dense cache is {'c': (L, B, Smax, row_width)}.

Departures from the upstream code: W_kv_b is stored as its two halves,
W_uk and W_uv; the rotary pairs are de-interleaved at run time as upstream
does (`layers.apply_rope_pairs`), so the weights keep the published layout.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope_pairs, dense_init, rms_norm,
                                 rope_freqs, yarn_freqs, yarn_mscale)

NEG = -1e30
LANES = 128


def row_width(cfg) -> int:
    """Width of a cached row as stored: the latent row, padded to 128."""
    return -(-cfg.latent_row // LANES) * LANES


def init_mla(key: jax.Array, cfg, dtype=jnp.float32) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    lat, nope, rope, vd = (cfg.kv_lora_rank, cfg.qk_nope_dim,
                           cfg.qk_rope_dim, cfg.v_head_dim)
    kq, ka, ku, kv, ko = jax.random.split(key, 5)
    return {
        "wq": dense_init(kq, (d, h, nope + rope), fan_in=d, dtype=dtype),
        "w_kva": dense_init(ka, (d, lat + rope), fan_in=d, dtype=dtype),
        "kv_norm": {"scale": jnp.ones((lat,), dtype)},
        "w_uk": dense_init(ku, (lat, h, nope), fan_in=lat, dtype=dtype),
        "w_uv": dense_init(kv, (lat, h, vd), fan_in=lat, dtype=dtype),
        "wo": dense_init(ko, (h, vd, d), fan_in=h * vd, dtype=dtype),
    }


def rope_tables(cfg):
    """(inverse frequencies (rope/2,), cos/sin factor): YaRN's when
    `rope_factor` is set, else plain RoPE at `rope_theta`."""
    dim = cfg.qk_rope_dim
    if cfg.rope_factor <= 0:
        return rope_freqs(dim, cfg.rope_theta), 1.0
    inv = yarn_freqs(dim, cfg.rope_theta, cfg.rope_factor,
                     cfg.rope_original_max, cfg.rope_beta_fast,
                     cfg.rope_beta_slow)
    return inv, (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                 / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))


def softmax_scale(cfg) -> float:
    """(nope + rope)^-1/2, times YaRN's m^2 (m at `rope_mscale_all_dim`)."""
    s = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.rope_factor > 0 and cfg.rope_mscale_all_dim:
        s *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return s


def _project(p: dict, x: jax.Array, cfg, q_pos: jax.Array):
    """x (B, S, d) -> q_nope (B, S, H, nope), roped q_pe (B, S, H, rope),
    the cached rows (B, S, row_width) in x's dtype: [normed c | roped k_pe
    | zeros]."""
    nope, lat = cfg.qk_nope_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kva = jnp.einsum("bsd,dr->bsr", x, p["w_kva"])
    inv, msc = rope_tables(cfg)
    pos = q_pos if q_pos.ndim == 2 else q_pos[None, :]
    q_pe = apply_rope_pairs(q[..., nope:], pos, inv, msc).astype(x.dtype)
    k_pe = apply_rope_pairs(kva[..., None, lat:], pos, inv,
                            msc)[..., 0, :].astype(x.dtype)
    c = rms_norm(kva[..., :lat], p["kv_norm"]["scale"], cfg.norm_eps)
    pad = jnp.zeros(c.shape[:-1] + (row_width(cfg) - cfg.latent_row,),
                    x.dtype)
    return q[..., :nope], q_pe, jnp.concatenate([c, k_pe, pad], -1)


def _plain(p: dict, q_nope, q_pe, rows, cfg, q_pos):
    """The plain form over the tokens' own rows, causal: (B, S, d)."""
    lat, h = cfg.kv_lora_rank, cfg.n_heads
    c, k_pe = rows[..., :lat], rows[..., lat:cfg.latent_row]
    k_nope = jnp.einsum("bsc,chn->bshn", c, p["w_uk"])
    v = jnp.einsum("bsc,chv->bshv", c, p["w_uv"])
    q = jnp.concatenate([q_nope, q_pe], -1).astype(jnp.float32)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], k_pe.shape[:2] + (
            h, k_pe.shape[-1]))], -1).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * softmax_scale(cfg)
    qp = q_pos if q_pos.ndim == 2 else q_pos[None, :]
    causal = qp[:, :, None] >= qp[:, None, :]               # (B|1, S, S)
    s = jnp.where(causal[:, None], s, NEG)
    o = jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(s, -1),
                   v.astype(jnp.float32)).astype(q_nope.dtype)
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"])


def absorbed_query(p: dict, q_nope, q_pe, cfg):
    """One token's query against a cached row: (B, H, row_width),
    [q_nope W_uk^T | q_pe | zeros]."""
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, p["w_uk"])
    pad = jnp.zeros(q_lat.shape[:-1] + (row_width(cfg) - cfg.latent_row,),
                    q_lat.dtype)
    return jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype), pad], -1)


def _absorbed_out(p: dict, o_lat, x_dtype):
    """o_lat (B, H, lat) -> the layer's output (B, 1, d)."""
    o = jnp.einsum("bhc,chv->bhv", o_lat.astype(x_dtype), p["w_uv"])
    return jnp.einsum("bhv,hvd->bd", o, p["wo"])[:, None]


def attend_rows(qa, rows, valid, cfg):
    """The absorbed form over gathered rows (B, T, R) where valid (B, T)
    holds: o_lat (B, H, lat), f32."""
    s = jnp.einsum("bhr,btr->bht", qa.astype(jnp.float32),
                   rows.astype(jnp.float32)) * softmax_scale(cfg)
    s = jnp.where(valid[:, None], s, NEG)
    return jnp.einsum("bht,btc->bhc", jax.nn.softmax(s, -1),
                      rows[..., :cfg.kv_lora_rank].astype(jnp.float32))


def self_attention(p: dict, x: jax.Array, cfg, *, q_pos: jax.Array,
                   cache: Optional[dict] = None, cache_pos=None,
                   page_table: Optional[jax.Array] = None, layer=None,
                   fresh: bool = False):
    """MLA over x (B, S, d) -> (out (B, S, d), cache).

    cache None (training): the plain form, causal; returns the rows
    {'c': (B, S, row_width)}.  A dense cache {'c': (B, Smax, R)}: a prompt
    (S > 1) is written from position 0, which `fresh` (python-static) must
    vouch for, and attends itself in the plain form; a decode token
    (S == 1, cache_pos scalar or per-lane (B,)) writes its row and attends
    rows 0..cache_pos in the absorbed form.  Paged ({'c': (L, P, ps, R)},
    page_table (B, max_pages), layer): per-lane single-token decode
    through `mla_decode` (cfg.paged_attn_kernel, as for K/V) or the XLA
    fallback that gathers the whole table; the stacked pool comes back
    whole, written at `layer`."""
    from repro.models.attention import _use_paged_kernel
    b, s, _ = x.shape
    q_nope, q_pe, rows = _project(p, x, cfg, q_pos)
    if cache is None:
        return _plain(p, q_nope, q_pe, rows, cfg, q_pos), {"c": rows}
    pool = cache["c"]
    if page_table is None and s > 1:
        if not fresh:
            raise NotImplementedError(
                "an MLA prompt is written to a dense cache from position "
                "0 only (the engine's prefill)")
        c = jax.lax.dynamic_update_slice(pool, rows.astype(pool.dtype),
                                         (0, 0, 0))
        return _plain(p, q_nope, q_pe, rows, cfg, q_pos), {"c": c}
    if s != 1:
        raise NotImplementedError(
            "MLA decode over a cache is single-token")
    qa = absorbed_query(p, q_nope[:, 0], q_pe[:, 0], cfg)
    row = rows[:, 0].astype(pool.dtype)
    if page_table is None:
        if jnp.ndim(cache_pos) == 1:
            c = jax.vmap(lambda cl, r, pp: jax.lax.dynamic_update_slice(
                cl, r[None], (pp, 0)))(pool, row, cache_pos)
        else:
            c = jax.lax.dynamic_update_slice(pool, row[:, None],
                                             (0, cache_pos, 0))
        t = jnp.arange(c.shape[1])
        valid = t[None, :] <= jnp.broadcast_to(cache_pos, (b,))[:, None]
        o_lat = attend_rows(qa, c, valid, cfg)
        return _absorbed_out(p, o_lat, x.dtype), {"c": c}
    if jnp.ndim(cache_pos) != 1:
        raise NotImplementedError(
            "paged MLA decode is per-lane single-token")
    if layer is None:
        raise ValueError("paged decode needs the layer index of its pool "
                         "in the stacked pools")
    if _use_paged_kernel(cfg.paged_attn_kernel):
        from repro.kernels import ops as kernel_ops
        o_lat, c = kernel_ops.mla_decode_attention(
            qa, row, pool, page_table, cache_pos, layer,
            lat=cfg.kv_lora_rank, scale=softmax_scale(cfg))
        return _absorbed_out(p, o_lat, x.dtype), {"c": c}
    ps = pool.shape[2]
    lanes = jnp.arange(b)
    c = pool.at[layer, page_table[lanes, cache_pos // ps],
                cache_pos % ps].set(row)
    t = jnp.arange(page_table.shape[1] * ps)
    gathered = c[layer, page_table[:, t // ps], t % ps]       # (B, T, R)
    o_lat = attend_rows(qa, gathered, t[None, :] <= cache_pos[:, None], cfg)
    return _absorbed_out(p, o_lat, x.dtype), {"c": c}
