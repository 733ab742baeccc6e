"""Recurrent-family assemblies: xlstm-350m and zamba2-7b.

xLSTM: groups of (slstm_every - 1) mLSTM blocks + 1 sLSTM block, each
wrapped in an up(d->2d)/SiLU-gate/down(d->d) projection pair — the gate
half is the DSG site (DRS estimates the gate pre-activations and masks
neuron groups; masked groups skip gate columns and down-proj rows).

Zamba2: groups of `shared_attn_every` Mamba2 blocks followed by ONE shared
attention+FFN block (weight-shared across all groups, its own KV cache per
invocation).  DSG sites: the Mamba2 z-gate branch (DRS over z columns of
the fused in_proj) and the shared block's SwiGLU FFN.

Both are sub-quadratic in sequence length (chunked scans; the zamba shared
attention uses a sliding window for the long_500k shape) — these two archs
run the long_500k cell (DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import drs, masks, projection
from repro.core import dsg_linear as dl
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import xlstm as xl
from repro.models.layers import dense_init, embed_init, norm_apply, norm_init


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _gate_mask(x: jax.Array, r: jax.Array, fw: jax.Array,
               cfg: ModelConfig) -> jax.Array:
    """DRS over a gate branch: x (B,S,d) -> expanded neuron mask (B,S,F)."""
    fx = projection.project_rows(r, x)
    mask, _ = drs.drs_mask(fx, fw, cfg.dsg.drs_cfg())
    return drs.expand_mask(masks.freeze(mask), cfg.dsg.block).astype(x.dtype)


# ===========================================================================
# xLSTM
# ===========================================================================

def _xlstm_groups(cfg: ModelConfig):
    every = cfg.slstm_every or cfg.n_layers
    n_m = every - 1 if cfg.slstm_every else cfg.n_layers
    groups = max(1, cfg.n_layers // max(every, 1))
    return groups, n_m, bool(cfg.slstm_every)


def _init_wrap(key, d, dtype):
    ku, kd = jax.random.split(key)
    return {"ln": norm_init("rmsnorm", d, dtype),
            "w_up": dense_init(ku, (d, 2 * d), fan_in=d, dtype=dtype),
            "w_down": dense_init(kd, (d, d), fan_in=d, dtype=dtype)}


def init_xlstm_model(key: jax.Array, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    groups, n_m, has_s = _xlstm_groups(cfg)
    dm = xl.mlstm_dims(cfg.d_model, cfg.n_heads)
    ke, km, ks, kh = jax.random.split(key, 4)

    def init_m(k):
        k1, k2 = jax.random.split(k)
        return {"wrap": _init_wrap(k1, cfg.d_model, dt),
                "core": xl.init_mlstm(k2, dm, dt)}

    def init_s(k):
        k1, k2 = jax.random.split(k)
        return {"wrap": _init_wrap(k1, cfg.d_model, dt),
                "core": xl.init_slstm(k2, cfg.d_model, dt)}

    m_keys = jax.random.split(km, groups * n_m).reshape(groups, n_m, 2)
    p = {
        "embed": embed_init(ke, cfg.vocab, cfg.d_model, dt),
        "mlstm": jax.vmap(jax.vmap(init_m))(m_keys),
        "ln_final": norm_init("rmsnorm", cfg.d_model, dt),
        "lm_head": (jax.random.normal(kh, (cfg.d_model, cfg.vocab))
                    / math.sqrt(cfg.d_model)).astype(dt),
    }
    if has_s:
        s_keys = jax.random.split(ks, groups)
        p["slstm"] = jax.vmap(init_s)(s_keys)
    return p


def init_xlstm_dsg(key, params, cfg: ModelConfig) -> Optional[dict]:
    if not cfg.dsg.enabled:
        return None
    d = cfg.d_model
    k = dl.proj_dim(d, d, cfg.dsg)
    r = projection.make_projection(key, k, d, dtype=_dtype(cfg))

    def fw_of(wrap):  # gate half of w_up: (d, d)
        return jnp.einsum("kd,de->ke", r, wrap["w_up"][:, d:])

    st = {"r": r, "fw_m": jax.vmap(jax.vmap(fw_of))(params["mlstm"]["wrap"])}
    if "slstm" in params:
        st["fw_s"] = jax.vmap(fw_of)(params["slstm"]["wrap"])
    return st


def refresh_xlstm_dsg(dsg, params, cfg):
    if dsg is None:
        return None
    d = cfg.d_model
    r = dsg["r"]

    def fw_of(wrap):
        return jnp.einsum("kd,de->ke", r, wrap["w_up"][:, d:])

    out = {"r": r, "fw_m": jax.vmap(jax.vmap(fw_of))(params["mlstm"]["wrap"])}
    if "fw_s" in dsg:
        out["fw_s"] = jax.vmap(fw_of)(params["slstm"]["wrap"])
    return out


def _wrapped_block(wrap, core_apply, x, r, fw, cfg):
    """pre-norm -> up -> (core(a) * silu-gate(g)) -> down -> residual."""
    d = cfg.d_model
    h = norm_apply("rmsnorm", wrap["ln"], x)
    u = jnp.einsum("bsd,de->bse", h, wrap["w_up"])
    a, g = jnp.split(u, 2, axis=-1)
    y, new_state = core_apply(a)
    gate = jax.nn.silu(g)
    if fw is not None:
        gate = gate * _gate_mask(h, r, fw, cfg)
    out = jnp.einsum("bsd,de->bse", y * gate, wrap["w_down"])
    return x + out, new_state


def xlstm_forward(params, dsg, cfg: ModelConfig, tokens,
                  state: Optional[dict] = None, last_only=False):
    """tokens (B,S) -> (logits, new_state).  state carries mLSTM (c, n) and
    sLSTM scalar states for decode."""
    dt = _dtype(cfg)
    groups, n_m, has_s = _xlstm_groups(cfg)
    dm = xl.mlstm_dims(cfg.d_model, cfg.n_heads)
    x = params["embed"].astype(dt)[tokens]
    b = x.shape[0]
    r = dsg["r"] if dsg else None

    if state is None:
        zm = jnp.zeros((groups, n_m, b, dm.heads, dm.dk, dm.dv), jnp.float32)
        zn = jnp.ones((groups, n_m, b, dm.heads, dm.dk), jnp.float32)
        state = {"m_c": zm, "m_n": zn}
        if has_s:
            zs = jnp.zeros((groups, b, cfg.d_model), jnp.float32)
            state["s"] = {"c": zs, "n": zs + 1.0, "m": zs, "h": zs}

    def group_body(xc, scanned):
        p_m, fw_m, mc, mn, p_s, fw_s, s_state = scanned

        def m_body(xc2, sc):
            p_l, fw_l, c0, n0 = sc
            def core(a):
                return xl.mlstm_forward(p_l["core"], a, dm,
                                        {"c": c0, "n": n0})
            y, st = _wrapped_block(p_l["wrap"], core, xc2, r, fw_l, cfg)
            return y, (st["c"], st["n"])

        xc, (mc_new, mn_new) = jax.lax.scan(m_body, xc, (p_m, fw_m, mc, mn))
        new_s = s_state
        if has_s:
            def score(a):
                return xl.slstm_forward(p_s["core"], a, s_state)
            xc, new_s = _wrapped_block(p_s["wrap"], score, xc, r, fw_s, cfg)
        return xc, (mc_new, mn_new, new_s)

    if cfg.remat:
        group_body = jax.checkpoint(group_body)

    fw_m = dsg["fw_m"] if dsg else None
    fw_s = dsg.get("fw_s") if dsg else None
    p_s = params.get("slstm")
    s_state = state.get("s") if has_s else None
    x, (mc, mn, new_s) = jax.lax.scan(
        group_body, x,
        (params["mlstm"], fw_m, state["m_c"], state["m_n"], p_s, fw_s,
         s_state))
    new_state = {"m_c": mc, "m_n": mn}
    if has_s:
        new_state["s"] = new_s
    x = norm_apply("rmsnorm", params["ln_final"], x)
    if last_only:
        x = x[:, -1:]
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(dt))
    return logits, new_state


# ===========================================================================
# Zamba2
# ===========================================================================

def init_zamba_model(key: jax.Array, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    dm = m2.dims(cfg.d_model, cfg.ssm_expand, cfg.ssm_state, cfg.ssm_heads,
                 cfg.ssm_chunk)
    every = cfg.shared_attn_every
    groups = cfg.n_layers // every
    ke, km, ks, kh = jax.random.split(key, 4)

    def init_mblock(k):
        return {"ln": norm_init(cfg.norm, cfg.d_model, dt),
                "mamba": m2.init_mamba2(k, dm, dt)}

    m_keys = jax.random.split(km, groups * every).reshape(groups, every, 2)
    ka, kf = jax.random.split(ks)
    shared = {
        "ln_attn": norm_init(cfg.norm, cfg.d_model, dt),
        "attn": attn.init_attention(ka, cfg.d_model, cfg.n_heads, cfg.n_kv,
                                    cfg.head_dim, dt),
        "ln_ffn": norm_init(cfg.norm, cfg.d_model, dt),
        "ffn": dl.init_swiglu(kf, cfg.d_model, cfg.d_ff, dt),
    }
    return {
        "embed": embed_init(ke, cfg.vocab, cfg.d_model, dt),
        "mamba": jax.vmap(jax.vmap(init_mblock))(m_keys),
        "shared": shared,                      # ONE set of weights
        "ln_final": norm_init(cfg.norm, cfg.d_model, dt),
        "lm_head": (jax.random.normal(kh, (cfg.d_model, cfg.vocab))
                    / math.sqrt(cfg.d_model)).astype(dt),
    }


def init_zamba_dsg(key, params, cfg: ModelConfig) -> Optional[dict]:
    if not cfg.dsg.enabled:
        return None
    dt = _dtype(cfg)
    dm = m2.dims(cfg.d_model, cfg.ssm_expand, cfg.ssm_state, cfg.ssm_heads,
                 cfg.ssm_chunk)
    k = dl.proj_dim(cfg.d_model, dm.d_in, cfg.dsg)
    r = projection.make_projection(key, k, cfg.d_model, dtype=dt)

    def fw_z(mb):  # z projection: (d, d_in)
        return jnp.einsum("kd,de->ke", r, mb["w_z"])

    return {
        "r": r,
        "fw_z": jax.vmap(jax.vmap(fw_z))(params["mamba"]["mamba"]),
        "fw_shared": jnp.einsum("kd,df->kf", r,
                                params["shared"]["ffn"]["w_gate"]),
    }


def refresh_zamba_dsg(dsg, params, cfg):
    if dsg is None:
        return None
    dm = m2.dims(cfg.d_model, cfg.ssm_expand, cfg.ssm_state, cfg.ssm_heads,
                 cfg.ssm_chunk)
    r = dsg["r"]

    def fw_z(mb):
        return jnp.einsum("kd,de->ke", r, mb["w_z"])

    return {"r": r,
            "fw_z": jax.vmap(jax.vmap(fw_z))(params["mamba"]["mamba"]),
            "fw_shared": jnp.einsum("kd,df->kf", r,
                                    params["shared"]["ffn"]["w_gate"])}


def zamba_forward(params, dsg, cfg: ModelConfig, tokens,
                  state: Optional[dict] = None, pos0=0, last_only=False):
    """state: {'ssm': (G,M,B,H,N,P), 'conv': (G,M,B,K-1,C),
               'k'/'v': (G,B,Smax,Kv,D)} for decode; None for training."""
    dt = _dtype(cfg)
    dm = m2.dims(cfg.d_model, cfg.ssm_expand, cfg.ssm_state, cfg.ssm_heads,
                 cfg.ssm_chunk)
    every = cfg.shared_attn_every
    groups = cfg.n_layers // every
    x = params["embed"].astype(dt)[tokens]
    b, s = x.shape[:2]
    q_pos = pos0 + jnp.arange(s)
    r = dsg["r"] if dsg else None
    fw_sh = dsg["fw_shared"] if dsg else None
    decode = state is not None

    def group_body(xc, scanned):
        p_g, fw_z_g, ssm_g, cx_g, cbc_g, kv_g = scanned
        if cfg.seq_sharded_residual:
            from repro.parallel import context as pctx
            xc = pctx.constrain(xc, pctx.batch_axes(), "model", None)

        def m_body(xc2, sc):
            p_l, fw_l, ssm_l, cx_l, cbc_l = sc
            h = norm_apply(cfg.norm, p_l["ln"], xc2, cfg.norm_eps)
            gmask = None
            if fw_l is not None:
                gmask = _gate_mask(h, r, fw_l, cfg)
            st = ({"ssm": ssm_l, "conv_x": cx_l, "conv_bc": cbc_l}
                  if decode else None)
            y, new_st = m2.mamba2_forward(p_l["mamba"], h, dm, st, gmask)
            return xc2 + y, (new_st["ssm"], new_st["conv_x"],
                             new_st["conv_bc"])

        xc, (ssm_new, cx_new, cbc_new) = jax.lax.scan(
            m_body, xc, (p_g, fw_z_g, ssm_g, cx_g, cbc_g))

        sh = params["shared"]
        h = norm_apply(cfg.norm, sh["ln_attn"], xc, cfg.norm_eps)
        cache_pos = pos0
        cache_kv_pos = None
        if decode and cfg.window and kv_g is not None:
            w = kv_g["k"].shape[1]
            cache_pos = pos0 % w       # ring-buffer slot for windowed cache
            cache_kv_pos = pos0 - ((pos0 - jnp.arange(w)) % w)
        a, kv_new = attn.self_attention(
            sh["attn"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            rope_theta=cfg.rope_theta, q_pos=q_pos, causal=True,
            window=cfg.window, cache=kv_g if decode else None,
            cache_pos=cache_pos, cache_kv_pos=cache_kv_pos,
            shard=cfg.attn_shard)
        xc = xc + a
        h = norm_apply(cfg.norm, sh["ln_ffn"], xc, cfg.norm_eps)
        st = {"r": r, "fw": fw_sh} if fw_sh is not None else None
        xc = xc + dl.swiglu_ffn(sh["ffn"], h, st, cfg.dsg)
        return xc, (ssm_new, cx_new, cbc_new, kv_new)

    if cfg.remat and not decode:
        group_body = jax.checkpoint(group_body)

    if decode:
        ssm0, cx0, cbc0 = state["ssm"], state["conv_x"], state["conv_bc"]
        kv0 = {"k": state["k"], "v": state["v"]}
    else:
        ssm0 = jnp.zeros((groups, every, b, dm.heads, dm.n, dm.head_dim),
                         jnp.float32)
        cx0 = jnp.zeros((groups, every, b, m2.CONV_K - 1, dm.d_in), dt)
        cbc0 = jnp.zeros((groups, every, b, m2.CONV_K - 1, 2 * dm.n), dt)
        kv0 = None

    fw_z = dsg["fw_z"] if dsg else None
    x, (ssm_f, cx_f, cbc_f, kv_f) = jax.lax.scan(
        group_body, x, (params["mamba"], fw_z, ssm0, cx0, cbc0, kv0))
    x = norm_apply(cfg.norm, params["ln_final"], x, cfg.norm_eps)
    if last_only:
        x = x[:, -1:]
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(dt))
    new_state = {"ssm": ssm_f, "conv_x": cx_f, "conv_bc": cbc_f}
    if kv_f is not None:
        new_state.update(kv_f)
    return logits, new_state


def init_zamba_cache(cfg: ModelConfig, batch: int, max_seq: int,
                     dtype=jnp.float32) -> dict:
    dm = m2.dims(cfg.d_model, cfg.ssm_expand, cfg.ssm_state, cfg.ssm_heads,
                 cfg.ssm_chunk)
    every = cfg.shared_attn_every
    groups = cfg.n_layers // every
    kv_len = min(max_seq, cfg.window) if cfg.window else max_seq
    return {
        "ssm": jnp.zeros((groups, every, batch, dm.heads, dm.n, dm.head_dim),
                         jnp.float32),
        "conv_x": jnp.zeros((groups, every, batch, m2.CONV_K - 1, dm.d_in),
                            dtype),
        "conv_bc": jnp.zeros((groups, every, batch, m2.CONV_K - 1,
                              2 * dm.n), dtype),
        "k": jnp.zeros((groups, batch, kv_len, cfg.n_kv, cfg.head_dim),
                       dtype),
        "v": jnp.zeros((groups, batch, kv_len, cfg.n_kv, cfg.head_dim),
                       dtype),
    }
