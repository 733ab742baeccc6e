"""DeepSeek-V2 (`model_type` "deepseek_v2", arXiv:2405.04434): the model module.

Everything of the benchmark that knows this architecture: how the program is
configured to serve it, its weights, its plain reference, and the work its
arithmetic requires.  `chipbench/bench.py` lists what a model module defines.

The block is DeepSeekMoE's (`deepseek.py`: the leading dense layers, then the
router of `n_routed_experts_published` outputs, top `num_experts_per_tok` of a
float32 softmax not renormalised, the routed and the shared experts) with
multi-head latent attention in place of multi-head attention.  For the normed
input h of a layer:

- q = h W_q, (heads, qk_nope_head_dim + qk_rope_head_dim), split into q_nope
  and q_pe (`q_lora_rank` is null: no query compression);
- [c, k_pe] = h W_kva (kv_lora_rank + qk_rope_head_dim), c = RMSNorm(c) with a
  scale of its own;
- q_pe and k_pe get rotary embeddings in the upstream layout: each vector's
  interleaved pairs are gathered to its two halves, then rotate-half, at YaRN's
  frequencies (`rope_scaling`: base `rope_theta`, `factor` over
  `original_max_position_embeddings`, blended between `beta_fast` and
  `beta_slow` rotations) with cos and sin scaled by m(factor, mscale) /
  m(factor, mscale_all_dim), m(f, s) = 0.1 s ln f + 1; k_pe is one part shared
  by all heads;
- [k_nope, v] = c W_kv_b, (heads, qk_nope_head_dim + v_head_dim); W_kv_b is
  held as its two halves, `w_uk` and `w_uv`;
- score = (q_nope . k_nope + q_pe . k_pe) x (qk_nope + qk_rope)^-1/2 x
  m(factor, mscale_all_dim)^2, a causal softmax, the sum over v, then W_o.

This is the plain, non-absorbed form; the program decodes in the absorbed
form over cached rows [c | k_pe] (its `models/mla.py`).  The configuration is
one chip's share of an expert-parallel deployment, as in `deepseek.py`, whose
routing, SwiGLU and expert reference this module uses; expert g of a layer is
made from a key of its global index g there and here alike.

The reference is `jax.numpy` at float32 with every matrix product at HIGHEST
precision, layer by layer, with no cache, kernel, paging, batching or grouping
of the engine.  It imports nothing of the program.  The control (`quant=True`)
has every weight product in int8 (`reference.mm`).

Departures from the published description: the weights are random (see the
configuration's `assumed`); the routed experts held on other chips are left
out, as in `deepseek.py`; the auxiliary balance loss is training-only and
absent.
"""
from __future__ import annotations

import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from chipbench import bench, workcount
from chipbench.reference import HI, fake_int8, mm, rms_norm
from chipbench.weights import dtype_of, key_of

#: deepseek.py beside this file: routing, SwiGLU, the held experts, counts
ds = bench.load_model("deepseek", Path(__file__).resolve().parents[2])

# --------------------------------------------------------------------------
# the program


def program_config(cfg: dict):
    """The program's ModelConfig for the configuration file, as stated."""
    from repro import configs
    if cfg["q_lora_rank"] is not None:
        raise ValueError("the program projects queries uncompressed")
    if cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError("the program scales rotary frequencies by YaRN")
    if cfg["topk_method"] != "greedy" or cfg["routed_scaling_factor"] != 1:
        raise ValueError("the program routes greedily, unscaled")
    if cfg["arch"] not in configs.ARCHS:
        raise bench.UnknownModel(
            f"the program has no architecture {cfg['arch']!r}")
    rs = cfg["rope_scaling"]
    pc = ds.program_config(dict(
        cfg, head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]))
    return pc.replace(
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_factor=float(rs["factor"]),
        rope_original_max=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]))


def make_weights(cfg: dict, seed: int) -> dict:
    """The engine's params tree, {'embed', 'dense_layers', 'layers',
    'ln_final', 'lm_head'}; see the configuration's `assumed` list.  The
    program configuration is built first, so a program that cannot serve
    the configuration stops the run before any weight is made."""
    program_config(cfg)
    L, k = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    d, v, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    lat, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                           cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * fe
    held = jnp.arange(cfg["n_routed_experts"]) + cfg["expert_offset"]
    dt = dtype_of(cfg)

    def make(key):
        ks = iter(jax.random.split(key, 32))

        def normal(shape, std, key=None):
            key = next(ks) if key is None else key
            return (jax.random.normal(key, shape) * std).astype(dt)

        def scale(shape):
            return {"scale": (1.0 + 0.05 * jax.random.normal(next(ks), shape)
                              ).astype(dt)}

        def block(n):
            return {"ln_attn": scale((n, d)),
                    "attn": {"wq": normal((n, d, H, nope + rope), d ** -0.5),
                             "w_kva": normal((n, d, lat + rope), d ** -0.5),
                             "kv_norm": scale((n, lat)),
                             "w_uk": normal((n, lat, H, nope), lat ** -0.5),
                             "w_uv": normal((n, lat, H, vd), lat ** -0.5),
                             "wo": normal((n, H, vd, d), (H * vd) ** -0.5)},
                    "ln_ffn": scale((n, d))}

        def swiglu(n, width):
            return {"w_gate": normal((n, d, width), d ** -0.5),
                    "w_up": normal((n, d, width), d ** -0.5),
                    "w_down": normal((n, width, d), width ** -0.5)}

        def expert(layer_key, g):
            kg, ku, kd = jax.random.split(jax.random.fold_in(layer_key, g), 3)
            return (normal((d, fe), d ** -0.5, kg),
                    normal((d, fe), d ** -0.5, ku),
                    normal((fe, d), fe ** -0.5, kd))

        layer_keys = jax.random.split(next(ks), L - k)
        wg, wu, wd = jax.vmap(lambda lk: jax.vmap(
            lambda g: expert(lk, g))(held))(layer_keys)
        moe = block(L - k)
        moe["moe"] = {"router": normal(
                          (L - k, d, cfg["n_routed_experts_published"]),
                          d ** -0.5),
                      "w_gate": wg, "w_up": wu, "w_down": wd,
                      "shared": swiglu(L - k, fs)}
        dense = block(k)
        dense["ffn"] = swiglu(k, f)
        return {"embed": normal((v, d), 1.0), "dense_layers": dense,
                "layers": moe, "ln_final": scale((d,)),
                "lm_head": normal((d, v), d ** -0.5)}

    return jax.jit(make)(key_of(seed, 0))


def program_params(w: dict) -> dict:
    """The engine's params tree: the weights as made."""
    return w


# --------------------------------------------------------------------------
# the plain reference


def dsg_groups(cfg: dict) -> int:
    raise ValueError("DSG is not served on a mixture of experts")


def yarn(cfg: dict):
    """(inverse frequencies (rope/2,), cos/sin factor, softmax scale) of
    the configuration's rotary scaling, as the upstream code computes
    them."""
    rs, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, factor = cfg["rope_theta"], rs["factor"]

    def corr_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    inv = extra / factor * (1.0 - keep) + extra * keep
    scale = ((cfg["qk_nope_head_dim"] + dim) ** -0.5
             * m(rs["mscale_all_dim"]) ** 2)
    return inv, m(rs["mscale"]) / m(rs["mscale_all_dim"]), scale


def rope_upstream(x, pos, inv, msc):
    """x (B, T, heads, dim) with interleaved pairs: gathered to the two
    halves (upstream's view(d/2, 2).transpose), then rotate-half at `inv`,
    cos and sin times `msc`."""
    b, t, h, dim = x.shape
    x = x.reshape(b, t, h, dim // 2, 2).transpose(0, 1, 2, 4, 3).reshape(
        b, t, h, dim)
    ang = pos[:, None].astype(jnp.float32) * inv
    emb = jnp.concatenate([ang, ang], -1)
    cos, sin = (jnp.cos(emb) * msc)[:, None], (jnp.sin(emb) * msc)[:, None]
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rot * sin


def _attention(x, w, cfg, quant):
    """Pre-norm multi-head latent attention over rows x (B, T, d), plain
    form, residual added."""
    lat, nope = cfg["lat"], cfg["nope"]
    t = x.shape[1]
    pos = jnp.arange(t)
    inv, msc, scale = cfg["yarn"]
    a = w["attn"]
    h = rms_norm(x, w["ln_attn"]["scale"], cfg["eps"])
    q = mm("btd,dhk->bthk", h, a["wq"], (0,), quant)
    kva = mm("btd,dr->btr", h, a["w_kva"], (0,), quant)
    c = rms_norm(kva[..., :lat], a["kv_norm"]["scale"], cfg["eps"])
    q_pe = rope_upstream(q[..., nope:], pos, inv, msc)
    k_pe = rope_upstream(kva[..., None, lat:], pos, inv, msc)
    k_nope = mm("btc,chn->bthn", c, a["w_uk"], (0,), quant)
    v = mm("btc,chv->bthv", c, a["w_uv"], (0,), quant)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3] + k_pe.shape[3:])],
        -1)
    causal = pos[:, None] >= pos[None, :]

    def one_row(qkv):
        q1, k1, v1 = qkv
        s = jnp.einsum("qhk,shk->hqs", q1, k1, precision=HI) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hqs,shv->qhv", jax.nn.softmax(s, -1), v1,
                          precision=HI)

    # a row at a time: the (heads, T, T) scores of every row at once would
    # not fit beside the weights at the cell's 3072 positions
    o = jax.lax.map(one_row, (q, k, v))
    return x + mm("bthv,hvd->btd", o, a["wo"], (0, 1), quant)


@partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _dense_layer(x, layers, li, cfg_key, quant):
    """One leading layer: attention and the dense FFN, rows (B, T, d)."""
    cfg = _cfg_of(cfg_key)
    w = jax.tree.map(lambda a: a[li].astype(jnp.float32), layers)
    x = _attention(x, w, cfg, quant)
    h = rms_norm(x, w["ln_ffn"]["scale"], cfg["eps"])
    f = w["ffn"]
    return x + ds._swiglu(h, f["w_gate"], f["w_up"], f["w_down"], quant)


@partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _moe_layer(x, layers, li, cfg_key, quant):
    """One MoE layer over rows (B, T, d): attention, then the held routed
    experts and the shared experts (`deepseek.py`'s)."""
    cfg = _cfg_of(cfg_key)
    w = jax.tree.map(lambda a: a[li].astype(jnp.float32), layers)
    x = _attention(x, w, cfg, quant)
    h = rms_norm(x, w["ln_ffn"]["scale"], cfg["eps"])
    m = w["moe"]
    top_p, top_e = ds.routing(h, m["router"], cfg, quant)
    y = ds._swiglu(h, m["shared"]["w_gate"], m["shared"]["w_up"],
                   m["shared"]["w_down"], quant)
    for j in range(m["w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(top_e == cfg["offset"] + j, top_p, 0.0), -1)
        y = y + gate[..., None] * ds._swiglu(h, m["w_gate"][j], m["w_up"][j],
                                             m["w_down"][j], quant)
    return x + y


def _cfg_key(cfg: dict) -> tuple:
    rs = cfg["rope_scaling"]
    return tuple(sorted(dict(
        eps=cfg["rms_norm_eps"], topk=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]), offset=cfg["expert_offset"],
        lat=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], theta=cfg["rope_theta"],
        rope_scaling=tuple(sorted(rs.items()))).items()))


def _cfg_of(cfg_key: tuple) -> dict:
    """The reference's settings from `_cfg_key`, with the YaRN tables."""
    c = dict(cfg_key)
    c["yarn"] = yarn({"rope_scaling": dict(c["rope_scaling"]),
                      "qk_rope_head_dim": c["rope"], "rope_theta": c["theta"],
                      "qk_nope_head_dim": c["nope"]})
    return c


def hidden(cfg: dict, w: dict, tokens, src, quant: bool, given=None,
           use_given=None):
    """Final residual stream (B, T, d) float32 of the token rows, and no
    DSG scores (a mixture of experts is served without DSG)."""
    x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    if quant:
        x = fake_int8(x, (-1,))
    key = _cfg_key(cfg)
    for li in range(cfg["first_k_dense_replace"]):
        x = _dense_layer(x, w["dense_layers"], li, cfg_key=key, quant=quant)
    for li in range(cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]):
        x = _moe_layer(x, w["layers"], li, cfg_key=key, quant=quant)
    return x, []


def logits(cfg: dict, w: dict, x, quant: bool):
    """The final norm and the output head over one row's residual stream
    x (T, d) -> (T, V) float32."""
    return ds.logits(cfg, w, x, quant)


# --------------------------------------------------------------------------
# work counts (chipbench/workcount.py says what they count)


def attn_weights(cfg: dict) -> int:
    """Weights a decode token multiplies in one layer's latent attention,
    absorbed form: W_q, W_kva, W_uk (absorbing q_nope), W_uv (expanding
    the output) and W_o."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    lat, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                           cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return (d * H * (nope + rope) + d * (lat + rope) + H * nope * lat
            + H * lat * vd + H * vd * d)


def weight_flops_per_token(cfg: dict) -> float:
    """Two FLOPs per weight a token multiplies: the latent attention's
    projections, the leading layers' dense FFN, each MoE layer's router,
    shared experts and the held experts at `held_rows_per_token`, and the
    output head (the embedding is a lookup)."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    k = cfg["first_k_dense_replace"]
    fe = cfg["moe_intermediate_size"]
    moe = (d * cfg["n_routed_experts_published"]
           + 3 * d * fe * (cfg["n_shared_experts"]
                           + held_rows_per_token(cfg)))
    return 2.0 * (L * attn_weights(cfg) + k * 3 * d * cfg["intermediate_size"]
                  + (L - k) * moe + d * cfg["vocab_size"])


def row_elems(cfg: dict) -> int:
    """Elements of a cached row: the latent and the rotary key part."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attn_flops(cfg: dict, depth_sum: float) -> float:
    """The absorbed form, the lesser of the two: each head's query against
    a whole row and the weights over the latent, 2 x heads x (row + latent)
    a key, every layer."""
    H = cfg["num_attention_heads"]
    return (2.0 * cfg["num_hidden_layers"] * H
            * (row_elems(cfg) + cfg["kv_lora_rank"]) * depth_sum)


def attn_bytes(cfg: dict, lanes: float, depth_sum: float) -> float:
    """Latent decode attention: one row of every key attended (the new one
    written), and each lane's query (heads x row) read and output (heads x
    latent) written, every layer."""
    H = cfg["num_attention_heads"]
    b = workcount.BYTES[cfg["torch_dtype"]]
    return cfg["num_hidden_layers"] * b * (
        row_elems(cfg) * depth_sum
        + lanes * H * (row_elems(cfg) + cfg["kv_lora_rank"]))


def drs_flops(cfg: dict, rows: float) -> float:
    """No DSG here: no DRS scoring."""
    return 0.0


def ffn_csr_flops(cfg: dict, lanes: float) -> float:
    raise ValueError("no sparse FFN is served on a mixture of experts")


def ffn_csr_bytes(cfg: dict, steps: float, lanes: float) -> float:
    raise ValueError("no sparse FFN is served on a mixture of experts")


held_rows_per_token = ds.held_rows_per_token
moe_flops = ds.moe_flops
moe_bytes = ds.moe_bytes
