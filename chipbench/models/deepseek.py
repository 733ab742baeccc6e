"""DeepSeekMoE (`model_type` "deepseek", arXiv:2401.06066): the model module.

Everything of the benchmark that knows this architecture: how the program is
configured to serve it, its weights, its plain reference, and the work its
arithmetic requires.  `chipbench/bench.py` lists what a model module defines.

The block: RMSNorm before attention and before the FFN, rotary position
embeddings (rotate-half, base `rope_theta`), multi-head attention (as many
key/value heads as query heads), a final RMSNorm and an untied output head.
The first `first_k_dense_replace` layers have a SiLU-gated FFN of
`intermediate_size`; every later layer has a mixture of experts: a router of
`n_routed_experts_published` outputs, a softmax over all of them in float32,
the top `num_experts_per_tok` experts weighted by their probabilities (not
renormalised, `norm_topk_prob` false), each expert a SiLU-gated FFN of
`moe_intermediate_size`, plus `n_shared_experts` shared experts as one
SiLU-gated FFN of `n_shared_experts x moe_intermediate_size` that every token
passes through.

The configuration is one chip's share of an expert-parallel deployment (its
`deployment` key): the chip holds routed experts `expert_offset` ..
`expert_offset + n_routed_experts - 1` of every MoE layer and everything
else whole.  The router keeps its published width and experts per token; a
token's routed output is the weighted sum over the top experts that are held
here, and what the experts held on the other chips would add is left out, in
the program and in this reference alike.  Expert g of a layer is made from a
key of its global index g, so the shares of every chip together are one
model (`chipbench/tests/test_deepseek.py` adds them up).

The reference is written in `jax.numpy` at float32 with every matrix product
at HIGHEST precision, layer by layer (one compiled layer program per layer
kind walks the stacked weights), with no cache, kernel, paging, batching or
grouping of the engine: every held expert runs over every position, and each
position keeps the experts it chose.  It imports nothing of the program.  The
control (`quant=True`) is the same reference with every weight product in
int8 (symmetric, per output channel for weights, per token for activations).

Departures from the published description: the weights are random (see the
configuration's `assumed`); the routed experts held on other chips are left
out, as above; the auxiliary balance loss is training-only and absent.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import workcount
from chipbench.reference import HI, fake_int8, mm, rms_norm, rope
from chipbench.weights import dtype_of, key_of

# --------------------------------------------------------------------------
# the program


def program_config(cfg: dict):
    """The program's ModelConfig for the configuration file, as stated."""
    from repro import configs
    if cfg["dsg"]["enabled"]:
        raise ValueError("the program serves no DSG on a mixture of experts")
    if cfg["scoring_func"] != "softmax" or cfg["moe_layer_freq"] != 1:
        raise ValueError("the program routes by a softmax in every layer "
                         "after the leading dense ones")
    base = configs.get_config(cfg["arch"])
    return base.replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"], tie_embeddings=cfg["tie_word_embeddings"],
        n_dense_layers=cfg["first_k_dense_replace"],
        moe_experts=cfg["n_routed_experts_published"],
        moe_experts_held=cfg["n_routed_experts"],
        moe_expert_offset=cfg["expert_offset"],
        moe_topk=cfg["num_experts_per_tok"],
        moe_shared=cfg["n_shared_experts"],
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_norm_topk=cfg["norm_topk_prob"],
        dsg=base.dsg._replace(enabled=False))


def make_weights(cfg: dict, seed: int) -> dict:
    """The engine's params tree, {'embed', 'dense_layers', 'layers',
    'ln_final', 'lm_head'}; see the configuration's `assumed` list.  The
    program configuration is built first, so a program that cannot serve
    the configuration stops the run before any weight is made."""
    program_config(cfg)
    L, k = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    H, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
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
                    "attn": {"wq": normal((n, d, H, hd), d ** -0.5),
                             "wk": normal((n, d, kv, hd), d ** -0.5),
                             "wv": normal((n, d, kv, hd), d ** -0.5),
                             "wo": normal((n, H, hd, d), (H * hd) ** -0.5)},
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


def _attention(x, w, cfg, quant):
    """Pre-norm multi-head attention over rows x (B, T, d), residual added."""
    heads, hd = cfg["heads"], cfg["hd"]
    t = x.shape[1]
    pos = jnp.arange(t)
    h = rms_norm(x, w["ln_attn"]["scale"], cfg["eps"])
    q = rope(mm("btd,dhk->bthk", h, w["attn"]["wq"], (0,), quant), pos,
             cfg["theta"])
    k = rope(mm("btd,dhk->bthk", h, w["attn"]["wk"], (0,), quant), pos,
             cfg["theta"])
    v = mm("btd,dhk->bthk", h, w["attn"]["wv"], (0,), quant)
    k = jnp.repeat(k, heads // cfg["kv"], axis=2)
    v = jnp.repeat(v, heads // cfg["kv"], axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v, precision=HI)
    return x + mm("bthk,hkd->btd", o, w["attn"]["wo"], (0, 1), quant)


def _swiglu(h, wg, wu, wd, quant):
    a = (jax.nn.silu(mm("btd,df->btf", h, wg, (0,), quant))
         * mm("btd,df->btf", h, wu, (0,), quant))
    return mm("btf,fd->btd", a, wd, (0,), quant)


def routing(h, router, cfg, quant):
    """Router probabilities of the top experts (B, T, K) and their global
    indices (B, T, K): top-k of a float32 softmax over every expert."""
    p = jax.nn.softmax(mm("btd,de->bte", h, router, (0,), quant), -1)
    top_p, top_e = jax.lax.top_k(p, cfg["topk"])
    if cfg["norm_topk"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return top_p, top_e


@partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _dense_layer(x, layers, li, cfg_key, quant):
    """One leading layer: attention and the dense FFN, rows (B, T, d)."""
    cfg = dict(cfg_key)
    w = jax.tree.map(lambda a: a[li].astype(jnp.float32), layers)
    x = _attention(x, w, cfg, quant)
    h = rms_norm(x, w["ln_ffn"]["scale"], cfg["eps"])
    f = w["ffn"]
    return x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"], quant)


@partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _moe_layer(x, layers, li, cfg_key, quant):
    """One MoE layer over rows (B, T, d): attention, then the held routed
    experts (each over every position, weighted by its router probability
    where the position chose it) and the shared experts."""
    cfg = dict(cfg_key)
    w = jax.tree.map(lambda a: a[li].astype(jnp.float32), layers)
    x = _attention(x, w, cfg, quant)
    h = rms_norm(x, w["ln_ffn"]["scale"], cfg["eps"])
    m = w["moe"]
    top_p, top_e = routing(h, m["router"], cfg, quant)
    y = _swiglu(h, m["shared"]["w_gate"], m["shared"]["w_up"],
                m["shared"]["w_down"], quant)
    for j in range(m["w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(top_e == cfg["offset"] + j, top_p, 0.0), -1)
        y = y + gate[..., None] * _swiglu(h, m["w_gate"][j], m["w_up"][j],
                                          m["w_down"][j], quant)
    return x + y


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(x, ln_final, head, eps, quant):
    """Final norm and output head for one row: (T, d) -> (T, V)."""
    h = rms_norm(x, ln_final.astype(jnp.float32), eps)
    return mm("td,dv->tv", h, head.astype(jnp.float32), (0,), quant)


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted(dict(
        heads=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], eps=cfg["rms_norm_eps"],
        theta=cfg["rope_theta"], topk=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]),
        offset=cfg["expert_offset"]).items()))


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
    return _logits(x, w["ln_final"]["scale"], w["lm_head"],
                   eps=cfg["rms_norm_eps"], quant=quant)


# --------------------------------------------------------------------------
# work counts (chipbench/workcount.py says what they count)


def _dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"])


def held_rows_per_token(cfg: dict) -> float:
    """Rows a token sends to the held experts of one MoE layer at the
    uniform-routing expectation: experts per token x the held share."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_published"])


def weight_flops_per_token(cfg: dict) -> float:
    """Two FLOPs per weight a token multiplies: attention projections, the
    leading layers' dense FFN, each MoE layer's router, shared experts and
    the held experts at `held_rows_per_token`, and the output head (the
    embedding is a lookup)."""
    L, d, H, kv, hd, v = _dims(cfg)
    k = cfg["first_k_dense_replace"]
    fe = cfg["moe_intermediate_size"]
    attn = d * (H + 2 * kv) * hd + H * hd * d
    moe = (d * cfg["n_routed_experts_published"]
           + 3 * d * fe * (cfg["n_shared_experts"]
                           + held_rows_per_token(cfg)))
    return 2.0 * (L * attn + k * 3 * d * cfg["intermediate_size"]
                  + (L - k) * moe + d * v)


def attn_flops(cfg: dict, depth_sum: float) -> float:
    """QK^T and PV over `depth_sum` keys in all, every layer."""
    L, _, H, _, hd, _ = _dims(cfg)
    return 4.0 * L * H * hd * depth_sum


def attn_bytes(cfg: dict, lanes: float, depth_sum: float) -> float:
    """Paged decode attention: K and V of every key attended, the new K and
    V written, and each lane's query read and output written, every layer."""
    L, _, H, kv, hd, _ = _dims(cfg)
    b = workcount.BYTES[cfg["torch_dtype"]]
    return L * b * (2 * kv * hd * depth_sum
                    + lanes * (2 * kv * hd + 2 * H * hd))


def drs_flops(cfg: dict, rows: float) -> float:
    """No DSG here: no DRS scoring."""
    return 0.0


def ffn_csr_flops(cfg: dict, lanes: float) -> float:
    raise ValueError("no sparse FFN is served on a mixture of experts")


def ffn_csr_bytes(cfg: dict, steps: float, lanes: float) -> float:
    raise ValueError("no sparse FFN is served on a mixture of experts")


def moe_flops(cfg: dict, rows: float) -> float:
    """The held experts' products for `rows` routed rows: gate, up and down,
    2 x 3 x d x moe_intermediate_size a row."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * rows


def moe_bytes(cfg: dict, rows: float, experts_hit: float) -> float:
    """The least the held experts' products move: the three matrices of
    every expert hit, once, and each routed row read and written."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    b = workcount.BYTES[cfg["torch_dtype"]]
    return b * (3 * d * fe * experts_hit + 2 * d * rows)
