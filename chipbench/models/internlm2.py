"""InternLM2 (`model_type` "internlm2", arXiv:2403.17297): the model module.

Everything of the benchmark that knows this architecture: how the program is
configured to serve it, its weights, its plain reference, and the work its
arithmetic requires.  `chipbench/bench.py` lists what a model module defines.

The block: RMSNorm before attention and before the FFN, rotary position
embeddings (rotate-half, base `rope_theta`), grouped-query attention with each
key/value head shared by `num_attention_heads / num_key_value_heads`
consecutive query heads, a SiLU-gated FFN, a final RMSNorm and an untied
output head.  The reference is written in `jax.numpy` at float32 with every
matrix product at HIGHEST precision, layer by layer (one compiled layer
program walks the stacked weights), with no cache, kernel, paging or batching
of the engine.  It imports nothing of the program.  The control (`quant=True`)
is the same reference with every weight product in int8 (symmetric, per
output channel for weights, per token for activations).

DSG (the configuration's `dsg` group, arXiv:1810.00859 with neuron groups of
`block`): each FFN input h is projected by the ternary matrix R; a group's score
is the sum over its `block` neurons of relu((h R^T)(R W_gate)); the top
ceil((1 - gamma) G) groups are kept and the others' SiLU-gated activations are
zeroed.  Which position's scores choose the groups at each position, and how
the run's own selections are checked, is `chipbench/reference.py`'s.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import workcount
from chipbench.reference import HI, fake_int8, mm, rms_norm, rope
from chipbench.weights import dtype_of, key_of

# --------------------------------------------------------------------------
# the program


def program_config(cfg: dict):
    """The program's ModelConfig for the configuration file, as stated."""
    from repro import configs
    from repro.core import dsg_linear
    base = configs.get_config(cfg["arch"])
    d = cfg["dsg"]
    dsg = base.dsg._replace(enabled=bool(d["enabled"]))
    if d["enabled"]:
        dsg = dsg._replace(gamma=d["gamma"], block=d["block"], eps=d["eps"],
                           threshold_mode=d["threshold_mode"])
    pc = base.replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], dtype=cfg["torch_dtype"],
        tie_embeddings=cfg["tie_word_embeddings"], dsg=dsg)
    if d["enabled"]:
        k = dsg_linear.proj_dim(pc.d_model, pc.d_ff, dsg)
        if k != d["proj_dim"]:
            raise ValueError(f"the program projects to {k} dimensions, the "
                             f"configuration states {d['proj_dim']}")
    return pc


def make_weights(cfg: dict, seed: int) -> dict:
    """{'embed', 'layers', 'ln_final', 'lm_head'} and, for DSG, 'r' (the
    ternary projection); see the configuration's `assumed` list."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    dt = dtype_of(cfg)
    dsg = cfg["dsg"]

    def make(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, std):
            return (jax.random.normal(next(ks), shape) * std).astype(dt)

        def scale(shape):
            return {"scale": (1.0 + 0.05 * jax.random.normal(next(ks), shape)
                              ).astype(dt)}

        w = {
            "embed": normal((v, d), 1.0),
            "layers": {
                "ln_attn": scale((L, d)),
                "attn": {"wq": normal((L, d, H, hd), d ** -0.5),
                         "wk": normal((L, d, kv, hd), d ** -0.5),
                         "wv": normal((L, d, kv, hd), d ** -0.5),
                         "wo": normal((L, H, hd, d), (H * hd) ** -0.5)},
                "ln_ffn": scale((L, d)),
                "ffn": {"w_gate": normal((L, d, f), d ** -0.5),
                        "w_up": normal((L, d, f), d ** -0.5),
                        "w_down": normal((L, f, d), f ** -0.5)},
            },
            "ln_final": scale((d,)),
            "lm_head": normal((d, v), d ** -0.5),
        }
        if dsg["enabled"]:
            k = dsg["proj_dim"]
            u = jax.random.uniform(next(ks), (k, d))
            sign = jnp.where(jax.random.uniform(next(ks), (k, d)) < 0.5,
                             1.0, -1.0)
            r = jnp.where(u < 1.0 / 3.0, sign * math.sqrt(3.0), 0.0)
            w["r"] = (r / math.sqrt(k)).astype(dt)
        return w

    return jax.jit(make)(key_of(seed, 0))


def program_params(w: dict) -> dict:
    """The engine's params tree: every leaf but the DSG projection."""
    return {k: a for k, a in w.items() if k != "r"}


# --------------------------------------------------------------------------
# the plain reference


def dsg_groups(cfg: dict) -> int:
    """FFN neuron groups of a layer that DSG chooses among."""
    return cfg["intermediate_size"] // cfg["dsg"]["block"]


def _kept(cfg: dict) -> int:
    """Groups DSG keeps of a layer's `dsg_groups`."""
    return workcount.kept_of(dsg_groups(cfg), cfg["dsg"]["gamma"])


@partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _layer(x, layers, li, r, src, given, use_given, cfg_key, quant):
    """One decoder layer over rows x (B, T, d) -> (x, DSG group scores
    (B, T, G), or a placeholder for a dense model).  Under DSG, position t
    keeps the groups of `given` (B, T, G) where `use_given` (B, T), else
    the reference's own top-k at position src[t]."""
    cfg = dict(cfg_key)
    f32 = lambda a: a.astype(jnp.float32)                # noqa: E731
    w = jax.tree.map(lambda a: f32(a[li]), layers)
    b, t, _ = x.shape
    heads, kv, hd = cfg["heads"], cfg["kv"], cfg["hd"]
    pos = jnp.arange(t)

    h = rms_norm(x, w["ln_attn"]["scale"], cfg["eps"])
    q = rope(mm("btd,dhk->bthk", h, w["attn"]["wq"], (0,), quant), pos,
             cfg["theta"])
    k = rope(mm("btd,dhk->bthk", h, w["attn"]["wk"], (0,), quant), pos,
             cfg["theta"])
    v = mm("btd,dhk->bthk", h, w["attn"]["wv"], (0,), quant)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + mm("bthk,hkd->btd", o, w["attn"]["wo"], (0, 1), quant)

    h = rms_norm(x, w["ln_ffn"]["scale"], cfg["eps"])
    wg, wu, wd = w["ffn"]["w_gate"], w["ffn"]["w_up"], w["ffn"]["w_down"]
    a = (jax.nn.silu(mm("btd,df->btf", h, wg, (0,), quant))
         * mm("btd,df->btf", h, wu, (0,), quant))
    if cfg["dsg"]:
        blk, keep = cfg["block"], cfg["keep"]
        rr = f32(r)
        if quant:
            rr = fake_int8(rr, (1,))
            wg = fake_int8(wg, (0,))
        fx = mm("btd,kd->btk", h, rr, (1,), quant)
        fw = jnp.einsum("kd,df->kf", rr, wg, precision=HI)
        virt = jnp.einsum("btk,kf->btf", fx, fw, precision=HI)
        sc = jax.nn.relu(virt).reshape(b, t, -1, blk).sum(-1)     # (B, T, G)
        thr = jax.lax.top_k(sc, keep)[0][..., keep - 1:]
        sel = jnp.take_along_axis(sc >= thr, src[..., None], axis=1)
        sel = jnp.where(use_given[..., None], given, sel)
        a = a * jnp.repeat(sel, blk, axis=-1).astype(a.dtype)
    else:
        sc = jnp.zeros((1, 1, 1), jnp.float32)
    return x + mm("btf,fd->btd", a, wd, (0,), quant), sc


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(x, ln_final, head, eps, quant):
    """Final norm and output head for one row: (T, d) -> (T, V)."""
    h = rms_norm(x, ln_final.astype(jnp.float32), eps)
    return mm("td,dv->tv", h, head.astype(jnp.float32), (0,), quant)


def _cfg_key(cfg: dict) -> tuple:
    dsg = cfg["dsg"]
    key = dict(heads=cfg["num_attention_heads"],
               kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
               eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
               dsg=bool(dsg["enabled"]))
    if dsg["enabled"]:
        key.update(block=dsg["block"], keep=_kept(cfg))
    return tuple(sorted(key.items()))


def hidden(cfg: dict, w: dict, tokens, src, quant: bool, given=None,
           use_given=None):
    """Final residual stream (B, T, d) float32 of the token rows, and the
    DSG group scores of every layer ([(B, T, G)], empty for a dense model).
    `given` (L, B, T, G) and `use_given` (B, T): selections to run under."""
    x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    if quant:
        x = fake_int8(x, (-1,))
    key = _cfg_key(cfg)
    r = w.get("r", jnp.zeros((1, 1), jnp.float32))
    src = jnp.asarray(src)
    if use_given is None:
        use_given = np.zeros(tokens.shape, bool)
    use_given = jnp.asarray(use_given)
    scores = []
    for li in range(cfg["num_hidden_layers"]):
        g = (jnp.asarray(given[li]) if given is not None
             else jnp.zeros((1, 1, 1), bool))
        x, sc = _layer(x, w["layers"], li, r, src, g, use_given, cfg_key=key,
                       quant=quant)
        if cfg["dsg"]["enabled"]:
            scores.append(sc)
    return x, scores


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
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def weight_flops_per_token(cfg: dict) -> float:
    """Two FLOPs per weight a token multiplies: attention projections, the
    FFN at its kept share, and the output head (the embedding is a lookup)."""
    L, d, H, kv, hd, f, v = _dims(cfg)
    attn = d * (H + 2 * kv) * hd + H * hd * d
    share = _kept(cfg) / dsg_groups(cfg) if cfg["dsg"]["enabled"] else 1.0
    ffn = 3 * d * f * share
    return 2.0 * (L * (attn + ffn) + d * v)


def attn_flops(cfg: dict, depth_sum: float) -> float:
    """QK^T and PV over `depth_sum` keys in all, every layer."""
    L, _, H, _, hd, _, _ = _dims(cfg)
    return 4.0 * L * H * hd * depth_sum


def attn_bytes(cfg: dict, lanes: float, depth_sum: float) -> float:
    """Paged decode attention: K and V of every key attended, the new K and
    V written, and each lane's query read and output written, every layer."""
    L, _, H, kv, hd, _, _ = _dims(cfg)
    b = workcount.BYTES[cfg["torch_dtype"]]
    return L * b * (2 * kv * hd * depth_sum
                    + lanes * (2 * kv * hd + 2 * H * hd))


def drs_flops(cfg: dict, rows: float) -> float:
    """DRS scoring of `rows` FFN inputs in every layer: the projection
    h R^T and the virtual product with R W_gate."""
    dsg = cfg["dsg"]
    if not dsg["enabled"]:
        return 0.0
    L, d, _, _, _, f, _ = _dims(cfg)
    k = dsg["proj_dim"]
    return 2.0 * L * rows * (d * k + k * f)


def ffn_csr_flops(cfg: dict, lanes: float) -> float:
    """Sparse FFN of `lanes` tokens: each lane's kept groups of the three
    matrices, every layer."""
    L, d, _, _, _, _, _ = _dims(cfg)
    blk = cfg["dsg"]["block"]
    return 2.0 * L * lanes * _kept(cfg) * blk * d * 3


def ffn_csr_bytes(cfg: dict, steps: float, lanes: float) -> float:
    """Lower bound on the sparse FFN's bytes: one lane's kept groups of the
    three matrices per layer and step (no selection can read less; the
    union over lanes is larger), plus each lane's input and output row."""
    L, d, _, _, _, _, _ = _dims(cfg)
    b = workcount.BYTES[cfg["torch_dtype"]]
    blk = cfg["dsg"]["block"]
    return L * b * (steps * _kept(cfg) * blk * d * 3
                    + lanes * 2 * d)
