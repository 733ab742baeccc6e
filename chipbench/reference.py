"""Plain reference of the served model, and the comparison that decides `correct`.

The model is the published InternLM2 decoder (arXiv:2403.17297): RMSNorm
before attention and before the FFN, rotary position embeddings
(rotate-half, base `rope_theta`), grouped-query attention with each key/value
head shared by `num_attention_heads / num_key_value_heads` consecutive query
heads, a SiLU-gated FFN, a final RMSNorm and an untied output head.  Written in
`jax.numpy` at float32 with every matrix product at HIGHEST precision, layer by
layer (one compiled layer program walks the stacked weights), with no cache,
kernel, paging or batching of the engine.  It imports nothing of the program.

DSG (the configuration's `dsg` group, arXiv:1810.00859 with neuron groups of
`block`): each FFN input h is projected by the ternary matrix R; a group's score
is the sum over its `block` neurons of relu((h R^T)(R W_gate)); the top
ceil((1 - gamma) G) groups are kept and the others' SiLU-gated activations are
zeroed.  A prompt token selects from its own scores.  A generated token at
position t (prompt length P) uses the selection made from the scores at
position P - 1 + refresh_interval * floor((t - P) / refresh_interval): the last
prompt token, then the input of every refresh_interval-th generated token.

The comparison: for each sampled request, the prompt followed by its served
tokens is run once; at each position that predicted a served token, the gap is
the reference's best logit minus its logit of that token.  The number compared
is the mean gap over the served tokens; the widest gap and the share of served
tokens that are not the reference's first choice are reported beside it.  (The
widest gap of sound runs and of the control overlap at this model's size: see
PERF.md.)  The control (`quant=True`) is the same reference with every weight
product in int8 (symmetric, per output channel for weights, per token for
activations): at each of those positions its gap is that of the token it puts
first.

Under DSG a top-k over 64 group scores is not continuous: rounding moves a
score across the cut, and a sound bf16 run keeps other groups than a float32
one would.  So the selection is checked apart from the FFN it drives.  Each
group selection that the run's DSG runtime wrote for a sampled request (at
admission, from the last prompt token; at each refresh, from the token just
decoded) is held against the reference's own scores at the position that chose
it: its miss is the highest score it dropped less the lowest it kept, over the
reference's cut (the keep-th highest score), and `selection_miss` is the worst
miss over every layer and selection of the sample.  The logit gaps are then
read with the reference run under those selections, from the position that
chose each one on; prompt positions before the last keep the reference's own.
The control makes its own selections, on the engine's schedule, from its int8
scores.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def fake_int8(a, axes):
    """Round `a` to int8 levels, one symmetric scale per slice over `axes`."""
    s = jnp.max(jnp.abs(a), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(a / s), -127, 127) * s


def mm(spec, x, w, w_axes, quant):
    """einsum of activations x (contracted over their last axis) with a
    weight contracted over `w_axes`, optionally both in int8."""
    if quant:
        x = fake_int8(x, (-1,))
        w = fake_int8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """Rotate-half RoPE on x (B, T, heads, hd) at positions pos (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def dsg_keep(cfg) -> int:
    g = cfg["intermediate_size"] // cfg["dsg"]["block"]
    return max(1, math.ceil((1.0 - cfg["dsg"]["gamma"]) * g - 1e-9))


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


@jax.jit
def _row_gaps(ref, ctl, served, valid):
    """Over the valid positions: (sum, widest, count above 0) of the gap
    between the reference's best logit and its logit of the served token,
    and the same for the control's first token."""
    best = jnp.max(ref, -1)
    at = lambda idx: jnp.take_along_axis(ref, idx[:, None], -1)[:, 0]  # noqa

    def stats(g):
        g = jnp.where(valid, g, 0.0)
        return jnp.stack([jnp.sum(g), jnp.max(g), jnp.sum(g > 0)])

    prog = stats(best - at(served))
    ctrl = stats(best - at(jnp.argmax(ctl, -1))) if ctl is not None else prog
    return prog, ctrl


def _cfg_key(cfg: dict) -> tuple:
    dsg = cfg["dsg"]
    key = dict(heads=cfg["num_attention_heads"],
               kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
               eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
               dsg=bool(dsg["enabled"]))
    if dsg["enabled"]:
        key.update(block=dsg["block"], keep=dsg_keep(cfg))
    return tuple(sorted(key.items()))


def selection_source(prompt_len: int, length: int, refresh: int) -> np.ndarray:
    """Position whose scores pick the FFN groups at each position (DSG)."""
    t = np.arange(length)
    dec = prompt_len - 1 + refresh * ((t - prompt_len) // refresh)
    return np.where(t < prompt_len, t, dec).astype(np.int32)


def hidden(cfg: dict, w: dict, tokens: np.ndarray, src: np.ndarray,
           quant: bool, given=None, use_given=None):
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


def selection_miss(scores: np.ndarray, kept: np.ndarray, keep: int) -> float:
    """How far a selection falls from the top-k of `scores` (..., G): the
    highest score dropped less the lowest kept, over the keep-th highest
    score; at most 0 where the selection is a top-k."""
    cut = -np.sort(-scores, axis=-1)[..., keep - 1]
    dropped = np.where(kept, -np.inf, scores).max(-1)
    lowest = np.where(kept, scores, np.inf).min(-1)
    return float(np.max((dropped - lowest) / np.maximum(cut, 1e-30)))


def given_selections(cfg: dict, selections: list, n_rows: int, t_len: int):
    """(given (L, n_rows, T, G), use_given (n_rows, T)) from each row's
    selections [(source position, first position, kept (L, G))]: a
    selection holds from its first position to the next one's."""
    n_layers = cfg["num_hidden_layers"]
    groups = cfg["intermediate_size"] // cfg["dsg"]["block"]
    given = np.zeros((n_layers, n_rows, t_len, groups), bool)
    use = np.zeros((n_rows, t_len), bool)
    for i, sel in enumerate(selections):
        sel = sorted(sel, key=lambda e: e[1])
        for j, (_, first, kept) in enumerate(sel):
            last = sel[j + 1][1] if j + 1 < len(sel) else t_len
            given[:, i, first:last] = kept[:, None, :]
            use[i, first:last] = True
    return given, use


def compare(cfg: dict, w: dict, rows: list, *, n_rows: int, control: bool,
            selections: list = None):
    """Gaps of the served tokens of `rows` [(prompt, served tokens)].

    Rows are right-padded to the configured `max_seq` and to `n_rows`
    (repeating the last), so one compiled program serves every run.
    Returns {'program': gaps, 'control': gaps or None, 'tokens': served
    tokens compared}, the gaps as {'mean_gap', 'widest_gap', 'not_first'}
    (the share of served tokens that are not the reference's first
    choice) and, with DSG `selections` (one list per row, as
    `given_selections` takes them), 'selection_miss'."""
    t_len = cfg["serving"]["max_seq"]
    tokens = np.zeros((n_rows, t_len), np.int32)
    src = np.zeros((n_rows, t_len), np.int32)
    refresh = cfg["dsg"].get("refresh_interval", 1)
    padded = rows + [rows[-1]] * (n_rows - len(rows))
    for i, (prompt, out) in enumerate(padded):
        seq = np.concatenate([prompt, np.asarray(out, np.int32)])[:t_len]
        tokens[i, :len(seq)] = seq
        src[i] = selection_source(len(prompt), t_len, refresh)
    eps = cfg["rms_norm_eps"]
    given = use = None
    if selections is not None:
        given, use = given_selections(cfg, selections, n_rows, t_len)
    xs, scs = {}, {}
    xs[False], scs[False] = hidden(cfg, w, tokens, src, quant=False,
                                   given=given, use_given=use)
    del given
    if control:
        xs[True], scs[True] = hidden(cfg, w, tokens, src, quant=True)
    prog, ctrl, n_tok = np.zeros(3), np.zeros(3), 0
    for i, (prompt, out) in enumerate(rows):
        p, n = len(prompt), len(out)
        # position p - 1 + j predicted served token j
        served = np.zeros(t_len, np.int32)
        valid = np.zeros(t_len, bool)
        served[p - 1:p - 1 + n] = out
        valid[p - 1:p - 1 + n] = True
        ref = _logits(xs[False][i], w["ln_final"]["scale"], w["lm_head"],
                      eps=eps, quant=False)
        ctl = (_logits(xs[True][i], w["ln_final"]["scale"], w["lm_head"],
                       eps=eps, quant=True) if control else None)
        pg, cg = (np.asarray(a, np.float64)
                  for a in _row_gaps(ref, ctl, served, valid))
        prog = np.array([prog[0] + pg[0], max(prog[1], pg[1]),
                         prog[2] + pg[2]])
        ctrl = np.array([ctrl[0] + cg[0], max(ctrl[1], cg[1]),
                         ctrl[2] + cg[2]])
        n_tok += n

    def named(a):
        return {"mean_gap": float(a[0] / max(n_tok, 1)),
                "widest_gap": float(a[1]),
                "not_first": float(a[2] / max(n_tok, 1))}

    got = {"program": named(prog),
           "control": named(ctrl) if control else None, "tokens": n_tok}
    if selections is not None:
        # each selection against the reference's scores where it was made;
        # the control's: its own top-k at the same positions
        keep = dsg_keep(cfg)
        ref = np.stack([np.asarray(s) for s in scs[False]])   # (L, B, T, G)
        at = [(i, s, kept) for i, sel in enumerate(selections)
              for s, _, kept in sel]
        pos = (np.array([a[0] for a in at]), np.array([a[1] for a in at]))
        ref_at = ref[:, pos[0], pos[1]]                         # (L, N, G)
        kept = np.stack([a[2] for a in at], 1)                  # (L, N, G)
        got["program"]["selection_miss"] = selection_miss(ref_at, kept, keep)
        if control:
            q_at = np.stack([np.asarray(s) for s in scs[True]])[
                :, pos[0], pos[1]]
            cut = -np.sort(-q_at, axis=-1)[..., keep - 1:keep]
            got["control"]["selection_miss"] = selection_miss(
                ref_at, q_at >= cut, keep)
    return got


#: the configuration's limits, and the statistic of `compare` each holds
COMPARED = {"mean_logit_gap": "mean_gap", "selection_miss": "selection_miss"}


def verdict(cfg: dict, rows: list, gaps: dict):
    """`correct` and the numbers compared, each beside its limit: a sample
    that is not empty, every served token inside the vocabulary, and each
    number that the configuration limits (`gaps` as `compare` returns them,
    of the program or of the control) no higher than its limit."""
    vocab_ok = all(0 <= t < cfg["vocab_size"] for _, out in rows for t in out)
    check = {k: {"value": float(gaps[COMPARED[k]]), "limit": limit}
             for k, limit in cfg["limits"].items()}
    return (bool(rows and vocab_ok
                 and all(c["value"] <= c["limit"] for c in check.values())),
            check)
