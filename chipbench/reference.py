"""The comparison that decides `correct`, against the served model's plain
reference.

The reference itself is the configuration's model module's (`bench.model`:
`hidden` and `logits`): the published forward pass in `jax.numpy` at float32
with every matrix product at HIGHEST precision, with no cache, kernel, paging
or batching of the engine, importing nothing of the program; with
`quant=True` it is the control, every weight product in int8.  What stays here
is the same for every model: the shared building blocks (`mm`, `fake_int8`,
`rms_norm`, `rope`), the rows and their padding, the gap statistics, DSG's
selection schedule and check, and the verdict.

The comparison: for each sampled request, the prompt followed by its served
tokens is run once; at each position that predicted a served token, the gap is
the reference's best logit minus its logit of that token.  The number compared
is the mean gap over the served tokens; the widest gap and the share of served
tokens that are not the reference's first choice are reported beside it.  (The
widest gap of sound runs and of the control overlap at this model's size: see
PERF.md.)  For the control, at each of those positions the gap is that of the
token it puts first.

DSG: a prompt token selects its FFN groups from its own scores.  A generated
token at position t (prompt length P) uses the selection made from the scores
at position P - 1 + refresh_interval * floor((t - P) / refresh_interval): the
last prompt token, then the input of every refresh_interval-th generated token.
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

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, workcount

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
    """Groups of a layer that DSG keeps (`workcount.kept_groups`)."""
    return workcount.kept_groups(cfg)


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


def selection_source(prompt_len: int, length: int, refresh: int) -> np.ndarray:
    """Position whose scores pick the FFN groups at each position (DSG)."""
    t = np.arange(length)
    dec = prompt_len - 1 + refresh * ((t - prompt_len) // refresh)
    return np.where(t < prompt_len, t, dec).astype(np.int32)


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
    groups = bench.model(cfg).dsg_groups(cfg)
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
    model = bench.model(cfg)
    given = use = None
    if selections is not None:
        given, use = given_selections(cfg, selections, n_rows, t_len)
    xs, scs = {}, {}
    xs[False], scs[False] = model.hidden(cfg, w, tokens, src, quant=False,
                                         given=given, use_given=use)
    del given
    if control:
        xs[True], scs[True] = model.hidden(cfg, w, tokens, src, quant=True)
    prog, ctrl, n_tok = np.zeros(3), np.zeros(3), 0
    for i, (prompt, out) in enumerate(rows):
        p, n = len(prompt), len(out)
        # position p - 1 + j predicted served token j
        served = np.zeros(t_len, np.int32)
        valid = np.zeros(t_len, bool)
        served[p - 1:p - 1 + n] = out
        valid[p - 1:p - 1 + n] = True
        ref = model.logits(cfg, w, xs[False][i], quant=False)
        ctl = (model.logits(cfg, w, xs[True][i], quant=True) if control
               else None)
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
