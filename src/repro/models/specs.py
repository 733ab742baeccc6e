"""PartitionSpec trees for params / DSG state / caches / inputs.

Built by walking the pytree with key paths and applying per-family rules
(DESIGN.md §6).  A returned spec of P() means fully replicated.  All rules
collapse gracefully on a 1-device mesh.
"""
from __future__ import annotations

from typing import Optional

from jax.sharding import PartitionSpec as P
from jax.tree_util import tree_map_with_path, DictKey

from repro.configs.base import ModelConfig
from repro.parallel.sharding import Axes


def _path_names(path) -> tuple:
    out = []
    for k in path:
        if isinstance(k, DictKey):
            out.append(str(k.key))
        else:
            out.append(str(k))
    return tuple(out)


def _div(n: int, shards: int) -> bool:
    return shards > 0 and n % shards == 0


def _attn_mode(cfg: ModelConfig, n_model: int) -> str:
    if n_model <= 1:
        return "none"
    if cfg.attn_shard != "auto":
        return cfg.attn_shard
    return "head" if cfg.n_heads % n_model == 0 else "seq"


def param_specs(params: dict, cfg: ModelConfig, ax: Axes,
                n_model: int) -> dict:
    """Sharding rules keyed on the parameter path.

    Leading stacked-layer dims (L / G / (G, M)) are always replicated; the
    rules below describe the trailing semantic dims.
    """
    m = ax.model if n_model > 1 else None
    mode = _attn_mode(cfg, n_model)

    def rule(path, leaf):
        names = _path_names(path)
        name = names[-1]
        lead = (None,) * (leaf.ndim - 2)     # layer-stack prefix dims

        # ---- embeddings / heads -------------------------------------
        if name in ("embed", "tok_embed"):
            return P(m, None) if _div(cfg.vocab, n_model) else P()
        if name == "lm_head":
            return P(None, m) if _div(cfg.vocab, n_model) else P()
        # ---- norms / scalars ----------------------------------------
        if name in ("scale", "bias", "a_log", "dt_bias", "d_skip",
                    "b_gates", "skip", "r_diag", "conv_w", "router"):
            return P()
        # ---- attention ----------------------------------------------
        if names[-2] in ("attn", "cross") or (name in ("wq", "wk", "wv",
                                                       "wo")):
            if mode == "head":
                if name == "wq":
                    return P(*lead[:-1], None, m, None)
                if name in ("wk", "wv"):
                    ok = _div(cfg.n_kv, n_model)
                    return P(*lead[:-1], None, m, None) if ok else P()
                if name == "wo":
                    return P(*lead[:-1], m, None, None)
            return P()   # seq mode: weights replicated, activations S-sharded
        # ---- FFN (dense swiglu / gelu; also zamba shared) ------------
        if name in ("w_gate", "w_up") and leaf.ndim - len(lead) == 2 \
                and "moe" not in names:
            f = leaf.shape[-1]
            return P(*lead, None, m) if _div(f, n_model) else P()
        if name == "w_down" and "moe" not in names:
            f = leaf.shape[-2]
            return P(*lead, m, None) if _div(f, n_model) else P()
        # ---- MoE ------------------------------------------------------
        if "moe" in names and "shared" in names:
            if name in ("w_gate", "w_up"):
                return P(*lead, None, m) if _div(leaf.shape[-1], n_model) else P()
            if name == "w_down":
                return P(*lead, m, None) if _div(leaf.shape[-2], n_model) else P()
        if "moe" in names and name in ("w_gate", "w_up", "w_down"):
            e = leaf.shape[len(lead) - 1] if leaf.ndim >= 3 else 0
            # (L, E, d, f): experts over 'model' (EP)
            return P(*lead[:-1], m, None, None) if _div(e, n_model) else P()
        # ---- recurrent-family projections (row/col parallel) ---------
        if name in ("w_z", "w_x"):   # mamba2 head-parallel: columns over
            # 'model' -> gate/conv/SSM core all run head-sharded
            return P(*lead, None, m) if _div(leaf.shape[-1], n_model) else P()
        if name == "conv_x":         # depthwise conv follows its channels
            return P(*lead, None, m) if _div(leaf.shape[-1], n_model) else P()
        if name in ("w_bcdt", "conv_bc"):
            return P()
        if name == "w_in":           # (.., d, E_out): row-parallel over d
            return P(*lead, m, None) if _div(leaf.shape[-2], n_model) else P()
        if name in ("w_qkv",):
            return P(*lead, m, None) if _div(leaf.shape[-2], n_model) else P()
        if name in ("w_out", "w_gates"):
            return P(*lead, m, None) if _div(leaf.shape[-2], n_model) else P()
        return P()

    return tree_map_with_path(rule, params)


def dsg_specs(dsg: Optional[dict], cfg: ModelConfig, ax: Axes,
              n_model: int) -> Optional[dict]:
    if dsg is None:
        return None
    m = ax.model if n_model > 1 else None

    def rule(path, leaf):
        names = _path_names(path)
        name = names[-1]
        if name == "r":
            return P()
        if name == "fw_experts":      # (L, E, k, fe): follow experts
            e = leaf.shape[1]
            return P(None, m, None, None) if _div(e, n_model) else P()
        # (.., k, F): follow FFN column sharding when F divides
        f = leaf.shape[-1]
        lead = (None,) * (leaf.ndim - 2)
        return P(*lead, None, m) if _div(f, n_model) else P()

    return tree_map_with_path(rule, dsg)


def cache_specs(cache, cfg: ModelConfig, ax: Axes, n_model: int):
    """Decode caches: KV sequence-sharded over 'model' (split-KV decode);
    recurrent states batch-sharded only."""
    if cache is None:
        return None
    m = ax.model if n_model > 1 else None
    b = ax.batch

    def rule(path, leaf):
        names = _path_names(path)
        name = names[-1]
        if name in ("k", "v"):
            lead = (None,) * (leaf.ndim - 4)
            # (..., B, S, Kv, D)
            return P(*lead, b, m, None, None)
        if name == "c" and cfg.is_mla:
            # latent rows (L, B, S, R), sequence-sharded as K/V are
            return P(None, b, m, None)
        # recurrent states: (..., B, ...) with leading stack dims
        if name in ("ssm", "conv_x", "conv_bc", "m_c", "m_n",
                    "c", "n", "m", "h"):
            idx = {"ssm": 2, "conv_x": 2, "conv_bc": 2,
                   "m_c": 2, "m_n": 2}.get(name, None)
            if idx is None:
                # xlstm slstm states (G, B, d) or (B, d)
                idx = leaf.ndim - 2
            lead = [None] * leaf.ndim
            lead[idx] = b
            return P(*lead)
        return P()

    return tree_map_with_path(rule, cache)


def input_specs(batch: dict, cfg: ModelConfig, ax: Axes) -> dict:
    b = ax.batch

    def rule(path, leaf):
        # all inputs are batch-major
        return P(b, *([None] * (leaf.ndim - 1)))

    return tree_map_with_path(rule, batch)
