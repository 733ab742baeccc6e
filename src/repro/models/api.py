"""Family-dispatching model API used by the launcher, dry-run, and tests.

Every architecture family exposes the same verbs:
  init_model / init_dsg / refresh_dsg
  train_loss(params, dsg, cfg, batch)            -> scalar
  make_cache(cfg, batch, max_seq)                -> decode state pytree
  prefill(params, dsg, cfg, inputs, cache)       -> (last_logits, state)
  decode_step(params, dsg, cfg, token, state, pos) -> (logits, state)
  make_inputs(cfg, shape, kind, concrete)        -> batch pytree

make_cache builds the dense worst-case layout; serving picks the cache
LAYOUT through repro.serving.kv_cache backends ("dense" | "paged") and
passes the backend's view into prefill/decode_step — decoder-family
decode also accepts the paged view ({'pages_k','pages_v','page_table'},
see serving/kv_cache.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, recurrent, transformer

DECODER_FAMILIES = ("dense", "moe", "vlm")


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def init_model(key: jax.Array, cfg: ModelConfig) -> dict:
    if cfg.family in DECODER_FAMILIES:
        return transformer.init_model(key, cfg)
    if cfg.family == "encdec":
        return encdec.init_model(key, cfg)
    if cfg.family == "xlstm":
        return recurrent.init_xlstm_model(key, cfg)
    if cfg.family == "zamba":
        return recurrent.init_zamba_model(key, cfg)
    raise ValueError(cfg.family)


def init_dsg(key: jax.Array, params: dict, cfg: ModelConfig) -> Optional[dict]:
    if cfg.family in DECODER_FAMILIES:
        return transformer.init_dsg(key, params, cfg)
    if cfg.family == "encdec":
        return encdec.init_dsg(key, params, cfg)
    if cfg.family == "xlstm":
        return recurrent.init_xlstm_dsg(key, params, cfg)
    if cfg.family == "zamba":
        return recurrent.init_zamba_dsg(key, params, cfg)
    raise ValueError(cfg.family)


def refresh_dsg(dsg, params, cfg: ModelConfig):
    if cfg.family in DECODER_FAMILIES:
        return transformer.refresh_dsg(dsg, params, cfg)
    if cfg.family == "encdec":
        return encdec.refresh_dsg(dsg, params, cfg)
    if cfg.family == "xlstm":
        return recurrent.refresh_xlstm_dsg(dsg, params, cfg)
    if cfg.family == "zamba":
        return recurrent.refresh_zamba_dsg(dsg, params, cfg)
    raise ValueError(cfg.family)


def train_loss(params, dsg, cfg: ModelConfig, batch, mesh=None,
               batch_axes=None) -> jax.Array:
    if cfg.family in DECODER_FAMILIES:
        return transformer.train_loss(params, dsg, cfg, batch, mesh,
                                      batch_axes)
    if cfg.family == "encdec":
        return encdec.train_loss(params, dsg, cfg, batch, mesh, batch_axes)
    if cfg.family == "xlstm":
        logits, _ = recurrent.xlstm_forward(params, dsg, cfg,
                                            batch["tokens"])
        return transformer.cross_entropy(logits, batch["labels"])
    if cfg.family == "zamba":
        logits, _ = recurrent.zamba_forward(params, dsg, cfg,
                                            batch["tokens"])
        return transformer.cross_entropy(logits, batch["labels"])
    raise ValueError(cfg.family)


def make_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    dt = dtype or _dtype(cfg)
    if cfg.family in DECODER_FAMILIES:
        return transformer.init_cache(cfg, batch, max_seq, dt)
    if cfg.family == "encdec":
        return encdec.init_cache(cfg, batch, max_seq // cfg.dec_ratio, dt)
    if cfg.family == "xlstm":
        return None   # state built lazily inside xlstm_forward
    if cfg.family == "zamba":
        return recurrent.init_zamba_cache(cfg, batch, max_seq, dt)
    raise ValueError(cfg.family)


def prefill(params, dsg, cfg: ModelConfig, inputs: dict, cache,
            mesh=None, batch_axes=None, collect_drs_scores: bool = False,
            moe_count=None):
    if cfg.family in DECODER_FAMILIES:
        return transformer.prefill(params, dsg, cfg, inputs["tokens"], cache,
                                   prefix_embeds=inputs.get("prefix_embeds"),
                                   mesh=mesh, batch_axes=batch_axes,
                                   collect_drs_scores=collect_drs_scores,
                                   moe_count=moe_count)
    if collect_drs_scores:
        raise NotImplementedError(
            f"DRS score collection is a decoder-family serving feature "
            f"(family {cfg.family!r})")
    if cfg.family == "encdec":
        return encdec.prefill(params, dsg, cfg, inputs["frames"],
                              inputs["tokens"], cache)
    if cfg.family == "xlstm":
        logits, st = recurrent.xlstm_forward(params, dsg, cfg,
                                             inputs["tokens"],
                                             last_only=True)
        return logits[:, -1], st
    if cfg.family == "zamba":
        logits, st = recurrent.zamba_forward(params, dsg, cfg,
                                             inputs["tokens"], state=None,
                                             last_only=True)
        return logits[:, -1], st
    raise ValueError(cfg.family)


def decode_step(params, dsg, cfg: ModelConfig, token, state, pos,
                live_pages=None, mesh=None, batch_axes=None,
                ffn_csr=None, collect_drs_scores: bool = False,
                moe_count=None):
    if cfg.family in DECODER_FAMILIES:
        return transformer.decode_step(params, dsg, cfg, token, state, pos,
                                       live_pages=live_pages, mesh=mesh,
                                       batch_axes=batch_axes,
                                       ffn_csr=ffn_csr,
                                       collect_drs_scores=collect_drs_scores,
                                       moe_count=moe_count)
    if ffn_csr is not None or collect_drs_scores:
        raise NotImplementedError(
            f"group-CSR decode / DRS score collection are decoder-family "
            f"serving features (family {cfg.family!r})")
    if cfg.family == "encdec":
        return encdec.decode_step(params, dsg, cfg, token, state, pos)
    if cfg.family == "xlstm":
        logits, st = recurrent.xlstm_forward(params, dsg, cfg, token,
                                             state=state)
        return logits[:, -1], st
    if cfg.family == "zamba":
        logits, st = recurrent.zamba_forward(params, dsg, cfg, token,
                                             state=state, pos0=pos)
        return logits[:, -1], st
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# per-slot cache surgery — DEPRECATED thin wrappers
# ---------------------------------------------------------------------------
#
# The engine-facing cache surface now lives in repro.serving.kv_cache: a
# pluggable KVCacheBackend ("dense" | "paged") builds and mutates an opaque
# CacheHandle pytree (make / write / ensure / free / view_for_attention),
# and the serving scheduler drives that protocol instead of these helpers.
# They predate the backend API and are kept as thin wrappers for callers
# that still hold raw dense cache dicts; they assume every cache leaf
# carries the batch on axis 1 (L, B, ...), which holds for transformer and
# encdec caches.

def make_slot_cache(cfg: ModelConfig, max_seq: int, dtype=None):
    """Deprecated: a 1-lane dense cache for solo prompt prefill.  Same as
    ``make_cache(cfg, 1, max_seq)``; new code should build caches through a
    serving.kv_cache backend."""
    return make_cache(cfg, 1, max_seq, dtype)


def prefill_slot(params, dsg, cfg: ModelConfig, tokens, lane_cache,
                 mesh=None, batch_axes=None):
    """Deprecated: prefill a single prompt lane.  tokens (1, P) int32 ->
    (last_logits (1, V), filled 1-lane cache).  Same as ``prefill`` with a
    ``{"tokens": ...}`` batch."""
    return prefill(params, dsg, cfg, {"tokens": tokens}, lane_cache,
                   mesh=mesh, batch_axes=batch_axes)


def merge_slot_cache(cache, lane_cache, slot):
    """Deprecated: scatter a 1-lane cache into lane `slot` of a batched
    dense cache.  Delegates to serving.kv_cache.dense_merge (the
    DenseBackend write primitive)."""
    from repro.serving.kv_cache import dense_merge
    return dense_merge(cache, lane_cache, slot)


# ---------------------------------------------------------------------------
# input construction (ShapeDtypeStructs for dry-run, arrays for smoke tests)
# ---------------------------------------------------------------------------

def make_inputs(cfg: ModelConfig, shape: ShapeConfig, *,
                concrete: bool = False, seed: int = 0) -> dict:
    """Batch pytree for the given shape cell.

    kind='train': {'tokens','labels'} (+family extras).
    kind='prefill': prompt inputs.
    kind='decode': single-token inputs (cache built separately).
    """
    b, s = shape.global_batch, shape.seq_len
    dt = _dtype(cfg)

    def tok(shp):
        if concrete:
            rng = np.random.default_rng(seed)
            return jnp.asarray(rng.integers(0, cfg.vocab, shp, dtype=np.int32))
        return jax.ShapeDtypeStruct(shp, jnp.int32)

    def emb(shp):
        if concrete:
            rng = np.random.default_rng(seed + 1)
            return jnp.asarray(rng.standard_normal(shp), dtype=dt)
        return jax.ShapeDtypeStruct(shp, dt)

    if cfg.family == "encdec":
        sd = max(1, s // cfg.dec_ratio)
        if shape.kind == "train":
            return {"frames": emb((b, s, cfg.d_model)),
                    "tokens": tok((b, sd)), "labels": tok((b, sd))}
        if shape.kind == "prefill":
            return {"frames": emb((b, s, cfg.d_model)), "tokens": tok((b, sd))}
        return {"token": tok((b, 1))}

    if cfg.family == "vlm":
        p = min(cfg.vision_prefix, max(s // 4, 1))
        st = s - p
        if shape.kind == "train":
            return {"prefix_embeds": emb((b, p, cfg.d_model)),
                    "tokens": tok((b, st)), "labels": tok((b, st))}
        if shape.kind == "prefill":
            return {"prefix_embeds": emb((b, p, cfg.d_model)),
                    "tokens": tok((b, st))}
        return {"token": tok((b, 1))}

    if shape.kind == "train":
        return {"tokens": tok((b, s)), "labels": tok((b, s))}
    if shape.kind == "prefill":
        return {"tokens": tok((b, s))}
    return {"token": tok((b, 1))}
