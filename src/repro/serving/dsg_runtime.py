"""Serving-side DSG runtime: per-lane group-CSR patterns + DRS refresh.

The training stack runs the dimension-reduction search online, per token,
inside the forward (core/dsg_linear.swiglu_dsg_mask) — and then multiplies
a dense mask into a full matmul, saving nothing at serve time.  This
runtime moves the selection OUT of the decode hot path:

  * Each lane (slot) holds a per-layer active-group index list in the
    structured group-CSR form of core/sparse_mask.py, seeded at admission
    from the DRS scores of the prompt's last token (collected during the
    prefill dispatch) and stored host-side — pattern updates are O(keep)
    integer writes, the same "host bookkeeping lags the device" split as
    the paged backend's page-table mirror.
  * The jitted decode step contracts ONLY the listed groups
    (models/transformer._ffn_apply -> core/dsg_linear.swiglu_csr), with
    the CSR row width bucketed to a power of two
    (sparse_mask.active_group_bound) so counts drifting under the "ema"
    threshold never trigger per-count recompiles.
  * Every `refresh_interval` emitted tokens (per lane, so streams are
    invariant to co-scheduling and replica count) the decode step also
    runs `ops.drs_project`/`ops.drs_scores` on the current FFN inputs and
    returns the group scores; the host rewrites the due lanes' patterns
    off the measured decode window.  Between refreshes a lane's pattern
    rides unchanged — the paper's amortization (f(W) every 50 steps)
    applied to serving selection.

Threshold modes ("topk" | "ema") are PER-LANE here: serving lanes are
unrelated requests, so the paper's inter-sample threshold sharing
(threshold_mode="shared", batch row 0) degenerates to per-lane topk; the
online prefill path still honors cfg.dsg.threshold_mode.  "ema" carries a
per-(layer, lane) threshold EMA seeded from the admission topk threshold,
so selection needs no per-refresh sort and counts float with activation
mass.

Free lanes mirror the donor lane's pattern inside the jitted step
(mirror_csr) for the same reason they mirror its token: a paged free lane
writes duplicate K/V into the donor's pages, which is only harmless if
the duplicate is bit-identical — a diverging FFN path would corrupt the
pool.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import double_mask as dm
from repro.core import drs, sparse_mask
from repro.serving.telemetry import Telemetry


class DSGServingConfig(NamedTuple):
    """Runtime policy knobs (compute-dispatch knobs — which FFN executor
    applies the pattern — live on ModelConfig.dsg_ffn_apply, like
    paged_attn_kernel; sparsity level gamma lives on cfg.dsg)."""
    refresh_interval: int = 8     # emitted tokens between DRS refreshes,
                                  # per lane (1 = re-select every step)
    threshold: str = "topk"       # "topk" | "ema" per-lane selection
    ema_decay: float = 0.95       # threshold EMA decay ("ema" mode)


def as_serving_config(value) -> Optional[DSGServingConfig]:
    """Engine-kwarg coercion: True -> defaults, None/False -> disabled."""
    if value is None or value is False:
        return None
    if value is True:
        return DSGServingConfig()
    if isinstance(value, DSGServingConfig):
        return value
    raise TypeError(
        f"dsg_serving must be a DSGServingConfig, True, or None; got "
        f"{type(value).__name__}")


def mirror_csr(csr: dict, free_mask, donor) -> dict:
    """Overwrite free lanes' CSR rows with the donor lane's (jit-side,
    donor is traced).  csr = {'idx': (L, B, K), 'counts': (L, B)}."""
    idx, counts = csr["idx"], csr["counts"]
    fm = jnp.asarray(free_mask)
    d_idx = jnp.take(idx, donor, axis=1)          # (L, K)
    d_cnt = jnp.take(counts, donor, axis=1)       # (L,)
    return {"idx": jnp.where(fm[None, :, None], d_idx[:, None, :], idx),
            "counts": jnp.where(fm[None, :], d_cnt[:, None], counts)}


def double_mask_csr(norm_fn: Callable[[jax.Array], jax.Array],
                    x: jax.Array, idx: jax.Array, counts: jax.Array,
                    *, block: int, n_groups: int) -> jax.Array:
    """Double-mask selection (core/double_mask.py, paper §2.3) driven by
    a group-CSR pattern: y = Mask(norm(Mask(x))) with the mask expanded
    from the index list.  The decode stack here is pre-norm, which needs
    no DMS (the norm precedes the masked linear — see double_mask.py);
    this is the re-application hook for post-norm stacks, where the norm
    after the block densifies the zeros the CSR selection created."""
    mask = sparse_mask.csr_to_dense(idx, counts, n_groups)
    return dm.double_mask(norm_fn, x, mask, block)


class DSGRuntime:
    """Host-side per-lane DRS state for one ServingEngine.

    Patterns are kept full-width on the host — idx (L, B, G) int32,
    counts (L, B) int32 — and pushed to device sliced to the current pow2
    active-group bound (device_csr caches the pushed arrays per
    (version, bound), invalidated on any pattern write).  All updates are
    numpy: deterministic, cheap (O(L * keep) per lane), and off the
    device stream.
    """

    def __init__(self, cfg, scfg: DSGServingConfig, n_slots: int,
                 telemetry: Optional[Telemetry] = None):
        if not cfg.dsg.enabled:
            raise ValueError("dsg_serving needs cfg.dsg.enabled")
        if cfg.d_ff % cfg.dsg.block:
            raise ValueError(
                f"d_ff={cfg.d_ff} not divisible by DSG block "
                f"{cfg.dsg.block}")
        if scfg.threshold not in ("topk", "ema"):
            raise ValueError(
                f"serving threshold must be 'topk' or 'ema' (per-lane "
                f"modes), got {scfg.threshold!r}")
        if scfg.refresh_interval < 1:
            raise ValueError("refresh_interval must be >= 1")
        self.cfg = scfg
        self.block = cfg.dsg.block
        self.n_groups = cfg.d_ff // cfg.dsg.block
        self.keep = drs.keep_groups(cfg.d_ff, cfg.dsg.drs_cfg())
        self.n_layers = cfg.n_layers
        self.n_slots = n_slots
        shape = (cfg.n_layers, n_slots)
        # every lane starts at the minimal pattern {group 0}: inactive
        # lanes then never inflate the bound, and the in-jit donor mirror
        # makes their actual compute donor-identical anyway
        self.idx = np.zeros(shape + (self.n_groups,), np.int32)
        self.counts = np.ones(shape, np.int32)
        self.ema = np.zeros(shape, np.float32)
        self.lane_active = np.zeros(n_slots, bool)
        # the engine's recorder: the refresh span and the FLOP model's sums
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._dev = {}
        self._version = 0

    # -- pattern updates (host) ---------------------------------------------

    def _write_rows(self, lane: int, scores: np.ndarray, seed_ema: bool):
        """scores (L, G) float -> rewrite lane's per-layer CSR rows."""
        g, keep = self.n_groups, self.keep
        for l in range(self.n_layers):
            s = scores[l]
            thr_topk = np.partition(s, g - keep)[g - keep]
            if self.cfg.threshold == "ema" and not seed_ema:
                thr = self.ema[l, lane]
            else:
                thr = thr_topk
            mask = s >= thr
            if not mask.any():          # EMA threshold above every score
                mask[int(np.argmax(s))] = True
            active = np.flatnonzero(mask).astype(np.int32)
            row = np.zeros(g, np.int32)
            row[:len(active)] = active
            self.idx[l, lane] = row
            self.counts[l, lane] = len(active)
            if self.cfg.threshold == "ema":
                self.ema[l, lane] = (thr_topk if seed_ema else
                                     self.cfg.ema_decay * thr
                                     + (1 - self.cfg.ema_decay) * thr_topk)
        self._version += 1
        self._dev.clear()

    def set_lane_from_scores(self, lane: int, scores: np.ndarray):
        """Admission: seed the lane's pattern (and EMA state) from the
        DRS scores of the prompt's last token — the lane decodes sparsely
        from its FIRST step, no dense warm-in."""
        self._write_rows(lane, np.asarray(scores, np.float32),
                         seed_ema=True)
        self.lane_active[lane] = True

    def update_from_scores(self, scores: np.ndarray, lanes):
        """Refresh: scores (L, B, G) from the decode step's collect pass;
        only the DUE lanes' patterns are rewritten (per-lane cadence —
        co-scheduled lanes refreshing on their own token counts keeps
        streams invariant to slot assignment and replica count)."""
        with self.telemetry.span("repro.dsg.refresh") as span:
            scores = np.asarray(scores, np.float32)   # waits for the step
            due = [i for i in lanes if self.lane_active[i]]
            for i in due:
                self._write_rows(i, scores[:, i], seed_ema=False)
            span.set(lanes=len(due))

    def reset_lane(self, lane: int):
        """Retirement: drop back to the minimal pattern so a parked lane
        never inflates the group-wide bound."""
        self.idx[:, lane] = 0
        self.counts[:, lane] = 1
        self.ema[:, lane] = 0.0
        self.lane_active[lane] = False
        self._version += 1
        self._dev.clear()

    # -- decode-step operands (device) --------------------------------------

    def bound(self) -> int:
        """Static CSR row width for this step: pow2 bucket over the
        active lanes' counts."""
        if self.lane_active.any():
            mc = int(self.counts[:, self.lane_active].max())
        else:
            mc = 1
        return sparse_mask.active_group_bound(mc, self.n_groups)

    def warm_bounds(self) -> tuple:
        """Bounds warm_decode pre-compiles.  "topk" pins every lane at
        exactly `keep` groups (up to score ties), so one bucket suffices;
        "ema" counts float, so every bucket is reachable."""
        if self.cfg.threshold == "topk":
            return (sparse_mask.active_group_bound(self.keep,
                                                   self.n_groups),)
        return sparse_mask.active_group_buckets(self.n_groups)

    def device_csr(self, bound: int) -> dict:
        """Push the pattern state sliced to `bound`, cached per
        (version, bound) so steady decode re-uses the device arrays."""
        key = (self._version, bound)
        if key not in self._dev:
            self._dev[key] = {
                "idx": jnp.asarray(self.idx[:, :, :bound]),
                "counts": jnp.asarray(
                    np.minimum(self.counts, bound).astype(np.int32)),
            }
        return self._dev[key]

    # -- FLOP accounting (benchmarks/bench_dsg_serving.py) -------------------

    def record_step(self, active, bound: int):
        """Add this decode step's modeled FFN group-units to the running
        sums: dense = every group for every active lane; csr = the
        per-lane counts the CSR kernel walks; bound = what the padded XLA
        gather contracts (pow2 bucket, the static-shape overhead)."""
        n = len(active)
        count = self.telemetry.count
        count("dsg.steps")
        count("dsg.dense_units", self.n_layers * self.n_groups * n)
        count("dsg.csr_units", int(self.counts[:, list(active)].sum()))
        count("dsg.bound_units", self.n_layers * bound * n)

    def flop_stats(self) -> dict:
        """Modeled FFN FLOP reduction over the recorded steps (the
        `dsg.*` counters of the engine's recorder; reset them with
        `telemetry.reset_counters("dsg.")`)."""
        c = self.telemetry.counters
        if not c["dsg.steps"]:
            raise ValueError("no decode steps recorded")
        dense, csr, bnd = (c["dsg.dense_units"], c["dsg.csr_units"],
                           c["dsg.bound_units"])
        return {"steps": c["dsg.steps"],
                "dense_units": dense, "csr_units": csr,
                "bound_units": bnd,
                "flop_reduction_csr": dense / max(csr, 1),
                "flop_reduction_bound": dense / max(bnd, 1),
                "overhead_bytes": sparse_mask.csr_overhead_bytes(
                    (self.n_layers, self.n_slots), self.n_groups)}
