"""Hypothesis property tests for the DRS selection core (core/drs.py)
and the mask algebra (core/masks.py) the serving runtime leans on.

Scores are generated from a drawn PRNG seed (hypothesis shrinks the
seed), so rows are generically distinct floats; tie behavior gets its
own deterministic test.  These are host/jit-free pure functions —
hundreds of examples run in milliseconds."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # dev-only dep (requirements-dev.txt); skip, don't error
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import drs, masks

_SEED = st.integers(0, 2**32 - 1)
_ROWS = st.integers(1, 5)
_G = st.sampled_from([2, 4, 8, 16])
_BLOCK = st.sampled_from([4, 8])
_GAMMA = st.sampled_from([0.0, 0.25, 0.5, 0.75])


def _scores(seed, rows, g):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((rows, g)).astype(np.float32)


# ---------------------------------------------------------------------------
# select_mask threshold modes
# ---------------------------------------------------------------------------

@settings(max_examples=150, deadline=None)
@given(_SEED, _ROWS, _G, _BLOCK, _GAMMA)
def test_topk_density_respects_gamma(seed, rows, g, block, gamma):
    """topk mode: every row keeps at least keep_groups(gamma) groups, and
    EXACTLY that many when its scores are distinct (ties only widen)."""
    cfg = drs.DRSConfig(gamma=gamma, block=block, threshold_mode="topk")
    n_out = g * block
    s = _scores(seed, rows, g)
    mask, ema = drs.select_mask(jnp.asarray(s), n_out, cfg)
    assert ema is None
    k = drs.keep_groups(n_out, cfg)
    counts = np.asarray(mask).sum(axis=-1)
    assert (counts >= k).all()
    for r in range(rows):
        if len(np.unique(s[r])) == g:
            assert counts[r] == k
    assert float(masks.density(mask)) >= k / g - 1e-6


@settings(max_examples=150, deadline=None)
@given(_SEED, _ROWS, _G, _BLOCK, st.sampled_from([0.25, 0.5, 0.75]))
def test_shared_mode_uses_row0_topk_threshold(seed, rows, g, block,
                                              gamma):
    """shared mode == thresholding EVERY row at row 0's k-th largest
    score (paper Appendix B inter-sample sharing), including rows whose
    own top-k threshold would differ."""
    cfg = drs.DRSConfig(gamma=gamma, block=block,
                        threshold_mode="shared")
    n_out = g * block
    s = _scores(seed, rows, g)
    k = drs.keep_groups(n_out, cfg)
    mask, _ = drs.select_mask(jnp.asarray(s), n_out, cfg)
    got = np.asarray(mask) > 0
    if k >= g:
        assert got.all()
        return
    thr = np.sort(s[0])[g - k]          # row 0's k-th largest
    assert np.array_equal(got, s >= thr)


@settings(max_examples=150, deadline=None)
@given(_SEED, _ROWS, _G, _BLOCK)
def test_ema_deterministic_and_follows_decay(seed, rows, g, block):
    """ema mode is a pure function of (scores, carried threshold): same
    inputs -> identical mask and new EMA; the None seed-call adopts the
    batch threshold, and a carried EMA decays toward it."""
    cfg = drs.DRSConfig(gamma=0.5, block=block, threshold_mode="ema",
                        ema_decay=0.9)
    n_out = g * block
    s = jnp.asarray(_scores(seed, rows, g))
    k = drs.keep_groups(n_out, cfg)
    m1, e1 = drs.select_mask(s, n_out, cfg)
    m2, e2 = drs.select_mask(s, n_out, cfg)
    assert np.array_equal(np.asarray(m1), np.asarray(m2))
    if k >= g:                           # early all-ones path, EMA None
        assert e1 is None and e2 is None
        return
    assert float(e1) == float(e2)
    # seed call: EMA = decay*t + (1-decay)*t = t, the batch mean top-k
    # threshold (f32 mean over rows)
    per_row = np.sort(np.asarray(s), axis=-1)[:, g - k]
    thr_now = float(jnp.mean(jnp.asarray(per_row)))
    assert np.isclose(float(e1), thr_now, rtol=1e-5)
    assert np.array_equal(np.asarray(m1),
                          np.asarray(s) >= thr_now)
    # carried threshold: mask thresholds at the CARRIED value, new EMA
    # decays toward the batch threshold
    carried = jnp.asarray(thr_now + 1.0, jnp.float32)
    m3, e3 = drs.select_mask(s, n_out, cfg, ema_threshold=carried)
    assert np.array_equal(np.asarray(m3),
                          np.asarray(s) >= float(carried))
    assert np.isclose(float(e3), 0.9 * float(carried) + 0.1 * thr_now,
                      rtol=1e-5)


def test_topk_all_tied_scores_keep_everything():
    """Degenerate ties: every score equal -> threshold equals them all,
    the >= comparison keeps every group (never fewer than k)."""
    cfg = drs.DRSConfig(gamma=0.5, block=4, threshold_mode="topk")
    mask, _ = drs.select_mask(jnp.ones((3, 8)), 32, cfg)
    assert np.asarray(mask).all()


# ---------------------------------------------------------------------------
# mask algebra round trips
# ---------------------------------------------------------------------------

@settings(max_examples=150, deadline=None)
@given(_SEED, _ROWS, _G, _BLOCK)
def test_apply_expanded_matches_explicit_expansion(seed, rows, g, block):
    """apply_expanded == multiply by jnp.repeat-expanded mask, exactly
    (0/1 multiplies are exact in f32); re-applying the same mask is a
    no-op, and the all-ones mask is the identity."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, g * block)).astype(np.float32)
    gm = rng.integers(0, 2, (rows, g)).astype(np.float32)
    y = np.asarray(masks.apply_expanded(jnp.asarray(x),
                                        jnp.asarray(gm), block))
    assert np.array_equal(y, x * np.repeat(gm, block, axis=-1))
    y2 = np.asarray(masks.apply_expanded(jnp.asarray(y),
                                         jnp.asarray(gm), block))
    assert np.array_equal(y2, y)
    ident = np.asarray(masks.apply_expanded(jnp.asarray(x),
                                            jnp.ones((rows, g),
                                                     np.float32), block))
    assert np.array_equal(ident, x)


@settings(max_examples=200, deadline=None)
@given(st.lists(st.integers(1, 6), min_size=1, max_size=3), _G, _BLOCK)
def test_mask_overhead_bytes_bit_packs_per_row(batch, g, block):
    """One bit per group per row, byte-rounded — and the stash cost for
    an (..., N) tensor never depends on the block size beyond G."""
    shape = tuple(batch) + (g * block,)
    rows = int(np.prod(batch))
    b = masks.mask_overhead_bytes(shape, block)
    assert b == rows * ((g + 7) // 8)
    # doubling the batch doubles the cost; eight groups fit one byte
    assert masks.mask_overhead_bytes((2,) + shape, block) == 2 * b
    assert masks.mask_overhead_bytes((8 * block,), block) == 1
