"""Paged-attention decode kernel coverage (kernels/paged_attention.py).

Three rings of parity, all in interpret mode (the kernel body executes
exactly as Mosaic would see it):
  * kernel vs the pure-jnp oracle (ref.paged_decode_ref) across page
    sizes {8, 16}, ragged per-lane depths, partial final pages, GQA
    group sizes, dtypes, and sliding windows — pools must match the
    XLA scatter bit-for-bit;
  * the self_attention paged branch: Pallas executor vs the bounded
    XLA fallback on identical inputs, and the bounded fallback vs the
    whole-window gather;
  * the serving engine: a kernel-executor paged engine must reproduce
    the dense backend's token stream over admit -> decode -> retire ->
    readmit traffic (lane/page reuse included).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import (assert_streams_equal, engine_spec, make_engine_parts,
                     mixed_traffic, run_and_collect)
from repro.kernels import ops, paged_attention, ref
from repro.models import attention as attn

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _paged_setup(seed, b, h, kv, d, ps, max_pages, pos, dtype=jnp.float32):
    """Random pools + a page table mapping each lane's live pages to
    distinct physical pages (page 0 reserved as scratch, as the backend
    lays it out)."""
    rng = np.random.default_rng(seed)
    n_pages = 1 + b * max_pages
    mk = lambda shape: jnp.asarray(rng.standard_normal(shape), dtype)
    q = mk((b, h, d))
    k_new, v_new = mk((b, kv, d)), mk((b, kv, d))
    k_pages, v_pages = (mk((n_pages, ps, kv, d)) for _ in range(2))
    table = np.zeros((b, max_pages), np.int32)
    nxt = 1
    for lane in range(b):
        for j in range(pos[lane] // ps + 1):
            table[lane, j] = nxt
            nxt += 1
    return (q, k_new, v_new, k_pages, v_pages, jnp.asarray(table),
            jnp.asarray(np.asarray(pos, np.int32)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ps", [8, 16])
@pytest.mark.parametrize("h,kv", [(4, 2), (2, 2)])
def test_kernel_matches_oracle(dtype, ps, h, kv):
    # ragged depths: page-boundary cases (0, ps-1, ps) + partial pages
    pos = [0, ps - 1, ps, 2 * ps + 3, 5 * ps - 1]
    args = _paged_setup(0, len(pos), h, kv, 16, ps, 6, pos, dtype)
    o, kp, vp = paged_attention.paged_decode(*args, interpret=True)
    ow, kw, vw = ref.paged_decode_ref(*args)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ow, np.float32), **TOL[dtype])
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(kw))
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(vw))


def test_kernel_bounded_walk_and_window():
    ps, pos = 8, [5, 17, 40]
    args = _paged_setup(1, 3, 4, 2, 16, ps, 8, pos)
    full, _, _ = paged_attention.paged_decode(*args, interpret=True)
    # depth-bounded walk: 6 pages cover max(pos)=40 -> identical output
    bounded, _, _ = paged_attention.paged_decode(*args, num_pages=6,
                                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(bounded), np.asarray(full))
    w, _, _ = paged_attention.paged_decode(*args, window=10, interpret=True)
    ww, _, _ = ref.paged_decode_ref(*args, window=10)
    np.testing.assert_allclose(np.asarray(w), np.asarray(ww),
                               **TOL[jnp.float32])


def _attn_inputs(seed, b, d_model, h, kv, hd, ps, max_pages, pos):
    rng = np.random.default_rng(seed)
    p = attn.init_attention(jax.random.PRNGKey(seed), d_model, h, kv, hd)
    x = jnp.asarray(rng.standard_normal((b, 1, d_model)), jnp.float32)
    n_pages = 1 + b * max_pages
    pools = {"k": jnp.asarray(rng.standard_normal((n_pages, ps, kv, hd)),
                              jnp.float32),
             "v": jnp.asarray(rng.standard_normal((n_pages, ps, kv, hd)),
                              jnp.float32)}
    table = np.zeros((b, max_pages), np.int32)
    nxt = 1
    for lane in range(b):
        for j in range(pos[lane] // ps + 1):
            table[lane, j] = nxt
            nxt += 1
    cp = jnp.asarray(np.asarray(pos, np.int32))
    return p, x, pools, jnp.asarray(table), cp


@pytest.mark.parametrize("live_pages", [None, 4])
def test_self_attention_kernel_vs_xla(live_pages):
    """The full paged branch: Pallas executor vs XLA fallback on the same
    scatter + depth-bounded gather + attend step (RoPE included)."""
    ps, pos = 8, [3, 12, 25]
    p, x, pools, table, cp = _attn_inputs(3, 3, 32, 4, 2, 8, ps, 8, pos)
    kw = dict(n_heads=4, n_kv=2, rope_theta=10_000.0, q_pos=cp[:, None],
              cache_pos=cp, page_table=table, live_pages=live_pages)
    out_k, cache_k = attn.self_attention(p, x, cache=dict(pools),
                                         paged_kernel="kernel", **kw)
    out_x, cache_x = attn.self_attention(p, x, cache=dict(pools),
                                         paged_kernel="xla", **kw)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache_k[leaf]),
                                      np.asarray(cache_x[leaf]))


def test_xla_fallback_bounded_matches_whole_window():
    """Satellite fix: the XLA paged branch gathering only the live-page
    prefix must reproduce the historical whole-window gather."""
    ps, pos = 8, [3, 12, 25]
    p, x, pools, table, cp = _attn_inputs(4, 3, 32, 4, 2, 8, ps, 8, pos)
    kw = dict(n_heads=4, n_kv=2, rope_theta=10_000.0, q_pos=cp[:, None],
              cache_pos=cp, page_table=table, paged_kernel="xla")
    out_full, _ = attn.self_attention(p, x, cache=dict(pools),
                                      live_pages=None, **kw)
    out_bound, _ = attn.self_attention(p, x, cache=dict(pools),
                                       live_pages=4, **kw)
    np.testing.assert_allclose(np.asarray(out_bound), np.asarray(out_full),
                               rtol=2e-6, atol=2e-6)


def test_undersized_walk_never_corrupts_pools():
    """An undersized num_pages bound is a caller bug (the scheduler's
    live_page_bound always covers the batch) — it may truncate the
    attended window, but it must never flush garbage over live K/V
    pages: the write-back page is clamped into the walk and degrades to
    an identity rewrite."""
    ps, pos = 8, [5, 17, 40]                  # deepest lane needs 6 pages
    args = _paged_setup(7, 3, 4, 2, 16, ps, 8, pos)
    q, k_new, v_new, k_pages, v_pages, table, cp = args
    _, kp, vp = paged_attention.paged_decode(*args, num_pages=2,
                                             interpret=True)
    # lane 0 (depth 5, inside the walk) scatters its token normally;
    # lanes 1 and 2 are beyond the walk and must leave the pools intact
    want_k = k_pages.at[table[0, 0], 5].set(k_new[0])
    want_v = v_pages.at[table[0, 0], 5].set(v_new[0])
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(want_v))


def test_self_attention_kernel_bf16_scores_tolerance():
    """attn_bf16_scores halves the XLA chain's score-tensor HBM traffic;
    the kernel's score tile never leaves VMEM, so it keeps f32 stats —
    parity with the bf16-scores XLA path is tolerance-level (standard
    flash-kernel numerics), pinned here so the divergence stays bounded."""
    ps, pos = 8, [3, 12, 25]
    p, x, pools, table, cp = _attn_inputs(5, 3, 32, 4, 2, 8, ps, 8, pos)
    p = {k: v.astype(jnp.bfloat16) for k, v in p.items()}
    x = x.astype(jnp.bfloat16)
    pools = {k: v.astype(jnp.bfloat16) for k, v in pools.items()}
    kw = dict(n_heads=4, n_kv=2, rope_theta=10_000.0, q_pos=cp[:, None],
              cache_pos=cp, page_table=table, bf16_scores=True)
    out_k, _ = attn.self_attention(p, x, cache=dict(pools),
                                   paged_kernel="kernel", **kw)
    out_x, _ = attn.self_attention(p, x, cache=dict(pools),
                                   paged_kernel="xla", **kw)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_x, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_paged_kernel_mode_guard():
    with pytest.raises(ValueError):
        attn._use_paged_kernel("mosaic")


def test_live_page_bound_covered_by_warm_buckets():
    """Every bound the scheduler can request must be in the set
    warm_decode pre-compiles, or a jit compile lands mid-measurement."""
    from repro.serving.scheduler import live_page_bound, live_page_buckets
    for cap in (1, 3, 4, 5, 8, 16):
        buckets = live_page_buckets(cap)
        for pos in range(cap * 8):
            b = live_page_bound(pos, 8, cap)
            assert b in buckets and b * 8 > pos


def test_repro_interpret_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    assert ops._interpret()
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert not ops._interpret()
    monkeypatch.delenv("REPRO_INTERPRET")
    assert ops._interpret() == (jax.default_backend() == "cpu")


def test_tpu_backend_never_interprets(monkeypatch):
    """On a TPU the kernels always compile natively: interpret mode there
    would hide the device, so REPRO_INTERPRET=1 raises."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)
    assert not ops._interpret()
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert not ops._interpret()
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="CPU only"):
        ops._interpret()


# ---------------------------------------------------------------------------
# engine-level: kernel executor vs dense backend token stream
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_parts():
    return make_engine_parts()


@pytest.mark.parametrize("page_size", [8, 16])
def test_kernel_engine_stream_matches_dense(engine_parts, page_size):
    """6 requests through 2 slots: every lane is retired and readmitted,
    pages are freed and reused — the Pallas-executor paged engine must
    emit the dense backend's exact token stream."""
    cfg, params, dsg = engine_parts
    dense_out = run_and_collect(engine_spec(*engine_parts),
                                mixed_traffic(cfg))
    kcfg = cfg.replace(paged_attn_kernel="kernel")
    kernel_out, eng = run_and_collect(
        engine_spec(kcfg, params, dsg, cache_backend="paged",
                    page_size=page_size, cache_tokens=80),
        mixed_traffic(cfg), return_engine=True)
    assert_streams_equal(dense_out, kernel_out, "kernel engine vs dense")
    alloc = eng.backend.allocator
    assert alloc.free_pages == alloc.n_pages - alloc.reserved
