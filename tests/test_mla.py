"""Multi-head latent attention (models/mla.py) and its paged latent pool on
the CPU: YaRN's frequencies and scale at DeepSeek-V2-Lite's widths, the
absorbed decode against the plain form, the `mla_decode` kernel (interpret
mode) against the XLA scatter-and-gather, the paged backend over a latent
pool, and the serving engine's streams and row counts on an MLA model.
Agreement with the published model's reference is
chipbench/tests/test_deepseek_v2.py's."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import paged_attention
from repro.models import api, mla
from repro.models.layers import (apply_rope_pairs, rope_freqs,
                                 yarn_correction_range, yarn_freqs,
                                 yarn_mscale)
from repro.serving import kv_cache
from repro.serving.scheduler import Request, ServingEngine

V2 = configs.get_config("deepseek-v2-lite")


def smoke(**kw):
    base = configs.get_smoke_config("deepseek-v2-lite")
    return base.replace(dsg=base.dsg._replace(enabled=False), **kw)


# --------------------------------------------------------------------------
# YaRN at the published widths


def test_yarn_correction_range_and_scale_of_the_published_config():
    """64 rotary dims at base 10000 over 4096 original positions, beta_fast
    32 and beta_slow 1: pairs 10 to 23 blend; m = 0.1 x 0.707 x ln 40 + 1,
    and the softmax scale is 192^-1/2 m^2; cos and sin keep factor 1."""
    assert yarn_correction_range(64, 10000.0, 4096, 32.0, 1.0) == (10, 23)
    m = yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert round(m, 4) == 1.2608
    assert mla.softmax_scale(V2) == pytest.approx(192 ** -0.5 * m * m)
    _, cos_sin = mla.rope_tables(V2)
    assert cos_sin == pytest.approx(1.0)


def test_yarn_frequencies_differ_from_plain_rope_below_4096():
    """Pairs up to 10 keep their frequency, pairs from 23 are divided by
    40, the ones between are blended: so a rotation at position 100, far
    below the original 4096, is not plain RoPE's."""
    inv, _ = mla.rope_tables(V2)
    plain = rope_freqs(64, 10000.0)
    inv, plain = np.asarray(inv), np.asarray(plain)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all(inv[11:23] < plain[11:23])
    assert np.all(inv[11:23] > plain[11:23] / 40)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, 64))
    pos = jnp.array([[100]])
    yarn = apply_rope_pairs(x, pos, jnp.asarray(inv))
    rope = apply_rope_pairs(x, pos, jnp.asarray(plain))
    assert float(jnp.max(jnp.abs(yarn - rope))) > 0.1
    np.testing.assert_allclose(np.asarray(yarn_freqs(
        64, 10000.0, 40.0, 4096, 32.0, 1.0)), inv)


# --------------------------------------------------------------------------
# absorbed against plain


def test_absorbed_decode_matches_the_plain_form():
    """Each position's output decoded in the absorbed form, over a dense
    cache of the rows before it, against the plain form over the whole
    sequence.  Same mathematics in another association, float32 on the
    CPU: 2e-5 on outputs of unit scale (a wrong rotary position, scale or
    latent norm moves them by O(0.1))."""
    cfg = smoke()
    p = mla.init_mla(jax.random.PRNGKey(1), cfg)
    p["kv_norm"]["scale"] = 1 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (cfg.kv_lora_rank,))
    s = 21
    x = jax.random.normal(jax.random.PRNGKey(3), (2, s, cfg.d_model))
    plain, new = mla.self_attention(p, x, cfg, q_pos=jnp.arange(s))
    rows = new["c"]
    assert rows.shape == (2, s, mla.row_width(cfg))
    np.testing.assert_array_equal(np.asarray(rows[..., cfg.latent_row:]), 0)
    for t in range(s):
        cache = jnp.zeros((2, 32, mla.row_width(cfg))).at[:, :t].set(
            rows[:, :t])
        out, c = mla.self_attention(p, x[:, t:t + 1], cfg,
                                    q_pos=jnp.array([t]), cache={"c": cache},
                                    cache_pos=jnp.int32(t))
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(plain[:, t]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(c["c"][:, t]),
                                   np.asarray(rows[:, t]), rtol=1e-6,
                                   atol=1e-6)


def test_mla_prompt_into_a_dense_cache_starts_at_zero():
    cfg = smoke()
    p = mla.init_mla(jax.random.PRNGKey(1), cfg)
    x = jnp.ones((1, 4, cfg.d_model))
    cache = {"c": jnp.zeros((1, 16, mla.row_width(cfg)))}
    with pytest.raises(NotImplementedError):
        mla.self_attention(p, x, cfg, q_pos=jnp.arange(4), cache=cache,
                           cache_pos=jnp.int32(0))
    out, c = mla.self_attention(p, x, cfg, q_pos=jnp.arange(4),
                                cache=cache, cache_pos=0, fresh=True)
    assert out.shape == x.shape and float(jnp.abs(c["c"][0, 3]).sum()) > 0


# --------------------------------------------------------------------------
# the kernel against the XLA scatter and gather


def _latent_setup(seed, pos, ps, max_pages, n_layers, dtype, h=4, lat=32,
                  rope=8):
    rng = np.random.default_rng(seed)
    r = 128
    b = len(pos)
    n_pages = 1 + b * max_pages
    mk = lambda shape: jnp.asarray(rng.standard_normal(shape), dtype)
    pad = lambda a: a.at[..., lat + rope:].set(0)
    q, c_new = pad(mk((b, h, r))), pad(mk((b, r)))
    pool = pad(mk((n_layers, n_pages, ps, r)))
    table = np.zeros((b, max_pages), np.int32)
    nxt = 1
    for lane in range(b):
        for j in range(min(pos[lane] // ps + 1, max_pages)):
            table[lane, j] = nxt
            nxt += 1
    return q, c_new, pool, jnp.asarray(table), jnp.asarray(
        np.asarray(pos, np.int32))


def _oracle(q, c_new, pool, table, pos, layer, lat, scale):
    """The XLA fallback's mathematics: scatter the row, gather the whole
    table, attend rows 0..pos (models/mla.py)."""
    ps = pool.shape[2]
    b = q.shape[0]
    pool = pool.at[layer, table[jnp.arange(b), pos // ps], pos % ps].set(
        c_new)
    t = jnp.arange(table.shape[1] * ps)
    rows = pool[layer, table[:, t // ps], t % ps]
    s = jnp.einsum("bhr,btr->bht", q.astype(jnp.float32),
                   rows.astype(jnp.float32)) * scale
    s = jnp.where((t[None, :] <= pos[:, None])[:, None], s, -1e30)
    o = jnp.einsum("bht,btc->bhc", jax.nn.softmax(s, -1),
                   rows[..., :lat].astype(jnp.float32))
    return o, pool


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("n_layers,layer", [(1, 0), (3, 2)])
@pytest.mark.parametrize("ps", [8, 16])
def test_mla_decode_kernel_matches_the_xla_path(dtype, tol, n_layers, layer,
                                                ps):
    """Ragged depths with page and block edges (0, ps - 1, ps, a walk of
    several blocks): the kernel's output against the oracle (f32 to 2e-5;
    bf16 products with f32 sums to 2e-2, the paged kernel's tolerance),
    the pool written at `layer` exactly as the scatter writes it, and
    every other layer untouched."""
    pos = [0, ps - 1, ps, 2 * ps + 3, 19 * ps + 5]
    q, c_new, pool, table, posv = _latent_setup(0, pos, ps, 24, n_layers,
                                                dtype)
    o, got = paged_attention.mla_decode(q, c_new, pool, table, posv,
                                        jnp.int32(layer), lat=32, scale=0.3,
                                        interpret=True)
    want_o, want = _oracle(q, c_new, pool, table, posv, layer, 32, 0.3)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want_o), rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mla_decode_mirrored_lanes_write_the_same_row():
    """Free lanes mirror a donor's table row and position: they write the
    donor's row to the donor's page and read what it reads."""
    ps = 8
    q, c_new, pool, table, pos = _latent_setup(1, [13, 4], ps, 4, 2,
                                               jnp.float32)
    q = q.at[1].set(q[0])
    c_new = c_new.at[1].set(c_new[0])
    table = table.at[1].set(table[0])
    pos = pos.at[1].set(pos[0])
    o, got = paged_attention.mla_decode(q, c_new, pool, table, pos,
                                        jnp.int32(1), lat=32, scale=0.3,
                                        interpret=True)
    want_o, want = _oracle(q[:1], c_new[:1], pool, table[:1], pos[:1], 1,
                           32, 0.3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o)[[0, 0]],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# the latent pool in the paged backend


def test_paged_backend_holds_one_latent_pool():
    """An MLA model's paged cache is one pool of rows, `pages_c`, beside
    the page table; a prompt's splice fills its pages with the lane's
    rows, and copy-on-write copies a page's rows."""
    cfg = smoke()
    be = kv_cache.PagedBackend(page_size=8, total_tokens=64,
                               prefix_sharing=True)
    h = be.make(cfg, 2, 32)
    assert set(h.data) == {"pages_c", "page_table"}
    assert h.data["pages_c"].shape == (cfg.n_layers, 9, 8,
                                       mla.row_width(cfg))
    assert be.resident_bytes(h) == (h.data["pages_c"].nbytes
                                    + h.data["page_table"].nbytes)
    lane = api.make_cache(cfg, 1, 32)
    assert set(lane) == {"c"}
    lane = {"c": jax.random.normal(jax.random.PRNGKey(0),
                                   lane["c"].shape)}
    prompt = np.arange(12, dtype=np.int32)
    chain = kv_cache.prefix_chain(prompt, 8)
    h = be.write(h, lane, 0, n_tokens=12, reserve_tokens=16, chain=chain)
    pages = be._table[0, :2]
    np.testing.assert_array_equal(
        np.asarray(h.data["pages_c"][:, pages]).reshape(cfg.n_layers, 16, -1),
        np.asarray(lane["c"][:, 0, :16]))
    # a second lane shares the full first page and the partial tail page;
    # its write at position 12 copies the tail page first
    h = be.write(h, None, 1, n_tokens=12, reserve_tokens=16, chain=chain)
    assert list(be._table[1, :2]) == list(pages)
    h = be.ensure(h, 1, 12)
    assert be.cow_copies == 1
    np.testing.assert_array_equal(
        np.asarray(h.data["pages_c"][:, be._table[1, 1]]),
        np.asarray(h.data["pages_c"][:, pages[1]]))


# --------------------------------------------------------------------------
# the serving engine on an MLA model


@pytest.fixture(scope="module")
def parts():
    cfg = smoke()
    return cfg, api.init_model(jax.random.PRNGKey(0), cfg)


def _serve(cfg, params, backend, prompts, max_new=9):
    eng = ServingEngine(cfg, params, None, n_slots=3, max_seq=64,
                        prompt_bucket=32, cache_backend=backend,
                        page_size=8)
    for uid, pr in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=pr, max_new=max_new + uid))
    while eng.queue_depth() or eng.busy_slots():
        eng.step()
    return eng, [eng.done[u].output for u in range(len(prompts))]


def test_engine_streams_agree_across_caches_and_count_rows(parts):
    """Greedy streams are one and the same over the dense latent cache,
    the paged pool through the XLA gather, and the paged pool through
    `mla_decode` (interpret mode); `kv.rows_written` counts each
    admission's prompt rows and one row per active lane per decode."""
    cfg, params = parts
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (16, 32, 16, 32, 16)]
    _, dense = _serve(cfg, params, "dense", prompts)
    _, xla = _serve(cfg.replace(paged_attn_kernel="xla"), params, "paged",
                    prompts)
    eng, kern = _serve(cfg.replace(paged_attn_kernel="kernel"), params,
                       "paged", prompts)
    assert dense == xla == kern
    spans = eng.telemetry.spans()
    written = (sum(s.attrs["bucket"] for s in spans
                   if s.name == "repro.engine.admit")
               + sum(s.attrs["lanes"] for s in spans
                     if s.name == "repro.engine.step"))
    assert eng.telemetry.counters["kv.rows_written"] == written
    blocks = [s.attrs["kv_blocks"] for s in spans
              if s.name == "repro.engine.dispatch"]
    assert all(b > 0 for b in blocks)
