"""The served expert layer: dropless routing over the experts a chip holds,
the grouped expert kernel, the leading dense layer's scan, and the counts
the engine records (models/moe.py `moe_ffn_dropless`,
kernels/moe_experts.py, models/transformer.py, serving/scheduler.py).

All on the CPU at smoke size, in float32 unless a case says otherwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import moe_experts as mk
from repro.models import api
from repro.models import moe as moe_mod
from repro.serving import kv_cache
from repro.serving.kv_cache import CacheHandle
from repro.serving.scheduler import (Request, ServingEngine, _restore_table,
                                     live_page_bound)

ARCH = "deepseek-moe-16b"


def smoke_cfg():
    cfg = configs.get_smoke_config(ARCH)
    return cfg.replace(dsg=cfg.dsg._replace(enabled=False))


@pytest.fixture(scope="module")
def parts():
    cfg = smoke_cfg()
    return cfg, api.init_model(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------------------
# the grouped expert kernel


def grouped_rows(sizes, d, dtype, key):
    """Rows for the kernel: groups of `sizes` rows, each at a multiple of
    the kernel's tile, with one tile of padding after the last."""
    sizes = jnp.asarray(sizes, jnp.int32)
    padded = (sizes + mk.TILE - 1) // mk.TILE * mk.TILE
    starts = jnp.cumsum(padded) - padded
    m = int(padded.sum()) + mk.TILE
    x = jax.random.normal(key, (m, d)).astype(dtype)
    return x, starts, sizes


def in_groups(starts, sizes, m):
    keep = np.zeros(m, bool)
    for s, n in zip(np.asarray(starts), np.asarray(sizes)):
        keep[s:s + n] = True
    return keep


@pytest.mark.parametrize("sizes,dtype", [
    ([5, 0, 17, 3], jnp.float32),        # an expert with no rows
    ([0, 0, 40, 0], jnp.float32),        # every row on one expert
    ([0, 0, 0, 0], jnp.float32),         # no row at all
    ([16, 1, 0, 33], jnp.bfloat16),      # the served dtype
])
def test_moe_experts_kernel_matches_its_reference(sizes, dtype):
    key = jax.random.PRNGKey(3)
    e, d, f = len(sizes), 128, 256
    kg, ku, kd, kx = jax.random.split(key, 4)
    wg = (jax.random.normal(kg, (e, d, f)) * d ** -0.5).astype(dtype)
    wu = (jax.random.normal(ku, (e, d, f)) * d ** -0.5).astype(dtype)
    wd = (jax.random.normal(kd, (e, f, d)) * f ** -0.5).astype(dtype)
    x, starts, sz = grouped_rows(sizes, d, dtype, kx)
    got = mk.moe_experts(x, starts, sz, wg, wu, wd, interpret=True)
    want = mk.moe_experts_ref(x, starts, sz, wg, wu, wd)
    keep = in_groups(starts, sz, x.shape[0])
    # the same products in the same order, bf16 included
    np.testing.assert_array_equal(np.asarray(got, np.float32)[keep],
                                  np.asarray(want, np.float32)[keep])


def test_moe_experts_kernel_over_several_row_blocks(monkeypatch):
    """Groups that straddle row blocks (a prefill's rows) come out the same
    as in one block."""
    monkeypatch.setattr(mk, "MAX_BLOCK_ROWS", 32)
    key = jax.random.PRNGKey(5)
    e, d, f = 3, 128, 128
    kg, ku, kd, kx = jax.random.split(key, 4)
    wg = jax.random.normal(kg, (e, d, f)) * d ** -0.5
    wu = jax.random.normal(ku, (e, d, f)) * d ** -0.5
    wd = jax.random.normal(kd, (e, f, d)) * f ** -0.5
    x, starts, sz = grouped_rows([40, 7, 21], d, jnp.float32, kx)
    got = mk.moe_experts(x, starts, sz, wg, wu, wd, interpret=True)
    want = mk.moe_experts_ref(x, starts, sz, wg, wu, wd)
    keep = in_groups(starts, sz, x.shape[0])
    np.testing.assert_array_equal(np.asarray(got)[keep],
                                  np.asarray(want)[keep])


def test_weight_source_repeats_the_block_before_an_empty_expert():
    src = mk.weight_source(jnp.array([0, 0, 3, 0, 2, 0], jnp.int32))
    assert src.tolist() == [2, 2, 2, 2, 4, 4]
    assert mk.weight_source(jnp.zeros(3, jnp.int32)).tolist() == [0, 0, 0]


# ---------------------------------------------------------------------------
# dropless routing over the held experts


def moe_params(key, d=32, e=8, fe=64, shared=1):
    return moe_mod.init_moe(key, d, e, fe, shared)


def share(p, offset, held):
    """The weights chip `offset // held` holds of the uncut layer `p`."""
    out = dict(p)
    for k in ("w_gate", "w_up", "w_down"):
        out[k] = p[k][offset:offset + held]
    return out


def dense_moe(p, x, top_k, norm_topk):
    """Every token through every expert it chose: the uncut layer."""
    x2d = x.reshape(-1, x.shape[-1])
    tw, te = moe_mod.route(x2d @ p["router"], top_k, norm_topk)
    y = jnp.zeros_like(x2d)
    for ei in range(p["w_gate"].shape[0]):
        g = jax.nn.silu(x2d @ p["w_gate"][ei]) * (x2d @ p["w_up"][ei])
        w = jnp.sum(jnp.where(te == ei, tw, 0.0), -1, keepdims=True)
        y = y + w * (g @ p["w_down"][ei])
    y = y.reshape(x.shape)
    if "shared" in p:
        from repro.core.dsg_linear import swiglu_dense
        y = y + swiglu_dense(p["shared"], x)
    return y


@pytest.mark.parametrize("norm_topk", [False, True])
def test_shares_add_up_to_the_uncut_layer(norm_topk):
    """Four chips of two experts each: their routed parts, plus the shared
    experts once, are the layer with all eight experts."""
    key = jax.random.PRNGKey(11)
    p = moe_params(key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 7, 32))
    kw = dict(top_k=3, norm_topk=norm_topk)
    full, _ = moe_mod.moe_ffn_dropless(p, x, **kw)
    shared_only = dense_moe(dict(p, w_down=p["w_down"] * 0), x, 3,
                            norm_topk)
    parts = [moe_mod.moe_ffn_dropless(share(p, c * 2, 2), x,
                                      expert_offset=c * 2, **kw)[0]
             - shared_only for c in range(4)]
    # float32 sums in another order: rounding only
    np.testing.assert_allclose(np.asarray(sum(parts) + shared_only),
                               np.asarray(full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(dense_moe(p, x, 3, norm_topk)),
                               rtol=1e-5, atol=1e-5)


def test_dropless_drops_no_token_where_capacity_would():
    """Every token routed to one expert: capacity routing drops most of
    them, the served layer none."""
    key = jax.random.PRNGKey(4)
    p = moe_params(key, shared=0)
    p["router"] = p["router"].at[:, 5].add(100.0)
    x = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (1, 24, 32)))
    want = dense_moe(p, x, 2, False)
    got, stats = moe_mod.moe_ffn_dropless(p, x, top_k=2, norm_topk=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert int(stats[2]) == 24                   # expert 5 took every row
    from repro.core.dsg_linear import DSGConfig
    capped, _ = moe_mod.moe_ffn(p, x, n_experts=8, top_k=2,
                                capacity_factor=1.0, dsg=DSGConfig(),
                                aux_kind="probs", norm_topk=False)
    assert not np.allclose(np.asarray(capped), np.asarray(want), atol=1e-3)


def test_counts_against_a_routing_by_hand():
    """rows, experts hit and the largest group, over the counted tokens
    only, as numpy reads them off the router's top-k."""
    key = jax.random.PRNGKey(8)
    p = moe_params(key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (3, 5, 32))
    count = np.array(jax.random.bernoulli(jax.random.fold_in(key, 2), 0.6,
                                          (3, 5)))
    offset, held, k = 2, 4, 3
    _, stats = moe_mod.moe_ffn_dropless(share(p, offset, held), x, top_k=k,
                                        norm_topk=False,
                                        expert_offset=offset,
                                        count=jnp.asarray(count))
    logits = np.asarray(x.reshape(-1, 32) @ p["router"])
    top = np.argsort(-logits, -1)[:, :k]            # softmax keeps order
    per = np.zeros(held, int)
    for t, experts in enumerate(top):
        if count.reshape(-1)[t]:
            for e in experts:
                if offset <= e < offset + held:
                    per[e - offset] += 1
    assert stats.tolist() == [per.sum(), (per > 0).sum(), per.max()]


# ---------------------------------------------------------------------------
# the served path


def serve_logits(cfg, params, prompts, n_slots, steps, page_size=8,
                 max_seq=64):
    """The logits of each prompt's prefill and `steps` greedy decode steps
    down the engine's path: a 1-lane prefill spliced into the paged pool,
    then decode steps over `n_slots` lanes in which the lanes past the
    prompts are free and mirror lane 0 (ServingEngine.begin_step)."""
    backend = kv_cache.get_backend("paged", page_size=page_size)
    c = backend.make(cfg, n_slots, max_seq)
    n = len(prompts)
    out = [[] for _ in prompts]
    tok, pos = np.zeros(n_slots, np.int32), np.zeros(n_slots, np.int32)
    for i, pr in enumerate(prompts):
        logits, lane = api.prefill(params, None, cfg,
                                   {"tokens": jnp.asarray(pr)[None]},
                                   api.make_cache(cfg, 1, max_seq))
        c = backend.write(c, lane, i, n_tokens=len(pr),
                          reserve_tokens=len(pr) + steps)
        out[i].append(np.asarray(logits[0]))
        tok[i], pos[i] = int(jnp.argmax(logits[0])), len(pr)
    free = np.arange(n_slots) >= n
    for _ in range(steps):
        for i in range(n):
            c = backend.ensure(c, i, int(pos[i]))
        tok[free], pos[free] = tok[0], pos[0]
        live = live_page_bound(int(pos.max()), page_size,
                               max_seq // page_size)
        logits, data = api.decode_step(
            params, None, cfg, jnp.asarray(tok)[:, None],
            kv_cache.decode_view(c, jnp.asarray(free), 0),
            jnp.asarray(pos), live_pages=live)
        c = CacheHandle(_restore_table(data, c), c.kind, c.page_size)
        for i in range(n):
            out[i].append(np.asarray(logits[i]))
        tok = np.array(jnp.argmax(logits, -1), np.int32)
        pos = pos + 1
    return out


def test_a_lane_is_the_same_alone_and_beside_other_lanes(parts):
    """An active lane's logits do not depend on the batch: alone, beside
    mirrored free lanes, and beside another active lane.  Dropless
    routing makes that so; only float32 rounding of other row layouts
    (matrix products of another row count) may differ."""
    cfg, params = parts
    rng = np.random.default_rng(1)
    a, b = (rng.integers(0, cfg.vocab, n, dtype=np.int32) for n in (11, 6))
    alone = serve_logits(cfg, params, [a], 1, 5)[0]
    mirrored = serve_logits(cfg, params, [a], 4, 5)[0]
    beside = serve_logits(cfg, params, [a, b], 4, 5)[0]
    for other in (mirrored, beside):
        np.testing.assert_allclose(np.stack(other), np.stack(alone),
                                   rtol=1e-5, atol=1e-5)


def test_capacity_routing_would_couple_the_lanes(parts, monkeypatch):
    """The same check fails under the training path's capacity routing:
    the mirrored lanes' rows take the lane's places."""
    cfg, params = parts

    def capped(p, x, *, top_k, norm_topk, expert_offset, count, experts,
               layer):
        from repro.core.dsg_linear import DSGConfig
        p = dict(p, **{k: w[layer] for k, w in experts.items()})
        y, _ = moe_mod.moe_ffn(p, x, n_experts=cfg.moe_experts, top_k=top_k,
                               capacity_factor=1.0, dsg=DSGConfig(),
                               aux_kind="probs", norm_topk=norm_topk,
                               expert_offset=expert_offset)
        return y, jnp.zeros(3, jnp.int32)

    monkeypatch.setattr(moe_mod, "moe_ffn_dropless", capped)
    a = np.random.default_rng(1).integers(0, cfg.vocab, 11, dtype=np.int32)
    alone = np.stack(serve_logits(cfg, params, [a], 1, 3)[0])
    mirrored = np.stack(serve_logits(cfg, params, [a], 8, 3)[0])
    assert not np.allclose(mirrored, alone, rtol=1e-5, atol=1e-5)


def _scans(jaxpr, length):
    """Every `scan` equation of `length` steps in a jaxpr, nested ones
    included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == length:
            found.append(eqn)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _scans(sub, length)
    return found


def test_pools_cross_both_layer_scans(parts):
    """The leading dense layer has a scan of its own: both scans carry the
    stacked pools (addressed by global layer index) and neither scans a
    pool-shaped array as xs or ys."""
    from repro.serving.scheduler import make_decode_fns
    cfg, params = parts
    n_slots = 2
    handle = kv_cache.PagedBackend(page_size=8).make(cfg, n_slots, 64)
    pool = tuple(handle.data["pages_k"].shape)
    assert pool[0] == cfg.n_layers == 3
    closed = jax.make_jaxpr(make_decode_fns(cfg)[0], static_argnums=(7,))(
        params, None, jnp.zeros((n_slots, 1), jnp.int32), handle,
        jnp.zeros(n_slots, jnp.int32), jnp.zeros(n_slots, bool), 0, 8)
    shape = lambda vs: [tuple(v.aval.shape) for v in vs]   # noqa: E731
    carried = []
    for n in (cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers):
        for eqn in _scans(closed.jaxpr, n):
            n_c, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
            assert pool not in shape(eqn.invars[n_c + n_carry:]) + shape(
                eqn.outvars[n_carry:])
            carried.append(shape(eqn.invars[n_c:n_c + n_carry]).count(pool))
    assert carried == [2, 2], carried


def engine(cfg, params, n_slots=4):
    return ServingEngine(cfg, params, None, n_slots=n_slots, max_seq=64,
                         prompt_bucket=32, cache_backend="paged",
                         page_size=8)


def test_engine_counts_true_prompt_tokens_and_active_lanes(parts):
    """Admission counts the prompt's own tokens, never its bucket's
    padding; a decode step counts its active lanes, never the mirrored
    free ones; both reach the spans and the running counters."""
    cfg, params = parts
    n_moe = cfg.n_layers - cfg.n_dense_layers
    most = min(cfg.moe_topk, cfg.moe_held)          # rows a token can send
    eng = engine(cfg, params)
    prompt = np.arange(1, 12, dtype=np.int32)       # 11 tokens, bucket 16
    eng.submit(Request(uid=0, prompt=prompt, max_new=4))
    eng.run()
    tel = eng.telemetry
    (admit,) = tel.spans(name="repro.engine.admit")
    assert 0 < admit.attrs["moe_rows"] <= n_moe * most * len(prompt)
    steps = tel.spans(name="repro.engine.step")
    assert len(steps) == 4
    for s in steps:                                  # one active lane of 4
        assert 0 < s.attrs["moe_rows"] <= n_moe * most
        assert s.attrs["moe_rows_max"] <= s.attrs["moe_rows"]
        assert 0 < s.attrs["moe_experts_hit"] <= n_moe * cfg.moe_held
    spans = [admit] + steps
    assert tel.counters["moe.rows"] == sum(s.attrs["moe_rows"]
                                           for s in spans)
    assert tel.counters["moe.experts_hit"] == sum(
        s.attrs["moe_experts_hit"] for s in spans)


def test_engine_counts_match_the_layer_counts(parts):
    """The admission's counts are those of the prompt's tokens through the
    prefill, read back from the forward itself."""
    cfg, params = parts
    prompt = np.arange(3, 19, dtype=np.int32)       # 16 tokens, on a bucket
    eng = engine(cfg, params)
    eng.submit(Request(uid=0, prompt=prompt, max_new=1))
    eng.run()
    (admit,) = eng.telemetry.spans(name="repro.engine.admit")
    _, _, stats = api.prefill(params, None, cfg,
                              {"tokens": jnp.asarray(prompt)[None]},
                              api.make_cache(cfg, 1, 64),
                              moe_count=jnp.ones((1, 16), bool))
    assert [admit.attrs[k] for k in ("moe_rows", "moe_experts_hit",
                                     "moe_rows_max")] == stats.tolist()


def test_dense_model_records_no_moe_counts():
    cfg = configs.get_smoke_config("internlm2-1.8b")
    cfg = cfg.replace(dsg=cfg.dsg._replace(enabled=False))
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    eng = engine(cfg, params)
    eng.submit(Request(uid=0, prompt=np.arange(1, 9, dtype=np.int32),
                       max_new=3))
    eng.run()
    for s in eng.telemetry.spans():
        assert not any(k.startswith("moe_") for k in s.attrs)
    assert not any(k.startswith("moe.") for k in eng.telemetry.counters)


def test_training_keeps_capacity_routing(parts, monkeypatch):
    """A forward without a cache (training) never takes the dropless
    path."""
    cfg, params = parts

    def refuse(*a, **k):
        raise AssertionError("dropless routing in training")

    monkeypatch.setattr(moe_mod, "moe_ffn_dropless", refuse)
    batch = api.make_inputs(cfg, configs.SMOKE_SHAPE, concrete=True)
    assert np.isfinite(float(api.train_loss(params, None, cfg, batch)))
