"""Paged-attention decode kernel coverage (kernels/paged_attention.py).

Three rings of parity, all in interpret mode (the kernel body executes
exactly as Mosaic would see it):
  * kernel vs the pure-jnp oracle (ref.paged_decode_ref) across page
    sizes {8, 16}, ragged per-lane depths, partial final pages, GQA
    group sizes, dtypes, and sliding windows — pools must match the
    XLA scatter bit-for-bit, in the addressed layer of a stacked pool,
    and every other layer's pages must come back untouched;
  * the self_attention paged branch: Pallas executor vs the XLA
    fallback's whole-table gather on identical inputs, over stacked
    pools and ragged depths;
  * the serving engine: a kernel-executor paged engine must reproduce
    the dense backend's token stream over admit -> decode -> retire ->
    readmit traffic (lane/page reuse included), and compiles one decode
    program however deep its lanes go.

The decode step's layer scan carries the stacked pools (the kernel
writes them in place at the layer index); a jaxpr test pins that no
pool-shaped array is scanned as xs or ys.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import (assert_streams_equal, engine_spec, make_engine_parts,
                     mixed_traffic, run_and_collect)
from repro.kernels import ops, paged_attention, ref
from repro.models import attention as attn
from repro.serving.scheduler import Request, ServingEngine

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# (layers in the stacked pool, layer addressed): the single-layer pool
# as the L = 1 case, and the first and last layer of a 3-layer stack --
# a wrong layer in an index map reads or writes a neighbour's pages
LAYERS = [(1, 0), (3, 0), (3, 2)]


def _paged_setup(seed, b, h, kv, d, ps, max_pages, pos, dtype=jnp.float32,
                 n_layers=1):
    """Random stacked pools (n_layers, P, ps, Kv, D) + a page table
    mapping each lane's live pages to distinct physical pages (page 0
    reserved as scratch, as the backend lays it out)."""
    rng = np.random.default_rng(seed)
    n_pages = 1 + b * max_pages
    mk = lambda shape: jnp.asarray(rng.standard_normal(shape), dtype)
    q = mk((b, h, d))
    k_new, v_new = mk((b, kv, d)), mk((b, kv, d))
    k_pages, v_pages = (mk((n_layers, n_pages, ps, kv, d))
                        for _ in range(2))
    table = np.zeros((b, max_pages), np.int32)
    nxt = 1
    for lane in range(b):
        for j in range(min(pos[lane] // ps + 1, max_pages)):
            table[lane, j] = nxt
            nxt += 1
    return (q, k_new, v_new, k_pages, v_pages, jnp.asarray(table),
            jnp.asarray(np.asarray(pos, np.int32)))


def _layer_args(args, layer):
    """The oracle's single-layer view of stacked-pool kernel args."""
    q, k_new, v_new, k_pages, v_pages, table, pos = args
    return q, k_new, v_new, k_pages[layer], v_pages[layer], table, pos


def _assert_other_layers_untouched(before, after, layer):
    others = [i for i in range(before.shape[0]) if i != layer]
    np.testing.assert_array_equal(np.asarray(after)[others],
                                  np.asarray(before)[others])


@pytest.mark.parametrize("n_layers,layer", LAYERS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ps", [8, 16])
@pytest.mark.parametrize("h,kv", [(4, 2), (2, 2), (16, 8), (16, 16), (4, 1)])
def test_kernel_matches_oracle(dtype, ps, h, kv, n_layers, layer):
    # ragged depths: page-boundary cases (0, ps-1, ps) + partial pages
    pos = [0, ps - 1, ps, 2 * ps + 3, 5 * ps - 1]
    args = _paged_setup(0, len(pos), h, kv, 16, ps, 6, pos, dtype,
                        n_layers)
    o, kp, vp = paged_attention.paged_decode(*args, layer, interpret=True)
    ow, kw, vw = ref.paged_decode_ref(*_layer_args(args, layer))
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ow, np.float32), **TOL[dtype])
    np.testing.assert_array_equal(np.asarray(kp[layer]), np.asarray(kw))
    np.testing.assert_array_equal(np.asarray(vp[layer]), np.asarray(vw))
    _assert_other_layers_untouched(args[3], kp, layer)
    _assert_other_layers_untouched(args[4], vp, layer)


def test_kernel_bounded_walk_and_window():
    ps, pos = 8, [5, 17, 40]
    args = _paged_setup(1, 3, 4, 2, 16, ps, 8, pos)
    full, _, _ = paged_attention.paged_decode(*args, 0, interpret=True)
    # each lane's walk stops at its depth: the table's entries past it,
    # pointed at other lanes' pages, change nothing
    q, k_new, v_new, k_pages, v_pages, table, cp = args
    live = np.arange(8)[None, :] <= np.asarray(pos)[:, None] // ps
    junk = jnp.where(jnp.asarray(live), table, table[::-1, ::-1] + 1)
    bounded, _, _ = paged_attention.paged_decode(
        q, k_new, v_new, k_pages, v_pages, junk, cp, 0, interpret=True)
    np.testing.assert_array_equal(np.asarray(bounded), np.asarray(full))
    w, _, _ = paged_attention.paged_decode(*args, 0, window=10,
                                           interpret=True)
    ww, _, _ = ref.paged_decode_ref(*_layer_args(args, 0), window=10)
    np.testing.assert_allclose(np.asarray(w), np.asarray(ww),
                               **TOL[jnp.float32])


def _edge_case(case, ps, blk):
    """(lane depths, table width, window) of a walk-edge case; `blk`
    tokens make one block of the walk."""
    return {
        # depths at block edges: the first token, the last token of the
        # first block, the first of the second, 3 blocks and a partial page
        "block_edges": ([0, blk - 1, blk, 3 * blk + ps + 3], 64, 0),
        # one deep lane beside lanes on their first page
        "deep_beside_shallow": ([0, 3 * blk + 5, ps - 1, 2], 64, 0),
        # lanes 0 and 1 share a page-table row (a mirrored free lane)
        "mirrored": ([blk + 2 * ps + 1, 0, ps + 4, 2 * blk], 64, 0),
        # a table far wider than every depth
        "wide_table": ([3, ps + 1, 2 * ps], 64, 0),
        # a window whose first position lies inside a block
        "window_mid_block": ([2 * blk + 5, blk + 3, 7, 3 * blk], 64,
                             blk // 2 + 3),
        # one lane past the table's end (a caller bug) beside lanes in it
        "past_table": ([blk + 3, 20 * ps + 5, ps - 1, 2], 20, 0),
    }[case]


@pytest.mark.parametrize("case", ["block_edges", "deep_beside_shallow",
                                  "mirrored", "wide_table",
                                  "window_mid_block", "past_table"])
def test_kernel_walk_edges(case):
    """The block walk at its edges, f32 pools in a 3-layer stack: output
    against the oracle, the addressed layer's pools bit for bit, every
    other layer untouched.  A lane past the table's end walks the whole
    table and writes nothing: the other lanes' outputs and the pools
    equal the oracle's over those lanes alone."""
    ps, h, kv, d, layer = 8, 4, 2, 16, 1
    ppb = paged_attention.pages_per_block(ps, kv, d, jnp.float32, 64)
    pos, width, window = _edge_case(case, ps, ppb * ps)
    args = _paged_setup(11, len(pos), h, kv, d, ps, width, pos,
                        n_layers=3)
    q, k_new, v_new, k_pages, v_pages, table, cp = args
    if case == "mirrored":
        # lane 1 mirrors lane 0: its row, depth, query and new K/V
        cp = cp.at[1].set(cp[0])
        table = table.at[1].set(table[0])
        q, k_new, v_new = (a.at[1].set(a[0]) for a in (q, k_new, v_new))
        args = (q, k_new, v_new, k_pages, v_pages, table, cp)
    o, kp, vp = paged_attention.paged_decode(*args, layer, window=window,
                                             interpret=True)
    inside = np.flatnonzero(np.asarray(pos) < width * ps)
    ow, kw, vw = ref.paged_decode_ref(
        q[inside], k_new[inside], v_new[inside], k_pages[layer],
        v_pages[layer], table[inside], cp[inside], window=window)
    np.testing.assert_allclose(np.asarray(o)[inside], np.asarray(ow),
                               **TOL[jnp.float32])
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_array_equal(np.asarray(kp[layer]), np.asarray(kw))
    np.testing.assert_array_equal(np.asarray(vp[layer]), np.asarray(vw))
    _assert_other_layers_untouched(k_pages, kp, layer)
    _assert_other_layers_untouched(v_pages, vp, layer)


@pytest.mark.parametrize("ps,kv,d,dtype,width,want", [
    (16, 8, 128, jnp.bfloat16, 64, 8),     # internlm2-1.8b: 128 tokens
    (16, 16, 128, jnp.bfloat16, 40, 8),    # deepseek-moe-16b
    (8, 2, 16, jnp.float32, 64, 16),       # the tests' pools
    (8, 2, 16, jnp.float32, 6, 6),         # clamped to the table
    (16, 32, 256, jnp.float32, 64, 2),     # clamped by the VMEM budget
])
def test_pages_per_block_from_shapes(ps, kv, d, dtype, width, want):
    assert paged_attention.pages_per_block(ps, kv, d, dtype, width) == want


def test_walk_blocks_counts_each_lanes_live_blocks():
    # 1, 8, 9 and 33 live pages at 8 pages a block
    pos = np.array([0, 127, 128, 16 * 32 + 3])
    assert paged_attention.walk_blocks(pos, 16, 8) == 1 + 1 + 2 + 5


def _attn_inputs(seed, b, d_model, h, kv, hd, ps, max_pages, pos,
                 n_layers=1):
    rng = np.random.default_rng(seed)
    p = attn.init_attention(jax.random.PRNGKey(seed), d_model, h, kv, hd)
    x = jnp.asarray(rng.standard_normal((b, 1, d_model)), jnp.float32)
    n_pages = 1 + b * max_pages
    shape = (n_layers, n_pages, ps, kv, hd)
    pools = {"k": jnp.asarray(rng.standard_normal(shape), jnp.float32),
             "v": jnp.asarray(rng.standard_normal(shape), jnp.float32)}
    table = np.zeros((b, max_pages), np.int32)
    nxt = 1
    for lane in range(b):
        for j in range(pos[lane] // ps + 1):
            table[lane, j] = nxt
            nxt += 1
    cp = jnp.asarray(np.asarray(pos, np.int32))
    return p, x, pools, jnp.asarray(table), cp


# lane depths for a 64-token table of 8-token pages: ragged depths, the
# edges (the first position, the last of the first page, the first of the
# second, the table's last), and lanes spread over every page count
DEPTHS = {"ragged": [3, 12, 25], "edges": [0, 7, 8, 63],
          "spread": [5, 20, 33, 41, 50, 58]}


@pytest.mark.parametrize("n_layers,layer", LAYERS)
@pytest.mark.parametrize("depths", sorted(DEPTHS))
def test_self_attention_kernel_vs_xla(depths, n_layers, layer):
    """The full paged branch: Pallas executor vs XLA fallback on the same
    scatter + gather + attend step (RoPE included), both addressing one
    layer of the stacked pools.  With no walk bound the kernel walks each
    lane to its own depth and the fallback gathers the whole table: the
    same outputs and pools at every depth up to the table's last
    position, and every other layer untouched."""
    ps, pos = 8, DEPTHS[depths]
    p, x, pools, table, cp = _attn_inputs(3, len(pos), 32, 4, 2, 8, ps, 8,
                                          pos, n_layers)
    kw = dict(n_heads=4, n_kv=2, rope_theta=10_000.0, q_pos=cp[:, None],
              cache_pos=cp, page_table=table, layer=layer)
    out_k, cache_k = attn.self_attention(p, x, cache=dict(pools),
                                         paged_kernel="kernel", **kw)
    out_x, cache_x = attn.self_attention(p, x, cache=dict(pools),
                                         paged_kernel="xla", **kw)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache_k[leaf]),
                                      np.asarray(cache_x[leaf]))
        _assert_other_layers_untouched(pools[leaf], cache_k[leaf], layer)


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
              cache_pos=cp, page_table=table, bf16_scores=True, layer=0)
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


def _scans(jaxpr, length):
    """Every `scan` equation of `length` steps in a jaxpr, nested ones
    (inside jit, cond, other scans) included."""
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


@pytest.mark.parametrize("executor,sparse_ffn",
                         [("kernel", False), ("xla", False), ("kernel", True)])
def test_decode_layer_scan_carries_the_pools(engine_parts, executor,
                                             sparse_ffn):
    """The paged decode step's layer scan (dense FFN, and the DSG
    group-CSR step) carries the stacked pools and scans no pool-shaped
    array as xs or ys: scanning them would slice each layer's pool out
    of the stack and write it back every layer (plus a whole-stack copy
    after the loop) -- the copies this layout removes."""
    from repro.serving.kv_cache import PagedBackend
    from repro.serving.scheduler import make_decode_fns
    cfg, params, dsg = engine_parts
    cfg = cfg.replace(paged_attn_kernel=executor)
    n_slots = 2
    handle = PagedBackend(page_size=8).make(cfg, n_slots, 64)
    pool = tuple(handle.data["pages_k"].shape)
    assert pool[0] == cfg.n_layers
    args = (params, dsg, jnp.zeros(n_slots, jnp.int32), handle,
            jnp.zeros(n_slots, jnp.int32), jnp.zeros(n_slots, bool), 0,
            jnp.zeros(n_slots, jnp.int32))
    greedy = make_decode_fns(cfg, dsg=sparse_ffn)[0]
    if sparse_ffn:
        csr = {"idx": jnp.zeros((cfg.n_layers, n_slots, 2), jnp.int32),
               "counts": jnp.full((cfg.n_layers, n_slots), 2, jnp.int32)}
        closed = jax.make_jaxpr(functools.partial(greedy, refresh=True))(
            *args, csr)
    else:
        closed = jax.make_jaxpr(greedy)(*args, None)
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]
    carried = []
    for eqn in _scans(closed.jaxpr, cfg.n_layers):
        n_c, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        xs = shapes(eqn.invars[n_c + n_carry:])
        ys = shapes(eqn.outvars[n_carry:])
        assert pool not in xs + ys, (xs, ys)
        carried.append(shapes(eqn.invars[n_c:n_c + n_carry]).count(pool))
    assert carried == [2], carried


@pytest.mark.parametrize("page_size", [8, 16])
def test_kernel_engine_stream_matches_dense(engine_parts, page_size,
                                           monkeypatch):
    """6 requests through 2 slots: every lane is retired and readmitted,
    pages are freed and reused — the Pallas-executor paged engine must
    emit the dense backend's exact token stream."""
    cfg, params, dsg = engine_parts
    dense_out = run_and_collect(engine_spec(*engine_parts),
                                mixed_traffic(cfg))
    depths = _record_depths(monkeypatch)
    kcfg = cfg.replace(paged_attn_kernel="kernel")
    kernel_out, eng = run_and_collect(
        engine_spec(kcfg, params, dsg, cache_backend="paged",
                    page_size=page_size, cache_tokens=80),
        mixed_traffic(cfg), return_engine=True)
    assert_streams_equal(dense_out, kernel_out, "kernel engine vs dense")
    _assert_dispatches_count_their_blocks(eng, depths)
    alloc = eng.backend.allocator
    assert alloc.free_pages == alloc.n_pages - alloc.reserved


def _record_depths(monkeypatch):
    """The lanes' depths of each step an engine plans from now on."""
    depths, plan_step = [], ServingEngine._plan

    def record(self, inflight):
        plan = plan_step(self, inflight)
        if plan is not None:
            depths.append(plan.pos.copy())
        return plan

    monkeypatch.setattr(ServingEngine, "_plan", record)
    return depths


def _assert_dispatches_count_their_blocks(eng, depths):
    """Every dispatch counts the blocks the kernel walks in its own step:
    each lane's live pages 0..pos//ps in blocks of ppb pages.  Returns
    the per-lane block counts of each step."""
    pool = eng.cache.data["pages_k"]
    _, _, ps, kv, d = pool.shape
    width = eng.cache.data["page_table"].shape[1]
    ppb = paged_attention.pages_per_block(ps, kv, d, pool.dtype, width)
    lanes = [[-(-(int(p) // ps + 1) // ppb) for p in pos] for pos in depths]
    disp = [s.attrs["kv_blocks"] for s in eng.telemetry.spans()
            if s.name == "repro.engine.dispatch"]
    assert disp and disp == [sum(n) for n in lanes]
    return lanes


def test_dispatch_kv_blocks_follow_each_steps_depths(engine_parts,
                                                     monkeypatch):
    """A lane deeper than one block beside a shallow one: each step's
    `kv_blocks` sums its own lanes' blocks, not the deepest lane's."""
    cfg, params, dsg = engine_parts
    depths = _record_depths(monkeypatch)
    eng = ServingEngine(cfg.replace(paged_attn_kernel="kernel"), params,
                        dsg, n_slots=2, max_seq=192, prompt_bucket=160,
                        cache_backend="paged", page_size=8)
    rng = np.random.default_rng(3)
    for u, (n, new) in enumerate([(140, 4), (10, 6), (20, 3)]):
        eng.submit(Request(uid=u, max_new=new, prompt=rng.integers(
            0, cfg.vocab, n, dtype=np.int32)))
    eng.run()
    lanes = _assert_dispatches_count_their_blocks(eng, depths)
    assert any(len(set(n)) > 1 for n in lanes), lanes


# (arch, DSG serving): the dense-FFN smoke model, the MoE smoke model
# (its leading dense layer and expert layers), and the DSG sparse FFN
ONE_PROGRAM = {"internlm2": ("internlm2-1.8b", False),
               "moe": ("deepseek-moe-16b", False),
               "dsg": ("internlm2-1.8b", True)}


def _deep_traffic(vocab):
    """4 greedy requests whose lanes go from 16 to 59 tokens deep: 3 to 8
    pages of 8 tokens in a 64-token window."""
    rng = np.random.default_rng(5)
    return [Request(uid=u, prompt=rng.integers(0, vocab, n, dtype=np.int32),
                    max_new=new)
            for u, (n, new) in enumerate([(5, 20), (27, 28), (12, 6),
                                          (30, 10)])]


@pytest.mark.parametrize("executor", ["kernel", "xla"])
@pytest.mark.parametrize("model", sorted(ONE_PROGRAM))
def test_one_decode_program_per_variant(model, executor):
    """A paged engine compiles one decode program per static variant
    (greedy here; a DSG engine's CSR width and refresh flag) however deep
    its lanes go: traffic across at least 4 page counts adds no compile
    to what warm_decode built, and the streams equal the same model's on
    the dense cache backend."""
    from repro.serving.dsg_runtime import DSGServingConfig
    arch, sparse = ONE_PROGRAM[model]
    cfg, params, dsg = make_engine_parts(arch)
    if not sparse:
        cfg, dsg = cfg.replace(dsg=cfg.dsg._replace(enabled=False)), None
    serving = DSGServingConfig(refresh_interval=4) if sparse else None
    cfg = cfg.replace(paged_attn_kernel=executor)
    dense = run_and_collect(engine_spec(cfg, params, dsg,
                                        dsg_serving=serving),
                            _deep_traffic(cfg.vocab))
    eng = ServingEngine(cfg, params, dsg, n_slots=2, max_seq=64,
                        prompt_bucket=32, cache_backend="paged", page_size=8,
                        cache_tokens=160, dsg_serving=serving)
    eng.warm_decode()
    rt = eng.dsg_rt
    want = 1 if rt is None else 2 * len(rt.warm_bounds())
    greedy = eng._jit_decode[False]
    assert greedy._cache_size() == want
    for r in _deep_traffic(cfg.vocab):
        eng.submit(r)
    eng.run()
    assert_streams_equal(dense, {u: r.output for u, r in eng.done.items()},
                         f"{model} {executor} paged vs dense")
    pages = {p // 8 + 1 for r in eng.done.values()
             for p in range(eng._bucket_for(len(r.prompt)),
                            eng._bucket_for(len(r.prompt)) + len(r.output))}
    assert len(pages) >= 4, pages
    assert greedy._cache_size() == want
    assert eng._jit_decode[True]._cache_size() == 0
