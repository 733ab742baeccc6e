"""Compile the served path's Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) runs the kernel bodies on the
CPU and accepts BlockSpecs and layouts that the chip's compiler refuses.
These tests lower each kernel at internlm2-1.8b widths (d=2048, 16 heads
over 8 KV heads of 128, d_ff=8192, 8 decode lanes, 16-token pages over a
1024-token window), the paged decode kernel also at deepseek-moe-16b's
attention (16 heads over 16 KV heads, a 640-token window), and the
expert kernel at deepseek-moe-16b's (8 held experts of 1408), for one
chip of a `v5e:2x2` topology that is described, not attached, and assert
that the compiled program holds the Mosaic kernel (`tpu_custom_call`).
Nothing runs, so they say nothing about results or times.  Two more compile the serving engine's whole decode
step at those widths, over two layers and over deepseek's dense layer and
two MoE layers, and pin that the paged KV pools are updated in place: no
pool-sized copy, slice or write-back.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test collection
imports this file in every worker.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import (drs_search, dsg_ffn, moe_experts, ops,
                           paged_attention)
from repro.models import api
from repro.serving.kv_cache import CacheHandle
from repro.serving.scheduler import make_decode_fns

B, H, KV, D = 8, 16, 8, 128             # lanes, heads, KV heads, head dim
D_MODEL, D_FF, BLOCK = 2048, 8192, 128
PAGE, MAX_PAGES = 16, 64                 # 1024-token window
N_PAGES = B * MAX_PAGES + 1              # pool with the scratch page
K_PROJ = 256                             # projection.jll_dim(2048, 8193, 0.5)
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # persistent-cache entries written for a described chip cannot be
    # read back without one; keep these compiles out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(name, fn, sharding, *shapes):
    """Compile `fn` for the described chip; the compiled program must
    call the Mosaic kernel `name`."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert f"%{name}" in text


# (heads, KV heads, page-table width): internlm2-1.8b's GQA over a
# 1024-token window, deepseek-moe-16b's MHA over 640
@pytest.mark.parametrize("h,kv,max_pages", [(H, KV, MAX_PAGES), (16, 16, 40)])
def test_paged_decode_compiles(one_chip, h, kv, max_pages):
    def step(q, kn, vn, kp, vp, pt, pos, layer):
        return paged_attention.paged_decode(q, kn, vn, kp, vp, pt, pos,
                                            layer, num_pages=max_pages)
    pool = ((2, B * max_pages + 1, PAGE, kv, D), BF16)
    _compile("paged_decode", step, one_chip,
             ((B, h, D), BF16), ((B, kv, D), BF16), ((B, kv, D), BF16),
             pool, pool, ((B, max_pages), jnp.int32), ((B,), jnp.int32),
             ((), jnp.int32))


def _pool_moves(compiled, layer_pool, pool):
    """Lines of a compiled program that copy, slice or update one layer's
    pool or the stack, by opcode or by a fusion named for one."""
    shapes = "|".join(",".join(map(str, s)) for s in (layer_pool, pool))
    moves = "copy|dynamic-slice|dynamic-update-slice"
    pool_op = re.compile(
        r"= bf16\[(%s)\]\S* (%s)\(|%%\S*(%s)\S* = \(?bf16\[(%s)\]"
        % (shapes, moves, moves, shapes))
    return [ln for ln in compiled.as_text().splitlines()
            if pool_op.search(ln)]


def test_decode_step_updates_pools_in_place(one_chip, monkeypatch):
    """The engine's jitted greedy decode step (`_decode_greedy`) over two
    layers: the kernel writes the pools the layer scan carries, so the
    program holds no copy, dynamic slice or dynamic update of one
    layer's pool or of the stack, and its temporaries stay under one
    layer's pool."""
    monkeypatch.setenv("REPRO_INTERPRET", "0")     # the kernel, compiled
    base = configs.get_config("internlm2-1.8b")
    cfg = base.replace(n_layers=2, paged_attn_kernel="kernel",
                       dsg=base.dsg._replace(enabled=False))
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), t)
    params = put(jax.eval_shape(lambda k: api.init_model(k, cfg),
                                jax.random.PRNGKey(0)))
    layer_pool = (N_PAGES, PAGE, KV, D)
    pool = (cfg.n_layers,) + layer_pool
    handle = CacheHandle(put({"pages_k": jax.ShapeDtypeStruct(pool, BF16),
                              "pages_v": jax.ShapeDtypeStruct(pool, BF16),
                              "page_table": jax.ShapeDtypeStruct(
                                  (B, MAX_PAGES), jnp.int32)}),
                         "paged", PAGE)
    lanes = put((jax.ShapeDtypeStruct((B, 1), jnp.int32),
                 jax.ShapeDtypeStruct((B,), jnp.int32),
                 jax.ShapeDtypeStruct((B,), jnp.bool_),
                 jax.ShapeDtypeStruct((), jnp.int32)))
    step = jax.jit(make_decode_fns(cfg)[0], donate_argnums=(3,),
                   static_argnums=(7,))
    tok, pos, free, donor = lanes
    compiled = step.lower(params, None, tok, handle, pos, free, donor,
                          MAX_PAGES).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "%paged_decode" in text
    assert not _pool_moves(compiled, layer_pool, pool)
    layer_bytes = N_PAGES * PAGE * KV * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


def test_dsg_ffn_csr_compiles(one_chip):
    k = D_FF // BLOCK // 2                     # gamma 0.5 CSR bound
    _compile("dsg_ffn_csr", lambda x, wg, wu, wd, idx, cnt:
             dsg_ffn.dsg_ffn_csr(x, wg, wu, wd, idx, cnt, block=BLOCK),
             one_chip,
             ((B, D_MODEL), BF16), ((D_MODEL, D_FF), BF16),
             ((D_MODEL, D_FF), BF16), ((D_FF, D_MODEL), BF16),
             ((B, k), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("m,bm", [(B, B), (512, 128)])  # decode, prefill
def test_drs_project_compiles(one_chip, m, bm):
    _compile("drs_project",
             lambda x, r: drs_search.drs_project(x, r, bm=bm), one_chip,
             ((m, D_MODEL), BF16), ((K_PROJ, D_MODEL), BF16))


@pytest.mark.parametrize("m,bm", [(B, B), (512, 128)])  # decode, prefill
def test_drs_scores_compiles(one_chip, m, bm):
    _compile("drs_scores", lambda fx, fw: drs_search.drs_scores(
             fx, fw, block=BLOCK, bm=bm, bf=512), one_chip,
             ((m, K_PROJ), BF16), ((K_PROJ, D_FF), BF16))


def test_dsg_ffn_block_mask_compiles(one_chip):
    m = 256
    _compile("dsg_ffn", lambda x, wg, wu, wd, mask: dsg_ffn.dsg_ffn(
             x, wg, wu, wd, mask, block=BLOCK, bm=128, bf=128), one_chip,
             ((m, D_MODEL), BF16), ((D_MODEL, D_FF), BF16),
             ((D_MODEL, D_FF), BF16), ((D_FF, D_MODEL), BF16),
             ((m, D_FF // BLOCK), jnp.float32))


@pytest.mark.parametrize("rows", [320, 896])        # decode, a prefill
def test_moe_experts_compiles(one_chip, rows):
    """The grouped expert product at deepseek-moe-16b widths: 8 held
    experts of 1408, the rows of 32 decode lanes or of a 128-token prompt
    at 6 experts a token."""
    e, f = 8, 1408
    _compile("moe_experts", lambda x, st, sz, wg, wu, wd:
             moe_experts.moe_experts(x, st, sz, wg, wu, wd), one_chip,
             ((rows, D_MODEL), BF16), ((e,), jnp.int32), ((e,), jnp.int32),
             ((e, D_MODEL, f), BF16), ((e, D_MODEL, f), BF16),
             ((e, f, D_MODEL), BF16))


def test_moe_decode_step_updates_pools_in_place(one_chip, monkeypatch):
    """deepseek-moe-16b's greedy decode step at its widths over its dense
    layer and two MoE layers of 8 held experts: both layer scans carry the
    pools, the expert layer runs the `moe_experts` kernel, and no pool is
    copied, sliced or written back."""
    monkeypatch.setenv("REPRO_INTERPRET", "0")     # the kernels, compiled
    monkeypatch.setattr(moe_experts, "grouped_swiglu", ops.moe_experts)
    base = configs.get_config("deepseek-moe-16b")
    cfg = base.replace(n_layers=3, moe_experts_held=8,
                       paged_attn_kernel="kernel",
                       dsg=base.dsg._replace(enabled=False))
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), t)
    params = put(jax.eval_shape(lambda k: api.init_model(k, cfg),
                                jax.random.PRNGKey(0)))
    kv = cfg.n_kv
    layer_pool = (N_PAGES, PAGE, kv, D)
    pool = (cfg.n_layers,) + layer_pool
    handle = CacheHandle(put({"pages_k": jax.ShapeDtypeStruct(pool, BF16),
                              "pages_v": jax.ShapeDtypeStruct(pool, BF16),
                              "page_table": jax.ShapeDtypeStruct(
                                  (B, MAX_PAGES), jnp.int32)}),
                         "paged", PAGE)
    tok, pos, free, donor = put((jax.ShapeDtypeStruct((B, 1), jnp.int32),
                                 jax.ShapeDtypeStruct((B,), jnp.int32),
                                 jax.ShapeDtypeStruct((B,), jnp.bool_),
                                 jax.ShapeDtypeStruct((), jnp.int32)))
    step = jax.jit(make_decode_fns(cfg)[0], donate_argnums=(3,),
                   static_argnums=(7,))
    compiled = step.lower(params, None, tok, handle, pos, free, donor,
                          MAX_PAGES).compile()
    text = compiled.as_text()
    assert "%paged_decode" in text and "%moe_experts" in text
    assert not _pool_moves(compiled, layer_pool, pool)
    layer_bytes = N_PAGES * PAGE * kv * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
