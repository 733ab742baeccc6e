import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input-shape x mesh) cell: build the step function
(train_step with full AdamW update, or serve prefill/decode), attach
in/out shardings from the spec rules, .lower().compile() against the
production mesh, and record:
  * memory_analysis()  — bytes per device (proves it fits),
  * cost_analysis()    — per-device HLO FLOPs / bytes,
  * collective bytes   — parsed from the compiled HLO text,
into a JSON file consumed by the roofline analysis (benchmarks/roofline.py).

NOTE: the XLA_FLAGS and JAX_PLATFORMS lines above MUST run before any
other import touches jax — 512 host platform devices stand in for the
2x16x16 v5e fleet, and pinning the CPU keeps this process (and every
--all child, which inherits the pin) off an attached chip.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mistral-nemo-12b \
      --shape train_4k --mesh single --out results/
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.base import shape_by_name, SHAPES
from repro.launch.mesh import make_production_mesh
from repro.models import api, specs
from repro.optim import adamw
from repro.parallel import context as pctx
from repro.parallel.sharding import Axes, axes_for_mesh, data_shards, model_shards

from repro.launch import hlo_analysis


def named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def apply_overrides(cfg, overrides):
    """--set key=value pairs onto ModelConfig (dotted 'dsg.*' reaches the
    DSGConfig).  Values are literal-eval'd with string fallback."""
    import ast
    for kv in overrides or ():
        key, val = kv.split("=", 1)
        try:
            val = ast.literal_eval(val)
        except (ValueError, SyntaxError):
            pass
        if key.startswith("dsg."):
            cfg = cfg.replace(dsg=cfg.dsg._replace(**{key[4:]: val}))
        else:
            cfg = cfg.replace(**{key: val})
    return cfg


def build_cell(arch: str, shape_name: str, mesh, dsg_on: bool = True,
               remat: bool = True, overrides=None):
    """Returns (fn, example_args(SDS), in_shardings) for the cell."""
    cfg = configs.get_config(arch)
    if not dsg_on:
        cfg = cfg.replace(dsg=cfg.dsg._replace(enabled=False))
    if not remat:
        cfg = cfg.replace(remat=False)
    cfg = apply_overrides(cfg, overrides)
    shape = shape_by_name(shape_name)
    ax = axes_for_mesh(mesh)
    n_model = model_shards(mesh)
    n_data = data_shards(mesh)
    batch_ok = shape.global_batch % n_data == 0
    if not batch_ok:
        ax = Axes(batch=None, model=ax.model)

    key = jax.random.PRNGKey(0)
    params_sds = jax.eval_shape(lambda: api.init_model(key, cfg))
    dsg_sds = jax.eval_shape(lambda p: api.init_dsg(key, p, cfg),
                             params_sds) if cfg.dsg.enabled else None
    pspecs = specs.param_specs(params_sds, cfg, ax, n_model)
    dspecs = specs.dsg_specs(dsg_sds, cfg, ax, n_model)
    batch_axes = ax.batch

    if shape.kind == "train":
        batch_sds = api.make_inputs(cfg, shape)
        bspecs = specs.input_specs(batch_sds, cfg, ax)
        ospecs = adamw.opt_specs_with_master(pspecs, params_sds, zero1=True) \
            if cfg.dtype == "bfloat16" else \
            adamw.opt_specs(pspecs, params_sds, zero1=True)
        opt_sds = jax.eval_shape(
            lambda p: adamw.init_opt(p, cfg.dtype == "bfloat16"), params_sds)
        acfg = adamw.AdamWConfig()

        def train_step(state, batch):
            def loss_fn(p, b):
                return api.train_loss(p, state["dsg"], cfg, b,
                                      mesh=mesh, batch_axes=batch_axes)

            mb = max(1, cfg.microbatches)
            if mb == 1:
                loss, grads = jax.value_and_grad(loss_fn)(
                    state["params"], batch)
            else:
                # gradient accumulation: stash lives per microbatch
                split = jax.tree.map(
                    lambda t: t.reshape((mb, t.shape[0] // mb)
                                        + t.shape[1:]), batch)
                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32),
                    state["params"])

                def mb_body(acc, b):
                    g_acc, l_acc = acc
                    loss, g = jax.value_and_grad(loss_fn)(
                        state["params"], b)
                    g_acc = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), g_acc, g)
                    return (g_acc, l_acc + loss), None

                (grads, loss), _ = jax.lax.scan(
                    mb_body, (zero, jnp.float32(0.0)), split)
                grads = jax.tree.map(lambda g: g / mb, grads)
                loss = loss / mb
            new_p, new_opt, metrics = adamw.apply_updates(
                state["params"], grads, state["opt"], acfg)
            metrics["loss"] = loss
            return {"params": new_p, "dsg": state["dsg"],
                    "opt": new_opt}, metrics

        state_sds = {"params": params_sds, "dsg": dsg_sds, "opt": opt_sds}
        state_specs = {"params": pspecs, "dsg": dspecs, "opt": ospecs}
        fn = train_step
        args = (state_sds, batch_sds)
        in_sh = (named(mesh, state_specs), named(mesh, bspecs))
        out_sh = (named(mesh, state_specs), None)
        donate = (0,)
    elif shape.kind == "prefill":
        inputs_sds = api.make_inputs(cfg, shape)
        ispecs = specs.input_specs(inputs_sds, cfg, ax)
        cache_sds = jax.eval_shape(
            lambda: api.make_cache(cfg, shape.global_batch, shape.seq_len))
        cspecs = specs.cache_specs(cache_sds, cfg, ax, n_model)

        def prefill_fn(params, dsg, inputs, cache):
            return api.prefill(params, dsg, cfg, inputs, cache,
                               mesh=mesh, batch_axes=batch_axes)

        fn = prefill_fn
        args = (params_sds, dsg_sds, inputs_sds, cache_sds)
        in_sh = (named(mesh, pspecs), named(mesh, dspecs),
                 named(mesh, ispecs), named(mesh, cspecs))
        out_sh = None
        donate = (3,) if cache_sds is not None else ()
    else:  # decode
        inputs_sds = api.make_inputs(cfg, shape)
        cache_sds = jax.eval_shape(
            lambda: api.make_cache(cfg, shape.global_batch, shape.seq_len))
        prompt = api.make_inputs(
            cfg, shape_by_name(shape_name).__class__(
                name="p", seq_len=shape.seq_len, global_batch=shape.global_batch,
                kind="prefill"))
        state_sds = jax.eval_shape(
            lambda p, d, pr, c: api.prefill(p, d, cfg, pr, c),
            params_sds, dsg_sds, prompt, cache_sds)[1]
        sspecs = specs.cache_specs(state_sds, cfg, ax, n_model)

        def decode_fn(params, dsg, token, state, pos):
            return api.decode_step(params, dsg, cfg, token, state, pos,
                                   mesh=mesh, batch_axes=batch_axes)

        fn = decode_fn
        args = (params_sds, dsg_sds, inputs_sds["token"], state_sds,
                jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = (named(mesh, pspecs), named(mesh, dspecs),
                 NamedSharding(mesh, P(ax.batch, None)),
                 named(mesh, sspecs), NamedSharding(mesh, P()))
        out_sh = None
        donate = (3,)
    return cfg, fn, args, in_sh, out_sh, donate, batch_ok


_HLO_DIR = None     # set by main() to persist compiled HLO next to JSONs


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             dsg_on: bool = True, remat: bool = True,
             overrides=None, tag: str = None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi_pod" if multi_pod else "single_pod",
           "devices": mesh.size, "dsg": dsg_on,
           "overrides": list(overrides or ()), "tag": tag}
    if not configs.cell_is_runnable(arch, shape_name):
        rec["status"] = "skipped"
        rec["reason"] = ("pure full-attention arch: long_500k requires "
                         "sub-quadratic attention (DESIGN.md §4)")
        return rec
    t0 = time.perf_counter()
    cfg, fn, args, in_sh, out_sh, donate, batch_ok = build_cell(
        arch, shape_name, mesh, dsg_on, remat, overrides)
    with pctx.use_mesh(mesh, batch_shardable=batch_ok):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
    mem = compiled.memory_analysis()
    if mem is not None:
        rec["memory"] = {
            k: int(getattr(mem, k, 0) or 0)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")}
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):   # older jax: one dict per device
        cost = cost[0] if cost else {}
    rec["cost_xla"] = {k: float(v) for k, v in cost.items()
                      if isinstance(v, (int, float)) and k in
                      ("flops", "bytes accessed", "transcendentals",
                       "bytes accessed output", "optimal_seconds")}
    hlo = compiled.as_text()
    # scan-aware accounting (cost_analysis counts while bodies once)
    rec["analysis"] = hlo_analysis.analyze(hlo)
    rec["hlo_lines"] = len(hlo.splitlines())
    if _HLO_DIR:
        import gzip
        ftag = (f"{arch}__{shape_name}__"
                f"{'multi_pod' if multi_pod else 'single_pod'}__"
                f"{tag or ('dsg' if dsg_on else 'dense')}")
        with gzip.open(os.path.join(_HLO_DIR, ftag + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    rec["lower_s"] = round(t_lower, 1)
    rec["compile_s"] = round(t_compile, 1)
    rec["status"] = "ok"
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-dsg", action="store_true")
    ap.add_argument("--set", nargs="*", default=None,
                    help="cfg overrides, e.g. dsg.mode=gather_shared")
    ap.add_argument("--tag", default=None,
                    help="variant tag for output filenames")
    ap.add_argument("--out", default="results")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    global _HLO_DIR
    _HLO_DIR = os.path.join(args.out, "hlo")
    os.makedirs(_HLO_DIR, exist_ok=True)

    tag = "dsg" if not args.no_dsg else "dense"
    if args.all:
        # one subprocess per cell: isolates compiler memory and failures,
        # resumable (existing JSONs are skipped).
        import subprocess
        cells = [(arch, shape.name, mesh)
                 for arch in configs.ARCHS
                 for shape in SHAPES
                 for mesh in ("single", "multi")]
        for arch, shape, mesh in cells:
            fname = os.path.join(args.out,
                                 f"{arch}__{shape}__{mesh}__{tag}.json")
            if os.path.exists(fname):
                print(f"[skip existing] {fname}", flush=True)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh,
                   "--out", args.out] + (["--no-dsg"] if args.no_dsg else [])
            try:
                subprocess.run(cmd, timeout=3600,
                               env={**os.environ, "JAX_PLATFORMS": "cpu"})
            except subprocess.TimeoutExpired:
                with open(fname, "w") as f:
                    json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                               "status": "error",
                               "error": "compile timeout (3600s)"}, f)
                print(f"  -> TIMEOUT {arch} {shape} {mesh}", flush=True)
        return

    arch, shape, mesh = args.arch, args.shape, args.mesh
    tag = args.tag or tag
    fname = os.path.join(args.out, f"{arch}__{shape}__{mesh}__{tag}.json")
    if os.path.exists(fname):
        print(f"[skip existing] {fname}")
        return
    print(f"[dryrun] {arch} x {shape} x {mesh} ({tag}) ...", flush=True)
    try:
        rec = run_cell(arch, shape, mesh == "multi", dsg_on=not args.no_dsg,
                       overrides=args.set, tag=tag)
    except Exception as e:
        rec = {"arch": arch, "shape": shape, "mesh": mesh,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"  -> {rec['status']}"
          + (f" compile={rec.get('compile_s')}s" if rec.get("compile_s")
             else "")
          + (f" err={rec.get('error', '')[:300]}"
             if rec["status"] == "error" else ""), flush=True)


if __name__ == "__main__":
    main()
