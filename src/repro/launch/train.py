"""End-to-end fault-tolerant training driver.

Composes everything: config -> model + DSG state -> sharded train step
(pjit) -> synthetic data -> AdamW(+ZeRO-1) -> f(W) refresh every
dsg.refresh_every steps (the paper's projection amortization) -> async
checkpoints -> straggler monitor -> crash/restore loop.

Runs at smoke scale on CPU (examples/quickstart.py) and, unchanged, on the
production mesh (launcher flags pick the mesh).

  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --smoke --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.ckpt.manager import CheckpointManager
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import api, specs
from repro.optim import adamw
from repro.optim.compress import init_feedback, tree_compress_with_feedback
from repro.parallel import context as pctx
from repro.parallel.sharding import axes_for_mesh, model_shards
from repro.runtime.fault_tolerance import StragglerMonitor, run_with_restarts

log = logging.getLogger("repro.train")


def build_trainer(cfg, mesh, acfg: adamw.AdamWConfig, *,
                  grad_compress: bool = False, seed: int = 0):
    """Returns (state, step_fn, refresh_fn, state_shardings)."""
    ax = axes_for_mesh(mesh)
    n_model = model_shards(mesh)
    key = jax.random.PRNGKey(seed)

    with pctx.use_mesh(mesh):
        params = api.init_model(key, cfg)
        dsg = api.init_dsg(jax.random.fold_in(key, 1), params, cfg)
        use_master = cfg.dtype == "bfloat16"
        opt = adamw.init_opt(params, use_master)

        pspecs = specs.param_specs(params, cfg, ax, n_model)
        dspecs = specs.dsg_specs(dsg, cfg, ax, n_model)
        ospecs = (adamw.opt_specs_with_master(pspecs, params)
                  if use_master else adamw.opt_specs(pspecs, params))
        state = {"params": params, "dsg": dsg, "opt": opt}
        sspecs = {"params": pspecs, "dsg": dspecs, "opt": ospecs}
        if grad_compress:
            state["err"] = init_feedback(params)
            sspecs["err"] = pspecs
        if mesh.size > 1:
            sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                              is_leaf=lambda x: isinstance(x, P))
            state = jax.tree.map(
                lambda x, s: jax.device_put(x, s), state, sh)

        batch_axes = ax.batch

        def step_fn(state, batch):
            def loss_fn(p):
                return api.train_loss(p, state["dsg"], cfg, batch,
                                      mesh=mesh, batch_axes=batch_axes)
            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
            new_state = dict(state)
            if grad_compress:
                # ternary + error feedback on the gradient stream
                grads, new_state["err"] = tree_compress_with_feedback(
                    grads, state["err"])
            new_p, new_opt, metrics = adamw.apply_updates(
                state["params"], grads, state["opt"], acfg)
            metrics["loss"] = loss
            new_state.update(params=new_p, opt=new_opt)
            return new_state, metrics

        jit_step = jax.jit(step_fn, donate_argnums=(0,))

        def refresh_fn(state):
            new_dsg = api.refresh_dsg(state["dsg"], state["params"], cfg)
            return {**state, "dsg": new_dsg}

        jit_refresh = jax.jit(refresh_fn, donate_argnums=(0,))

    return state, jit_step, jit_refresh, sspecs


def train(cfg, *, mesh=None, steps: int = 100, ckpt_dir=None,
          ckpt_every: int = 20, grad_compress: bool = False,
          global_batch: int = 8, seq_len: int = 64, seed: int = 0,
          injector=None, log_every: int = 10):
    mesh = mesh or make_local_mesh()
    acfg = adamw.AdamWConfig(total_steps=steps, warmup=min(20, steps // 5 + 1))
    state, jit_step, jit_refresh, _ = build_trainer(
        cfg, mesh, acfg, grad_compress=grad_compress, seed=seed)

    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    if ckpt is not None:
        restored, rstep, _ = ckpt.restore(state)
        if restored is not None:
            state, start = restored, rstep
            log.info("resumed from step %d", start)

    def make_batch(step):
        return synthetic.batch_at(step, global_batch=global_batch,
                                  seq_len=seq_len, vocab=cfg.vocab,
                                  seed=seed)

    monitor = StragglerMonitor()
    refresh_every = max(1, cfg.dsg.refresh_every)

    def step_with_refresh(state, batch):
        new_state, metrics = jit_step(state, batch)
        step = int(new_state["opt"]["step"])
        if cfg.dsg.enabled and step % refresh_every == 0:
            new_state = jit_refresh(new_state)   # paper: every 50 steps
        return new_state, metrics

    state, history = run_with_restarts(
        step_fn=step_with_refresh, state=state, make_batch=make_batch,
        ckpt=ckpt, total_steps=steps, start_step=start,
        ckpt_every=ckpt_every, injector=injector, monitor=monitor,
        on_step=(lambda s, st, m: log.info(
            "step %d loss %.4f", s, float(m["loss"]))
            if s % log_every == 0 else None))
    return state, history, monitor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config for CPU")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    mesh = make_production_mesh() if args.production_mesh else None
    t0 = time.perf_counter()
    _, history, monitor = train(cfg, mesh=mesh, steps=args.steps,
                                ckpt_dir=args.ckpt_dir,
                                grad_compress=args.grad_compress,
                                global_batch=args.batch, seq_len=args.seq)
    losses = [h["loss"] for h in history]
    print(f"steps={len(history)} first_loss={losses[0]:.4f} "
          f"last_loss={losses[-1]:.4f} wall={time.perf_counter()-t0:.1f}s "
          f"stragglers={len(monitor.flagged)}")


if __name__ == "__main__":
    main()
