"""GPipe-style pipeline parallelism over a 'pipe' mesh axis (shard_map +
collective_permute).

Layers are grouped into n_stages contiguous stages; stage s lives on pipe
rank s (stage-stacked params sharded over the axis).  Microbatches enter
stage 0 one tick at a time and flow through the ring: at every tick each
rank applies its stage and ppermutes the activation to rank+1.  After
n_micro + n_stages - 1 ticks all microbatches have drained; the bubble
fraction is (n_stages - 1) / (n_micro + n_stages - 1) — the standard GPipe
trade-off, amortized by more microbatches.

This is the composable PP building block (used standalone or as an extra
mesh dimension ("pipe","data","model")); tests validate numerics against
the sequential reference on a multi-device host mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, stage_params, x_micro: jax.Array,
                     mesh: Mesh, axis: str = "pipe") -> jax.Array:
    """Run microbatches through the pipeline.

    stage_fn(params_for_stage, x) -> y  (same shape as x)
    stage_params: pytree with leading dim n_stages on every leaf
    x_micro: (n_micro, micro_batch, ...) microbatch stack
    Returns (n_micro, micro_batch, ...) outputs (from the last stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(params, xs):
        # params: (1, ...) local stage slice; xs: (n_micro, Bm, ...)
        local = jax.tree.map(lambda a: a[0], params)
        rank = jax.lax.axis_index(axis)
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros((n_ticks,) + xs.shape[1:], xs.dtype)

        def tick(t, carry):
            state, outs = carry
            feed = xs[jnp.minimum(t, n_micro - 1)]
            cur = jnp.where(rank == 0,
                            jnp.where(t < n_micro, feed,
                                      jnp.zeros_like(feed)),
                            state)
            y = stage_fn(local, cur)
            # last stage's result for this tick (zeros elsewhere)
            outs = outs.at[t].set(
                jnp.where(rank == n_stages - 1, y, jnp.zeros_like(y)))
            state = jax.lax.ppermute(y, axis, perm)
            return state, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (state, outs))
        # only the last stage holds real outputs; sum-over-axis broadcasts
        outs = jax.lax.psum(outs, axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    outs = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(), check_vma=False,
    )(stage_params, x_micro)
    # microbatch m exits the last stage at tick m + n_stages - 1
    return outs[n_stages - 1:]


def sequential_reference(stage_fn: Callable, stage_params,
                         x_micro: jax.Array) -> jax.Array:
    """Ground truth: apply all stages in order to each microbatch."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def run_one(x):
        for s in range(n_stages):
            local = jax.tree.map(lambda a: a[s], stage_params)
            x = stage_fn(local, x)
        return x

    return jax.vmap(run_one)(x_micro)
