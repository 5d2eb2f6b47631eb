"""GPipe-style pipeline parallelism over a mesh axis (default: 'pod').

The layer stack is split into ``num_stages`` contiguous stages; microbatches
stream through stages with jax.lax.ppermute boundary transfers inside
shard_map. Schedule: standard GPipe fill-drain over T = M + S - 1 ticks
(M microbatches, S stages); bubble fraction (S-1)/T.

This is the forward pipeline (inference / microbatched forward); the trainer
uses it with ``jax.grad`` through the shard_map for small stage counts
(S = 2 pods), where the fill-drain bubble at M >= 8 costs < 12%.

Each stage holds ``layers/S`` of the stacked layer params (leading-dim
shard), which is exactly a P('pod', ...) sharding of the scanned parameter
stack — so switching DP <-> PP over the pod axis is a resharding, not a
repartitioning of the program.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipelined_forward"]


def pipelined_forward(mesh: Mesh, layer_fn, num_microbatches: int,
                      axis: str = "pod"):
    """Build fn(stage_params, x) running layer_fn stacks as a pipeline.

    layer_fn(stage_params, x_micro) -> y_micro applies ONE stage (its share
    of layers, itself a lax.scan) to one microbatch.

    stage_params: pytree with leading dim = num_stages (sharded over
    ``axis``); x: (M * mb, ...) batch split into M microbatches.
    """
    S = mesh.shape[axis]
    M = num_microbatches

    def body(stage_params, x):
        # stage_params: this stage's params (leading dim 1) — squeeze
        sp = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        stage = jax.lax.axis_index(axis)
        mb = x.shape[0] // M
        xs = x.reshape(M, mb, *x.shape[1:])
        T = M + S - 1

        def tick(carry, t):
            buf, outs = carry
            # stage s works on microbatch (t - s) when 0 <= t - s < M
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < M)
            # first stage reads fresh input; others read the permuted buffer
            x_in = jnp.where(stage == 0,
                             xs[jnp.clip(mb_idx, 0, M - 1)], buf)
            y = layer_fn(sp, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # pass activation to the next stage
            buf_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            # last stage records its finished microbatch
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            is_last = stage == S - 1
            take = active & is_last
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(take, y, outs[out_idx]), out_idx, 0)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(T))
        # broadcast results from the last stage to all stages (psum of a
        # one-hot masked buffer keeps outs replicated over the axis)
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs.reshape(x.shape)

    def wrapped(stage_params, x):
        pspecs = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(pspecs, P()), out_specs=P(),
                             check_vma=False)(stage_params, x)

    return wrapped
