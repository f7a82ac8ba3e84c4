"""Microbatched pipeline parallelism over a mesh axis (GPipe-style).

The production mesh has no dedicated pipeline axis (DESIGN.md §5) — PP is
provided as an option for meshes that do (e.g. repurposing `pod`). Stages
are laid out over ``axis``; the schedule is the classic fill-drain loop
expressed in shard_map: each stage applies its layer block to the current
microbatch and ``ppermute``s activations to the next stage. Bubble fraction
= (S-1)/(M+S-1) for S stages / M microbatches, surfaced by ``bubble()``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def pipeline_apply(mesh: Mesh, axis: str, stage_fn, n_microbatches: int):
    """Build fn(stage_params, x) running a stage-partitioned pipeline.

    ``stage_params`` leaves carry a leading stage dim sharded over ``axis``;
    ``x`` is (n_microbatches, mb, ...) with microbatches entering stage 0.
    Returns outputs (n_microbatches, mb, ...) from the LAST stage (gathered).
    """
    n_stages = mesh.shape[axis]

    def local(params, x):
        # params: this stage's block params (leading dim 1) ; x: all mbs
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        mb = x[0]
        zero = jnp.zeros_like(mb)
        n_ticks = n_microbatches + n_stages - 1
        outs = jnp.zeros((n_microbatches,) + mb.shape, mb.dtype)

        def tick(t, carry):
            inflight, outs = carry
            # stage 0 injects microbatch t (if any); others use the permuted
            mb_idx = jnp.clip(t, 0, n_microbatches - 1)
            inject = jax.lax.dynamic_index_in_dim(x, mb_idx, 0, keepdims=False)
            cur = jnp.where(stage == 0, inject, inflight)
            active = (t - stage >= 0) & (t - stage < n_microbatches)
            y = stage_fn(params, cur)
            y = jnp.where(active, y, zero)
            # last stage emits its finished microbatch
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
            emit = (stage == n_stages - 1) & active
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(emit, y, outs[out_idx]), out_idx, 0)
            # forward activations to the next stage
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return nxt, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (zero, outs))
        # bring the last stage's outputs to every stage (replicated out)
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    shard = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    return shard
