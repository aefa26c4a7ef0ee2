"""Optional pipeline parallelism (DESIGN §8): GPipe-style microbatch pipeline
over a ``stage`` mesh axis, built on shard_map + lax.ppermute.

Each stage device holds one stage's params (stacked on a leading stage dim
outside shard_map). The schedule runs ``n_micro + n_stages - 1`` ticks; at
tick t, stage s processes microbatch ``t - s`` (bubble fraction =
(S-1)/(T+S-1)). ``ppermute`` moves activations stage->stage+1 — on real
hardware this is the neighbor ICI link, the cheapest collective there is.

Differentiable: jax AD transposes ppermute to the reverse permutation, so
``jax.grad`` through ``pipeline_apply`` yields the backward pipeline
(GPipe semantics: full activation stash, no interleaving).

The production meshes use DP x TP (pod/data/model); this module is
the composable PP option for depth-dominated models — enable by adding a
``stage`` axis to the mesh and scanning each stage's layers inside
``stage_fn``.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn: Callable, stage_params, x_micro: jnp.ndarray,
                   mesh: Mesh, axis: str = "stage") -> jnp.ndarray:
    """Run microbatches through a linear pipeline.

    stage_fn(params_one_stage, x: (B, ...)) -> (B, ...)   same in/out shape
    stage_params: pytree with leading stage dim == mesh.shape[axis]
    x_micro: (n_micro, B, ...) microbatched input
    Returns (n_micro, B, ...) outputs (valid on every device after the final
    gather — replicated for simplicity).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    fwd = [(i, i + 1) for i in range(n_stages - 1)]

    def per_stage(params, xs):
        params = jax.tree_util.tree_map(lambda p: p[0], params)  # my stage
        sidx = jax.lax.axis_index(axis)
        # xs is replicated: (n_micro, B, ...) on every stage
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            state, outs = carry
            inject = xs[jnp.minimum(t, n_micro - 1)]
            x_in = jnp.where(sidx == 0, inject, state)
            y = stage_fn(params, x_in)
            # stash finished microbatch (only meaningful on the last stage)
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (sidx == n_stages - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid, y, outs[jnp.maximum(out_idx, 0)]),
                jnp.maximum(out_idx, 0), 0)
            state = jax.lax.ppermute(y, axis, fwd) if fwd else y
            return state, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (state, outs))
        return outs[None]          # stacked over stages; caller takes row -1

    spec_p = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = shard_map(per_stage, mesh=mesh,
                   in_specs=(spec_p, P(None)),
                   out_specs=P(axis), check_rep=False)
    out = fn(stage_params, x_micro)
    # out: (n_stages, n_micro, ...) — the last stage's row holds the results
    return out.reshape((n_stages, n_micro) + x_micro.shape[1:])[-1]
