"""Pipeline parallelism: GPipe-style microbatch rotation over shard_map.

Optional parallelism mode (the production meshes default to DP×TP×EP; PP is
exercised by tests and available for meshes with a "stage" axis).  The
model's scanned layer groups map naturally onto stages: stage s owns
`num_groups / S` groups; microbatches flow through stages with
`jax.lax.ppermute` rotations — the classic bubble schedule with
(S - 1 + M) slots for M microbatches on S stages.

`pipeline_apply` is deliberately model-agnostic: it takes the per-stage
body `fn(stage_params, x) -> x` and runs the rotation; the caller provides
stage-stacked params (leading axis = stage).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(
    fn,
    stage_params,
    x: jax.Array,            # (M, micro_batch, ...) microbatched input
    mesh: Mesh,
    *,
    axis: str = "stage",
):
    """Run `fn` as an S-stage pipeline over the mesh axis `axis`.

    stage_params: pytree with leading stage axis (sharded over `axis`).
    x: (M, B_micro, ...) microbatches (replicated; stage 0 consumes them).
    Returns the pipeline output in microbatch order, (M, B_micro, ...).
    """
    s = mesh.shape[axis]
    m = x.shape[0]
    total = m + s - 1  # schedule length with bubbles

    def per_stage(params, xs):
        # params: this stage's slice (leading axis dropped by shard_map)
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])          # current activation holder
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            feed = jnp.where(t < m, t, m - 1)
            buf = jnp.where(stage == 0, xs[feed], buf)
            buf = fn(params, buf)
            # pass to the next stage (last stage's output wraps to 0 where
            # it is collected)
            nxt = jax.lax.ppermute(
                buf, axis, [(i, (i + 1) % s) for i in range(s)]
            )
            # stage 0 receives the finished microbatch (t - (s - 1))
            done = t - (s - 1)
            take = jnp.logical_and(stage == 0, done >= 0)
            idx = jnp.clip(done, 0, m - 1)
            outs = jnp.where(
                take,
                jax.lax.dynamic_update_index_in_dim(
                    outs, nxt, idx, 0
                ),
                outs,
            )
            return nxt, outs

        buf, outs = jax.lax.fori_loop(0, total, tick, (buf, outs))
        return outs

    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params),
        P(),              # microbatches replicated into every stage
    )
    out_specs = P()
    fn_sm = jax.shard_map(
        per_stage, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn_sm(stage_params, x)
