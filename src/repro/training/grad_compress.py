"""Gradient compression for the data-parallel all-reduce.

Two pieces:
  * `int8_compress` / `int8_decompress` — per-tensor symmetric int8
    quantisation with an error-feedback residual (the residual is added
    back into the next step's gradient so quantisation noise is unbiased
    over time — 1-bit Adam / EF-SGD style).
  * `compressed_psum` — an int8 all-reduce usable inside `shard_map`:
    quantise, widen to int16 (sum of <=64 int8 shards cannot overflow),
    psum, dequantise.  4x fewer wire bytes than f32 (2x after the int16
    widening — the widening happens on-chip; the collective itself moves
    int16).
  * `make_ddp_step` — a pure-DP (replicated-params) training step built on
    `shard_map` that exercises the compressed collective end to end; the
    SPMD TP/EP path keeps XLA's native collectives (DESIGN.md §4 records
    this split).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "int8_compress",
    "int8_decompress",
    "compressed_psum",
    "make_ddp_step",
]


def int8_compress(x: jax.Array, residual: Optional[jax.Array] = None):
    """-> (q int8, scale f32, new_residual).  Error feedback included."""
    x = x.astype(jnp.float32)
    if residual is not None:
        x = x + residual
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    new_residual = x - q.astype(jnp.float32) * scale
    return q, scale, new_residual


def int8_decompress(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(x: jax.Array, axis_name: str,
                    residual: Optional[jax.Array] = None):
    """int8 error-feedback psum for use inside shard_map.

    Returns (mean-reduced f32 value, new_residual).
    """
    q, scale, new_residual = int8_compress(x, residual)
    n = jax.lax.psum(1, axis_name)
    # Widen before summing: sum of n<=127 int8 values fits in int16 for
    # n<=255; the wire moves int16 (2 bytes vs 4 for f32 grads).
    total = jax.lax.psum(q.astype(jnp.int16), axis_name)
    # Each shard quantised with its own scale; psum of scales approximates
    # sum_i q_i * s_i when scales are close — we send the per-shard scale
    # alongside (a scalar; negligible bytes) and use the max for safety.
    scale_max = jax.lax.pmax(scale, axis_name)
    value = total.astype(jnp.float32) * scale_max / n
    return value, new_residual


def make_ddp_step(loss_fn, mesh: Mesh, axis_name: str = "data",
                  lr: float = 1e-2, compress: bool = True):
    """SGD data-parallel step over `shard_map` with compressed grad sync.

    loss_fn(params, batch) -> scalar.  Params replicated; batch sharded on
    its leading axis.  Returns step(params, residuals, batch) ->
    (params, residuals, loss).
    """
    rep = P()

    def local_step(params, residuals, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis_name)
        new_params = {}
        new_res = {}

        flat_g, tdef = jax.tree_util.tree_flatten(grads)
        flat_p = jax.tree_util.tree_leaves(params)
        flat_r = jax.tree_util.tree_leaves(residuals)
        out_p, out_r = [], []
        for p, g, r in zip(flat_p, flat_g, flat_r):
            if compress:
                g_sync, r_new = compressed_psum(g, axis_name, r)
            else:
                g_sync = jax.lax.pmean(g, axis_name)
                r_new = r
            out_p.append(p - lr * g_sync)
            out_r.append(r_new)
        return (
            jax.tree_util.tree_unflatten(tdef, out_p),
            jax.tree_util.tree_unflatten(tdef, out_r),
            loss,
        )

    batch_spec = P(axis_name)
    return jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(rep, rep, batch_spec),
        out_specs=(rep, rep, rep),
        check_vma=False,
    )
