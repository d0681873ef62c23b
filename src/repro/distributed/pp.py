"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

shard_map + ``lax.ppermute``: each rank owns a contiguous stage of layers;
microbatches flow through a steady-state loop with (S + M - 1) ticks for M
microbatches over S stages.  Offered as an alternative layout for archs
whose layer count dwarfs the TP width; correctness is covered by
tests/test_distributed.py against the single-device stack.  Forward-only
(inference PP) here; training PP composes this with recomputed backward
stages — out of scope for the assigned cells (FSDP+TP covers them) and
noted in DESIGN.md.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(block_fn: Callable, params_stacked, x,
                     mesh: Mesh, axis: str = "pipe",
                     microbatches: int = 4):
    """Run a layer stack split into ``pipe`` stages over microbatches.

    block_fn(layer_params, x) -> x;  params_stacked leaves: (L, ...) with
    L % n_stages == 0; x: (B, ...) with B % microbatches == 0.
    """
    n_stages = mesh.shape[axis]
    L = jax.tree.leaves(params_stacked)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)

    def stage(params_local, x_local):
        # params_local: (L/S, ...) this stage's layers
        def run_stage(xm):
            def body(h, lp):
                return block_fn(lp, h), None
            out, _ = jax.lax.scan(body, xm, params_local)
            return out

        rank = jax.lax.axis_index(axis)
        B = x_local.shape[0]
        mb = B // microbatches
        bufs = x_local.reshape((microbatches, mb) + x_local.shape[1:])
        # carries become rank-varying inside the loop; mark them so
        out = jax.lax.pcast(jnp.zeros_like(bufs), (axis,), to="varying")
        # steady-state loop: tick t processes microbatch (t - rank) at rank
        cur = jax.lax.pcast(
            jnp.zeros((mb,) + x_local.shape[1:], x_local.dtype), (axis,),
            to="varying")
        n_ticks = microbatches + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(t, carry):
            cur, out = carry
            # stage 0 injects microbatch t (if in range)
            inject = jax.lax.dynamic_index_in_dim(
                bufs, jnp.clip(t, 0, microbatches - 1), 0, keepdims=False)
            cur = jnp.where(rank == 0,
                            jnp.where(t < microbatches, inject, cur), cur)
            y = run_stage(cur)
            # last stage emits microbatch (t - n_stages + 1)
            emit_idx = jnp.clip(t - n_stages + 1, 0, microbatches - 1)
            emit_ok = (rank == n_stages - 1) & (t - n_stages + 1 >= 0)
            old = jax.lax.dynamic_index_in_dim(out, emit_idx, 0,
                                               keepdims=False)
            new = jnp.where(emit_ok, y, old)
            out = jax.lax.dynamic_update_index_in_dim(out, new, emit_idx, 0)
            # rotate activations to the next stage
            cur = jax.lax.ppermute(y, axis, perm)
            return cur, out

        cur, out = jax.lax.fori_loop(0, n_ticks, tick, (cur, out))
        # only the last stage holds real outputs; broadcast them
        out = jax.lax.psum(
            jnp.where(rank == n_stages - 1, out, jnp.zeros_like(out)), axis)
        return out.reshape(x_local.shape)

    f = jax.shard_map(
        stage, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P())
    return f(params_stacked, x)
