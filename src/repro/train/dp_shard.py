"""Explicit-collective data-parallel trainer (shard_map path).

The pjit path lets XLA schedule gradient reductions; this path makes them
explicit so the framework can (a) compress gradients on the wire
(train/compression.py) and (b) overlap the reduction with the optimizer
prologue.  Used by the multi-device integration tests and the gradient-
compression §Perf iteration.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.model import Model
from repro.train import compression
from repro.train.optimizer import AdamW


def build_dp_train_step(model: Model, opt: AdamW, mesh: Mesh,
                        axis: str = "data",
                        compress_grads: bool = False) -> Callable:
    """Params replicated; batch sharded over ``axis``; explicit psum."""

    def local_step(params, opt_state, ef, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch))(params)
        if compress_grads:
            grads, ef = compression.allreduce_compressed(grads, ef, axis)
        else:
            grads = jax.lax.pmean(grads, axis)
        loss = jax.lax.pmean(loss, axis)
        new_params, new_state, gnorm = opt.update(grads, opt_state, params)
        return new_params, new_state, ef, {"loss": loss, "grad_norm": gnorm}

    batch_specs = {"tokens": P(axis, None), "labels": P(axis, None)}

    def spec_for_batch(batch):
        return {k: P(axis) if v.ndim == 1 else
                P(*((axis,) + (None,) * (v.ndim - 1)))
                for k, v in batch.items()}

    def step(params, opt_state, ef, batch):
        in_specs = (P(), P(), P(), spec_for_batch(batch))
        out_specs = (P(), P(), P(), P())
        f = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs)
        return f(params, opt_state, ef, batch)

    return step
