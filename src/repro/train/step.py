"""Train-step builder: loss + grad (+ microbatch accumulation) + optimizer.

``build_train_step(model, parallel, opt)`` returns a pure
``step(params, opt_state, batch) -> (params', opt_state', metrics)``
suitable for jit/pjit.  Gradient accumulation runs as a ``lax.scan`` over
microbatches with the per-layer remat policy applied inside, so activation
memory is bounded by one microbatch regardless of global batch.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ParallelismConfig
from repro.models.model import Model
from repro.train.optimizer import AdamW, AdamWState


def _split_microbatches(batch: Dict, n: int) -> Dict:
    return jax.tree.map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)


def build_train_step(model: Model, parallel: ParallelismConfig,
                     opt: AdamW) -> Callable:
    remat = parallel.remat
    n_micro = parallel.microbatches

    def loss_and_grads(params, mb):
        # value_and_grad as a vjp, so that the profiler's op names tell
        # the forward pass from the backward one
        with jax.named_scope("forward"):
            loss, pullback, stats = jax.vjp(
                lambda p: model.loss_and_stats(p, mb, remat=remat), params,
                has_aux=True)
        with jax.named_scope("backward"):
            (grads,) = pullback(jnp.ones_like(loss))
        return loss, grads, stats

    def step(params, opt_state: AdamWState, batch):
        if n_micro > 1:
            mbs = _split_microbatches(batch, n_micro)

            def acc(carry, mb):
                loss, g, stats = loss_and_grads(params, mb)
                return jax.tree.map(jnp.add, carry, g), (loss, stats)

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, (losses, stats) = jax.lax.scan(acc, zero, mbs)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = jnp.mean(losses)
            stats = jax.tree.map(jnp.mean, stats)
        else:
            loss, grads, stats = loss_and_grads(params, batch)
        with jax.named_scope("optimizer"):
            new_params, new_state, gnorm = opt.update(grads, opt_state,
                                                      params)
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm,
                   **stats}
        return new_params, new_state, metrics

    return step


def build_eval_step(model: Model) -> Callable:
    def step(params, batch):
        return model.loss(params, batch)
    return step
