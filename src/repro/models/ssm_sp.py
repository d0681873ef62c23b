"""Sequence-parallel SSD (beyond-paper, §Perf zamba2/mamba2 iteration).

Prefill at 32k with TP pays a residual-stream all-reduce per mamba layer
(~0.5 GB each).  This layout shards the *sequence* over 'model' instead and
keeps weights replicated; the only cross-rank traffic per layer is

* a conv halo — the previous rank's last (d_conv-1) pre-conv rows;
* the SSD state hand-off — per-rank summaries (final state with h0=0 and the
  rank's total log-decay) are all-gathered (~4 MB) and every rank computes
  its incoming state as the exclusive affine scan over rank summaries:

      h0_r = sum_{j<r} S_j * exp( cum[r-1] - cum[j] ),   cum = cumsum(logD)

The SSD core runs twice (once for summaries with h0=0, once with the true
h0); the intra-chunk quadratic work is a small fraction of the block's
projection FLOPs, so the second pass costs ~15% compute for a ~10x drop in
wire bytes.  Validated against the single-device ssm_block in
tests/test_distributed.py::test_seq_parallel_ssd_matches_local.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.ssm import _ssd_core

F32 = jnp.float32


def ssm_block_seq_parallel(p: Dict, x: jax.Array, cfg: ModelConfig,
                           mesh, *, axis: str = "model",
                           batch_axes=("data",)) -> jax.Array:
    """Mamba2 block with the sequence sharded over ``axis``.

    x: (B, S, D), S divisible by mesh.shape[axis]; weights replicated.
    """
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    n = mesh.shape[axis]
    K = s.d_conv

    def local(x_l, wz, wx, wB, wC, wdt, dt_bias, A_log, D_skip,
              conv_x, conv_B, conv_C, norm_w, wo):
        B, S_loc, _ = x_l.shape
        z = jnp.einsum("bsd,di->bsi", x_l, wz)
        xs = jnp.einsum("bsd,di->bsi", x_l, wx)
        Bm = jnp.einsum("bsd,dn->bsn", x_l, wB)
        Cm = jnp.einsum("bsd,dn->bsn", x_l, wC)
        dt = jnp.einsum("bsd,dh->bsh", x_l, wdt)

        # ---- causal conv with halo from the previous rank ----
        cat = jnp.concatenate([xs, Bm, Cm], axis=-1)      # (B, S_loc, C)
        perm = [(i, i + 1) for i in range(n - 1)]
        halo = jax.lax.ppermute(cat[:, -(K - 1):, :], axis, perm)
        full = jnp.concatenate([halo, cat], axis=1)       # (B,S_loc+K-1,C)
        wfull = jnp.concatenate([conv_x, conv_B, conv_C], axis=-1)  # (K, C)
        conv = jnp.zeros(cat.shape, F32)
        for k in range(K):
            conv = conv + full[:, k:k + S_loc, :].astype(F32) \
                * wfull[k].astype(F32)
        conv = jax.nn.silu(conv).astype(x_l.dtype)
        xs = conv[..., :d_in]
        Bm = conv[..., d_in:d_in + s.d_state]
        Cm = conv[..., d_in + s.d_state:]

        dt = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
        A = -jnp.exp(A_log.astype(F32))
        xh = xs.reshape(B, S_loc, nh, s.head_dim)

        # ---- pass 1: local summaries (h0 = 0) ----
        chunk = min(s.chunk, S_loc)
        vary = tuple(batch_axes) + (axis,)
        z0 = jax.lax.pcast(
            jnp.zeros((B, nh, s.head_dim, s.d_state), F32), vary,
            to="varying")
        _, S_r = _ssd_core(xh, dt, A, Bm, Cm, chunk, h0=z0)
        logD_r = jnp.sum(dt * A, axis=1)                  # (B, nh)

        # ---- exclusive affine scan across ranks ----
        Ss = jax.lax.all_gather(S_r, axis)                # (n, B, nh, P, N)
        Ls = jax.lax.all_gather(logD_r, axis)             # (n, B, nh)
        r = jax.lax.axis_index(axis)
        cum = jnp.cumsum(Ls, axis=0)
        cum_prev = cum[r] - Ls[r]                         # cum[r-1]
        w = jnp.exp(cum_prev[None] - cum)                 # (n, B, nh)
        mask = (jnp.arange(n) < r)[:, None, None]
        w = jnp.where(mask, w, 0.0)
        h0 = jnp.einsum("nbh,nbhpq->bhpq", w, Ss)

        # ---- pass 2: true state ----
        y, _ = _ssd_core(xh, dt, A, Bm, Cm, chunk, h0=h0)
        y = y + xh.astype(F32).astype(y.dtype) \
            * D_skip.astype(y.dtype)[None, None, :, None]
        y = y.reshape(B, S_loc, d_in)
        y = y * jax.nn.silu(z.astype(F32)).astype(y.dtype)
        yf = y.astype(F32)
        y = (yf * jax.lax.rsqrt(
            jnp.mean(yf * yf, -1, keepdims=True) + cfg.norm_eps)
            * norm_w.astype(F32)).astype(x_l.dtype)
        return jnp.einsum("bsi,id->bsd", y, wo)

    weights = (p["wz"], p["wx"], p["wB"], p["wC"], p["wdt"], p["dt_bias"],
               p["A_log"], p["D_skip"], p["conv_x"], p["conv_B"],
               p["conv_C"], p["norm"], p["wo"])
    x_spec = P(batch_axes, axis, None)
    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec,) + (P(),) * len(weights),
        out_specs=x_spec)
    return f(x, *weights)
