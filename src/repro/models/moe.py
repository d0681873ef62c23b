"""Mixture-of-Experts FFN with expert parallelism.

Design (see DESIGN.md §3):

* **Routing** — top-k softmax gating with capacity-based token dropping.
* **Dispatch** — sort-based: token/expert assignments are sorted by expert id
  and scattered into a dense ``(E_local, C, D)`` buffer.  No ``(T, E, C)``
  one-hot einsum is ever materialized (that classic "dropping" formulation
  costs ~40% extra FLOPs at 384 experts; the sorted form keeps the FLOP count
  equal to the useful expert GEMMs).
* **Expert parallelism** — the layer runs under ``shard_map``: activations
  arrive batch-sharded over the data axes and replicated over ``model``;
  expert weights are sharded over ``model``.  Each model-rank dispatches only
  to its local experts and the partial outputs are combined with a single
  ``psum`` over ``model``.  Router compute is replicated across model ranks
  (it is ~E·D flops/token — noise next to the expert GEMMs).
* **Shared experts** — fused into one dense gated MLP of width
  ``n_shared * d_ff_expert`` (TP-sharded like a regular MLP).

Without a mesh (smoke tests) the same sort-based dispatch runs locally over
all experts.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import current_rules, shard
from repro.models.params import ParamDef

F32 = jnp.float32


def moe_defs(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    e = cfg.moe
    defs = {
        "router": ParamDef((d, e.n_experts), ("embed", "expert"), scale=0.1),
        "we_gate": ParamDef((e.n_experts, d, e.d_ff_expert),
                            ("expert", "embed", None)),
        "we_up": ParamDef((e.n_experts, d, e.d_ff_expert),
                          ("expert", "embed", None)),
        "we_out": ParamDef((e.n_experts, e.d_ff_expert, d),
                           ("expert", None, "embed"),
                           scale=1.0 / max(1, (2 * cfg.n_layers)) ** 0.5),
    }
    if e.n_shared:
        f = e.n_shared * e.d_ff_expert
        defs["ws_gate"] = ParamDef((d, f), ("embed", "mlp"))
        defs["ws_up"] = ParamDef((d, f), ("embed", "mlp"))
        defs["ws_out"] = ParamDef((f, d), ("mlp", "embed"),
                                  scale=1.0 / max(1, (2 * cfg.n_layers)) ** 0.5)
    return defs


# ---------------------------------------------------------------------------
# Local (per-shard) sorted dispatch + expert GEMMs
# ---------------------------------------------------------------------------

def _dispatch_local(x2d: jax.Array, top_e: jax.Array, top_g: jax.Array,
                    e_start: int, n_local: int, capacity: int,
                    we_gate, we_up, we_out) -> jax.Array:
    """Sorted capacity dispatch over experts [e_start, e_start+n_local).

    x2d: (T, D);  top_e/top_g: (T, k) expert ids / gate weights.
    Returns partial output (T, D) — contributions of local experts only.
    """
    T, D = x2d.shape
    k = top_e.shape[1]
    flat_e = top_e.reshape(-1)                       # (T*k,)
    flat_g = top_g.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), k)

    local = (flat_e >= e_start) & (flat_e < e_start + n_local)
    # sort by (is_remote, expert): local assignments first, grouped by expert
    sort_key = jnp.where(local, flat_e - e_start, n_local)
    order = jnp.argsort(sort_key, stable=True)
    s_e = sort_key[order]                            # sorted local-expert ids
    s_tok = flat_tok[order]
    s_g = flat_g[order]

    # position within expert (for capacity slotting): running count per expert
    ones = jnp.ones_like(s_e)
    seg_pos = jnp.cumsum(ones) - 1
    # index of first occurrence of each expert id in the sorted list
    first_idx = jnp.searchsorted(s_e, jnp.arange(n_local + 1), side="left")
    pos_in_e = seg_pos - first_idx[jnp.clip(s_e, 0, n_local)]

    keep = (s_e < n_local) & (pos_in_e < capacity)
    slot = jnp.where(keep, s_e * capacity + pos_in_e, n_local * capacity)

    # gather tokens into (E_local*C, D) buffer (one overflow row, dropped)
    buf = jnp.zeros((n_local * capacity + 1, D), x2d.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], x2d[s_tok], 0))
    buf = buf[:-1].reshape(n_local, capacity, D)

    # expert GEMMs (batched over local experts)
    g = jnp.einsum("ecd,edf->ecf", buf, we_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, we_up)
    h = jax.nn.silu(g.astype(F32)).astype(x2d.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, we_out)        # (E_local, C, D)

    # combine: gather back to assignments, weight by gate, sum into tokens
    y_flat = y.reshape(n_local * capacity, D)
    y_tok = jnp.where(keep[:, None],
                      y_flat[jnp.clip(slot, 0, n_local * capacity - 1)], 0)
    y_tok = y_tok * s_g[:, None].astype(y_tok.dtype)
    out = jnp.zeros_like(x2d).at[s_tok].add(y_tok)
    return out


def _route(x2d: jax.Array, router_w: jax.Array, k: int):
    logits = jnp.einsum("td,de->te", x2d, router_w).astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(probs, k)
    top_g = top_g / jnp.clip(jnp.sum(top_g, axis=-1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch-style): E * sum_e f_e * p_e
    E = router_w.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32), axis=1), axis=0) / k
    aux = E * jnp.sum(me * ce)
    return top_e, top_g.astype(x2d.dtype), aux


def _capacity(T: int, k: int, E: int, factor: float) -> int:
    cap = int(T * k * factor / E) + 1
    return max(cap, 4)


# ---------------------------------------------------------------------------
# Public layer
# ---------------------------------------------------------------------------

def moe_ffn(p: Dict, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN. x: (B, S, D). Returns (y, aux_loss)."""
    e = cfg.moe
    B, S, D = x.shape
    rules = current_rules()

    shared_y = 0.0
    if "ws_gate" in p:
        g = jnp.einsum("bsd,df->bsf", x, p["ws_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["ws_up"])
        h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
        h = shard(h, "batch", "act_seq", "act_mlp")
        shared_y = jnp.einsum("bsf,fd->bsd", h, p["ws_out"])

    use_ep = (rules.enabled and rules.mesh is not None
              and rules.ep_axis is not None)
    if use_ep:
        mesh = rules.mesh
        ep_axis = rules.ep_axis
        ep_size = mesh.shape[ep_axis]
        n_local = e.n_experts // ep_size
        batch_spec = rules.batch_axes
        if batch_spec is None:
            reduce_axes: tuple = ()
        elif isinstance(batch_spec, tuple):
            reduce_axes = batch_spec
        else:
            reduce_axes = (batch_spec,)

        def body(x_l, router_w, we_gate, we_up, we_out):
            Bl, Sl, Dl = x_l.shape
            x2d = x_l.reshape(Bl * Sl, Dl)
            top_e, top_g, aux = _route(x2d, router_w, e.top_k)
            cap = _capacity(Bl * Sl, e.top_k, e.n_experts, e.capacity_factor)
            r = jax.lax.axis_index(ep_axis)
            part = _dispatch_local(
                x2d, top_e, top_g, r * n_local, n_local, cap,
                we_gate, we_up, we_out)
            out = jax.lax.psum(part, ep_axis)
            if reduce_axes:
                aux = jax.lax.pmean(aux, reduce_axes)
            return out.reshape(Bl, Sl, Dl), aux

        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(batch_spec, None, None), P(None, None),
                      P(ep_axis, None, None), P(ep_axis, None, None),
                      P(ep_axis, None, None)),
            out_specs=(P(batch_spec, None, None), P()),
        )(x, p["router"], p["we_gate"], p["we_up"], p["we_out"])
    else:
        x2d = x.reshape(B * S, D)
        top_e, top_g, aux = _route(x2d, p["router"], e.top_k)
        cap = _capacity(B * S, e.top_k, e.n_experts, e.capacity_factor)
        y = _dispatch_local(x2d, top_e, top_g, 0, e.n_experts, cap,
                            p["we_gate"], p["we_up"], p["we_out"])
        y = y.reshape(B, S, D)

    y = y + shared_y
    return shard(y, "batch", "act_seq", "act_embed"), aux * e.aux_loss_weight
