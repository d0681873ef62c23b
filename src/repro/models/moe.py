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

The ``"sigmoid"`` router (DeepSeek-V3's ``noaux_tc`` with one group) is a
layer told which experts it holds: it scores all ``n_experts``, selects
the top k by score plus a correction bias, and computes only the held
experts' part of the result, for every assignment routed to them (no
capacity, nothing dropped), as one grouped matmul over the assignments
sorted by expert (``jax.lax.ragged_dot``).  The layer holds experts
``[0, held)``; under expert parallelism each model-rank holds its slice
of them and the parts are summed with the same ``psum``.  With
``n_held`` below ``n_experts`` what the experts held elsewhere would add
is left out.

Every MoE layer returns, beside its output, ``{"loss", "held", "load"}``:
its balance loss times ``aux_loss_weight``, the share of its
assignments that land on experts it holds (1 where it holds them all),
and the busiest held expert's assignment count over the held experts'
mean.
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
        "we_gate": ParamDef((e.held, d, e.d_ff_expert),
                            ("expert", "embed", None)),
        "we_up": ParamDef((e.held, d, e.d_ff_expert),
                          ("expert", "embed", None)),
        "we_out": ParamDef((e.held, e.d_ff_expert, d),
                           ("expert", None, "embed"),
                           scale=1.0 / max(1, (2 * cfg.n_layers)) ** 0.5),
    }
    if e.router == "sigmoid":
        # the noaux_tc correction bias: selection only, no gradient; its
        # update rule is not part of the model and it stays at zero
        defs["router_bias"] = ParamDef((e.n_experts,), (None,),
                                       init="zeros", dtype=jnp.float32)
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


def _route_sigmoid(x2d: jax.Array, router_w: jax.Array, bias: jax.Array,
                   k: int, scaling: float):
    """(top_e, top_w, scores): the k experts of highest ``sigmoid(x W_r)
    + bias``, weighted by their unbiased scores normalized over the k
    and times ``scaling``; ``scores`` are all experts' (T, E), float32."""
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", x2d, router_w,
                                       preferred_element_type=F32))
    _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * scaling
    return top_e, top_w, scores


def _seq_balance_loss(scores: jax.Array, top_e: jax.Array,
                      B: int) -> jax.Array:
    """DeepSeek-V3's sequence-wise balance loss (arXiv:2412.19437
    §2.1.2), unweighted: per sequence of T tokens, ``sum_i f_i P_i`` with
    ``f_i = E / (k T) * #(tokens selecting i)`` and ``P_i`` the mean over
    the tokens of expert i's score normalized over all experts; the mean
    over the batch's sequences."""
    TT, E = scores.shape
    k = top_e.shape[1]
    T = TT // B
    sel = jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32), axis=1)
    f = jnp.sum(sel.reshape(B, T, E), axis=1) * (E / (k * T))
    p = jnp.mean((scores / jnp.sum(scores, -1, keepdims=True)
                  ).reshape(B, T, E), axis=1)
    return jnp.mean(jnp.sum(f * p, axis=-1))


def _held_experts(x2d: jax.Array, top_e: jax.Array, top_w: jax.Array,
                  e_start: int, we_gate, we_up, we_out):
    """The part of the MoE output that experts [e_start, e_start + n_held)
    give, for every assignment routed to them.

    Assignments are sorted by held expert (the others last) and each
    held expert's rows go through its gated MLP in one grouped matmul.
    Rows past the held ones are masked on the way in and out: the chip
    leaves a grouped matmul's rows outside every group unwritten.
    Returns (out (T, D), each held expert's assignment count)."""
    T, D = x2d.shape
    k = top_e.shape[1]
    n_held = we_gate.shape[0]
    local = top_e.reshape(-1) - e_start
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n_held + 1)[:n_held]
    keep = (jnp.arange(T * k) < jnp.sum(sizes))[:, None]
    xs = jnp.where(keep, x2d[order // k], 0)
    g = jnp.where(keep, jax.lax.ragged_dot(xs, we_gate, sizes), 0)
    u = jnp.where(keep, jax.lax.ragged_dot(xs, we_up, sizes), 0)
    h = jax.nn.silu(g.astype(F32)).astype(x2d.dtype) * u
    y = jnp.where(keep, jax.lax.ragged_dot(h, we_out, sizes), 0)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype))
    w = jnp.where(held, top_w.reshape(-1), 0.0).astype(y.dtype)
    out = jnp.einsum("tkd,tk->td", y[inverse].reshape(T, k, D),
                     w.reshape(T, k))
    return out, sizes


def _load_stats(counts: jax.Array, k: int, T: int, n_experts: int):
    """(share of the T*k assignments on the experts ``counts`` counts,
    the busiest one's count over their mean).  The share is 1, and not
    counted, where ``counts`` covers every expert."""
    counts = counts.astype(F32)
    share = jnp.ones((), F32) if counts.shape[0] == n_experts \
        else jnp.sum(counts) / (T * k)
    return share, jnp.max(counts) / jnp.maximum(jnp.mean(counts), 1e-9)


def _capacity(T: int, k: int, E: int, factor: float) -> int:
    cap = int(T * k * factor / E) + 1
    return max(cap, 4)


def _routed(x2d: jax.Array, B: int, route_p: Dict, experts: Tuple,
            e, e_start, ep_axis: Optional[str] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The routed experts' part of the output that ``experts`` (from
    expert ``e_start`` on) give for the B sequences in ``x2d``, the
    unweighted balance loss, and each held expert's assignment count.
    Inside ``shard_map``, ``ep_axis`` names the axis the experts are
    split over."""
    T = x2d.shape[0]
    with jax.named_scope("moe.route"):
        if e.router == "sigmoid":
            top_e, top_w, scores = _route_sigmoid(
                x2d, route_p["router"], route_p["router_bias"], e.top_k,
                e.routed_scaling)
            bal = _seq_balance_loss(scores, top_e, B)
        else:
            top_e, top_w, bal = _route(x2d, route_p["router"], e.top_k)
    if ep_axis is not None:
        # each rank gathers its own rows of the tokens and the weights:
        # marked varying, their cotangents are summed over the ranks on
        # the way back (a gather of a value that is the same on every
        # rank would keep only this rank's share of its cotangent)
        x2d, top_w = jax.lax.pcast((x2d, top_w), ep_axis, to="varying")
    with jax.named_scope("moe.experts"):
        if e.router == "sigmoid":
            y, _ = _held_experts(x2d, top_e, top_w, e_start, *experts)
        else:
            cap = _capacity(T, e.top_k, e.n_experts, e.capacity_factor)
            y = _dispatch_local(x2d, top_e, top_w, e_start,
                                experts[0].shape[0], cap, *experts)
    counts = jnp.bincount(top_e.reshape(-1), length=e.n_experts)[:e.held]
    return y, bal, counts


# ---------------------------------------------------------------------------
# Public layer
# ---------------------------------------------------------------------------

def moe_ffn(p: Dict, x: jax.Array, cfg: ModelConfig
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """MoE FFN. x: (B, S, D). Returns (y, {"loss", "held", "load"})."""
    e = cfg.moe
    B, S, D = x.shape
    rules = current_rules()

    shared_y = 0.0
    if "ws_gate" in p:
        with jax.named_scope("moe.shared"):
            g = jnp.einsum("bsd,df->bsf", x, p["ws_gate"])
            u = jnp.einsum("bsd,df->bsf", x, p["ws_up"])
            h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
            h = shard(h, "batch", "act_seq", "act_mlp")
            shared_y = jnp.einsum("bsf,fd->bsd", h, p["ws_out"])

    route_p = {k: p[k] for k in ("router", "router_bias") if k in p}
    experts = (p["we_gate"], p["we_up"], p["we_out"])
    if rules.enabled and rules.mesh is not None \
            and rules.ep_axis is not None:
        mesh = rules.mesh
        ep_axis = rules.ep_axis
        n_local = e.held // mesh.shape[ep_axis]
        batch_spec = rules.batch_axes
        if batch_spec is None:
            reduce_axes: tuple = ()
        elif isinstance(batch_spec, tuple):
            reduce_axes = batch_spec
        else:
            reduce_axes = (batch_spec,)

        def body(x_l, route_p, experts):
            Bl, Sl, Dl = x_l.shape
            part, bal, counts = _routed(
                x_l.reshape(Bl * Sl, Dl), Bl, route_p, experts, e,
                jax.lax.axis_index(ep_axis) * n_local, ep_axis)
            out = jax.lax.psum(part, ep_axis)
            if reduce_axes:
                bal = jax.lax.pmean(bal, reduce_axes)
                counts = jax.lax.psum(counts, reduce_axes)
            return out.reshape(Bl, Sl, Dl), bal, counts

        y, bal, counts = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(batch_spec, None, None), P(), P(ep_axis, None, None)),
            out_specs=(P(batch_spec, None, None), P(), P()),
        )(x, route_p, experts)
    else:
        y, bal, counts = _routed(x.reshape(B * S, D), B, route_p, experts,
                                 e, 0)
        y = y.reshape(B, S, D)
    held, load = _load_stats(counts, e.top_k, B * S, e.n_experts)

    y = y + shared_y
    return (shard(y, "batch", "act_seq", "act_embed"),
            {"loss": bal * e.aux_loss_weight, "held": held, "load": load})
