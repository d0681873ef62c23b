"""Transformer building blocks — pure functions over ParamDef-declared params.

Conventions:
* activations bf16, reductions/norm/softmax accumulate fp32;
* attention layout (B, S, H, hd); GQA groups q-heads over kv-heads;
* logical sharding via :func:`repro.distributed.sharding.shard`;
* every block has both a full-sequence form and a single-token decode form.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models.params import ParamDef

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_def(d: int) -> ParamDef:
    return ParamDef((d,), ("embed",), init="ones")


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(F32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rotary(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    if positions.ndim == 1:
        ang = positions.astype(F32)[:, None] * freqs[None, :]        # (S, half)
        ang = ang[None, :, None, :]                                   # (1,S,1,half)
    else:
        ang = positions.astype(F32)[..., None] * freqs                # (B,S,half)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig, *, cross: bool = False) -> Dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, K = cfg.n_heads, cfg.n_kv_heads
    defs = {
        "wq": ParamDef((d, H * hd), ("embed", "q_heads")),
        "wk": ParamDef((d, K * hd), ("embed", "kv_heads")),
        "wv": ParamDef((d, K * hd), ("embed", "kv_heads")),
        "wo": ParamDef((H * hd, d), ("q_heads", "embed"),
                       scale=1.0 / max(1, (2 * cfg.n_layers)) ** 0.5),
    }
    if cfg.qkv_bias and not cross:
        defs["bq"] = ParamDef((H * hd,), ("q_heads",), init="zeros")
        defs["bk"] = ParamDef((K * hd,), ("kv_heads",), init="zeros")
        defs["bv"] = ParamDef((K * hd,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), (None,), init="ones")
        defs["k_norm"] = ParamDef((hd,), (None,), init="ones")
    return defs


def _project_qkv(p: Dict, x: jax.Array, kv_x: jax.Array, cfg: ModelConfig,
                 positions, kv_positions, *, use_rope: bool = True):
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    H, K = cfg.n_heads, cfg.n_kv_heads
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", kv_x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", kv_x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, -1, H, hd)
    k = k.reshape(B, -1, K, hd)
    v = v.reshape(B, -1, K, hd)
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        q = rotary(q, positions, cfg.rope_theta)
        k = rotary(k, kv_positions, cfg.rope_theta)
    q = shard(q, "batch", "act_seq", "act_heads", None)
    k = shard(k, "batch", "act_seq", "act_kv", None)
    v = shard(v, "batch", "act_seq", "act_kv", None)
    return q, k, v


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """Grouped scaled-dot-product attention. q/k:(B,S,H|K,hd) v:(B,Sk,K,hd_v).

    Materializes (Sq, Sk) scores — use only when Sq*Sk is small (decode,
    short sequences).  Long sequences go through :func:`blockwise_attention`.
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K if K else 1
    q = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(F32) / (hd ** 0.5)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_offset: int = 0, q_block: int = 512):
    """Flash-style attention expressed in XLA: lax.scan over query blocks.

    Never materializes more than one (B, K, G, q_block, Sk) score tile, so
    32k prefill compiles within HBM.  Online softmax is unnecessary because
    each scan step owns its complete score row.
    q: (B,Sq,H,hd); k: (B,Sk,K,hd); v: (B,Sk,K,hd_v); q_offset = absolute
    position of q[0].
    """
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    qb = min(q_block, Sq)
    nb = Sq // qb
    assert Sq % qb == 0, (Sq, qb)
    qr = q.reshape(B, nb, qb, K, G, hd)
    qr = jnp.moveaxis(qr, 1, 0)                       # (nb, B, qb, K, G, hd)
    kpos = jnp.arange(Sk)[None, :]

    def step(_, qi_and_idx):
        qi, bidx = qi_and_idx
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qi, k).astype(F32)
        scores = scores / (hd ** 0.5)
        qpos = q_offset + bidx * qb + jnp.arange(qb)[:, None]
        m = jnp.ones((qb, Sk), bool)
        if causal:
            m &= kpos <= qpos
        if window:
            m &= kpos > qpos - window
        scores = jnp.where(m[None, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(qi.dtype)
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
        return None, out

    _, outs = jax.lax.scan(step, None, (qr, jnp.arange(nb)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, v.shape[-1])
    return out


def causal_mask(Sq: int, Sk: int, *, window: int = 0,
                offset: int = 0) -> jax.Array:
    """(1,1,1,Sq,Sk) bool; offset = absolute position of query 0."""
    qpos = jnp.arange(Sq)[:, None] + offset
    kpos = jnp.arange(Sk)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m[None, None, None, :, :]


# score tiles above this element count switch to blockwise attention
_DIRECT_SDPA_LIMIT = 1 << 21


def attention(p: Dict, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array, causal: bool, window: int = 0,
              kv_x: Optional[jax.Array] = None,
              kv_positions: Optional[jax.Array] = None,
              use_rope: bool = True, return_kv: bool = False):
    """Full-sequence attention (training / prefill / cross)."""
    kv_x = x if kv_x is None else kv_x
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = _project_qkv(p, x, kv_x, cfg, positions, kv_positions,
                           use_rope=use_rope)
    Sq, Sk = q.shape[1], k.shape[1]
    if Sq * Sk <= _DIRECT_SDPA_LIMIT:
        mask = causal_mask(Sq, Sk, window=window) if causal else None
        out = _sdpa(q, k, v, mask, cfg)
    else:
        out = blockwise_attention(q, k, v, causal=causal, window=window)
    out = out.reshape(x.shape[0], -1, cfg.n_heads * cfg.resolved_head_dim)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    y = shard(y, "batch", "act_seq", "act_embed")
    if return_kv:
        return y, k, v
    return y


def mla_defs(cfg: ModelConfig) -> Dict:
    d, H, a = cfg.d_model, cfg.n_heads, cfg.mla
    r = a.kv_lora_rank
    return {
        "wq": ParamDef((d, H * a.qk_head_dim), ("embed", "q_heads")),
        "wkv_a": ParamDef((d, r + a.qk_rope_head_dim), ("embed", None)),
        "kv_norm": ParamDef((r,), (None,), init="ones"),
        "wkv_b": ParamDef((r, H * (a.qk_nope_head_dim + a.v_head_dim)),
                          (None, "q_heads")),
        "wo": ParamDef((H * a.v_head_dim, d), ("q_heads", "embed"),
                       scale=1.0 / max(1, (2 * cfg.n_layers)) ** 0.5),
    }


def mla_attention(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                  positions: jax.Array) -> jax.Array:
    """Causal multi-head latent attention over the full sequence.

    Per head, q is ``qk_nope_head_dim`` dims without position and
    ``qk_rope_head_dim`` rotary dims.  ``x W_kv_a`` gives the latent
    (RMS-normed, then ``W_kv_b`` maps it to each head's unrotated key and
    its value) and one rotary key that every head shares.  Scores are
    scaled by 1/sqrt(qk_head_dim); values are ``v_head_dim`` wide."""
    with jax.named_scope("mla"):
        B, S, _ = x.shape
        a, H = cfg.mla, cfg.n_heads
        nope, r = a.qk_nope_head_dim, a.kv_lora_rank
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(
            B, S, H, a.qk_head_dim)
        q = jnp.concatenate(
            [q[..., :nope], rotary(q[..., nope:], positions, cfg.rope_theta)],
            axis=-1)
        kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
        latent = rmsnorm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
        k_rope = rotary(kv[:, :, None, r:], positions, cfg.rope_theta)
        kvb = jnp.einsum("bsr,rh->bsh", latent, p["wkv_b"]).reshape(
            B, S, H, nope + a.v_head_dim)
        k = jnp.concatenate(
            [kvb[..., :nope],
             jnp.broadcast_to(k_rope, (B, S, H, a.qk_rope_head_dim))],
            axis=-1)
        v = kvb[..., nope:]
        q = shard(q, "batch", "act_seq", "act_heads", None)
        k = shard(k, "batch", "act_seq", "act_heads", None)
        v = shard(v, "batch", "act_seq", "act_heads", None)
        if S * S <= _DIRECT_SDPA_LIMIT:
            out = _sdpa(q, k, v, causal_mask(S, S), cfg)
        else:
            out = blockwise_attention(q, k, v, causal=True)
        y = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * a.v_head_dim),
                       p["wo"])
        return shard(y, "batch", "act_seq", "act_embed")


def attention_decode(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                     cache_k: jax.Array, cache_v: jax.Array,
                     index: jax.Array, window: int = 0,
                     use_rope: bool = True):
    """One-token decode against a preallocated KV cache.

    x: (B, 1, D); cache_k/v: (B, S_max, K, hd); index: scalar position.
    Returns (y, new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    pos = jnp.full((1,), index, dtype=jnp.int32)
    q, k, v = _project_qkv(p, x, x, cfg, pos, pos, use_rope=use_rope)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, index, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, index, axis=1)
    cache_k = shard(cache_k, "batch", "kv_seq", "act_kv", None)
    cache_v = shard(cache_v, "batch", "kv_seq", "act_kv", None)
    S_max = cache_k.shape[1]
    kpos = jnp.arange(S_max)
    valid = kpos <= index
    if window:
        valid &= kpos > index - window
    mask = valid[None, None, None, None, :]
    out = _sdpa(q, cache_k, cache_v, mask, cfg)
    out = out.reshape(B, 1, cfg.n_heads * hd)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return shard(y, "batch", None, "act_embed"), cache_k, cache_v


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi_gate": ParamDef((d, f), ("embed", "mlp")),
        "wi_up": ParamDef((d, f), ("embed", "mlp")),
        "wo": ParamDef((f, d), ("mlp", "embed"),
                       scale=1.0 / max(1, (2 * cfg.n_layers)) ** 0.5),
    }


def mlp(p: Dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["wi_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"])
    h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    h = shard(h, "batch", "act_seq", "act_mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"])
    return shard(y, "batch", "act_seq", "act_embed")


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------

def embed_defs(cfg: ModelConfig, v_pad: int) -> Dict:
    d = cfg.d_model
    defs = {"tok": ParamDef((v_pad, d), ("vocab", "embed"), init="embed")}
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((d, v_pad), ("embed", "vocab"))
    return defs


def embed(p: Dict, tokens: jax.Array) -> jax.Array:
    y = p["tok"][tokens]
    return shard(y, "batch", "act_seq", "act_embed")


def logits(p: Dict, x: jax.Array) -> jax.Array:
    w = p["head"] if "head" in p else p["tok"].T
    out = jnp.einsum("bsd,dv->bsv", x, w)
    return shard(out, "batch", "act_seq", "act_vocab")
