"""Model assembly for every family in the pool.

Families: dense / moe / vlm (decoder-only LM), encdec (seamless), ssm
(mamba2), hybrid (zamba2), encoder (vit).  All stacks scan over stacked
per-layer params so the HLO (and 512-way SPMD compile time) stays small.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import layers as lyr
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.params import ParamDef, padded_vocab, stack_defs

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------

def _block_defs(cfg: ModelConfig, *, cross: bool = False,
                ssm: bool = False, dense: bool = False) -> Dict:
    d = {"ln1": lyr.rmsnorm_def(cfg.d_model)}
    if ssm:
        d["ssm"] = ssm_mod.ssm_defs(cfg)
        return d
    d["attn"] = lyr.mla_defs(cfg) if cfg.mla else lyr.attention_defs(cfg)
    if cross:
        d["lnc"] = lyr.rmsnorm_def(cfg.d_model)
        d["cross"] = lyr.attention_defs(cfg, cross=True)
    d["ln2"] = lyr.rmsnorm_def(cfg.d_model)
    if cfg.moe is not None and not dense:
        d["moe"] = moe_mod.moe_defs(cfg)
    else:
        d["mlp"] = lyr.mlp_defs(cfg)
    return d


def param_defs(cfg: ModelConfig) -> Dict:
    v_pad = padded_vocab(cfg.vocab_size) if cfg.vocab_size else 0
    defs: Dict = {"final_norm": lyr.rmsnorm_def(cfg.d_model)}
    if cfg.family in ("dense", "moe", "vlm"):
        defs["embed"] = lyr.embed_defs(cfg, v_pad)
        k = cfg.first_dense_layers
        if k:
            defs["dense"] = stack_defs(_block_defs(cfg, dense=True), k)
        defs["blocks"] = stack_defs(_block_defs(cfg), cfg.n_layers - k)
    elif cfg.family == "ssm":
        defs["embed"] = lyr.embed_defs(cfg, v_pad)
        defs["blocks"] = stack_defs(_block_defs(cfg, ssm=True), cfg.n_layers)
    elif cfg.family == "hybrid":
        defs["embed"] = lyr.embed_defs(cfg, v_pad)
        defs["blocks"] = stack_defs(_block_defs(cfg, ssm=True), cfg.n_layers)
        defs["shared"] = _block_defs(cfg)          # weight-tied attn block
    elif cfg.family in ("encdec", "audio"):
        defs["embed"] = lyr.embed_defs(cfg, v_pad)
        defs["enc_blocks"] = stack_defs(_block_defs(cfg),
                                        cfg.n_encoder_layers)
        defs["enc_norm"] = lyr.rmsnorm_def(cfg.d_model)
        defs["blocks"] = stack_defs(_block_defs(cfg, cross=True),
                                    cfg.n_layers)
    elif cfg.family == "encoder":
        defs["pos_embed"] = ParamDef((cfg.frontend_tokens, cfg.d_model),
                                     (None, "embed"), init="embed")
        defs["blocks"] = stack_defs(_block_defs(cfg), cfg.n_layers)
        defs["head"] = ParamDef((cfg.d_model, cfg.n_classes),
                                ("embed", "classes"))
    else:
        raise ValueError(cfg.family)
    return defs


# ---------------------------------------------------------------------------
# Stacks (full-sequence)
# ---------------------------------------------------------------------------

def _attn_block(lp: Dict, x: jax.Array, cfg: ModelConfig, positions,
                *, causal: bool, window: int = 0, enc_out=None,
                use_rope: bool = True, return_kv: bool = False):
    h = lyr.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        if return_kv or not causal or window:
            raise NotImplementedError(
                "latent attention runs causal over the full sequence only")
        a = lyr.mla_attention(lp["attn"], h, cfg, positions=positions)
    else:
        a = lyr.attention(lp["attn"], h, cfg, positions=positions,
                          causal=causal, window=window, use_rope=use_rope,
                          return_kv=return_kv)
    if return_kv:
        a, k, v = a
    x = x + a
    if "cross" in lp:
        h = lyr.rmsnorm(x, lp["lnc"], cfg.norm_eps)
        c = lyr.attention(lp["cross"], h, cfg, positions=positions,
                          causal=False, kv_x=enc_out,
                          kv_positions=jnp.arange(enc_out.shape[1]),
                          use_rope=False)
        x = x + c
    h = lyr.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if "moe" in lp:
        f, aux = moe_mod.moe_ffn(lp["moe"], h, cfg)
    else:
        f, aux = lyr.mlp(lp["mlp"], h), jnp.zeros((), F32)
    x = shard(x + f, "batch", "act_seq", "act_embed")
    if return_kv:
        return x, aux, k, v
    return x, aux


def _ssm_block(lp: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = lyr.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    from repro.distributed.sharding import current_rules
    rules = current_rules()
    if (rules.enabled and rules.mesh is not None
            and rules.mapping.get("act_seq") == "model"
            and cfg.family == "ssm"):
        from repro.models.ssm_sp import ssm_block_seq_parallel
        y = ssm_block_seq_parallel(
            lp["ssm"], h, cfg, rules.mesh,
            batch_axes=rules.batch_axes or ("data",))
        return x + y
    return x + ssm_mod.ssm_block(lp["ssm"], h, cfg)


def _scan_blocks(blocks, x, body, remat: str, aux=None):
    if remat != "none":
        body = jax.checkpoint(body)

    def wrapped(carry, lp):
        return body(carry, lp), None

    if aux is None:
        aux = jnp.zeros((), F32)
    (x, aux), _ = jax.lax.scan(wrapped, (x, aux), blocks)
    return x, aux


def _aux_zero(stack: Dict):
    """The sum the decoder carries over ``stack``'s layers: each MoE
    layer's ``{"loss", "held", "load", "rows"}``, else a zero loss."""
    if "moe" in stack:
        return {k: jnp.zeros((), F32)
                for k in ("loss", "held", "load", "rows")}
    return jnp.zeros((), F32)


def _add_aux(a, b):
    return jax.tree.map(jnp.add, a, b)


def run_decoder(params, x, cfg: ModelConfig, positions, *,
                causal: bool = True, window: int = 0, enc_out=None,
                use_rope: bool = True, remat: str = "none"):
    """Run the main block stack. Returns (x, aux_loss)."""
    if cfg.family in ("ssm",):
        def body(carry, lp):
            h, aux = carry
            return (_ssm_block(lp, h, cfg), aux)
        return _scan_blocks(params["blocks"], x, body, remat)

    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        sites = cfg.n_layers // k if k else 0
        aux_total = jnp.zeros((), F32)

        def body(carry, lp):
            h, aux = carry
            return (_ssm_block(lp, h, cfg), aux)

        done = 0
        for s in range(sites):
            grp = jax.tree.map(lambda a: a[s * k:(s + 1) * k],
                               params["blocks"])
            x, _ = _scan_blocks(grp, x, body, remat)
            x, aux = _attn_block(params["shared"], x, cfg, positions,
                                 causal=True, window=cfg.attn_window)
            aux_total = aux_total + aux
            done += k
        if done < cfg.n_layers:
            grp = jax.tree.map(lambda a: a[done:], params["blocks"])
            x, _ = _scan_blocks(grp, x, body, remat)
        return x, aux_total

    def body(carry, lp):
        h, aux = carry
        h, a = _attn_block(lp, h, cfg, positions, causal=causal,
                           window=window, enc_out=enc_out,
                           use_rope=use_rope)
        return (h, _add_aux(aux, a))

    if "dense" in params:
        x, _ = _scan_blocks(params["dense"], x, body, remat)
    return _scan_blocks(params["blocks"], x, body, remat,
                        _aux_zero(params["blocks"]))


def run_encoder(params, src: jax.Array, cfg: ModelConfig,
                remat: str = "none"):
    """Bidirectional encoder over frame embeddings (encdec families)."""
    positions = jnp.arange(src.shape[1])

    def body(carry, lp):
        h, aux = carry
        h, a = _attn_block(lp, h, cfg, positions, causal=False)
        return (h, aux + a)

    x, aux = _scan_blocks(params["enc_blocks"], src, body, remat)
    return lyr.rmsnorm(x, params["enc_norm"], cfg.norm_eps), aux


# ---------------------------------------------------------------------------
# Forward passes (train / prefill)
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, batch: Dict, *,
            remat: str = "none") -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits, aux_loss).

    batch keys by family:
      dense/moe/ssm/hybrid: tokens (B,S)
      vlm:    tokens (B,S-P) + patch_embeds (B,P,D)
      encdec: src_embeds (B,S_src,D) + tokens (B,S)
      encoder: patch_embeds (B,T,D)  -> returns class logits (B,n_classes)
    """
    if cfg.family == "encoder":
        x = batch["patch_embeds"].astype(jnp.bfloat16) + params["pos_embed"]
        x = shard(x, "batch", "act_seq", "act_embed")
        positions = jnp.arange(x.shape[1])

        def body(carry, lp):
            h, aux = carry
            h, a = _attn_block(lp, h, cfg, positions, causal=False,
                               use_rope=False)
            return (h, aux + a)

        x, aux = _scan_blocks(params["blocks"], x, body, remat)
        x = lyr.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dc->bc", x[:, 0], params["head"])
        return logits, aux

    enc_out = None
    if cfg.family in ("encdec", "audio"):
        enc_out, _ = run_encoder(params, batch["src_embeds"].astype(
            jnp.bfloat16), cfg, remat)

    x = lyr.embed(params["embed"], batch["tokens"])
    if cfg.family == "vlm":
        pe = batch["patch_embeds"].astype(x.dtype)
        pe = shard(pe, "batch", "act_seq", "act_embed")
        x = jnp.concatenate([pe, x], axis=1)
    positions = jnp.arange(x.shape[1])
    x, aux = run_decoder(params, x, cfg, positions, causal=True,
                         window=0, enc_out=enc_out, remat=remat)
    x = lyr.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = lyr.logits(params["embed"], x)
    return logits, aux


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab_size: int) -> jax.Array:
    """Masked CE over a padded vocab. labels < 0 are ignored."""
    v_pad = logits.shape[-1]
    lf = logits.astype(F32)
    if vocab_size and v_pad > vocab_size:
        pad_mask = jnp.arange(v_pad) >= vocab_size
        lf = jnp.where(pad_mask, -1e30, lf)
    lse = jax.nn.logsumexp(lf, axis=-1)
    tgt = jnp.take_along_axis(
        lf, jnp.clip(labels, 0, v_pad - 1)[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    mask = (labels >= 0).astype(F32)
    return jnp.sum(nll * mask) / jnp.clip(jnp.sum(mask), 1.0)


def loss_fn(params, cfg: ModelConfig, batch: Dict, *,
            remat: str = "none") -> jax.Array:
    return loss_and_stats(params, cfg, batch, remat=remat)[0]


def loss_and_stats(params, cfg: ModelConfig, batch: Dict, *,
                   remat: str = "none") -> Tuple[jax.Array, Dict]:
    """(loss, step counters).  MoE configs count, as means over their MoE
    layers, ``moe_held_share`` (the share of the assignments that land
    on the experts a layer holds), ``moe_load_max_over_mean`` (the
    busiest held expert's assignments over the held experts' mean) and
    ``moe_rows_share`` (the assignment rows the experts' part of a layer
    moved, over all of them: the held share rounded up to whole chunks
    of the held-expert loop, 1 for a capacity dispatch); other configs
    count nothing."""
    logits, aux = forward(params, cfg, batch, remat=remat)
    if cfg.family == "encoder":
        lbl = batch["labels"]
        ce = cross_entropy(logits[:, None, :], lbl[:, None], cfg.n_classes)
        return ce + aux, {}
    ce = cross_entropy(logits, batch["labels"], cfg.vocab_size)
    if cfg.moe is None:
        return ce + aux, {}
    n_moe = cfg.n_layers - cfg.first_dense_layers
    return ce + aux["loss"], {"moe_held_share": aux["held"] / n_moe,
                              "moe_load_max_over_mean": aux["load"] / n_moe,
                              "moe_rows_share": aux["rows"] / n_moe}


# ---------------------------------------------------------------------------
# KV / state caches + decode
# ---------------------------------------------------------------------------

def cache_defs(cfg: ModelConfig, B: int, s_max: int) -> Dict:
    """Decode-state ParamDefs (init=zeros; reuses the ParamDef machinery
    so abstract shapes and PartitionSpecs come for free)."""
    if cfg.mla is not None:
        raise NotImplementedError("no decode cache for latent attention")
    hd = cfg.resolved_head_dim
    K = cfg.n_kv_heads
    L = cfg.n_layers
    bf16, f32 = jnp.bfloat16, jnp.float32
    kv_axes = ("layers", "batch", "kv_seq", "act_kv", None)

    if cfg.family in ("dense", "moe", "vlm"):
        return {
            "k": ParamDef((L, B, s_max, K, hd), kv_axes, "zeros", dtype=bf16),
            "v": ParamDef((L, B, s_max, K, hd), kv_axes, "zeros", dtype=bf16),
        }
    if cfg.family == "ssm":
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        nh = d_in // s.head_dim
        return {
            "h": ParamDef((L, B, nh, s.head_dim, s.d_state),
                          ("layers", "batch", "act_inner", None, None),
                          "zeros", dtype=f32),
            "conv": ParamDef((L, B, s.d_conv - 1, d_in + 2 * s.d_state),
                             ("layers", "batch", None, None), "zeros",
                             dtype=bf16),
        }
    if cfg.family == "hybrid":
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        nh = d_in // s.head_dim
        sites = cfg.n_layers // cfg.hybrid_attn_every
        W = min(s_max, cfg.attn_window or s_max)
        return {
            "h": ParamDef((L, B, nh, s.head_dim, s.d_state),
                          ("layers", "batch", "act_inner", None, None),
                          "zeros", dtype=f32),
            "conv": ParamDef((L, B, s.d_conv - 1, d_in + 2 * s.d_state),
                             ("layers", "batch", None, None), "zeros",
                             dtype=bf16),
            "ak": ParamDef((sites, B, W, K, hd), kv_axes, "zeros", dtype=bf16),
            "av": ParamDef((sites, B, W, K, hd), kv_axes, "zeros", dtype=bf16),
        }
    if cfg.family in ("encdec", "audio"):
        s_src = encdec_src_len(s_max)
        return {
            "k": ParamDef((L, B, s_max, K, hd), kv_axes, "zeros", dtype=bf16),
            "v": ParamDef((L, B, s_max, K, hd), kv_axes, "zeros", dtype=bf16),
            "ck": ParamDef((L, B, s_src, K, hd), kv_axes, "zeros", dtype=bf16),
            "cv": ParamDef((L, B, s_src, K, hd), kv_axes, "zeros", dtype=bf16),
        }
    raise ValueError(f"no decode cache for family {cfg.family}")


def encdec_src_len(seq_len: int) -> int:
    """Audio frames entering the encoder (8x downsampled frontend)."""
    return max(seq_len // 8, 16)


def _decode_attn_block(lp, x, cfg, ck, cv, index, *, window=0,
                       cross_kv=None):
    h = lyr.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    if window:
        a, ck, cv = _attention_decode_window(lp["attn"], h, cfg, ck, cv,
                                             index, window)
    else:
        a, ck, cv = lyr.attention_decode(lp["attn"], h, cfg, cache_k=ck,
                                         cache_v=cv, index=index)
    x = x + a
    if cross_kv is not None:
        hq = lyr.rmsnorm(x, lp["lnc"], cfg.norm_eps)
        x = x + _cross_attention_cached(lp["cross"], hq, cfg, *cross_kv)
    h = lyr.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if "moe" in lp:
        f, _ = moe_mod.moe_ffn(lp["moe"], h, cfg)
    else:
        f = lyr.mlp(lp["mlp"], h)
    return x + f, ck, cv


def _cross_attention_cached(p, x, cfg, ck, cv):
    """Decode-time cross attention against precomputed encoder KV."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, 1, cfg.n_heads, hd)
    out = lyr._sdpa(q, ck, cv, None, cfg)
    out = out.reshape(B, 1, cfg.n_heads * hd)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


def _attention_decode_window(p, x, cfg, ck, cv, index, window):
    """Ring-buffer windowed decode: slot = index % W; positions derivable."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    pos = jnp.full((1,), index, dtype=jnp.int32)
    q, k, v = lyr._project_qkv(p, x, x, cfg, pos, pos)
    W = ck.shape[1]
    slot = jnp.mod(index, W)
    ck = jax.lax.dynamic_update_slice_in_dim(ck, k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, v, slot, axis=1)
    j = jnp.arange(W)
    slot_pos = index - jnp.mod(index - j, W)     # absolute pos stored in slot
    mask = (slot_pos >= 0)[None, None, None, None, :]
    out = lyr._sdpa(q, ck, cv, mask, cfg)
    out = out.reshape(B, 1, cfg.n_heads * hd)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return y, ck, cv


def decode_step(params, cfg: ModelConfig, cache: Dict, tokens: jax.Array,
                index: jax.Array) -> Tuple[jax.Array, Dict]:
    """One-token decode. tokens: (B,1) int32; index: scalar position.

    Returns (logits (B,1,V), new cache).
    """
    x = lyr.embed(params["embed"], tokens)

    if cfg.family in ("dense", "moe", "vlm", "encdec", "audio"):
        cross = cfg.family in ("encdec", "audio")

        def body(x, inp):
            if cross:
                lp, ck, cv, cck, ccv = inp
                x, ck, cv = _decode_attn_block(lp, x, cfg, ck, cv, index,
                                               cross_kv=(cck, ccv))
                return x, (ck, cv, cck, ccv)
            lp, ck, cv = inp
            x, ck, cv = _decode_attn_block(lp, x, cfg, ck, cv, index)
            return x, (ck, cv)

        xs = (params["blocks"], cache["k"], cache["v"])
        if cross:
            xs = xs + (cache["ck"], cache["cv"])
        x, outs = jax.lax.scan(body, x, xs)
        new_cache = dict(cache)
        new_cache["k"], new_cache["v"] = outs[0], outs[1]
        if cross:
            new_cache["ck"], new_cache["cv"] = outs[2], outs[3]

    elif cfg.family == "ssm":
        def body(x, inp):
            lp, h, conv = inp
            hh = lyr.rmsnorm(x, lp["ln1"], cfg.norm_eps)
            y, h, conv = ssm_mod.ssm_decode_step(lp["ssm"], hh, cfg, h, conv)
            return x + y, (h, conv)

        x, (hs, convs) = jax.lax.scan(
            body, x, (params["blocks"], cache["h"], cache["conv"]))
        new_cache = {"h": hs, "conv": convs}

    elif cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        sites = cfg.n_layers // k
        hs_out, conv_out, ak_out, av_out = [], [], [], []

        def body(x, inp):
            lp, h, conv = inp
            hh = lyr.rmsnorm(x, lp["ln1"], cfg.norm_eps)
            y, h, conv = ssm_mod.ssm_decode_step(lp["ssm"], hh, cfg, h, conv)
            return x + y, (h, conv)

        done = 0
        for s in range(sites):
            sl = lambda a: a[s * k:(s + 1) * k]
            x, (hs, convs) = jax.lax.scan(
                body, x, (jax.tree.map(sl, params["blocks"]),
                          sl(cache["h"]), sl(cache["conv"])))
            hs_out.append(hs)
            conv_out.append(convs)
            x, ak, av = _decode_attn_block(
                params["shared"], x, cfg, cache["ak"][s], cache["av"][s],
                index, window=cache["ak"].shape[2])
            ak_out.append(ak)
            av_out.append(av)
            done += k
        if done < cfg.n_layers:
            sl = lambda a: a[done:]
            x, (hs, convs) = jax.lax.scan(
                body, x, (jax.tree.map(sl, params["blocks"]),
                          sl(cache["h"]), sl(cache["conv"])))
            hs_out.append(hs)
            conv_out.append(convs)
        new_cache = {
            "h": jnp.concatenate(hs_out, 0),
            "conv": jnp.concatenate(conv_out, 0),
            "ak": jnp.stack(ak_out, 0),
            "av": jnp.stack(av_out, 0),
        }
    else:
        raise ValueError(cfg.family)

    x = lyr.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = lyr.logits(params["embed"], x)
    return logits, new_cache


def prefill(params, cfg: ModelConfig, batch: Dict, cache: Dict,
            *, remat: str = "none") -> Tuple[jax.Array, Dict]:
    """Prefill: single forward pass that also populates the decode cache."""
    if cfg.family in ("ssm", "hybrid"):
        # recurrent state prefill lives in serve/step.py (uses
        # ssm_block(return_state=True)); logits come from plain forward.
        logits, _ = forward(params, cfg, batch, remat=remat)
        return logits, cache

    x = lyr.embed(params["embed"], batch["tokens"])
    if cfg.family == "vlm":
        pe = batch["patch_embeds"].astype(x.dtype)
        pe = shard(pe, "batch", "act_seq", "act_embed")
        x = jnp.concatenate([pe, x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S)
    enc_out = None
    if cfg.family in ("encdec", "audio"):
        enc_out, _ = run_encoder(params, batch["src_embeds"].astype(
            jnp.bfloat16), cfg, remat)

    cross = cfg.family in ("encdec", "audio")

    def body(carry, lp):
        x, aux = carry
        x, a, k, v = _attn_block(lp, x, cfg, positions, causal=True,
                                 enc_out=enc_out, return_kv=True)
        aux = _add_aux(aux, a)
        outs = (k, v)
        if cross:
            h = enc_out
            B, Ss = h.shape[0], h.shape[1]
            hd = cfg.resolved_head_dim
            kc = jnp.einsum("bsd,dh->bsh", h, lp["cross"]["wk"])
            vc = jnp.einsum("bsd,dh->bsh", h, lp["cross"]["wv"])
            outs = outs + (kc.reshape(B, Ss, cfg.n_kv_heads, hd),
                           vc.reshape(B, Ss, cfg.n_kv_heads, hd))
        return (x, aux), outs

    if remat != "none":
        body = jax.checkpoint(body)
    (x, _), outs = jax.lax.scan(body, (x, _aux_zero(params["blocks"])),
                                params["blocks"])
    x = lyr.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = lyr.logits(params["embed"], x)

    new_cache = dict(cache)
    s_max = cache["k"].shape[2]
    pad = [(0, 0), (0, 0), (0, s_max - S), (0, 0), (0, 0)]
    new_cache["k"] = jnp.pad(outs[0], pad).astype(cache["k"].dtype)
    new_cache["v"] = jnp.pad(outs[1], pad).astype(cache["v"].dtype)
    if cross:
        new_cache["ck"] = outs[2].astype(cache["ck"].dtype)
        new_cache["cv"] = outs[3].astype(cache["cv"].dtype)
    return logits, new_cache
