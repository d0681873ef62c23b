"""Public model API: build(cfg) -> Model with init/loss/forward/decode +
``input_specs`` ShapeDtypeStruct stand-ins for the dry-run.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tfm
from repro.models.params import (ParamDef, abstract_params, init_params,
                                 param_count, partition_specs)

SDS = jax.ShapeDtypeStruct


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ---- params ----
    def param_defs(self) -> Dict:
        return tfm.param_defs(self.cfg)

    def init(self, rng: jax.Array, dtype=jnp.bfloat16) -> Dict:
        return init_params(rng, self.param_defs(), dtype)

    def abstract(self, dtype=jnp.bfloat16) -> Dict:
        return abstract_params(self.param_defs(), dtype)

    def n_params(self) -> int:
        return param_count(self.param_defs())

    # ---- compute ----
    def loss(self, params, batch, *, remat: str = "none") -> jax.Array:
        return tfm.loss_fn(params, self.cfg, batch, remat=remat)

    def loss_and_stats(self, params, batch, *, remat: str = "none"):
        """(loss, step counters); see ``transformer.loss_and_stats``."""
        return tfm.loss_and_stats(params, self.cfg, batch, remat=remat)

    def forward(self, params, batch, *, remat: str = "none"):
        return tfm.forward(params, self.cfg, batch, remat=remat)

    def prefill(self, params, batch, cache, *, remat: str = "none"):
        return tfm.prefill(params, self.cfg, batch, cache, remat=remat)

    def decode_step(self, params, cache, tokens, index):
        return tfm.decode_step(params, self.cfg, cache, tokens, index)

    # ---- caches ----
    def cache_defs(self, batch: int, s_max: int) -> Dict:
        return tfm.cache_defs(self.cfg, batch, s_max)

    def init_cache(self, batch: int, s_max: int) -> Dict:
        return init_params(jax.random.key(0), self.cache_defs(batch, s_max))

    def abstract_cache(self, batch: int, s_max: int) -> Dict:
        return abstract_params(self.cache_defs(batch, s_max))

    # ---- dry-run inputs ----
    def input_specs(self, shape: ShapeConfig) -> Dict[str, SDS]:
        """ShapeDtypeStruct stand-ins for every model input of a cell.

        train/prefill: the full-sequence batch.  decode: one new token
        (the KV cache is a separate argument; see abstract_cache).
        Modality frontends are stubs — [audio]/[vlm] specs contain
        precomputed frame/patch embeddings (DESIGN.md §2).
        """
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32, bf16 = jnp.int32, jnp.bfloat16
        if shape.kind == "decode":
            return {"tokens": SDS((B, 1), i32)}
        if cfg.family == "encoder":
            spec = {"patch_embeds": SDS((B, cfg.frontend_tokens,
                                         cfg.d_model), bf16)}
            if shape.is_train:
                spec["labels"] = SDS((B,), i32)
            return spec
        if cfg.family == "vlm":
            p = cfg.frontend_tokens
            spec = {"tokens": SDS((B, S - p), i32),
                    "patch_embeds": SDS((B, p, cfg.d_model), bf16)}
            if shape.is_train:
                spec["labels"] = SDS((B, S), i32)
            return spec
        if cfg.family in ("encdec", "audio"):
            s_src = tfm.encdec_src_len(S)
            spec = {"tokens": SDS((B, S), i32),
                    "src_embeds": SDS((B, s_src, cfg.d_model), bf16)}
            if shape.is_train:
                spec["labels"] = SDS((B, S), i32)
            return spec
        spec = {"tokens": SDS((B, S), i32)}
        if shape.is_train:
            spec["labels"] = SDS((B, S), i32)
        return spec

    def batch_logical_axes(self, shape: ShapeConfig) -> Dict[str, Tuple]:
        """Logical sharding axes for each input (feeds in_shardings)."""
        cfg = self.cfg
        out: Dict[str, Tuple] = {}
        for name in self.input_specs(shape):
            if name in ("tokens", "labels"):
                if cfg.family == "encoder" and name == "labels":
                    out[name] = ("batch",)
                else:
                    out[name] = ("batch", "act_seq")
            elif name in ("patch_embeds", "src_embeds"):
                out[name] = ("batch", None, "act_embed")
        return out


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)


def make_batch(rng, model: Model, shape: ShapeConfig,
               reduced_shape: Optional[Tuple[int, int]] = None) -> Dict:
    """Random concrete batch matching input_specs (smoke tests/examples)."""
    cfg = model.cfg
    specs = model.input_specs(shape)
    if reduced_shape is not None:
        B, S = reduced_shape
        full = model.input_specs(shape)
        specs = {}
        for k, v in full.items():
            dims = list(v.shape)
            dims[0] = B
            if k in ("tokens", "labels") and len(dims) > 1 and \
                    cfg.family != "encoder":
                dims[1] = (S - cfg.frontend_tokens
                           if cfg.family == "vlm" and k == "tokens" else S)
            if k == "src_embeds":
                dims[1] = tfm.encdec_src_len(S)
            specs[k] = SDS(tuple(dims), v.dtype)
    batch = {}
    for k, v in specs.items():
        rng, sub = jax.random.split(rng)
        if v.dtype == jnp.int32:
            hi = cfg.n_classes if (cfg.family == "encoder" and k == "labels") \
                else cfg.vocab_size
            batch[k] = jax.random.randint(sub, v.shape, 0, hi, jnp.int32)
        else:
            batch[k] = jax.random.normal(sub, v.shape, jnp.float32).astype(
                v.dtype)
    return batch
