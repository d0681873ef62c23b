"""Architecture registry: ``--arch <id>`` resolution + default parallelism.

``get(arch_id)`` returns the full ModelConfig; ``get_reduced(arch_id)`` the
smoke-test config; ``default_parallelism(model, shape)`` encodes the layout
policy used by the dry-run and launchers (overridable from the CLI).
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

from repro.configs.base import (
    ALL_SHAPES, ModelConfig, ParallelismConfig, ShapeConfig, shape_applicable,
)

_MODULES: Dict[str, str] = {
    "seamless-m4t-large-v2": "repro.configs.seamless_m4t_large_v2",
    "qwen1.5-32b": "repro.configs.qwen1_5_32b",
    "llama3-405b": "repro.configs.llama3_405b",
    "qwen3-8b": "repro.configs.qwen3_8b",
    "deepseek-7b": "repro.configs.deepseek_7b",
    "deepseek-moe-16b": "repro.configs.deepseek_moe_16b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t_a32b",
    "internvl2-2b": "repro.configs.internvl2_2b",
    "kimi-vl-a3b": "repro.configs.kimi_vl_a3b",
    "zamba2-1.2b": "repro.configs.zamba2_1_2b",
    "mamba2-1.3b": "repro.configs.mamba2_1_3b",
    "vit-huge": "repro.configs.vit_huge",
}

ASSIGNED_ARCHS: Tuple[str, ...] = tuple(k for k in _MODULES if k != "vit-huge")


def list_archs() -> List[str]:
    return list(_MODULES)


def get(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[arch_id]).CONFIG


def get_reduced(arch_id: str) -> ModelConfig:
    return importlib.import_module(_MODULES[arch_id]).reduced()


def cells(arch_ids=None) -> List[Tuple[ModelConfig, ShapeConfig, bool, str]]:
    """All (arch x shape) cells with applicability flags (44 for the 11)."""
    out = []
    for aid in (arch_ids or ASSIGNED_ARCHS):
        m = get(aid)
        for s in ALL_SHAPES:
            ok, why = shape_applicable(m, s)
            out.append((m, s, ok, why))
    return out


# ---------------------------------------------------------------------------
# Default layout policy
# ---------------------------------------------------------------------------

# Archs whose param+optimizer footprint forces FSDP (ZeRO-style sharding of
# params/grads/opt-state over the 'data' axis) on a 16 GB/chip pod.
_FSDP_ARCHS = {"llama3-405b", "kimi-k2-1t-a32b", "qwen1.5-32b"}
# 8-bit optimizer state for the 1T arch (see DESIGN.md memory budget).
_OPT8_ARCHS = {"kimi-k2-1t-a32b"}


# Small archs whose 16-way TP is collective-bound at train_4k: the measured
# §Perf iterations (internvl2 0.09->0.63, mamba2 0.18->0.43) show pure-DP
# (batch over both axes, params replicated) removes the per-layer activation
# reductions.  Applied to the <=2.5B archs whose replicated params fit.
_PURE_DP_TRAIN = {"internvl2-2b", "mamba2-1.3b", "zamba2-1.2b",
                  "seamless-m4t-large-v2"}


def default_parallelism(model: ModelConfig, shape: ShapeConfig) -> ParallelismConfig:
    p = ParallelismConfig()
    if model.moe is not None:
        p = p.replace(ep=True)
    if shape.is_train:
        if model.name in _FSDP_ARCHS:
            p = p.replace(fsdp=True, remat="block", microbatches=4)
        if model.name in _OPT8_ARCHS:
            # §Perf kimi iterations: microbatches=1 avoids re-gathering
            # FSDP shards per microbatch; int8 moments use the structured
            # block layout (train/optimizer.py) so they inherit param specs
            p = p.replace(opt_state_dtype="int8", microbatches=1)
        elif model.name in _FSDP_ARCHS:
            p = p.replace(opt_state_dtype="bfloat16")
        if model.name in _PURE_DP_TRAIN and \
                shape.global_batch % 256 == 0:
            p = p.replace(tp=False, dp_over_model=True)
    else:
        # inference: no optimizer, no remat; batch=1 long decode replicates
        # data axis and uses sequence-parallel state sharding where possible.
        p = p.replace(remat="none", microbatches=1)
        if shape.name == "long_500k":
            p = p.replace(sp=True)
        if shape.name == "prefill_32k":
            p = p.replace(sp=True)   # sequence-shard activations for prefill
        if shape.kind == "prefill" and model.family == "ssm":
            # §Perf: sequence-parallel SSD replaces per-layer TP reductions
            # with ~4 MB state hand-offs (models/ssm_sp.py)
            p = p.replace(tp=False, sp_ssd=True)
    return p
