"""Architecture registry: ``--arch <id>`` resolution + dry-run input specs.

``get_config(name)`` returns the full published config;
``get_config(name, reduced=True)`` the reduced smoke-test variant;
further keyword arguments replace fields, a dict those of a nested
config (``moe={"held_experts": 8}``).
``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
model input of that (arch x shape) cell — weak-type-correct, shardable,
no device allocation.  Decode shapes also need cache specs, built with
``jax.eval_shape`` over ``model.init_caches`` (still allocation-free).
"""
from __future__ import annotations

import importlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (InputShape, ModelConfig, SHAPES,
                                shape_applicable)

_MODULES = {
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "smollm-135m": "smollm_135m",
    "qwen3-0.6b": "qwen3_0_6b",
    "olmo-1b": "olmo_1b",
    "gemma2-27b": "gemma2_27b",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "whisper-tiny": "whisper_tiny",
    "xlstm-1.3b": "xlstm_1_3b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
}

ARCH_NAMES = tuple(_MODULES)


def get_config(name: str, reduced: bool = False, **overrides) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_NAMES}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    cfg = mod.REDUCED if reduced else mod.CONFIG
    if overrides:
        import dataclasses
        # a dict overrides fields of a nested config (moe, mla, ...)
        overrides = {k: dataclasses.replace(getattr(cfg, k), **v)
                     if isinstance(v, dict) else v
                     for k, v in overrides.items()}
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def patch_len(cfg: ModelConfig, seq: int) -> int:
    return int(seq * cfg.patch_frac)


def input_specs(cfg: ModelConfig, shape: InputShape,
                max_len: Optional[int] = None) -> Dict[str, object]:
    """ShapeDtypeStruct batch for one (arch x shape) cell.

    train/prefill: token batch (+ frontend stubs).
    decode: one new token + cur_len; caches are produced separately by
    ``cache_specs`` (they are carried state, not part of the batch).
    """
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        batch = {"tokens": _sds((b, 1), jnp.int32)}
        if cfg.is_encoder_decoder:
            pass  # cross-attention K/V live in the cache
        return batch

    batch = {"tokens": _sds((b, s), jnp.int32)}
    if shape.kind == "train":
        batch["targets"] = _sds((b, s), jnp.int32)
    if cfg.family == "vlm":
        batch["patch_embeds"] = _sds((b, patch_len(cfg, s), cfg.d_model),
                                     jnp.bfloat16)
    if cfg.is_encoder_decoder:
        batch["frame_embeds"] = _sds((b, cfg.enc_len, cfg.d_model),
                                     jnp.bfloat16)
    return batch


def cache_specs(cfg: ModelConfig, shape: InputShape):
    """ShapeDtypeStruct tree for decode caches of size ``shape.seq_len``."""
    from repro.models import model as model_mod
    return jax.eval_shape(
        lambda: model_mod.init_caches(cfg, shape.global_batch,
                                      shape.seq_len))


__all__ = ["ARCH_NAMES", "get_config", "input_specs", "cache_specs",
           "SHAPES", "shape_applicable", "ModelConfig", "patch_len"]
