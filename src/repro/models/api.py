"""Unified model factory + abstract input specs for every assigned arch.

``build_model(cfg)`` returns an object exposing:
  init(rng) / init_abstract()
  loss_fn(params, batch)                      -- train shapes
  prefill(params, batch, n_gen) -> (logits, cache)  -- prefill shapes;
                                              the KV caches hold n_gen more
  decode_step(params, cache, tokens, pos)     -- decode shapes
  init_cache(batch, seq_len)

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
model input (weak-type-correct, shardable, no device allocation) — the
dry-run lowers against these.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig


def build_model(cfg: ModelConfig, *, remat: str = "full",
                kv_block: int = 512, seq_chunk: int = 2048):
    if cfg.family in ("dense", "moe", "vlm"):
        from repro.models.transformer import Transformer
        return Transformer(cfg, remat=remat, kv_block=kv_block,
                           seq_chunk=seq_chunk)
    if cfg.family == "audio":
        from repro.models.whisper import Whisper
        return Whisper(cfg, remat=remat, kv_block=kv_block,
                       seq_chunk=seq_chunk)
    if cfg.family == "ssm":
        from repro.models.xlstm import XLSTM
        return XLSTM(cfg, remat=remat, seq_chunk=seq_chunk)
    if cfg.family == "hybrid":
        from repro.models.zamba import Zamba
        return Zamba(cfg, remat=remat, kv_block=kv_block,
                     seq_chunk=seq_chunk)
    raise ValueError(f"unknown family {cfg.family!r}")


@functools.lru_cache(maxsize=None)
def _abstract_params(cfg: ModelConfig):
    return build_model(cfg).init_abstract()


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact parameter count from abstract shapes (no allocation).

    active_only: MoE experts contribute only top_k/E of their weights
    (the 6*N_active*D roofline convention).
    """
    abstract = _abstract_params(cfg)
    total = 0
    expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract)[0]:
        n = int(np.prod(leaf.shape))
        total += n
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        if cfg.n_experts and "ffn" in keys and any(
                k in ("wi", "wg", "wo") for k in keys):
            expert += n
    if active_only and cfg.n_experts:
        frac = cfg.n_experts_per_tok / cfg.n_experts
        return int(total - expert + expert * frac)
    return total


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStructs for one (arch x input-shape) cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.dtype(cfg.dtype)
    tok = jax.ShapeDtypeStruct((b, s), i32)

    def extras():
        e = {}
        if cfg.family == "audio":
            e["frames"] = jax.ShapeDtypeStruct((b, cfg.n_frames, cfg.d_model),
                                               bf16)
        if cfg.family == "vlm":
            e["image_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_image_tokens, cfg.d_model), bf16)
        return e

    if shape.kind == "train":
        return {"tokens": tok, "labels": jax.ShapeDtypeStruct((b, s), i32),
                **extras()}
    if shape.kind == "prefill":
        return {"tokens": tok, **extras()}
    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32),
                "pos": jax.ShapeDtypeStruct((b, 1), i32)}
    raise ValueError(shape.kind)


def abstract_cache(cfg: ModelConfig, shape: ShapeConfig):
    """Abstract KV-cache / recurrent-state pytree for decode lowering."""
    model = build_model(cfg)
    return jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))


def abstract_state(cfg: ModelConfig):
    return _abstract_params(cfg)
