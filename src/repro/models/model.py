"""Unified model API — dispatch on cfg.family.

    api = get_model(cfg)
    params = api.init(rng)
    loss, metrics = api.loss(params, batch)
    cache = api.init_cache(batch_size, max_len)
    logits, cache = api.decode(params, token, cache)

``input_specs(cfg, shape)`` builds ShapeDtypeStruct stand-ins for the dry-run
(never allocates). Modality frontends are stubs: whisper takes precomputed
frame embeddings; chameleon takes unified text+VQ token ids.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import deepseek, encdec, hybrid, ssm_lm, transformer


@dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    loss: Callable[..., Any]
    init_cache: Callable[..., Any]
    decode: Callable[..., Any]
    prefill: Optional[Callable[..., Any]] = None
    # (params, tokens [B, S], last) -> (logits [B, V] f32 at ``last``, the
    # rows of each paged-cache pool [L, B, S, *row], routed ids or None)
    paged_prefill: Optional[Callable[..., Any]] = None


def get_model(cfg: ModelConfig) -> ModelAPI:
    fam = cfg.family
    if cfg.is_mla:  # DeepSeek-V3 block: latent attention, held-expert MoE
        return ModelAPI(
            cfg=cfg,
            init=lambda rng: deepseek.init_params(rng, cfg),
            loss=lambda p, b, **kw: deepseek.loss_fn(p, b, cfg, **kw),
            init_cache=lambda bs, ml, **kw: deepseek.init_cache(cfg, bs, ml, **kw),
            decode=lambda p, t, c: deepseek.decode_step(p, t, c, cfg),
            prefill=lambda p, t, ml: deepseek.prefill_cache(p, t, cfg, ml),
            paged_prefill=lambda p, t, last: deepseek.prefill(p, t, last, cfg),
        )
    if fam in ("dense", "moe", "vlm"):
        return ModelAPI(
            cfg=cfg,
            init=lambda rng: transformer.init_params(rng, cfg),
            loss=lambda p, b, **kw: transformer.loss_fn(p, b, cfg, **kw),
            init_cache=lambda bs, ml, **kw: transformer.init_kv_cache(cfg, bs, ml, **kw),
            decode=lambda p, t, c: transformer.decode_step(p, t, c, cfg),
            prefill=lambda p, t, ml: transformer.prefill(p, t, cfg, ml),
            paged_prefill=lambda p, t, last: transformer.paged_prefill(p, t, last, cfg),
        )
    if fam == "ssm":
        return ModelAPI(
            cfg=cfg,
            init=lambda rng: ssm_lm.init_params(rng, cfg),
            loss=lambda p, b, **kw: ssm_lm.loss_fn(p, b, cfg, **kw),
            init_cache=lambda bs, ml=0, **kw: ssm_lm.init_cache(cfg, bs, ml, **kw),
            decode=lambda p, t, c: ssm_lm.decode_step(p, t, c, cfg),
        )
    if fam == "hybrid":
        return ModelAPI(
            cfg=cfg,
            init=lambda rng: hybrid.init_params(rng, cfg),
            loss=lambda p, b, **kw: hybrid.loss_fn(p, b, cfg, **kw),
            init_cache=lambda bs, ml, **kw: hybrid.init_cache(cfg, bs, ml, **kw),
            decode=lambda p, t, c: hybrid.decode_step(p, t, c, cfg),
        )
    if fam == "audio":
        return ModelAPI(
            cfg=cfg,
            init=lambda rng: encdec.init_params(rng, cfg),
            loss=lambda p, b, **kw: encdec.loss_fn(p, b, cfg, **kw),
            init_cache=lambda bs, ml, **kw: encdec.init_cache(cfg, bs, ml, **kw),
            decode=lambda p, t, c: encdec.decode_step(p, t, c, cfg),
            prefill=lambda p, e, ml: encdec.prefill_cross(p, e, cfg, ml),
        )
    raise ValueError(f"unknown family {fam}")


# --------------------------------------------------------------------------- specs
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell.

    train/prefill cells feed ``loss_fn`` (prefill cost == one fwd pass);
    decode cells feed ``serve_step`` (handled by launch.dryrun, which also
    builds the cache spec via eval_shape)."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    if shape.is_decode:
        specs["token"] = jax.ShapeDtypeStruct((B,), i32)
        return specs
    specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    if cfg.is_encoder_decoder:
        specs["enc_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.max_encoder_len, cfg.d_model), cfg.cdtype
        )
    return specs
