"""ShapeDtypeStruct input stand-ins + step builders for every
(architecture × input shape) combination. No device allocation — the
dry-run lowers against these specs only.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig
from repro.models import frontends
from repro.models.transformer import (decode_step, forward, init_cache,
                                      init_model, lm_loss)
from repro.optim.optimizers import adamw, clip_by_global_norm


def batch_specs(cfg: ModelConfig, shape: InputShape) -> Dict:
    """Training/prefill batch ShapeDtypeStructs."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    specs: Dict = {}
    if cfg.frontend == "audio_stub":
        specs["features"] = frontends.feature_spec(cfg, B, S)
        specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    elif cfg.frontend == "vision_stub":
        s_text = S - cfg.frontend_tokens
        specs["features"] = frontends.feature_spec(cfg, B, S)
        specs["tokens"] = jax.ShapeDtypeStruct((B, s_text), i32)
        specs["labels"] = jax.ShapeDtypeStruct((B, s_text), i32)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    if shape.kind == "prefill":
        specs.pop("labels", None)
    return specs


def param_shapes(cfg: ModelConfig):
    return jax.eval_shape(functools.partial(init_model, cfg=cfg),
                          jax.random.PRNGKey(0))


def make_train_step_fn(cfg: ModelConfig, policy, lr: float = 1e-4):
    """Full fine-tuning step (fwd + bwd + AdamW update), remat'd scan."""
    opt = adamw(lr)

    def train_step(params, opt_state, batch):
        def loss_of(p):
            return lm_loss(p, cfg, batch.get("tokens"), batch["labels"],
                           features=batch.get("features"), policy=policy,
                           remat=True)
        (loss, metrics), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step, opt


def make_prefill_fn(cfg: ModelConfig, policy):
    def prefill_step(params, batch):
        logits, _ = forward(params, cfg, tokens=batch.get("tokens"),
                            features=batch.get("features"), policy=policy,
                            remat=False)
        return logits[:, -1]          # next-token logits
    return prefill_step


def make_serve_fn(cfg: ModelConfig, policy):
    def serve_step(params, cache, token, t):
        logits, cache = decode_step(params, cache, cfg, token, t,
                                    policy=policy)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None], cache
    return serve_step


def decode_specs(cfg: ModelConfig, shape: InputShape):
    B, S = shape.global_batch, shape.seq_len
    cache = jax.eval_shape(
        functools.partial(init_cache, cfg=cfg, batch=B, max_len=S))
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    t = jax.ShapeDtypeStruct((), jnp.int32)
    return cache, token, t
