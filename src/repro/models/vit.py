"""ViT for the paper's own experiments (ViT-small, 12 blocks, 6 heads).

Reuses the shared transformer blocks (bidirectional attention, learned
positional embeddings, classification head over the CLS token) so D2FT head
-group gating works identically to the LLM backbones.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN_GLOBAL, ModelConfig
from repro.models.layers import apply_norm, dense_init, init_norm
from repro.models.transformer import _init_block, apply_block


@dataclass(frozen=True)
class ViTConfig:
    n_layers: int = 12
    d_model: int = 384
    n_heads: int = 6
    d_ff: int = 1536
    patch: int = 16
    image_size: int = 224
    n_classes: int = 10

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch) ** 2

    def backbone(self) -> ModelConfig:
        return ModelConfig(
            name="vit", arch_type="vit", n_layers=self.n_layers,
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_heads, d_ff=self.d_ff, vocab_size=self.n_classes,
            causal=False, rope=False, mlp_act="gelu", mlp_gated=False,
            norm="layer", block_pattern=(ATTN_GLOBAL,))


def vit_small(n_classes: int = 10) -> ViTConfig:
    return ViTConfig(n_classes=n_classes)


def init_vit(key, cfg: ViTConfig, dtype=jnp.float32):
    bb = cfg.backbone()
    ks = jax.random.split(key, cfg.n_layers + 4)
    patch_dim = cfg.patch * cfg.patch * 3
    params = {
        "patch_proj": dense_init(ks[0], patch_dim, cfg.d_model, dtype),
        "patch_bias": jnp.zeros((cfg.d_model,), dtype),
        "cls": (jax.random.normal(ks[1], (1, 1, cfg.d_model)) * 0.02).astype(dtype),
        "pos": (jax.random.normal(ks[2], (1, cfg.n_patches + 1, cfg.d_model))
                * 0.02).astype(dtype),
        "blocks": [_init_block(ks[3 + i], ATTN_GLOBAL, bb, dtype)
                   for i in range(cfg.n_layers)],
        "final_norm": init_norm("layer", cfg.d_model, dtype),
        "head": dense_init(ks[3 + cfg.n_layers], cfg.d_model, cfg.n_classes, dtype),
    }
    return params


def patchify(images, patch: int):
    """images: [B, H, W, 3] -> [B, n_patches, patch*patch*3]."""
    B, H, W, C = images.shape
    ph, pw = H // patch, W // patch
    x = images.reshape(B, ph, patch, pw, patch, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, ph * pw, patch * patch * C)


def vit_forward(params, images, cfg: ViTConfig, gates=None,
                use_kernel: bool = False, live_bounds=None):
    """images: [B,H,W,3]; gates: optional (g_f, g_b) [n_layers, B, G];
    use_kernel routes attention through the Pallas gated flash kernel
    (gate-aware backward) instead of the masked dense path; live_bounds is
    the optional static (live_fwd, live_bwd) (sample, group) slice bound
    pair (``core.schedule.live_slice_bounds``) enabling the kernel's
    compaction dispatch.

    Returns logits [B, n_classes].
    """
    bb = cfg.backbone()
    with jax.named_scope("embed"):
        x = (patchify(images, cfg.patch) @ params["patch_proj"]
             + params["patch_bias"])
        cls = jnp.broadcast_to(params["cls"], (x.shape[0], 1, cfg.d_model))
        x = jnp.concatenate([cls, x], axis=1) + params["pos"]
    for i, blk in enumerate(params["blocks"]):
        lg = None
        if gates is not None:
            lg = (gates[0][i], gates[1][i])
        x, _ = apply_block(blk, x, ATTN_GLOBAL, bb, lg,
                           use_kernel=use_kernel, live_bounds=live_bounds)
    with jax.named_scope("head"):
        x = apply_norm(params["final_norm"], x, "layer")
        return x[:, 0] @ params["head"]


def vit_loss(params, images, labels, cfg: ViTConfig, gates=None,
             use_kernel: bool = False, live_bounds=None):
    logits = vit_forward(params, images, cfg, gates, use_kernel=use_kernel,
                         live_bounds=live_bounds)
    with jax.named_scope("head"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        loss = -jnp.mean(ll)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"acc": acc}
