"""LoRA extension of D2FT (paper §II-D).

LoRA adapters attach to the Q/K/V projections of every attention block; the
foundation weights stay frozen, gradients flow only into the low-rank A/B
matrices. Each adapter is co-located with its head's subnet, so the D2FT
gates act on the LoRA contribution exactly as on full fine-tuning (the
paper's "subnet = frozen head + its LoRA matrices").

Cost model (paper §III-A): rank controls the LoRA compute; the operation
schedule controls how many micro-batches exercise fwd/bwd per subnet.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

LORA_TARGETS = ("wq", "wk", "wv")


def _target_paths(tree, targets, prefix=()):
    """Yield (path, leaf) for every 2-D target weight in a params tree."""
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _target_paths(v, targets, prefix + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _target_paths(v, targets, prefix + (i,))
    else:
        if prefix and prefix[-1] in targets and tree.ndim >= 2:
            yield prefix, tree


def init_lora(key, params, rank: int, targets: Sequence[str] = LORA_TARGETS,
              dtype=jnp.float32):
    """Returns a flat dict {path_str: {"a": [.., in, r], "b": [.., r, out]}}.

    Stacked (scan-cycled) weights get stacked adapters — leading dims are
    preserved so the LoRA tree is scan-compatible.
    """
    lora = {}
    paths = list(_target_paths(params, tuple(targets)))
    keys = jax.random.split(key, max(len(paths), 1))
    for (path, w), k in zip(paths, keys):
        lead, (din, dout) = w.shape[:-2], w.shape[-2:]
        a = (jax.random.normal(k, lead + (din, rank)) / jnp.sqrt(din)).astype(dtype)
        b = jnp.zeros(lead + (rank, dout), dtype)
        lora["/".join(map(str, path))] = {"a": a, "b": b}
    return lora


def merge_lora(params, lora, scale: float = 1.0):
    """Return params with W <- stop_grad(W) + scale * A @ B for targets and
    stop_grad elsewhere; gradients flow only through the adapters."""
    frozen = jax.lax.stop_gradient(params)

    def set_path(tree, path, value):
        head = path[0]
        if len(path) == 1:
            if isinstance(tree, dict):
                return {**tree, head: value}
            out = list(tree)
            out[int(head)] = value
            return type(tree)(out)
        if isinstance(tree, dict):
            return {**tree, head: set_path(tree[head], path[1:], value)}
        out = list(tree)
        out[int(head)] = set_path(tree[int(head)], path[1:], value)
        return type(tree)(out)

    merged = frozen
    for path_str, ab in lora.items():
        path = path_str.split("/")
        w = merged
        for pkey in path:
            w = w[pkey] if isinstance(w, dict) else w[int(pkey)]
        delta = jnp.einsum("...ir,...ro->...io", ab["a"], ab["b"]) * scale
        merged = set_path(merged, path, w + delta.astype(w.dtype))
    return merged


def lora_param_count(lora) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(lora))


def lora_flops_fraction(cfg: ModelConfig, rank: int) -> float:
    """Relative LoRA-branch compute vs the frozen QKV matmuls — used to map
    the paper's rank-matched baselines (R=1/60/200/240)."""
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    qkv_cols = (cfg.n_heads + 2 * cfg.n_kv_heads) * hd
    full = d * qkv_cols
    lora = rank * (d + qkv_cols)
    return lora / full
