"""Work a D2FT step requires, counted from shapes and the schedule.

A schedule marks each (layer l, head group g, micro-batch i) as full
(p_f = 1: forward and backward), forward-only (p_o = 2) or skipped
(p_s = 3). Group g of layer l owns its heads' query/key/value columns, the
attention core of those heads, their rows of the output projection, and
its F/G slice of the MLP. So, per sample:

* a p_f group costs its forward three times (forward, then twice that for
  the backward: gradients of the inputs and of the weights);
* a p_o group costs its forward once;
* a p_s group costs nothing;
* matmuls outside the groups run in full: the unembedding and the
  classifier three times their forward; the patch embedding twice (its
  input, the image, needs no gradient).

Nothing recomputed and no padding is counted, so the count does not depend
on what implements the step, and a share of a peak built on it cannot pass
1. A standard fine-tuning step is the schedule with every entry p_f.

The model dict ``m`` holds: ``family`` ("vit" or "lm"), ``d_model``,
``n_heads``, ``n_kv_heads``, ``head_dim``, ``d_ff``, ``mlp_gated``,
``seq`` (positions per sample), ``causal``, and ``vocab`` (lm) or
``n_classes``, ``patch_dim`` and ``n_patches`` (vit).
"""
from __future__ import annotations

import numpy as np

P_F, P_O, P_S = 1, 2, 3
BYTES = 4          # float32 activations


def attention_pairs(S: int, causal: bool) -> int:
    """(query, key) pairs one head scores: the lower triangle if causal."""
    return S * (S + 1) // 2 if causal else S * S


def attn_core_flops(m, n_heads: float) -> float:
    """Forward QK^T and PV of ``n_heads`` heads of one sample."""
    return 2 * 2 * m["head_dim"] * attention_pairs(m["seq"], m["causal"]) \
        * n_heads


def group_forward_flops(m, G: int) -> float:
    """Forward FLOPs of one (layer, group) for one sample."""
    D, S, hd = m["d_model"], m["seq"], m["head_dim"]
    hq = m["n_heads"] / G
    hkv = m["n_kv_heads"] / G
    f = m["d_ff"] / G
    per_token = (2 * D * hq * hd            # q columns
                 + 2 * 2 * D * hkv * hd     # k and v columns
                 + 2 * hq * hd * D          # wo rows
                 + (2 if m["mlp_gated"] else 1) * 2 * D * f   # up (+ gate)
                 + 2 * f * D)               # down rows
    return S * per_token + attn_core_flops(m, hq)


def ungrouped_flops(m) -> float:
    """Per-sample FLOPs of the matmuls outside the groups, fwd + bwd."""
    D = m["d_model"]
    if m["family"] == "lm":
        return 3 * 2 * D * m["vocab"] * m["seq"]
    return (2 * 2 * m["patch_dim"] * D * m["n_patches"]
            + 3 * 2 * D * m["n_classes"])


def per_sample_ops(table: np.ndarray, mb_of: np.ndarray) -> np.ndarray:
    """[L, G, B] op of every (layer, group, sample); ``table`` is
    [L, G, N] over micro-batches, ``mb_of`` [B] each sample's one."""
    return np.asarray(table)[:, :, np.asarray(mb_of)]


def _multiplicity(ops: np.ndarray) -> float:
    """Forward-equivalents the ops require: 3 per p_f, 1 per p_o."""
    return float(3 * np.sum(ops == P_F) + np.sum(ops == P_O))


def required_step_flops(m, table: np.ndarray, mb_of: np.ndarray) -> float:
    """FLOPs one step requires under ``table`` ([L, G, N])."""
    ops = per_sample_ops(table, mb_of)
    G = ops.shape[1]
    B = ops.shape[2]
    return (_multiplicity(ops) * group_forward_flops(m, G)
            + B * ungrouped_flops(m))


def required_attention(m, table: np.ndarray, mb_of: np.ndarray):
    """(FLOPs, bytes) the attention core of one step requires.

    Live (sample, head) slices only, at the real sequence length. Bytes are
    the least any kernel moves: the forward reads q, k, v and writes o and
    the row statistics; the backward reads q, k, v, o, dO and two row
    statistics and writes dq, dk, dv.
    """
    ops = per_sample_ops(table, mb_of)
    G = ops.shape[1]
    hq = m["n_heads"] / G
    S, hd = m["seq"], m["head_dim"]
    flops = _multiplicity(ops) * attn_core_flops(m, hq)
    n_fwd = float(np.sum(ops != P_S)) * hq      # live (sample, head) slices
    n_bwd = float(np.sum(ops == P_F)) * hq
    fwd_bytes = (4 * S * hd + S) * BYTES
    bwd_bytes = (8 * S * hd + 2 * S) * BYTES
    return flops, n_fwd * fwd_bytes + n_bwd * bwd_bytes


def full_table(L: int, G: int, N: int) -> np.ndarray:
    """Standard fine-tuning: every (layer, group, micro-batch) is p_f."""
    return np.full((L, G, N), P_F, np.int8)
