"""Work a D2FT step requires, counted from shapes and the schedule.

A schedule marks each (layer l, head group g, micro-batch i) as full
(p_f = 1: forward and backward), forward-only (p_o = 2) or skipped
(p_s = 3). Each layer is of a kind, read from the configuration's
``layer_types`` (Hugging Face's key; where it is absent every layer is
``attention``). Group g of a layer owns:

* ``attention``: its heads' query/key/value columns, the attention core of
  those heads, their rows of the output projection, and its F/G slice of
  the MLP;
* ``mamba`` (Mamba-2 SSD with one B/C group): its heads' z, x and dt
  columns of the input projection, their channels of the causal
  convolution, their chunked scan (the intra-chunk, chunk-state and
  inter-chunk terms), their rows of the output projection, and its F/G
  slice of the MLP. The B and C columns, their convolution channels and
  each chunk's C B^T serve every head: they count once per sample, at the
  multiplicity of the most demanding op of that sample in the layer (3 if
  any group is p_f, 1 if any is p_o, 0 if all are p_s).

So, per sample:

* a p_f group costs its forward three times (forward, then twice that for
  the backward: gradients of the inputs and of the weights);
* a p_o group costs its forward once;
* a p_s group costs nothing;
* matmuls outside the groups run in full: the unembedding and the
  classifier three times their forward; the patch embedding twice (its
  input, the image, needs no gradient).

Matmuls and the convolution count (2 FLOPs a multiply-add); elementwise
work does not. Attention counts the causal lower triangle, and so does
the scan's intra-chunk term (each chunk's C B^T and its product with x):
a position reads only the positions before it in its chunk, l (l + 1) / 2
pairs in a chunk of length l, and where the chunk length does not divide
the sequence the last chunk is shorter. Nothing recomputed, masked or
padded is counted, so the count does not depend on what implements the
step, and a share of a peak built on it cannot pass 1. A standard fine-tuning step is the schedule
with every entry p_f.

The model dict ``m`` holds: ``family`` ("vit" or "lm"), ``layer_kinds``
(one a layer, as ``layer_kinds`` reads them), ``d_model``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``d_ff``, ``mlp_gated``, ``seq`` (positions
per sample), ``causal``, ``vocab`` (lm) or ``n_classes``, ``patch_dim`` and
``n_patches`` (vit), and with a mamba layer the ``mamba_dims`` of the
configuration.
"""
from __future__ import annotations

import numpy as np

P_F, P_O, P_S = 1, 2, 3
BYTES = 4          # float32 activations
ATTENTION, MAMBA = "attention", "mamba"
KINDS = (ATTENTION, MAMBA)


# ------------------------------------------------------------ layer kinds
def layer_kinds(c: dict) -> list:
    """The kind of each layer of configuration ``c``; a kind this module
    cannot count raises, naming it."""
    L = c["num_hidden_layers"]
    kinds = list(c.get("layer_types") or [ATTENTION] * L)
    if len(kinds) != L:
        raise ValueError(f"layer_types has {len(kinds)} entries for "
                         f"{L} layers")
    for i, k in enumerate(kinds):
        if k not in KINDS:
            raise ValueError(f"layer kind {k!r} (layer {i}) cannot be "
                             f"counted; flops.py counts {list(KINDS)}")
    return kinds


def mamba_dims(c: dict) -> dict:
    """A Mamba-2 layer's sizes, from the published ``mamba_*`` keys."""
    H, P = c["mamba_n_heads"], c["mamba_d_head"]
    if c["mamba_n_groups"] != 1:
        raise ValueError(f"mamba_n_groups {c['mamba_n_groups']}: only one "
                         "B/C group is counted")
    if H * P != c["mamba_expand"] * c["hidden_size"]:
        raise ValueError(f"mamba_n_heads x mamba_d_head = {H * P} is not "
                         "mamba_expand x hidden_size")
    return {"mamba_n_heads": H, "mamba_d_head": P,
            "mamba_d_state": c["mamba_d_state"],
            "mamba_chunk": c["mamba_chunk_size"],
            "mamba_d_conv": c["mamba_d_conv"]}


# -------------------------------------------------------------- attention
def attention_pairs(S: int, causal: bool) -> int:
    """(query, key) pairs one head scores: the lower triangle if causal."""
    return S * (S + 1) // 2 if causal else S * S


def attn_core_flops(m, n_heads: float) -> float:
    """Forward QK^T and PV of ``n_heads`` heads of one sample."""
    return 2 * 2 * m["head_dim"] * attention_pairs(m["seq"], m["causal"]) \
        * n_heads


def mlp_flops(m, G: int) -> float:
    """Forward FLOPs of one group's F/G slice of the MLP, per token."""
    f = m["d_ff"] / G
    return ((2 if m["mlp_gated"] else 1) * 2 * m["d_model"] * f  # up (+ gate)
            + 2 * f * m["d_model"])                            # down rows


def group_forward_flops(m, G: int) -> float:
    """Forward FLOPs of one (attention layer, group) for one sample."""
    D, S, hd = m["d_model"], m["seq"], m["head_dim"]
    hq = m["n_heads"] / G
    hkv = m["n_kv_heads"] / G
    per_token = (2 * D * hq * hd            # q columns
                 + 2 * 2 * D * hkv * hd     # k and v columns
                 + 2 * hq * hd * D          # wo rows
                 + mlp_flops(m, G))
    return S * per_token + attn_core_flops(m, hq)


# ------------------------------------------------------------------ mamba
def chunk_pairs(m) -> int:
    """(position, earlier-or-same position) pairs within the sequence's
    chunks: the causal lower triangle of each chunk."""
    S, Q = m["seq"], m["mamba_chunk"]
    return (S // Q) * attention_pairs(Q, True) \
        + attention_pairs(S % Q, True)


def ssd_head_flops(m) -> float:
    """Forward scan of one head of one sample: the intra-chunk product
    with x (2 P per pair), the chunk states (2 S P N) and the inter-chunk
    output (2 S N P)."""
    P, N = m["mamba_d_head"], m["mamba_d_state"]
    return 2 * P * chunk_pairs(m) + 4 * m["seq"] * P * N


def ssd_shared_core_flops(m) -> float:
    """Each chunk's C B^T (2 N per pair), shared by the heads, for one
    sample."""
    return 2 * m["mamba_d_state"] * chunk_pairs(m)


def mamba_group_forward_flops(m, G: int) -> float:
    """Forward FLOPs of one (mamba layer, group) for one sample."""
    D, S = m["d_model"], m["seq"]
    h = m["mamba_n_heads"] / G
    hp = h * m["mamba_d_head"]
    per_token = (2 * D * (2 * hp + h)               # z, x and dt columns
                 + 2 * m["mamba_d_conv"] * hp       # x channels' conv
                 + 2 * hp * D                       # w_out rows
                 + mlp_flops(m, G))
    return S * per_token + h * ssd_head_flops(m)


def mamba_shared_flops(m) -> float:
    """Forward FLOPs of a mamba layer's shared part for one sample: the B
    and C columns, their convolution channels and C B^T."""
    N2 = 2 * m["mamba_d_state"]
    return (m["seq"] * (2 * m["d_model"] * N2 + 2 * m["mamba_d_conv"] * N2)
            + ssd_shared_core_flops(m))


# ------------------------------------------------------------------ steps
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


def _shared_multiplicity(ops: np.ndarray) -> float:
    """Forward-equivalents of a part every group of a layer uses, over
    [L, G, B] ops: per (layer, sample), 3 if a group is p_f, else 1 if one
    is p_o, else 0."""
    return float(3 * np.sum(np.any(ops == P_F, 1))
                 + np.sum(np.any(ops == P_O, 1) & ~np.any(ops == P_F, 1)))


def _of_kind(m, ops: np.ndarray, kind: str) -> np.ndarray:
    """The [L, G, B] ops of the layers of ``kind``."""
    return ops[np.asarray(m["layer_kinds"]) == kind]


def kind_flops(m, table: np.ndarray, mb_of: np.ndarray) -> dict:
    """{kind: FLOPs one step requires in the layers of that kind}."""
    ops = per_sample_ops(table, mb_of)
    G = ops.shape[1]
    out = {}
    for kind in KINDS:
        k = _of_kind(m, ops, kind)
        if not len(k):
            continue
        if kind == ATTENTION:
            out[kind] = _multiplicity(k) * group_forward_flops(m, G)
        else:
            out[kind] = (_multiplicity(k) * mamba_group_forward_flops(m, G)
                         + _shared_multiplicity(k) * mamba_shared_flops(m))
    return out


def required_step_flops(m, table: np.ndarray, mb_of: np.ndarray) -> float:
    """FLOPs one step requires under ``table`` ([L, G, N])."""
    B = len(mb_of)
    return sum(kind_flops(m, table, mb_of).values(), 0.0) \
        + B * ungrouped_flops(m)


def required_attention(m, table: np.ndarray, mb_of: np.ndarray):
    """(FLOPs, bytes) the attention core of one step requires.

    Attention layers only; live (sample, head) slices, at the real sequence
    length. Bytes are the least any kernel moves: the forward reads q, k, v
    and writes o and the row statistics; the backward reads q, k, v, o, dO
    and two row statistics and writes dq, dk, dv.
    """
    ops = _of_kind(m, per_sample_ops(table, mb_of), ATTENTION)
    G = ops.shape[1]
    hq = m["n_heads"] / G
    S, hd = m["seq"], m["head_dim"]
    flops = _multiplicity(ops) * attn_core_flops(m, hq)
    n_fwd = float(np.sum(ops != P_S)) * hq      # live (sample, head) slices
    n_bwd = float(np.sum(ops == P_F)) * hq
    fwd_bytes = (4 * S * hd + S) * BYTES
    bwd_bytes = (8 * S * hd + 2 * S) * BYTES
    return flops, n_fwd * fwd_bytes + n_bwd * bwd_bytes


def required_ssd(m, table: np.ndarray, mb_of: np.ndarray):
    """(FLOPs, bytes) the SSD scan core of one step requires, in the form
    of ``required_attention``.

    Mamba layers only: the live heads' scans and, once per sample, each
    chunk's C B^T. Bytes are the least any kernel moves: per live
    (sample, head) the forward reads x and dt and writes y; per p_f one the
    backward reads x, dt and dy and writes dx and ddt; per sample the
    forward reads B and C, and the backward, where a head is p_f, reads
    them again and writes dB and dC.
    """
    ops = _of_kind(m, per_sample_ops(table, mb_of), MAMBA)
    if not len(ops):
        return 0.0, 0.0
    G = ops.shape[1]
    h = m["mamba_n_heads"] / G
    S, P, N = m["seq"], m["mamba_d_head"], m["mamba_d_state"]
    flops = (_multiplicity(ops) * h * ssd_head_flops(m)
             + _shared_multiplicity(ops) * ssd_shared_core_flops(m))
    n_fwd = float(np.sum(ops != P_S)) * h       # live (sample, head) slices
    n_bwd = float(np.sum(ops == P_F)) * h
    fwd_samples = float(np.sum(np.any(ops != P_S, 1)))
    bwd_samples = float(np.sum(np.any(ops == P_F, 1)))
    moved = (n_fwd * (2 * S * P + S) + n_bwd * (3 * S * P + 2 * S)
             + fwd_samples * 2 * S * N + bwd_samples * 4 * S * N)
    return flops, moved * BYTES


def full_table(L: int, G: int, N: int) -> np.ndarray:
    """Standard fine-tuning: every (layer, group, micro-batch) is p_f."""
    return np.full((L, G, N), P_F, np.int8)
