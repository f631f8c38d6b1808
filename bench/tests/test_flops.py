"""FLOP and byte counts against hand counts at smoke shapes."""
import numpy as np
import pytest

from bench import flops

LM = dict(family="lm", d_model=8, n_heads=2, n_kv_heads=2, head_dim=4,
          d_ff=16, mlp_gated=True, seq=3, causal=True, vocab=10)
VIT = dict(family="vit", d_model=8, n_heads=2, n_kv_heads=2, head_dim=4,
           d_ff=16, mlp_gated=False, seq=5, causal=False, n_classes=3,
           patch_dim=12, n_patches=4)


def test_attention_pairs():
    assert flops.attention_pairs(3, causal=True) == 6
    assert flops.attention_pairs(3, causal=False) == 9


@pytest.mark.parametrize("causal,attn", [(True, 2 * 2 * 4 * 6),
                                         (False, 2 * 2 * 4 * 9)])
def test_group_forward_lm(causal, attn):
    # one head of 4 and 8 MLP columns per group (G = 2), per token:
    # q 2*8*4, k and v 2*2*8*4, wo 2*4*8, up and gate 2*2*8*8, down 2*8*8
    per_token = 64 + 128 + 64 + 256 + 128
    m = dict(LM, causal=causal)
    assert flops.group_forward_flops(m, 2) == 3 * per_token + attn


def test_group_forward_vit_ungated_mlp():
    per_token = 64 + 128 + 64 + 2 * 8 * 8 + 2 * 8 * 8   # up only, no gate
    attn = 2 * 2 * 4 * 25
    assert flops.group_forward_flops(VIT, 2) == 5 * per_token + attn


def test_ungrouped():
    assert flops.ungrouped_flops(LM) == 3 * 2 * 8 * 10 * 3
    # patch embedding forward + weight gradient, classifier fwd + bwd
    assert flops.ungrouped_flops(VIT) == 2 * 2 * 12 * 8 * 4 + 3 * 2 * 8 * 3


def test_required_step_three_op_kinds():
    # layer 0: group 0 is p_f on micro-batch 0, p_o on 1; group 1 is p_s on
    # 0 and p_f on 1; one sample per micro-batch
    table = np.array([[[1, 2], [3, 1]]], np.int8)
    mb_of = np.array([0, 1])
    group = flops.group_forward_flops(LM, 2)
    want = (3 + 1 + 0 + 3) * group + 2 * flops.ungrouped_flops(LM)
    assert flops.required_step_flops(LM, table, mb_of) == want


def test_full_table_counts_every_group_p_f():
    table = flops.full_table(1, 2, 2)
    mb_of = np.array([0, 0, 1, 1])
    want = 4 * 2 * 3 * flops.group_forward_flops(LM, 2) \
        + 4 * flops.ungrouped_flops(LM)
    assert flops.required_step_flops(LM, table, mb_of) == want


def test_required_attention():
    table = np.array([[[1, 2], [3, 1]]], np.int8)
    mb_of = np.array([0, 1])
    f, b = flops.required_attention(LM, table, mb_of)
    assert f == (3 + 1 + 3) * 2 * 2 * 4 * 6
    fwd_slice = (4 * 3 * 4 + 3) * 4
    bwd_slice = (8 * 3 * 4 + 2 * 3) * 4
    assert b == 3 * fwd_slice + 2 * bwd_slice


def test_share_of_real_cells_below_one_at_peak():
    """At the chip's peak, a step needs at least its FLOPs' time."""
    m = dict(LM, d_model=2560, n_heads=32, n_kv_heads=32, head_dim=80,
             d_ff=6912, seq=400, vocab=50304)
    f = flops.required_step_flops(m, flops.full_table(4, 32, 5),
                                  np.arange(5))
    # 6 N T with N the matmul weights of 4 layers and the unembedding
    n = 4 * (4 * 2560 * 2560 + 3 * 2560 * 6912) + 2560 * 50304
    attn = 4 * 32 * 5 * 3 * 2 * 2 * 80 * 400 * 401 // 2
    assert f == pytest.approx(6 * n * 5 * 400 + attn, rel=1e-12)
