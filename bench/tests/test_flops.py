"""FLOP and byte counts against hand counts at smoke shapes."""
import numpy as np
import pytest

from bench import flops

LM = dict(family="lm", d_model=8, n_heads=2, n_kv_heads=2, head_dim=4,
          d_ff=16, mlp_gated=True, seq=3, causal=True, vocab=10,
          layer_kinds=["attention"])
VIT = dict(family="vit", d_model=8, n_heads=2, n_kv_heads=2, head_dim=4,
           d_ff=16, mlp_gated=False, seq=5, causal=False, n_classes=3,
           patch_dim=12, n_patches=4, layer_kinds=["attention"])


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
             d_ff=6912, seq=400, vocab=50304, layer_kinds=["attention"] * 4)
    f = flops.required_step_flops(m, flops.full_table(4, 32, 5),
                                  np.arange(5))
    # 6 N T with N the matmul weights of 4 layers and the unembedding
    n = 4 * (4 * 2560 * 2560 + 3 * 2560 * 6912) + 2560 * 50304
    attn = 4 * 32 * 5 * 3 * 2 * 2 * 80 * 400 * 401 // 2
    assert f == pytest.approx(6 * n * 5 * 400 + attn, rel=1e-12)


# ------------------------------------------------------ counts by layer kind
def _before_group_forward(m, G):
    """``group_forward_flops`` as it was before layers had kinds."""
    D, S, hd = m["d_model"], m["seq"], m["head_dim"]
    hq = m["n_heads"] / G
    hkv = m["n_kv_heads"] / G
    f = m["d_ff"] / G
    per_token = (2 * D * hq * hd + 2 * 2 * D * hkv * hd + 2 * hq * hd * D
                 + (2 if m["mlp_gated"] else 1) * 2 * D * f + 2 * f * D)
    return S * per_token + flops.attn_core_flops(m, hq)


def _before_step_flops(m, table, mb_of):
    ops = flops.per_sample_ops(table, mb_of)
    mult = float(3 * np.sum(ops == flops.P_F) + np.sum(ops == flops.P_O))
    return (mult * _before_group_forward(m, ops.shape[1])
            + ops.shape[2] * flops.ungrouped_flops(m))


def _d2ft_table(L, G, rng):
    """3 p_f, 1 p_o, 1 p_s of 5 micro-batches per (layer, group)."""
    row = np.array([1, 1, 1, 2, 3], np.int8)
    return np.stack([[rng.permutation(row) for _ in range(G)]
                     for _ in range(L)])


# each configuration at a size it runs at: the ViT cell's traffic, and the
# stablelm traffic of PERF.md (5 sequences of 512 tokens)
SIZES = {"vit-s16.d2ft-paper": ("bench/configs/vit-s16.json",
                                {"batch": 200, "n_microbatches": 5}),
         "stablelm-3b-l4.d2ft-paper": ("bench/configs/stablelm-3b-l4.json",
                                       {"batch": 5, "seq": 512,
                                        "n_microbatches": 5})}


@pytest.mark.parametrize("cell", sorted(SIZES))
@pytest.mark.parametrize("layer_types", ["absent", "all attention"])
def test_all_attention_counts_as_before(cell, layer_types):
    import json
    from bench import harness
    path, t = SIZES[cell]
    c = json.loads((harness.REPO / path).read_text())
    if layer_types == "all attention":
        c = dict(c, layer_types=["attention"] * c["num_hidden_layers"])
    m = harness.model_dims(c, t)
    L, G = c["num_hidden_layers"], c["num_attention_heads"]
    mb_of = harness.microbatch_of(t["batch"], 5)
    for table in (_d2ft_table(L, G, np.random.default_rng(0)),
                  flops.full_table(L, G, 5)):
        assert flops.required_step_flops(m, table, mb_of) == \
            _before_step_flops(m, table, mb_of)
        ops = flops.per_sample_ops(table, mb_of)
        hq = m["n_heads"] / G
        want = float(3 * np.sum(ops == 1) + np.sum(ops == 2)) \
            * flops.attn_core_flops(m, hq)
        assert flops.required_attention(m, table, mb_of)[0] == want
        assert flops.required_ssd(m, table, mb_of) == (0.0, 0.0)


# a hybrid at a tiny size: layer 0 is Mamba-2 (4 heads of 4, state 4,
# chunk 4, conv 4, d_inner 16 = 2 x 8), layer 1 attention; 8 positions
HYBRID = dict(LM, seq=8, layer_kinds=["mamba", "attention"],
              mamba_n_heads=4, mamba_d_head=4, mamba_d_state=4,
              mamba_chunk=4, mamba_d_conv=4)


def test_mamba_group_by_hand():
    # G = 2: 2 heads of 4 (8 channels) a group, MLP columns 16 / 2 = 8
    per_token = (2 * 8 * (2 * 8 + 2)     # z and x columns, 2 dt columns
                 + 2 * 4 * 8             # conv over the 8 x channels
                 + 2 * 8 * 8             # w_out rows
                 + 2 * 2 * 8 * 8         # MLP up and gate
                 + 2 * 8 * 8)            # MLP down rows
    # per head: intra-chunk 2 x 4 (P) x 10 causal pairs in each of the 2
    # chunks of 4, chunk states and inter-chunk output 2 x 8 x 4 x 4 each
    head = 2 * 4 * 20 + 2 * (2 * 8 * 4 * 4)
    assert flops.mamba_group_forward_flops(HYBRID, 2) == \
        8 * per_token + 2 * head == 8256
    # B and C columns (2 x 4 of them), their conv, C B^T over 2 chunks
    shared = 8 * (2 * 8 * 8 + 2 * 4 * 8) + 2 * 4 * 20
    assert flops.mamba_shared_flops(HYBRID) == shared == 1696


def test_last_chunk_is_its_own_length():
    m = dict(HYBRID, seq=10)
    # chunks of 4, 4 and 2: 10 + 10 + 3 causal pairs
    assert flops.chunk_pairs(m) == 10 + 10 + 3
    # the scan of one head: 2 P pairs + 4 S P N
    assert flops.ssd_head_flops(m) == 2 * 4 * 23 + 4 * 10 * 4 * 4


def _plain_mamba_layer(p, x, dot, m):
    """One Mamba-2 layer and its gated SiLU MLP on one sample x [S, D], in
    plain jax.numpy; every matmul goes through ``dot``."""
    import jax
    import jax.numpy as jnp
    H, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    Q, W = m["mamba_chunk"], m["mamba_d_conv"]
    S, dI = x.shape[0], H * P
    zxbcdt = dot("sd,de->se", x, p["w_in"])
    z, xbc, dt = zxbcdt[:, :dI], zxbcdt[:, dI:2 * dI + 2 * N], \
        jax.nn.softplus(zxbcdt[:, 2 * dI + 2 * N:])
    pad = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1])), xbc])
    win = jnp.stack([pad[i:i + S] for i in range(W)], 1)     # [S, W, C]
    xbc = jax.nn.silu(dot("swc,wc->sc", win, p["conv"]))
    xs = xbc[:, :dI].reshape(S, H, P)
    Bm, Cm = xbc[:, dI:dI + N], xbc[:, dI + N:]
    y = ssd_scan(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm, Q, dot)
    y = (y + p["D"][:, None] * xs).reshape(S, dI) * jax.nn.silu(z)
    h = x + dot("se,ed->sd", y, p["w_out"])
    g = jax.nn.silu(dot("sd,df->sf", h, p["w_gate"])) \
        * dot("sd,df->sf", h, p["w_up"])
    return h + dot("sf,fd->sd", g, p["w_down"])


def ssd_scan(xs, dt, A, Bm, Cm, Q, dot):
    """The chunked SSD scan (Mamba-2, arXiv:2405.21060 section 6) of one
    sample: xs [S, H, P], dt [S, H], A [H], Bm and Cm [S, N]."""
    import jax.numpy as jnp
    S, H, P = xs.shape
    nc = S // Q
    cum = jnp.cumsum((dt * A).reshape(nc, Q, H), 1)            # [c, q, h]
    xc = (xs * dt[..., None]).reshape(nc, Q, H, P)
    Bc, Cc = Bm.reshape(nc, Q, -1), Cm.reshape(nc, Q, -1)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    decay = jnp.where(causal, jnp.exp(cum[:, :, None] - cum[:, None]), 0.0)
    cb = dot("cqn,ckn->cqk", Cc, Bc)
    y = dot("cqkh,ckhp->cqhp", cb[..., None] * decay, xc)
    to_end = jnp.exp(cum[:, -1:] - cum)                        # [c, k, h]
    states = dot("ckhp,ckn->chpn", to_end[..., None] * xc, Bc)
    prev, before = jnp.zeros_like(states[0]), []
    for c in range(nc):
        before.append(prev)
        prev = prev * jnp.exp(cum[c, -1])[:, None, None] + states[c]
    y = y + dot("cqn,chpn->cqhp", Cc, jnp.stack(before)) \
        * jnp.exp(cum)[..., None]
    return y.reshape(S, H, P)


def test_mamba_count_is_the_plain_layers_dots():
    """With every gate p_f, a mamba layer's count is three times the
    FLOPs XLA's cost analysis gives the plain layer's matmuls, less the
    upper triangle that the two intra-chunk products compute and the
    decay mask zeroes; its scan is the program's own chunked SSD."""
    import jax
    import jax.numpy as jnp
    from repro.models.ssm import ssd_chunked
    m = HYBRID
    D, H, P, N = 8, 4, 4, 4
    dI = H * P
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 12))
    p = {"w_in": jax.random.normal(next(ks), (D, 2 * dI + 2 * N + H)) / 3,
         "conv": jax.random.normal(next(ks), (4, dI + 2 * N)) / 2,
         "A_log": jax.random.normal(next(ks), (H,)) / 4,
         "D": jnp.ones((H,)),
         "w_out": jax.random.normal(next(ks), (dI, D)) / 4,
         "w_up": jax.random.normal(next(ks), (D, 16)) / 3,
         "w_gate": jax.random.normal(next(ks), (D, 16)) / 3,
         "w_down": jax.random.normal(next(ks), (16, D)) / 4}
    x = jax.random.normal(next(ks), (m["seq"], D))
    counted, dense = [], []
    intra = {"cqn,ckn->cqk", "cqkh,ckhp->cqhp"}   # over (q, k) of a chunk
    lower = (4 * 5 // 2) / 4 ** 2                  # 10 of 16 pairs, Q = 4

    def dot(spec, a, b):
        shapes = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in (a, b)]
        cost = jax.jit(lambda u, v: jnp.einsum(spec, u, v)).lower(
            *shapes).compile().cost_analysis()
        dense.append(cost["flops"])
        counted.append(cost["flops"] * (lower if spec in intra else 1))
        return jnp.einsum(spec, a, b, precision="highest")

    out = _plain_mamba_layer(p, x, dot, m)
    assert out.shape == x.shape and bool(jnp.all(jnp.isfinite(out)))
    table = flops.full_table(2, 2, 1)[:1]                 # the mamba layer
    one = dict(m, layer_kinds=["mamba"])
    got = flops.required_step_flops(one, table, np.zeros(1, int)) \
        - flops.ungrouped_flops(one)
    assert sum(dense) == 18688
    # the masked pairs: 2 FLOPs x 2 chunks x 6 pairs x (N + H P)
    assert sum(dense) - sum(counted) == 2 * 2 * 6 * (4 + 16) == 480
    assert got == 3 * sum(counted) == 3 * 18208

    xs = jax.random.normal(next(ks), (8, H, P))
    dt = jax.nn.softplus(jax.random.normal(next(ks), (8, H)))
    Bm, Cm = jax.random.normal(next(ks), (2, 8, N))
    A = -jnp.exp(p["A_log"])
    with jax.default_matmul_precision("highest"):
        want = ssd_chunked(xs[None], dt[None], A, Bm[None], Cm[None], 4)[0]
    got = ssd_scan(xs, dt, A, Bm, Cm, 4, lambda s, a, b: jnp.einsum(
        s, a, b, precision="highest"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_required_ssd_by_hand():
    # mamba layer: group 0 is p_f on sample 0 and p_o on sample 1, group 1
    # is p_s on both; the attention layer is all p_f
    table = np.array([[[1, 2], [3, 3]], [[1, 1], [1, 1]]], np.int8)
    mb_of = np.array([0, 1])
    f, b = flops.required_ssd(HYBRID, table, mb_of)
    head = flops.ssd_head_flops(HYBRID)
    # (3 + 1) op multiples of 2 heads; C B^T: 3 (sample 0) + 1 (sample 1)
    assert f == 4 * 2 * head + 4 * 2 * 4 * 20
    S, P, N = 8, 4, 4
    # 4 live (sample, head) forward slices, 2 backward; B and C read for
    # 2 samples, and read again with dB, dC written for 1
    moved = 4 * (2 * S * P + S) + 2 * (3 * S * P + 2 * S) \
        + 2 * 2 * S * N + 1 * 4 * S * N
    assert b == moved * 4
    # attention counts its own layer only
    only = dict(HYBRID, layer_kinds=["attention"])
    assert flops.required_attention(HYBRID, table, mb_of) == \
        flops.required_attention(only, table[1:], mb_of)


def test_shared_part_takes_the_most_demanding_op():
    mb_of = np.array([0])
    one = dict(HYBRID, layer_kinds=["mamba"])
    group = flops.mamba_group_forward_flops(one, 2)
    shared = flops.mamba_shared_flops(one)
    for ops, want in (([3, 3], 0.0), ([2, 3], group + shared),
                      ([2, 1], 4 * group + 3 * shared)):
        table = np.array(ops, np.int8).reshape(1, 2, 1)
        assert flops.kind_flops(one, table, mb_of)["mamba"] == want


def test_unknown_layer_kind_raises():
    with pytest.raises(ValueError, match="layer kind 'moe'"):
        flops.layer_kinds({"num_hidden_layers": 2,
                           "layer_types": ["attention", "moe"]})
    with pytest.raises(ValueError, match="3 entries for 2 layers"):
        flops.layer_kinds({"num_hidden_layers": 2,
                           "layer_types": ["attention"] * 3})
    assert flops.layer_kinds({"num_hidden_layers": 2}) == ["attention"] * 2
