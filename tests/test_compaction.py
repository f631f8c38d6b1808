"""Compaction dispatch + fused one-pass backward.

Covers: the compacted grid's leading dim (via the trace-time on_dispatch
hook), executed-block counts under compaction (via on_backward_block), the
dispatched-bytes accounting shrinking proportionally to live slices, the
g_b <= g_f invariant check, sliding-window + padded-seq backward parity,
and end-to-end train-step equivalence of the compacted vs uncompacted
kernel paths under a real Schedule.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import d2ft_attention as d2a
from repro.kernels.ops import gated_attention
from repro.kernels.ref import gated_attention_ref

TOL = 1e-4   # fp32, interpret mode


def _mix_40po_20ps(B, H, rng):
    """The paper-budget mix: 40% p_f, 40% p_o, 20% p_s of the B*H subnets
    (deterministic counts, shuffled placement)."""
    N = B * H
    n_pf, n_po = (4 * N) // 10, (4 * N) // 10
    ops_ = np.full(N, 2)
    ops_[:n_pf] = 0
    ops_[n_pf:n_pf + n_po] = 1
    rng.shuffle(ops_)
    ops_ = ops_.reshape(B, H)
    g_f = jnp.asarray((ops_ != 2).astype(np.float32))
    g_b = jnp.asarray((ops_ == 0).astype(np.float32))
    return g_f, g_b, int((ops_ != 2).sum()), int((ops_ == 0).sum())


# ------------------------------------------------------------ invariant
def test_gb_gt_gf_rejected():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 64, 16))
    g_f = jnp.asarray([[1., 0.]])
    g_b = jnp.asarray([[1., 1.]])        # backward live on a p_s head
    with pytest.raises(ValueError, match="g_b <= g_f"):
        gated_attention(q, q, q, g_f, g_b, interpret=True)


def test_undersized_live_bound_rejected():
    """A bound below the concrete live count would silently truncate the
    compaction gather — reject it up front."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 64, 16))
    g = jnp.ones((1, 4))
    with pytest.raises(ValueError, match="live_bwd=2 is below"):
        gated_attention(q, q, q, g, g, interpret=True, live_fwd=4,
                        live_bwd=2)


def test_gb_le_gf_accepted_and_shapes_checked():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 64, 16))
    ok = gated_attention(q, q, q, jnp.ones((1, 2)), jnp.zeros((1, 2)),
                         interpret=True)
    assert ok.shape == q.shape
    with pytest.raises(ValueError, match="gates must be"):
        gated_attention(q, q, q, jnp.ones((2, 2)), jnp.zeros((2, 2)),
                        interpret=True)


# ------------------------------------------- compacted dispatch grids
@pytest.mark.parametrize("S", [192, 384], ids=["short", "flash"])
def test_compacted_grid_leading_dim_and_parity(S):
    """With live bounds, the pallas_call grids lead with the bound instead
    of B*H — on the short path (S <= 256) with the bound over the slices
    a grid step takes, rounded up — executed backward blocks stay exactly
    the live share, and outputs/gradients equal the uncompacted
    dispatch."""
    B, H, hd = 2, 10, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v, do = (jax.random.normal(kk, (B, H, S, hd)) for kk in ks)
    g_f, g_b, live_f, live_b = _mix_40po_20ps(B, H, np.random.default_rng(3))
    assert (live_f, live_b) == (16, 8)   # 40% p_o + 20% p_s of 20 slices

    grids = {}
    blocks = {"n": 0}
    d2a.on_dispatch = lambda kind, grid: grids.__setitem__(kind, grid)
    d2a.on_backward_block = lambda: blocks.__setitem__("n", blocks["n"] + 1)
    jax.clear_caches()                   # hooks are read at trace time
    try:
        def run(live):
            lf, lb = (live_f, live_b) if live else (None, None)
            out, vjp = jax.vjp(
                lambda q, k, v: gated_attention(
                    q, k, v, g_f, g_b, interpret=True, live_fwd=lf,
                    live_bwd=lb), q, k, v)
            grads = vjp(do)
            jax.effects_barrier()
            return out, grads

        blocks["n"] = 0
        out_c, grads_c = run(live=True)
        compacted_blocks = blocks["n"]
        fwd_grid, bwd_grid = grids["fwd"], grids["bwd"]

        blocks["n"] = 0
        out_u, grads_u = run(live=False)

        # grid leading dims shrink to the live bounds (not B*H)
        if S <= d2a.SHORT_SEQ_ROWS:
            spb_f = d2a.slices_per_step(S, hd, 4, "fwd")
            spb_b = d2a.slices_per_step(S, hd, 4, "bwd")
            assert (spb_f, spb_b) == (8, 4)
            assert grids["fwd"] == (-(-B * H // spb_f),)
            assert grids["bwd"] == (-(-B * H // spb_b),)
            assert fwd_grid == (-(-live_f // spb_f),)
            assert bwd_grid == (-(-live_b // spb_b),)
        else:
            assert grids["fwd"][0] == B * H and grids["bwd"][0] == B * H
            assert fwd_grid[0] == live_f and fwd_grid[1:] == grids["fwd"][1:]
            assert bwd_grid[0] == live_b and bwd_grid[1:] == grids["bwd"][1:]
        # executed backward tiles: identical live share either way
        assert compacted_blocks == blocks["n"] > 0
        # numerics identical
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_u),
                                   atol=1e-6, rtol=1e-6)
        for name, a, b in zip(("dq", "dk", "dv"), grads_c, grads_u):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6, err_msg=name)
    finally:
        d2a.on_dispatch = None
        d2a.on_backward_block = None


def test_dispatched_bytes_shrink_proportionally():
    """The DMA model: dispatched bytes scale with the compacted slice
    count, not with B*H — the 40% p_o + 20% p_s mix streams 40% of the
    backward bytes and 80% of the forward bytes (flash path)."""
    B, H, S, hd = 2, 10, 384, 64
    g_f, g_b, live_f, live_b = _mix_40po_20ps(B, H, np.random.default_rng(0))
    full_f, full_b = d2a.gated_attention_dispatched_bytes(g_f, g_b, S, hd)
    comp_f, comp_b = d2a.gated_attention_dispatched_bytes(
        g_f, g_b, S, hd, live_fwd=live_f, live_bwd=live_b)
    N = B * H
    assert comp_f / full_f == live_f / N == 0.8
    assert comp_b / full_b == live_b / N == 0.4
    # uncompacted dispatch streams every slice regardless of gates
    ones_f, ones_b = d2a.gated_attention_dispatched_bytes(
        jnp.ones((B, H)), jnp.ones((B, H)), S, hd)
    assert (ones_f, ones_b) == (full_f, full_b)


def test_short_dispatched_bytes_count_launched_slices():
    """On the short path every launched slice streams each block once, and
    a launch is the dispatch count rounded up to whole grid steps (8
    forward, 4 backward slices at S=197, hd=64): 16 live forward slices
    launch 16, all 20 launch 3 steps of 7; 8 and 20 backward slices launch
    exactly."""
    B, H, S, hd = 2, 10, 197, 64
    g_f, g_b, live_f, live_b = _mix_40po_20ps(B, H, np.random.default_rng(0))
    full_f, full_b = d2a.gated_attention_dispatched_bytes(g_f, g_b, S, hd)
    comp_f, comp_b = d2a.gated_attention_dispatched_bytes(
        g_f, g_b, S, hd, live_fwd=live_f, live_bwd=live_b)
    assert full_f == 21 * (4 * S * hd + S) * 4
    assert full_b == 20 * (7 * S * hd + 2 * S) * 4
    assert comp_f / full_f == 16 / 21
    assert comp_b / full_b == live_b / (B * H) == 0.4


# --------------------------------- sliding window + padded seq backward
@pytest.mark.parametrize("live", [False, True])
def test_window_padded_backward_parity_and_counts(live):
    """window > 0 with a non-dividing S (257 -> padded 384): dq/dk/dv match
    the reference VJP and the fused kernel executes exactly the live-slice
    share of the window-reduced block set."""
    B, H, S, hd = 1, 4, 257, 32
    window = 64
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, do = (jax.random.normal(kk, (B, H, S, hd)) for kk in ks)
    g_f = jnp.asarray([[1., 1., 1., 0.]])
    g_b = jnp.asarray([[1., 0., 1., 0.]])
    lf, lb = (3, 2) if live else (None, None)

    count = {"n": 0}
    d2a.on_backward_block = lambda: count.__setitem__("n", count["n"] + 1)
    jax.clear_caches()
    try:
        out_k, vjp_k = jax.vjp(
            lambda q, k, v: gated_attention(
                q, k, v, g_f, g_b, causal=True, window=window,
                interpret=True, live_fwd=lf, live_bwd=lb), q, k, v)
        grads_k = vjp_k(do)
        jax.effects_barrier()
    finally:
        d2a.on_backward_block = None

    out_r, vjp_r = jax.vjp(
        lambda q, k, v: gated_attention_ref(q, k, v, g_f, g_b, causal=True,
                                            window=window), q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=TOL, rtol=TOL)
    for name, a, b in zip(("dq", "dk", "dv"), grads_k, vjp_r(do)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL,
                                   rtol=TOL, err_msg=name)

    from repro.kernels.d2ft_attention import select_blocks
    bq, bk, Sp = select_blocks(S, 128, 128)
    assert Sp == 384                     # padded, not slivered
    tiles = d2a.live_block_count(Sp, bq, bk, True, window, seq_len=S)
    n_live_b = int(np.sum(np.asarray(g_b) != 0))
    assert count["n"] == n_live_b * tiles
    # window + seq_len padding prune blocks vs full causal
    assert tiles < d2a.live_block_count(Sp, bq, bk, True, 0)


# ----------------------------------------------------------- end to end
def test_vit_step_compacted_matches_uncompacted():
    """One optimizer step under a real Schedule: compaction dispatch leaves
    the updated parameters bit-identical to the uncompacted kernel path."""
    from repro.configs.base import D2FTConfig
    from repro.core.d2ft import plan_schedule
    from repro.core.schedule import gates_from_schedule, live_slice_bounds
    from repro.data.synthetic import microbatch_assignment
    from repro.models.vit import ViTConfig, init_vit
    from repro.optim.optimizers import sgd
    from repro.train.loop import make_vit_step

    cfg = ViTConfig(n_layers=2, d_model=48, n_heads=6, d_ff=96, patch=8,
                    image_size=16, n_classes=4)
    params = init_vit(jax.random.PRNGKey(0), cfg)
    B, M = 10, 5
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((B, 16, 16, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 4, B))
    d2 = D2FTConfig(n_microbatches=M, n_pf=2, n_po=1)
    K = cfg.n_layers * cfg.n_heads
    sched = plan_schedule(d2, rng.random((K, M)) + .1,
                          rng.random((K, M)) + .1, cfg.n_layers, cfg.n_heads)
    mb_of = microbatch_assignment(B, M)
    gates = gates_from_schedule(sched, mb_of)
    bounds = live_slice_bounds(sched, mb_of)
    assert bounds[0] < cfg.n_heads * B and bounds[1] < cfg.n_heads * B

    opt = sgd(0.05)
    out = {}
    for name, lb in (("uncompacted", None), ("compacted", bounds)):
        step = jax.jit(make_vit_step(cfg, opt, True, use_kernel=True,
                                     live_bounds=lb))
        p2, _, metrics = step(params, opt.init(params), x, y, gates)
        out[name] = (p2, float(metrics["loss"]))
    assert abs(out["compacted"][1] - out["uncompacted"][1]) < 1e-6
    diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         out["compacted"][0], out["uncompacted"][0])
    assert max(jax.tree.leaves(diffs)) < 1e-6
