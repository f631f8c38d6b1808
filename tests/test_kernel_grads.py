"""Gate-aware backward pass of the Pallas attention kernel.

Grad parity (dq/dk/dv) vs the reference VJP under random p_f/p_o/p_s gate
mixes, exact-zero gradients and skipped MXU work for g_b == 0 slices, and
end-to-end kernel-path fine-tuning driven by a real Schedule.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import d2ft_attention as d2a
from repro.kernels.ops import gated_attention
from repro.kernels.ref import d2ft_attention_ref, gated_attention_ref

TOL = 1e-4   # fp32, interpret mode


def _random_mix(rng, B, H):
    """ops 0=p_f, 1=p_o, 2=p_s -> (g_f, g_b) with g_b <= g_f."""
    ops_ = rng.integers(0, 3, (B, H))
    g_f = jnp.asarray((ops_ != 2).astype(np.float32))
    g_b = jnp.asarray((ops_ == 0).astype(np.float32))
    return ops_, g_f, g_b


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
@pytest.mark.parametrize("S,hd", [(128, 64), (256, 32), (384, 32)])
def test_grad_parity_vs_reference_vjp(causal, window, S, hd):
    B, H = 2, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    do = jax.random.normal(ks[3], (B, H, S, hd))
    rng = np.random.default_rng(hash((causal, window, S)) % 2 ** 31)
    _, g_f, g_b = _random_mix(rng, B, H)

    out_k, vjp_k = jax.vjp(
        lambda q, k, v: gated_attention(q, k, v, g_f, g_b, causal=causal,
                                        window=window, interpret=True),
        q, k, v)
    out_r, vjp_r = jax.vjp(
        lambda q, k, v: gated_attention_ref(q, k, v, g_f, g_b, causal=causal,
                                            window=window),
        q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=TOL, rtol=TOL)
    for name, a, b in zip(("dq", "dk", "dv"), vjp_k(do), vjp_r(do)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL,
                                   rtol=TOL, err_msg=name)


def test_forward_matches_forward_only_oracle():
    """g_f drives the forward exactly like the forward-only kernel/oracle."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 3, 128, 64))
    k = jax.random.normal(ks[1], (2, 3, 128, 64))
    v = jax.random.normal(ks[2], (2, 3, 128, 64))
    g_f = jnp.asarray([[1., 0, 1], [0, 1, 1]])
    g_b = jnp.zeros_like(g_f)
    out = gated_attention(q, k, v, g_f, g_b, interpret=True)
    ref = d2ft_attention_ref(q, k, v, g_f)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=TOL,
                               rtol=TOL)


def test_gb_zero_heads_have_exact_zero_grads():
    B, H, S, hd = 2, 4, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    rng = np.random.default_rng(7)
    ops_, g_f, g_b = _random_mix(rng, B, H)

    def loss(q, k, v):
        return gated_attention(q, k, v, g_f, g_b, interpret=True).sum()

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, q, q)
    gb = np.asarray(g_b)
    for g in (dq, dk, dv):
        g = np.asarray(g)
        assert np.all(g[gb == 0] == 0.0)
    # p_f heads do produce gradient signal
    assert float(np.abs(np.asarray(dq)[gb == 1]).max()) > 0.0


def test_gb_zero_slices_do_no_backward_matmul_work():
    """Counts *executed* backward compute blocks via the kernel test hook.

    Static compiled-FLOPs can't observe the skip (interpret mode lowers the
    grid to a loop whose body XLA counts once regardless of taken branches),
    so we count the blocks that actually run: all-p_f executes the full
    block set of the fused backward kernel (one hook call per tile — the
    old split dq/dkv pair fired twice), all-p_o/p_s executes none, and a
    mix executes exactly the p_f share.
    """
    B, H, S, hd = 1, 4, 384, 32        # a flash-path sequence: 3x3 tiles
    bq = bk = 128
    q = jax.random.normal(jax.random.PRNGKey(3), (B, H, S, hd))
    count = {"n": 0}
    d2a.on_backward_block = lambda: count.__setitem__("n", count["n"] + 1)
    try:
        def run(g_b):
            def loss(q, k, v):
                return d2a.gated_flash_attention(
                    q, k, v, jnp.ones((B, H)), jnp.asarray(g_b),
                    True, 0, bq, bk, True).sum()
            count["n"] = 0
            jax.grad(loss, argnums=(0, 1, 2))(q, q, q)
            jax.effects_barrier()       # debug callbacks are async
            return count["n"]

        # causal live tiles per (b, h): 6 of 9; ONE fused backward kernel
        per_head = d2a.live_block_count(S, bq, bk, True, 0)
        assert run(np.ones((B, H), np.float32)) == B * H * per_head
        assert run(np.zeros((B, H), np.float32)) == 0
        half = np.asarray([[1., 1., 0., 0.]], np.float32)
        assert run(half) == 2 * per_head
        # the analytic accounting reports 5 matmuls per executed tile
        _, bwd_flops = d2a.gated_attention_flops(
            np.ones((B, H)), half, S, hd, causal=True, block_q=bq,
            block_k=bk)
        assert bwd_flops == 2 * per_head * 5 * (2 * bq * bk * hd)
    finally:
        d2a.on_backward_block = None


def test_awkward_seq_len_pads_and_matches():
    """S=137 (prime) takes the pad-to-tile-multiple path: forward and
    dq/dk/dv still match the reference, with zero grads in nothing real."""
    B, H, S, hd = 1, 2, 137, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    do = jax.random.normal(ks[3], (B, H, S, hd))
    g_f = jnp.asarray([[1., 1.]])
    g_b = jnp.asarray([[1., 0.]])

    out_k, vjp_k = jax.vjp(
        lambda q, k, v: gated_attention(q, k, v, g_f, g_b, interpret=True),
        q, k, v)
    out_r, vjp_r = jax.vjp(
        lambda q, k, v: gated_attention_ref(q, k, v, g_f, g_b), q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=TOL, rtol=TOL)
    for name, a, b in zip(("dq", "dk", "dv"), vjp_k(do), vjp_r(do)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL,
                                   rtol=TOL, err_msg=name)


@pytest.mark.parametrize("bounds", [False, True], ids=["all", "bounds"])
@pytest.mark.parametrize("causal,window", [(False, 0), (True, 0), (True, 3)],
                         ids=["full", "causal", "window"])
@pytest.mark.parametrize("S", [5, 197, 200, 256])
def test_short_path_parity_vs_reference(S, causal, window, bounds):
    """Sequences of at most 256 rows run one whole tile per slice, several
    slices per grid step: forward and dq/dk/dv match the reference VJP,
    g_b == 0 slices get exact zero gradients, and the backward executes
    exactly one block per live g_b slice. The bounds are one above the
    live counts, so the compacted launch carries dead slices, and 13
    slices are no multiple of a step's slices."""
    B, H, hd = 1, 13, 32
    ks = jax.random.split(jax.random.PRNGKey(S), 4)
    q, k, v, do = (jax.random.normal(kk, (B, H, S, hd)) for kk in ks)
    ops_, g_f, g_b = _random_mix(np.random.default_rng(S + window), B, H)
    n_f, n_b = int((ops_ != 2).sum()), int((ops_ == 0).sum())
    assert 0 < n_b < n_f < B * H
    lf, lb = (n_f + 1, n_b + 1) if bounds else (None, None)

    grids = {}
    count = {"n": 0}
    d2a.on_dispatch = lambda kind, grid: grids.__setitem__(kind, grid)
    d2a.on_backward_block = lambda: count.__setitem__("n", count["n"] + 1)
    jax.clear_caches()                   # hooks are read at trace time
    try:
        out_k, vjp_k = jax.vjp(
            lambda q, k, v: gated_attention(
                q, k, v, g_f, g_b, causal=causal, window=window,
                interpret=True, live_fwd=lf, live_bwd=lb), q, k, v)
        grads_k = vjp_k(do)
        jax.effects_barrier()
    finally:
        d2a.on_dispatch = None
        d2a.on_backward_block = None

    assert len(grids["fwd"]) == len(grids["bwd"]) == 1   # the short grids
    assert count["n"] == n_b

    @jax.jit
    def ref(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: gated_attention_ref(
            q, k, v, g_f, g_b, causal=causal, window=window), q, k, v)
        return out, vjp(do)

    out_r, grads_r = ref(q, k, v, do)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=TOL, rtol=TOL)
    gb = np.asarray(g_b)
    for name, a, b in zip(("dq", "dk", "dv"), grads_k, grads_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL,
                                   rtol=TOL, err_msg=name)
        assert np.all(np.asarray(a)[gb == 0] == 0.0), name


def test_attention_geometry():
    """The short path takes every sequence of at most 256 rows (rounded up
    to the sublane) as one unpadded tile; longer ones keep select_blocks'
    flash tiles. At ViT-S/16's shapes a grid step takes 8 forward and 4
    backward slices, and a launch rounds up to whole steps."""
    geo = d2a.attention_geometry
    assert geo(197, 128, 128) == (197, 197, 197)
    assert geo(5, 128, 128) == (5, 5, 5)
    assert geo(256, 128, 128) == (256, 256, 256)
    assert geo(257, 128, 128) == d2a.select_blocks(257, 128, 128) \
        == (128, 128, 384)
    assert d2a.is_short(256, 256, 256)
    assert not d2a.is_short(512, 512, 512)
    assert not d2a.is_short(256, 128, 128)
    assert d2a.slices_per_step(197, 64, 4, "fwd") == 8
    assert d2a.slices_per_step(197, 64, 4, "bwd") == 4
    assert d2a.launch_shape(960, 8) == (120, 8)
    assert d2a.launch_shape(720, 4) == (180, 4)
    assert d2a.launch_shape(13, 8) == (2, 7)      # one dead slice, not three


def test_select_blocks_geometry():
    from repro.kernels.d2ft_attention import select_blocks
    assert select_blocks(256, 128, 128) == (128, 128, 256)   # exact
    assert select_blocks(5, 128, 128) == (8, 8, 8)           # tiny seq pads
    assert select_blocks(192, 128, 128) == (96, 96, 192)     # near divisor
    assert select_blocks(257, 128, 128) == (128, 128, 384)   # pad, no slivers


def test_gates_get_zero_cotangents():
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 128, 32))
    g = jnp.ones((1, 2))

    def loss(g_f, g_b):
        return gated_attention(q, q, q, g_f, g_b, interpret=True).sum()

    dgf, dgb = jax.grad(loss, argnums=(0, 1))(g, g)
    assert float(jnp.abs(dgf).max()) == 0.0
    assert float(jnp.abs(dgb).max()) == 0.0


# --------------------------------------------------------------- end to end
def _tiny_vit():
    from repro.models.vit import ViTConfig
    return ViTConfig(n_layers=2, d_model=48, n_heads=6, d_ff=96, patch=8,
                     image_size=16, n_classes=4)


def _real_schedule(cfg, B, M=5, G=None):
    from repro.configs.base import D2FTConfig
    from repro.core.d2ft import plan_schedule
    from repro.core.schedule import gates_from_schedule
    from repro.data.synthetic import microbatch_assignment
    G = G or cfg.n_heads
    rng = np.random.default_rng(0)
    d2 = D2FTConfig(n_microbatches=M, n_pf=2, n_po=1)
    K = cfg.n_layers * G
    sched = plan_schedule(d2, rng.random((K, M)) + .1, rng.random((K, M)) + .1,
                          cfg.n_layers, G)
    return sched, gates_from_schedule(sched, microbatch_assignment(B, M))


def test_vit_step_kernel_matches_masked_path():
    """One optimizer step, kernel vs masked path, same real Schedule."""
    from repro.models.vit import init_vit
    from repro.optim.optimizers import sgd
    from repro.train.loop import make_vit_step

    cfg = _tiny_vit()
    params = init_vit(jax.random.PRNGKey(0), cfg)
    B = 10
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((B, 16, 16, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 4, B))
    _, gates = _real_schedule(cfg, B)
    opt = sgd(0.05)

    out = {}
    for uk in (False, True):
        step = jax.jit(make_vit_step(cfg, opt, True, use_kernel=uk))
        p2, _, metrics = step(params, opt.init(params), x, y, gates)
        out[uk] = (p2, metrics)
    assert abs(float(out[False][1]["loss"]) -
               float(out[True][1]["loss"])) < 1e-5
    diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         out[False][0], out[True][0])
    assert max(jax.tree.leaves(diffs)) < TOL


def test_finetune_vit_kernel_end_to_end():
    """The fine-tune loop runs with use_kernel=True driven by a Schedule."""
    from repro.models.vit import init_vit
    from repro.optim.optimizers import sgd
    from repro.train.loop import finetune_vit

    cfg = _tiny_vit()
    params = init_vit(jax.random.PRNGKey(0), cfg)
    B, M = 10, 5
    rng = np.random.default_rng(2)
    batches = [(rng.standard_normal((B, 16, 16, 3)).astype(np.float32),
                rng.integers(0, 4, B)) for _ in range(2)]
    sched, _ = _real_schedule(cfg, B, M)

    params, _, log = finetune_vit(
        params, cfg, sgd(0.05), iter(batches), steps=2,
        schedule_fn=lambda i, p, im, lb: sched if i == 0 else None,
        n_microbatches=M, use_kernel=True)
    assert len(log.losses) == 2
    assert all(np.isfinite(l) for l in log.losses)


def test_llm_loss_kernel_matches_masked_path():
    """GQA + global/local pattern through lm_loss, kernel vs masked."""
    from repro.configs.base import ATTN_GLOBAL, ATTN_LOCAL, ModelConfig
    from repro.models.transformer import init_model, lm_loss

    cfg = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=97,
                      block_pattern=(ATTN_GLOBAL, ATTN_LOCAL), window=16)
    params = init_model(jax.random.PRNGKey(0), cfg)
    B, S, G = 4, 64, 4
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, 97, (B, S)))
    labels = jnp.asarray(rng.integers(0, 97, (B, S)))
    ops_ = rng.integers(0, 3, (cfg.n_layers, B, G))
    gates = (jnp.asarray((ops_ != 2).astype(np.float32)),
             jnp.asarray((ops_ == 0).astype(np.float32)))

    out = {}
    for uk in (False, True):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(p, cfg, toks, labels, gates=gates,
                              use_kernel=uk)[0])(params)
        out[uk] = (float(loss), grads)
    assert abs(out[False][0] - out[True][0]) < 1e-5
    diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         out[False][1], out[True][1])
    assert max(jax.tree.leaves(diffs)) < 5e-4


@pytest.mark.parametrize("d2ft", [True, False], ids=["d2ft", "full"])
def test_finetune_kernel_matches_masked_path(d2ft):
    """The LM loop ``finetune``, kernel vs masked path over 3 steps: the
    same losses and final params, with a planned D2FT schedule (the kernel
    path's live-slice bounds and compaction dispatch) and without one."""
    from repro.configs.base import D2FTConfig, ModelConfig
    from repro.data.synthetic import lm_batches
    from repro.models.transformer import init_model
    from repro.optim.optimizers import sgd
    from repro.train.loop import finetune

    cfg = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64)
    d2 = D2FTConfig(n_microbatches=4, n_pf=2, n_po=1, head_groups=4) \
        if d2ft else None
    out = {}
    for uk in (False, True):
        params = init_model(jax.random.PRNGKey(0), cfg)
        batches = lm_batches(0, cfg.vocab_size, batch=8, seq=16, steps=3)
        out[uk] = finetune(params, cfg, d2, sgd(0.1), batches, steps=3,
                           use_kernel=uk)
    (p_m, _, log_m), (p_k, _, log_k) = out[False], out[True]
    assert len(log_k.losses) == 3
    np.testing.assert_allclose(log_k.losses, log_m.losses, atol=1e-5)
    diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), p_m, p_k)
    assert max(jax.tree.leaves(diffs)) < TOL
