"""The gated output projection (``transformer.gated_linear``) against the
grouped formulation it replaced.

The old path formed every group's projected contribution as a [B,S,G,D]
tensor (an einsum per head or per FFN column block), mixed it with
``gate_mix`` and summed over G. ``gated_linear`` is one matmul of the
hidden with each group's columns scaled by g_f, and a backward that masks
both cotangents by g_f * g_b. These tests pin that the two agree in value
and gradient for any gates, over a ViT training trajectory on the masked
and the kernel path, and that the G-fold tensor is gone from the step.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import vit_small_paper
from repro.configs.base import D2FTConfig
from repro.core.d2ft import plan_schedule
from repro.core.schedule import gates_from_schedule
from repro.data.synthetic import microbatch_assignment
from repro.models import transformer
from repro.models.transformer import gate_mix, gated_linear
from repro.models.vit import init_vit
from repro.optim.optimizers import sgd
from repro.train.loop import make_vit_step


# ------------------------------------------------ the grouped formulation
def _group_project(heads_out, wo, G):
    B, S, H, hd = heads_out.shape
    per_head = jnp.einsum("bshd,hdD->bshD", heads_out,
                          wo.reshape(H, hd, wo.shape[-1]))
    return per_head.reshape(B, S, G, H // G, -1).sum(axis=3)


def _grouped_proj(kind, x, w, layer_gates, hd):
    """The replaced path: per-group contributions, gate_mix, sum over G."""
    g_f, g_b = layer_gates
    G = g_f.shape[-1]
    B, S, F = x.shape
    if kind == "attn":
        c_g = _group_project(x.reshape(B, S, F // hd, hd), w, G)
    else:
        c_g = jnp.einsum("bsgf,gfD->bsgD", x.reshape(B, S, G, F // G),
                         w.reshape(G, F // G, w.shape[-1]))
    return gate_mix(c_g, g_f, g_b).sum(axis=2)


# ------------------------------------------------------- the op alone
B, S, D, HD = 4, 7, 32, 16
SHAPES = {"attn_one_head_per_group": ("attn", 6 * HD, 6),
          "attn_two_heads_per_group": ("attn", 12 * HD, 6),
          "mlp": ("mlp", 96, 6)}


def _gates(kind, G, rng):
    if kind == "all_pf":
        return np.ones((B, G)), np.ones((B, G))
    if kind == "all_ps":
        return np.zeros((B, G)), np.zeros((B, G))
    if kind == "mixed":
        state = rng.integers(0, 3, (B, G))      # 0 p_f, 1 p_o, 2 p_s
        state[0, :3] = (0, 1, 2)
        return (state != 2).astype(float), (state == 0).astype(float)
    return rng.random((B, G)), rng.random((B, G))     # non-binary


@pytest.mark.parametrize("gates", ["all_pf", "all_ps", "mixed", "nonbinary"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_gated_linear_matches_grouped(shape, gates):
    kind, F, G = SHAPES[shape]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, S, F)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((F, D)) / np.sqrt(F), jnp.float32)
    dy = jnp.asarray(rng.standard_normal((B, S, D)), jnp.float32)
    g_f, g_b = (jnp.asarray(g, jnp.float32) for g in _gates(gates, G, rng))

    def new(x, w, g_f, g_b):
        return transformer._gated_proj(kind, x, w, (g_f, g_b))

    def old(x, w, g_f, g_b):
        return _grouped_proj(kind, x, w, (g_f, g_b), HD)

    def rel(got, want):      # error relative to the reference's largest
        return float(jnp.abs(got - want).max()
                     / jnp.maximum(jnp.abs(want).max(), 1e-30))

    y_new, vjp_new = jax.vjp(new, x, w, g_f, g_b)
    y_old, vjp_old = jax.vjp(old, x, w, g_f, g_b)
    assert rel(y_new, y_old) <= 1e-5
    dx_new, dw_new, dgf, dgb = vjp_new(dy)
    dx_old, dw_old = vjp_old(dy)[:2]
    assert rel(dx_new, dx_old) <= 1e-6
    assert rel(dw_new, dw_old) <= 1e-6
    if gates == "all_ps":
        assert not jnp.any(y_new) and not jnp.any(dx_new) \
            and not jnp.any(dw_new)
    # the gates are schedule constants: no cotangent reaches them, neither
    # through the column masks nor through the [B, G] gates behind them
    assert not jnp.any(dgf) and not jnp.any(dgb)
    cols = jnp.asarray(rng.random((B, 1, F)), jnp.float32)
    _, vjp_op = jax.vjp(gated_linear, x, w, cols, cols * 0.5)
    _, _, dcf, dcm = vjp_op(dy)
    assert not jnp.any(dcf) and not jnp.any(dcm)


# ---------------------------------------------- a ViT training trajectory
VIT = vit_small_paper.smoke_config()
NB, M, STEPS = 10, 5, 3


@pytest.fixture(scope="module")
def vit_setup():
    params = init_vit(jax.random.PRNGKey(0), VIT)
    rng = np.random.default_rng(1)
    side = VIT.image_size
    x = jnp.asarray(rng.standard_normal((NB, side, side, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, VIT.n_classes, NB))
    K = VIT.n_layers * VIT.n_heads
    sched = plan_schedule(D2FTConfig(n_microbatches=M, n_pf=3, n_po=1),
                          rng.random((K, M)) + .1, rng.random((K, M)) + .1,
                          VIT.n_layers, VIT.n_heads)
    gates = gates_from_schedule(sched, microbatch_assignment(NB, M))
    return params, x, y, gates


def _grouped(kind, x, w, layer_gates):
    return _grouped_proj(kind, x, w, layer_gates, VIT.d_model // VIT.n_heads)


def _trajectory(vit_setup, use_kernel):
    params, x, y, gates = vit_setup
    opt = sgd(0.05)
    step = jax.jit(make_vit_step(VIT, opt, True, use_kernel=use_kernel))
    p, s = params, opt.init(params)
    for _ in range(STEPS):
        p, s, _ = step(p, s, x, y, gates)
    return p


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["masked", "kernel"])
def test_vit_trajectory_matches_grouped(vit_setup, use_kernel, monkeypatch):
    got = _trajectory(vit_setup, use_kernel)
    monkeypatch.setattr(transformer, "_gated_proj", _grouped)
    want = _trajectory(vit_setup, use_kernel)
    diff = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), got, want)
    assert max(jax.tree.leaves(diff)) <= 1e-6
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), got,
                         vit_setup[0])
    assert min(jax.tree.leaves(moved["blocks"])) > 0


# ------------------------------------------------------ structural guard
def _lowered_step(vit_setup, use_gates, use_kernel=False):
    params, x, y, gates = vit_setup
    opt = sgd(0.05)
    step = jax.jit(make_vit_step(VIT, opt, use_gates, use_kernel=use_kernel))
    return step.lower(params, opt.init(params), x, y,
                      gates if use_gates else None).as_text(dialect="hlo")


def test_no_group_fold_tensor_in_gated_step(vit_setup, monkeypatch):
    S = (VIT.image_size // VIT.patch) ** 2 + 1
    fold = f"f32[{NB},{S},{VIT.n_heads},{VIT.d_model}]"
    for use_kernel in (False, True):
        assert fold not in _lowered_step(vit_setup, True, use_kernel)
    # the guard sees the tensor where the grouped formulation builds it
    monkeypatch.setattr(transformer, "_gated_proj", _grouped)
    assert fold in _lowered_step(vit_setup, True)


@pytest.mark.parametrize("use_gates", [True, False], ids=["gated", "ungated"])
def test_gated_proj_hook_counts(vit_setup, use_gates, monkeypatch):
    seen = []
    monkeypatch.setattr(transformer, "on_gated_proj",
                        lambda kind, shape, G: seen.append((kind, shape, G)))
    _lowered_step(vit_setup, use_gates)
    if not use_gates:
        assert seen == []
        return
    S = (VIT.image_size // VIT.patch) ** 2 + 1
    assert len(seen) == 2 * VIT.n_layers
    assert sorted(set(seen)) == [
        ("attn", (NB, S, VIT.d_model), VIT.n_heads),
        ("mlp", (NB, S, VIT.d_ff), VIT.n_heads)]
