"""D2FT operation semantics: masked path gating, p_o kills subnet
gradients, p_s kills subnet contribution; scores."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import D2FTConfig, ModelConfig
from repro.core.d2ft import plan_schedule
from repro.core.schedule import P_F, P_O, P_S, Schedule
from repro.core.scores import (compute_scores, transformer_blocks,
                               weight_magnitude)
from repro.models.transformer import forward, init_model, lm_loss

CFG = ModelConfig(name="t", arch_type="dense", n_layers=4, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=97)


def _setup(seed=0, B=10, S=16):
    params = init_model(jax.random.PRNGKey(seed), CFG)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0, 97)
    return params, toks


def _schedule(seed=0, L=4, G=4, N=5, n_pf=3, n_po=1):
    rng = np.random.default_rng(seed)
    d2 = D2FTConfig(n_microbatches=N, n_pf=n_pf, n_po=n_po)
    bw = np.repeat(rng.random((L * G, 1)) + .1, N, 1)
    fw = rng.random((L * G, N)) + .1
    return plan_schedule(d2, bw, fw, L, G)


def test_all_pf_gates_equal_ungated():
    params, toks = _setup()
    ones = jnp.ones((4, 10, 4))
    l0, _ = forward(params, CFG, tokens=toks)
    l1, _ = forward(params, CFG, tokens=toks, gates=(ones, ones))
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), atol=1e-5)


def test_po_blocks_param_grads_and_ps_blocks_contribution():
    params, toks = _setup()
    L, B, G = 4, 10, 4
    # all p_o: forward equals full, but no gradient reaches block params
    g_f = jnp.ones((L, B, G))
    g_b = jnp.zeros((L, B, G))
    full, _ = forward(params, CFG, tokens=toks)
    po, _ = forward(params, CFG, tokens=toks, gates=(g_f, g_b))
    np.testing.assert_allclose(np.asarray(full), np.asarray(po), atol=1e-5)

    def loss(p, gates):
        return jnp.mean(forward(p, CFG, tokens=toks, gates=gates)[0] ** 2)

    grads = jax.grad(loss)(params, (g_f, g_b))
    block_grads = jax.tree.leaves(grads["cycles"])
    assert all(float(jnp.abs(g).max()) < 1e-12 for g in block_grads)

    # all p_s: block contribution removed -> logits == embedding-only model
    zero, _ = forward(params, CFG, tokens=toks,
                      gates=(jnp.zeros((L, B, G)), g_b))
    from repro.models.layers import apply_embedding, apply_norm
    x = apply_embedding(params["embed"], toks)
    x = apply_norm(params["final_norm"], x, CFG.norm)
    skip_logits = x @ params["unembed"]
    np.testing.assert_allclose(np.asarray(zero), np.asarray(skip_logits),
                               atol=1e-5)


def test_scores_shapes_and_variation():
    params, toks = _setup()
    mbs = [dict(tokens=toks[i * 2:(i + 1) * 2]) for i in range(5)]

    def loss_fn(p, mb):
        return lm_loss(p, CFG, mb["tokens"], mb["tokens"])[0]

    bw, fw = compute_scores(loss_fn, params,
                            lambda t: transformer_blocks(t, CFG), mbs, G=4)
    assert bw.shape == (16, 5) and fw.shape == (16, 5)
    assert np.allclose(bw, bw[:, :1])          # magnitude: mb-independent
    assert not np.allclose(fw, fw[:, :1])      # fisher: mb-dependent
    assert (bw > 0).all() and (fw >= 0).all()


def test_heterogeneous_capacities():
    """Paper §IV-D: per-device capacities; fast devices get more p_f."""
    rng = np.random.default_rng(0)
    L, G, N = 2, 4, 5
    d2 = D2FTConfig(n_microbatches=N, n_pf=2, n_po=2)
    bw = np.repeat(rng.random((L * G, 1)) + .1, N, 1)
    fw = rng.random((L * G, N)) + .1
    cap_pf = np.full(L * G, 2.0)
    cap_pf[:4] = 3.0                            # 4 "fast" devices
    sched = plan_schedule(d2, bw, fw, L, G, cap_pf=cap_pf, cap_po=0.8)
    per_dev_pf = (sched.table == P_F).sum(1)
    assert (per_dev_pf[:4] == 3).all() and (per_dev_pf[4:] == 2).all()
