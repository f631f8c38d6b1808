"""Plain jax.numpy pieces of the references (no kernels, no batching tricks).

Every matmul goes through ``mm`` with an explicit precision, so the
reference computes at ``highest`` on a TPU and the control, given
bfloat16 inputs, at bfloat16. D2FT gating is written in its plainest
form: the heads (and MLP columns) of p_f groups carry gradients, those of
p_o groups pass their forward value with no gradient, and those of p_s
groups add nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mm(a, b, precision):
    return jnp.matmul(a, b, precision=precision)


def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense(key, n_in, n_out, dtype):
    return normal(key, (n_in, n_out), n_in ** -0.5, dtype)


def layer_norm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def rope(x, theta):
    """Rotary embedding over the whole head, halves rotated ([B,S,H,hd])."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, causal, precision):
    """Softmax attention of [B,S,H,hd] tensors; returns [B,S,H,hd]."""
    S, hd = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision) \
        * jnp.asarray(hd ** -0.5, q.dtype)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, jnp.asarray(-1e30, s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=precision)


def gated_project(h, w, live, dead, precision):
    """h [B,S,W] through w [W,D], where the W inputs fall in G equal
    contiguous groups and live/dead [B,G] say per sample which groups carry
    gradients (p_f) and which pass only their value (p_o)."""
    B, S, W = h.shape
    G = live.shape[-1]
    rep = W // G
    lw = jnp.repeat(live, rep, axis=-1)[:, None, :].astype(h.dtype)
    dw = jnp.repeat(dead, rep, axis=-1)[:, None, :].astype(h.dtype)
    return mm(h * lw, w, precision) + jax.lax.stop_gradient(
        mm(h * dw, w, precision))


def gate_split(gf, gb):
    """(live, dead) group masks from forward and backward gates."""
    return gf * gb, gf * (1.0 - gb)


def mean_xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)
