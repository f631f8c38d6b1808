"""RG-LRU recurrent block (Griffin / RecurrentGemma, arXiv:2402.19427).

Recurrence (per channel):
    r_t = sigmoid(W_a x_t)                 (recurrence gate)
    i_t = sigmoid(W_x x_t)                 (input gate)
    log a_t = -c * softplus(Lambda) * r_t  (c = 8)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Training/prefill uses jax.lax.associative_scan on the affine recurrence;
decode is the single-step update. The full recurrent block is:
    x -> [gate branch: linear+gelu] * [linear -> conv1d -> RG-LRU] -> out proj
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RGLRUConfig
from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kernel_ref
from repro.models.layers import dense_init

_C = 8.0
# chunk used by both the gated Pallas kernel and its masked reference mix --
# they must match so the two paths see identical chunked-scan numerics
_SCAN_CHUNK = 128


def init_rglru(key, d_model: int, cfg: RGLRUConfig, dtype):
    width = cfg.lru_width or d_model
    ks = jax.random.split(key, 6)
    return {
        "w_gate_branch": dense_init(ks[0], d_model, width, dtype),
        "w_rec_branch": dense_init(ks[1], d_model, width, dtype),
        "conv_w": (jax.random.normal(ks[2], (cfg.conv_width, width)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((width,), dtype),
        "w_a": dense_init(ks[3], width, width, dtype, scale=0.02),
        "b_a": jnp.zeros((width,), dtype),
        "w_x": dense_init(ks[4], width, width, dtype, scale=0.02),
        "b_x": jnp.zeros((width,), dtype),
        # Lambda init so that a^c in [0.9, 0.999] at r=1 (Griffin appendix)
        "Lambda": jnp.asarray(
            jnp.log(jnp.expm1(-jnp.log(jnp.linspace(0.9, 0.999, width)) / _C)),
            dtype),
        "w_out": dense_init(ks[5], width, d_model, dtype),
    }


def _rglru_log_gates(params, x):
    """x: [..., width] (post-conv). Returns (log_a, gated_input) in f32 —
    the log-space operands the chunked/kernel scan consumes directly."""
    r = jax.nn.sigmoid(x @ params["w_a"] + params["b_a"])
    i = jax.nn.sigmoid(x @ params["w_x"] + params["b_x"])
    log_a = -_C * jax.nn.softplus(params["Lambda"].astype(jnp.float32)) * \
        r.astype(jnp.float32)
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    b = beta * (i * x).astype(jnp.float32)
    return log_a, b


def _rglru_gates(params, x):
    """x: [..., width] (post-conv). Returns (a, gated_input)."""
    log_a, b = _rglru_log_gates(params, x)
    return jnp.exp(log_a), b


def _assoc_scan(a, b, h0=None):
    """Affine scan h_t = a_t h_{t-1} + b_t over axis=1. a,b: [B,S,W]."""
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)
    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2
    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def _causal_conv(x, conv_w, conv_b, prev=None):
    W = conv_w.shape[0]
    if prev is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[-1]), x.dtype)
    else:
        pad = prev
    xp = jnp.concatenate([pad, x], axis=1)
    return sum(xp[:, i:i + x.shape[1]] * conv_w[i] for i in range(W)) + conv_b


def _gated_scan_ref(log_a, b, g_f, g_b):
    """Masked-path gated scan: the chunked log-space oracle (which the
    kernel mirrors op for op) with the stop-gradient mix, zero-padded to
    the chunk like ``ops.gated_rglru_scan`` pads for the kernel."""
    S = log_a.shape[1]
    Q = min(_SCAN_CHUNK, S)
    Sp = -(-S // Q) * Q
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0))
        log_a, b = jnp.pad(log_a, pad), jnp.pad(b, pad)
    h = kernel_ref.gated_rglru_ref(log_a, b, g_f, g_b, chunk=_SCAN_CHUNK)
    return h[:, :S] if Sp != S else h


def apply_rglru(params, x, cfg: RGLRUConfig,
                return_state: bool = False,
                gates: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                use_kernel: bool = False,
                live_bounds: Optional[Tuple[int, int]] = None):
    """Training/prefill forward. x: [B,S,d_model] -> [B,S,d_model].

    gates: optional per-group D2FT gates (g_f, g_b), each [B, G] in {0, 1}
    with g_b <= g_f — the G gate groups slice the LRU width into contiguous
    channel bands, gated on the scan output before the gate-branch multiply
    (the (1 - g_b) share routes through stop_gradient). use_kernel runs the
    gated scan in the Pallas kernel (``ops.gated_rglru_scan``) with
    ``live_bounds`` = static (live_fwd, live_bwd) band-slice upper bounds
    for compaction dispatch; otherwise the chunked reference scan with a
    masked stop-gradient mix computes the same function.

    return_state: additionally return the decode cache after the last token
    (``init_rglru_cache`` structure: the conv tail of raw pre-conv inputs
    plus the f32 hidden state) — the serving prefill dump."""
    gate = jax.nn.gelu(x @ params["w_gate_branch"])
    u_raw = x @ params["w_rec_branch"]
    u = _causal_conv(u_raw, params["conv_w"], params["conv_b"])
    if gates is not None:
        g_f, g_b = gates
        log_a, b = _rglru_log_gates(params, u)
        if use_kernel and not return_state:
            lf, lb = live_bounds if live_bounds is not None else (None, None)
            h32 = kernel_ops.gated_rglru_scan(log_a, b, g_f, g_b,
                                              chunk=_SCAN_CHUNK,
                                              live_fwd=lf, live_bwd=lb)
        else:
            h32 = _gated_scan_ref(log_a, b, g_f, g_b)
    else:
        a, b = _rglru_gates(params, u)
        h32 = _assoc_scan(a, b)                         # [B,S,W] f32
    h = h32.astype(x.dtype)
    y = h * gate
    out = y @ params["w_out"]
    if return_state:
        W = params["conv_w"].shape[0]
        pad = jnp.zeros((x.shape[0], W - 1, u_raw.shape[-1]), u_raw.dtype)
        conv_tail = jnp.concatenate([pad, u_raw], axis=1)[:, -(W - 1):]
        return out, {"conv": conv_tail, "h": h32[:, -1]}
    return out


def init_rglru_cache(batch: int, d_model: int, cfg: RGLRUConfig, dtype):
    width = cfg.lru_width or d_model
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, width), dtype),
        "h": jnp.zeros((batch, width), jnp.float32),
    }


def decode_rglru(params, cache, x, cfg: RGLRUConfig):
    """One-token decode. x: [B,1,d_model]."""
    gate = jax.nn.gelu(x @ params["w_gate_branch"])
    u = x @ params["w_rec_branch"]
    conv_in = jnp.concatenate([cache["conv"], u], axis=1)
    W = params["conv_w"].shape[0]
    u1 = sum(conv_in[:, i] * params["conv_w"][i] for i in range(W)) + params["conv_b"]
    a, b = _rglru_gates(params, u1)                     # [B,W]
    h = a * cache["h"] + b
    y = (h.astype(x.dtype)[:, None] * gate)
    return y @ params["w_out"], {"conv": conv_in[:, 1:], "h": h}
