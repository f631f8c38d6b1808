"""Mamba-2 SSD (state-space duality) block, chunked-scan implementation.

Follows arXiv:2405.21060: per-head scalar decay a_t = exp(dt_t * A_h),
state update h_t = a_t h_{t-1} + dt_t * x_t B_t^T, output y_t = C_t h_t + D x_t.
Training/prefill uses the chunked algorithm (intra-chunk quadratic term +
inter-chunk recurrence via lax.scan); decode is the O(1) single-step
recurrence against a cached state.

Shapes: d_inner = expand * d_model, H = d_inner // head_dim (P), state N.
Single B/C group (ngroups=1) as in mamba2-130m.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.kernels import ops as kernel_ops
from repro.models.layers import dense_init


def _dims(d_model: int, cfg: SSMConfig):
    d_inner = cfg.expand * d_model
    H = d_inner // cfg.head_dim
    return d_inner, H, cfg.head_dim, cfg.state_dim


def init_ssd(key, d_model: int, cfg: SSMConfig, dtype):
    d_inner, H, P, N = _dims(d_model, cfg)
    conv_ch = d_inner + 2 * N
    ks = jax.random.split(key, 5)
    return {
        # in_proj -> [z (d_inner) | xBC (d_inner + 2N) | dt (H)]
        "w_in": dense_init(ks[0], d_model, 2 * d_inner + 2 * N + H, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_width, conv_ch)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.zeros((H,), dtype),          # A = -exp(A_log)
        "dt_bias": jnp.zeros((H,), dtype),
        "D": jnp.ones((H,), dtype),
        "norm_scale": jnp.ones((d_inner,), dtype),
        "w_out": dense_init(ks[2], d_inner, d_model, dtype),
    }


def _split_in(params, x, d_model, cfg):
    d_inner, H, P, N = _dims(d_model, cfg)
    zxbcdt = x @ params["w_in"]
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:2 * d_inner + 2 * N]
    dt = zxbcdt[..., 2 * d_inner + 2 * N:]
    return z, xBC, dt


def _causal_conv(xBC, conv_w, conv_b, prev: Optional[jnp.ndarray] = None):
    """Depthwise causal conv over seq. xBC: [B,S,Ch]; prev: [B,W-1,Ch]."""
    W = conv_w.shape[0]
    if prev is None:
        pad = jnp.zeros((xBC.shape[0], W - 1, xBC.shape[-1]), xBC.dtype)
    else:
        pad = prev
    xp = jnp.concatenate([pad, xBC], axis=1)       # [B, S+W-1, Ch]
    out = sum(xp[:, i:i + xBC.shape[1]] * conv_w[i] for i in range(W))
    return jax.nn.silu(out + conv_b)


def _gated_rmsnorm(y, z, scale, eps=1e-6):
    y = y * jax.nn.silu(z)
    var = jnp.mean(y.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    return (y.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(y.dtype) * scale


def ssd_chunked(xh, dt, A, Bm, Cm, chunk: int, return_final_state=False):
    """Core SSD. xh: [B,S,H,P]; dt: [B,S,H]; A: [H] (negative);
    Bm, Cm: [B,S,N]. Returns y: [B,S,H,P]; with ``return_final_state`` also
    the recurrent state after the last token [B,H,P,N] f32 (what a decode
    cache must carry to continue the sequence — the serving prefill dump).
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    Sp = -(-S // Q) * Q
    if Sp != S:
        # odd lengths zero-pad to the chunk: a padded row has dt == 0, so
        # its log-decay is 0 (identity state update) and its input is zero
        pad = ((0, 0), (0, Sp - S))
        xh = jnp.pad(xh, pad + ((0, 0), (0, 0)))
        dt = jnp.pad(dt, pad + ((0, 0),))
        Bm = jnp.pad(Bm, pad + ((0, 0),))
        Cm = jnp.pad(Cm, pad + ((0, 0),))
    nc = Sp // Q
    # decay per step (log-space), weighted input
    dA = dt * A[None, None, :]                          # [B,S,H] (negative)
    xbar = xh * dt[..., None]                           # dt-weighted input
    # reshape into chunks
    dAc = dA.reshape(Bsz, nc, Q, H)
    xc = xbar.reshape(Bsz, nc, Q, H, P)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)
    cum = jnp.cumsum(dAc, axis=2)                       # [B,nc,Q,H]
    total = cum[:, :, -1]                               # [B,nc,H]

    # ---- intra-chunk (quadratic within chunk): L[q,k] = exp(cum_q - cum_k) causal
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]        # [B,nc,Q(q),Q(k),H]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(diff), 0.0)
    CB = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)                  # [B,nc,Q,Q]
    y_intra = jnp.einsum("bcqk,bcqkh,bckhp->bcqhp", CB, L, xc)

    # ---- chunk states: state_c = sum_k exp(total - cum_k) * xbar_k B_k^T
    # (state accumulation in f32 — also keeps the scan carry dtype stable
    # under bf16 compute)
    decay_to_end = jnp.exp(total[:, :, None] - cum)             # [B,nc,Q,H]
    states = jnp.einsum("bckh,bckhp,bckn->bchpn", decay_to_end, xc,
                        Bc).astype(jnp.float32)

    # ---- inter-chunk recurrence over chunk index
    def step(carry, inp):
        st, tot = inp                                           # [B,H,P,N], [B,H]
        new = carry * jnp.exp(tot)[:, :, None, None] + st
        return new, carry                                        # emit state *before* this chunk
    init = jnp.zeros((Bsz, H, P, N), jnp.float32)
    final_state, prev_states = jax.lax.scan(
        step, init,
        (jnp.moveaxis(states, 1, 0),
         jnp.moveaxis(total, 1, 0).astype(jnp.float32)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)               # [B,nc,H,P,N]

    # ---- inter-chunk output: y_q += C_q . (exp(cum_q) * prev_state)
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp",
                         Cc.astype(jnp.float32), jnp.exp(cum), prev_states)
    y = (y_intra.astype(jnp.float32) + y_inter).reshape(Bsz, Sp, H, P)
    y = y.astype(xh.dtype)
    if Sp != S:
        y = y[:, :S]
    if return_final_state:
        return y, final_state
    return y


def apply_ssd(params, x, d_model: int, cfg: SSMConfig,
              return_state: bool = False,
              gates: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              use_kernel: bool = False,
              live_bounds: Optional[Tuple[int, int]] = None):
    """Training/prefill forward. x: [B,S,d_model] -> [B,S,d_model].

    gates: optional per-head D2FT gates (g_f, g_b), each [B, H] in {0, 1}
    with g_b <= g_f — the scan output is gated per (sample, head) *before*
    the D-residual (the skip connection IS the p_s shortcut path) with the
    (1 - g_b) share routed through stop_gradient. use_kernel routes the
    gated scan through the Pallas kernel (``ops.gated_ssd_scan``) with
    ``live_bounds`` = static (live_fwd, live_bwd) head-slice upper bounds
    for compaction dispatch; otherwise a masked stop-gradient mix over the
    dense chunked scan computes the same function (the reference VJP).

    return_state: additionally return the decode cache after the last token
    (same structure as ``init_ssd_cache``: the conv tail of raw xBC inputs
    plus the f32 recurrent state) — the serving prefill dump, so a decode
    loop can continue the sequence without replaying it token by token.
    """
    d_inner, H, P, N = _dims(d_model, cfg)
    z, xBC_raw, dt = _split_in(params, x, d_model, cfg)
    xBC = _causal_conv(xBC_raw, params["conv_w"], params["conv_b"])
    xin = xBC[..., :d_inner].reshape(*x.shape[:2], H, P)
    Bm = xBC[..., d_inner:d_inner + N]
    Cm = xBC[..., d_inner + N:]
    dt = jax.nn.softplus(dt + params["dt_bias"])
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    if gates is not None and use_kernel and not return_state:
        g_f, g_b = gates
        # same operand preprocessing as ssd_chunked; the kernel pads odd S
        dA = dt * A[None, None, :]
        xbar = xin * dt[..., None]
        lf, lb = live_bounds if live_bounds is not None else (None, None)
        y = kernel_ops.gated_ssd_scan(xbar, dA, Bm, Cm, g_f, g_b,
                                      chunk=cfg.chunk, live_fwd=lf,
                                      live_bwd=lb)
        y = y.astype(xin.dtype)
        state = None
    else:
        y = ssd_chunked(xin, dt, A, Bm, Cm, cfg.chunk,
                        return_final_state=return_state)
        state = None
        if return_state:
            y, state = y
        if gates is not None:
            g_f, g_b = gates
            gf = g_f[:, None, :, None].astype(y.dtype)
            gb = g_b[:, None, :, None].astype(y.dtype)
            y = gf * (gb * y + (1.0 - gb) * jax.lax.stop_gradient(y))
    y = y + params["D"][None, None, :, None] * xin
    y = y.reshape(*x.shape[:2], d_inner)
    y = _gated_rmsnorm(y, z, params["norm_scale"])
    out = y @ params["w_out"]
    if return_state:
        W = params["conv_w"].shape[0]
        pad = jnp.zeros((x.shape[0], W - 1, xBC_raw.shape[-1]), xBC_raw.dtype)
        conv_tail = jnp.concatenate([pad, xBC_raw], axis=1)[:, -(W - 1):]
        return out, {"conv": conv_tail, "state": state}
    return out


# -------------------------------------------------------------------- decode
def init_ssd_cache(batch: int, d_model: int, cfg: SSMConfig, dtype):
    d_inner, H, P, N = _dims(d_model, cfg)
    conv_ch = d_inner + 2 * N
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_ch), dtype),
        # recurrent state accumulates in f32 regardless of compute dtype
        "state": jnp.zeros((batch, H, P, N), jnp.float32),
    }


def decode_ssd(params, cache, x, d_model: int, cfg: SSMConfig):
    """One-token decode. x: [B,1,d_model]."""
    d_inner, H, P, N = _dims(d_model, cfg)
    z, xBC, dt = _split_in(params, x, d_model, cfg)
    conv_in = jnp.concatenate([cache["conv"], xBC], axis=1)     # [B,W,Ch]
    W = params["conv_w"].shape[0]
    out = sum(conv_in[:, i] * params["conv_w"][i] for i in range(W))
    xBC1 = jax.nn.silu(out + params["conv_b"])[:, None]         # [B,1,Ch]
    new_conv = conv_in[:, 1:]
    xin = xBC1[..., :d_inner].reshape(x.shape[0], H, P)
    Bm = xBC1[:, 0, d_inner:d_inner + N]
    Cm = xBC1[:, 0, d_inner + N:]
    dt1 = jax.nn.softplus(dt[:, 0] + params["dt_bias"])         # [B,H]
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    a = jnp.exp(dt1.astype(jnp.float32) * A[None, :])           # [B,H]
    dBx = jnp.einsum("bhp,bn,bh->bhpn", xin, Bm,
                     dt1).astype(jnp.float32)
    state = cache["state"] * a[:, :, None, None] + dBx
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32),
                   state).astype(x.dtype)
    y = y + params["D"][None, :, None] * xin
    y = y.reshape(x.shape[0], 1, d_inner)
    y = _gated_rmsnorm(y, z, params["norm_scale"])
    return y @ params["w_out"], {"conv": new_conv, "state": state}
