"""Composable transformer backbone: ModelConfig -> init / forward / decode.

Design notes
------------
* Layers are grouped into *pattern cycles* (cfg.block_pattern) and executed
  with jax.lax.scan over stacked per-cycle params, keeping HLO size O(1) in
  depth (64-layer configs compile as a 1-cycle body). Remainder layers (when
  n_layers % len(pattern) != 0) run unstacked after the scan.
* D2FT gating: ``gates = (g_f, g_b)`` with shape [n_layers, B, G] each.
  Per block, the residual contribution is decomposed into G head/width
  groups c_g and mixed as
      c_eff = g_f * (g_b * c_g + (1 - g_b) * stop_gradient(c_g)),
  which implements p_f (1,1), p_o (1,0), p_s (0,·) exactly: p_o keeps the
  forward value but kills every gradient (params *and* activations) through
  the subnet for that sample; p_s removes the contribution so only the
  residual route remains. This is the masked reference path; the kernel
  path (``use_kernel=True``) computes the same function with gated Pallas
  kernels that skip the gated slices.
* Attention and dense-MLP groups are contiguous input columns of the output
  projection (``wo``, ``w_down``), so the mix above is one masked matmul,
  ``gated_linear``: the forward scales each group's columns by g_f, the
  backward masks both cotangents by g_f * g_b. No [B,S,G,D] per-group
  tensor is formed.
* MoE blocks treat the routed FFN as a single D2FT group (G position 0).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, RGLRU, SSD,
                                ModelConfig)
from repro.kernels import contract as kernel_contract
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_norm, dense_init, init_embedding,
                                 init_mlp, init_norm, softcap, _act)


# ============================================================ gating helpers
def gate_mix(c_g, g_f, g_b):
    """c_g: [B,S,G,D]; g_f,g_b: [B,G] in {0,1}. See module docstring."""
    gf = g_f[:, None, :, None].astype(c_g.dtype)
    gb = g_b[:, None, :, None].astype(c_g.dtype)
    return gf * (gb * c_g + (1.0 - gb) * jax.lax.stop_gradient(c_g))


# Hook: when set to a callable, every gated output projection calls
# ``on_gated_proj(kind, x.shape, G)`` once as it is traced, kind "attn" or
# "mlp". Tests use it to count the projections that take gated_linear.
on_gated_proj = None


@jax.custom_vjp
def gated_linear(x, w, gf_cols, gm_cols):
    """``(x * gf_cols) @ w`` whose backward masks by ``gm_cols`` instead.

    x: [..., F]; w: [F, D]; gf_cols, gm_cols: masks broadcastable to x,
    g_f and g_f * g_b repeated over each group's input columns. This is
    gate_mix of the per-group products, summed over groups, for any gate
    values: dx = (dy @ w.T) * gm_cols, dw = (x * gm_cols).T @ dy, and the
    masks (schedule constants) get no cotangent."""
    return (x * gf_cols) @ w


def _gated_linear_fwd(x, w, gf_cols, gm_cols):
    return gated_linear(x, w, gf_cols, gm_cols), (x, w, gm_cols)


def _gated_linear_bwd(res, dy):
    x, w, gm_cols = res
    dx = ((dy @ w.T) * gm_cols).astype(x.dtype)
    lead = tuple(range(x.ndim - 1))
    dw = jnp.tensordot(x * gm_cols, dy, axes=(lead, lead)).astype(w.dtype)
    return dx, dw, None, None


gated_linear.defvjp(_gated_linear_fwd, _gated_linear_bwd)


def _gated_proj(kind, x, w, layer_gates):
    """Gated output projection of x [B,S,F] by w [F,D]: group g of the G
    (sample, group) gates owns input columns [g*F/G, (g+1)*F/G)."""
    g_f, g_b = layer_gates
    G = g_f.shape[-1]
    if on_gated_proj is not None:
        on_gated_proj(kind, x.shape, G)
    rep = x.shape[-1] // G

    def cols(g):
        return jnp.repeat(g, rep, axis=1)[:, None, :].astype(x.dtype)

    with jax.named_scope("gated_proj"):
        return gated_linear(x, w, cols(g_f), cols(g_f * g_b))


# =================================================== tensor-parallel helpers
# tp = (axis_name, T): Megatron-style sharding over a shard_map mesh axis.
# Weights stay replicated (ZeRO over the data axis composes unchanged);
# each device COMPUTES only its contiguous block of heads / FFN columns
# and the partial residual contributions are psum'd over the axis.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_copy(x, axis_name):
    """Megatron's f operator: identity forward, psum backward. Placed at
    every tensor-parallel region's input so the activation cotangent —
    which each device only computes for its own head/column slice — is
    all-reduced, keeping grads of everything upstream replicated-exact."""
    return x


def _tp_copy_fwd(x, axis_name):
    return x, None


def _tp_copy_bwd(axis_name, _, g):
    return (jax.lax.psum(g, axis_name),)


_tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_sum(x, axis_name):
    """Megatron's g operator: psum forward, identity backward. Used at
    every tensor-parallel region's output — the downstream cotangent is
    replicated over the axis, so the backward must NOT re-reduce it
    (pinned here explicitly rather than relying on psum's transpose)."""
    return jax.lax.psum(x, axis_name)


def _tp_sum_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _tp_sum_bwd(axis_name, _, g):
    return (g,)


_tp_sum.defvjp(_tp_sum_fwd, _tp_sum_bwd)


def _tp_gate_slice(layer_gates, idx, G_local):
    """This device's contiguous block of head-group gates ([B, G] pair)."""
    g_f, g_b = layer_gates
    return (jax.lax.dynamic_slice_in_dim(g_f, idx * G_local, G_local, 1),
            jax.lax.dynamic_slice_in_dim(g_b, idx * G_local, G_local, 1))


# ============================================================== block params
def _init_block(key, kind: str, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    p = {"norm1": init_norm(cfg.norm, cfg.d_model, dtype)}
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        p["attn"] = attn.init_attention(
            ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.qkv_bias, dtype)
    elif kind == SSD:
        p["ssd"] = ssm_mod.init_ssd(ks[0], cfg.d_model, cfg.ssm, dtype)
    elif kind == RGLRU:
        p["rglru"] = rglru_mod.init_rglru(ks[0], cfg.d_model, cfg.rglru, dtype)
    else:
        raise ValueError(kind)
    has_ffn = (cfg.moe is not None) or cfg.d_ff > 0
    if kind == SSD:
        has_ffn = cfg.d_ff > 0       # mamba2: no FFN
    if has_ffn:
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        if cfg.moe is not None and kind != SSD:
            p["moe"] = moe_mod.init_moe(ks[1], cfg.d_model, cfg.moe, dtype)
        else:
            p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_gated, dtype)
    return p


def _split_gates(gates, idx):
    if gates is None:
        return None
    g_f, g_b = gates
    return g_f[idx], g_b[idx]


# ============================================================= block forward
def _apply_attn_inner(p, h, kind, cfg: ModelConfig, layer_gates, policy,
                      use_kernel: bool = False, live_bounds=None, tp=None):
    """Attention contribution (pre-residual), with per-head-group gating.

    live_bounds: static (live_fwd, live_bwd) bounds at (sample, group)
    granularity (``core.schedule.live_slice_bounds``); scaled to per-head
    slice counts here before reaching the kernel's compaction dispatch.
    tp: optional (axis_name, T) — shard the H heads over a shard_map
    tensor axis. Contiguous head blocks keep the GQA query->kv mapping
    and the (head-group) gate tiling exact when T divides H, Hkv and G."""
    window = cfg.window if kind == ATTN_LOCAL else 0
    hd = cfg.resolved_head_dim
    B, S, _ = h.shape
    n_heads, n_kv = cfg.n_heads, cfg.n_kv_heads
    if tp is not None:
        tp_axis, T = tp
        assert policy is None and not use_kernel, \
            "tensor parallelism has no policy/kernel route"
        assert n_heads % T == 0 and n_kv % T == 0, (n_heads, n_kv, T)
        idx = jax.lax.axis_index(tp_axis)
        h = _tp_copy(h, tp_axis)
        hq, hkv = n_heads // T, n_kv // T

        def sl(a, width, axis):
            return jax.lax.dynamic_slice_in_dim(a, idx * width, width, axis)

        p = dict(p, wq=sl(p["wq"], hq * hd, 1), wk=sl(p["wk"], hkv * hd, 1),
                 wv=sl(p["wv"], hkv * hd, 1), wo=sl(p["wo"], hq * hd, 0))
        if "bq" in p:
            p["bq"] = sl(p["bq"], hq * hd, 0)
            p["bk"] = sl(p["bk"], hkv * hd, 0)
            p["bv"] = sl(p["bv"], hkv * hd, 0)
        n_heads, n_kv = hq, hkv
        if layer_gates is not None:
            G = layer_gates[0].shape[-1]
            assert G % T == 0, (G, T)
            layer_gates = _tp_gate_slice(layer_gates, idx, G // T)
    if policy is not None and layer_gates is None:
        padding = policy.head_padding()
        if padding is not None:
            n_heads, n_kv = padding
            p = dict(p, **attn.pad_attention_params(
                p, cfg.n_heads, cfg.n_kv_heads, hd, n_heads, n_kv))
    q, k, v = attn._project_qkv(p, h, n_heads, n_kv, hd)
    if cfg.rope:
        pos = jnp.arange(S)[None, :]
        q = attn.apply_rope(q, pos, cfg.rope_theta)
        k = attn.apply_rope(k, pos, cfg.rope_theta)
    if use_kernel and policy is None:
        # Pallas kernel path: the gated flash kernel computes attention with
        # g_f forward gates and a custom VJP whose backward kernels skip all
        # g_b == 0 (sample, head) slices (see kernels/d2ft_attention.py).
        # The window branch is always causal-windowed, matching _window_mask.
        kernel_bounds = None
        if layer_gates is None:
            gf_h = gb_h = jnp.ones((B, n_heads), h.dtype)
        else:
            g_f, g_b = layer_gates
            rep = n_heads // g_f.shape[-1]
            gf_h = jnp.repeat(g_f, rep, axis=1).astype(h.dtype)
            gb_h = jnp.repeat(g_b, rep, axis=1).astype(h.dtype)
            if live_bounds is not None:
                # schedule bounds are per (sample, group); each group is
                # rep consecutive per-head slices after the expansion above
                kernel_bounds = (live_bounds[0] * rep, live_bounds[1] * rep)
        out = attn.gated_kernel_attention(q, k, v, gf_h, gb_h,
                                          causal=cfg.causal or window > 0,
                                          window=window,
                                          live_bounds=kernel_bounds)
    else:
        if use_kernel and policy is not None:
            kernel_contract.report_fallback(
                "attn", "sharded policy path has no kernel route")
        if policy is not None:
            q, k, v = policy.heads(q), policy.kv(k), policy.kv(v)
        chunk = policy.attn_q_chunk if policy is not None else 0
        if window and window > 0 and S > 2 * window and S % window == 0:
            out = attn._block_local_attention(q, k, v, window)
        elif chunk and chunk > 0 and S % chunk == 0 and S > chunk:
            out = attn._chunked_sdpa(q, k, v, chunk, causal=cfg.causal,
                                     window=window)
        elif window and window > 0:
            out = attn._sdpa(q, k, v, attn._window_mask(S, S, window))
        elif cfg.causal:
            out = attn._sdpa(q, k, v, attn._causal_mask(S, S))
        else:
            out = attn._sdpa(q, k, v, jnp.ones((1, 1, S, S), bool))
    if layer_gates is None:
        c = out.reshape(B, S, n_heads * hd) @ p["wo"]
        return _tp_sum(c, tp[0]) if tp is not None else c
    # a group is H // G contiguous heads, (H // G) * hd columns of wo; on the
    # kernel path the masked backward also cuts wo gradients for p_o groups
    c = _gated_proj("attn", out.reshape(B, S, n_heads * hd), p["wo"],
                    layer_gates)
    return _tp_sum(c, tp[0]) if tp is not None else c


def _apply_ffn(p, h, cfg: ModelConfig, layer_gates, policy,
               use_kernel: bool = False, live_bounds=None, tp=None):
    # MoE compute stays replicated under tensor parallelism (the
    # expert-parallel route is the GSPMD policy path) — replicated inputs
    # give replicated outputs/grads, so no psum is needed.
    if "moe" in p:
        if policy is not None and policy.moe_sharded(cfg):
            if use_kernel:
                kernel_contract.report_fallback(
                    "moe", "sharded expert-parallel path has no kernel route")
            y, aux = moe_mod.apply_moe_ep(
                p["moe"], h, cfg.moe, cfg.mlp_act, policy.mesh,
                policy.batch_axes if
                h.shape[0] % policy.data_size == 0 else None,
                seq_sharded=h.shape[1] % policy.model_size == 0
                and h.shape[1] > 1,
                expert_parallel=policy.expert_parallel)
        else:
            if use_kernel and policy is not None:
                kernel_contract.report_fallback(
                    "moe", "sharded policy path has no kernel route")
            moe_gates = None
            live_toks = bwd_toks = None
            if layer_gates is not None:
                g_f, g_b = layer_gates
                # MoE is one D2FT group (G position 0): per-sample gates
                moe_gates = (g_f[:, 0], g_b[:, 0])
                if live_bounds is not None:
                    live_toks = min(h.shape[0], live_bounds[0]) * h.shape[1]
                    # separate g_b bound: backward-live slots pack into a
                    # capacity prefix, so the kernel backward truncates to
                    # this even when the forward covers every p_o slot
                    bwd_toks = min(h.shape[0], live_bounds[1]) * h.shape[1]
            y, aux = moe_mod.apply_moe(
                p["moe"], h, cfg.moe, act=cfg.mlp_act,
                shard_fn=policy.moe if policy is not None else None,
                gates=moe_gates,
                use_kernel=use_kernel and policy is None,
                live_tokens=live_toks, live_bwd_tokens=bwd_toks)
        if layer_gates is not None:
            g_f, g_b = layer_gates
            y = gate_mix(y[:, :, None, :], g_f[:, :1], g_b[:, :1])[:, :, 0]
        return y, aux
    mlp = p["mlp"]
    if tp is not None:
        # FFN columns over the tensor axis: T | G keeps each device's
        # F/T-column block an integral number of whole gate groups, so the
        # gated w_down projection below stays exact on the local slice.
        tp_axis, T = tp
        assert policy is None, "tensor parallelism has no policy route"
        idx = jax.lax.axis_index(tp_axis)
        h = _tp_copy(h, tp_axis)
        F_full = mlp["w_up"].shape[-1]
        assert F_full % T == 0, (F_full, T)
        Fl = F_full // T

        def sl(a, axis):
            return jax.lax.dynamic_slice_in_dim(a, idx * Fl, Fl, axis)

        mlp = dict(mlp, w_up=sl(mlp["w_up"], 1), w_down=sl(mlp["w_down"], 0))
        if "w_gate" in mlp:
            mlp["w_gate"] = sl(mlp["w_gate"], 1)
        if layer_gates is not None:
            G = layer_gates[0].shape[-1]
            assert G % T == 0 and F_full % G == 0, (G, T, F_full)
            layer_gates = _tp_gate_slice(layer_gates, idx, G // T)
    up = h @ mlp["w_up"]
    if cfg.mlp_gated:
        hid = _act(cfg.mlp_act)(h @ mlp["w_gate"]) * up
    else:
        hid = _act(cfg.mlp_act)(up)
    if policy is not None:
        hid = policy.ffn(hid)
    if layer_gates is None:
        y = hid @ mlp["w_down"]
        return (_tp_sum(y, tp[0]) if tp is not None else y), None
    y = _gated_proj("mlp", hid, mlp["w_down"], layer_gates)
    return (_tp_sum(y, tp[0]) if tp is not None else y), None


def _apply_ssd_inner(p, h, cfg: ModelConfig, layer_gates,
                     use_kernel: bool = False, live_bounds=None):
    if layer_gates is None:
        return ssm_mod.apply_ssd(p, h, cfg.d_model, cfg.ssm)
    g_f, g_b = layer_gates
    G = g_f.shape[-1]
    d_inner, H, P, N = ssm_mod._dims(cfg.d_model, cfg.ssm)
    if H % G != 0:
        # heads don't tile into gate groups: fall back to the coarse
        # block-granularity run-twice mix (test-scale only)
        if use_kernel:
            kernel_contract.report_fallback(
                "ssd", f"H={H} not divisible by G={G} gate groups")
        full = ssm_mod.apply_ssd(p, h, cfg.d_model, cfg.ssm)
        sg = jax.lax.stop_gradient(full)
        gf = g_f[:, :1].mean(-1)[:, None, None]         # block granularity
        gb = g_b[:, :1].mean(-1)[:, None, None]
        return gf * (gb * full + (1 - gb) * sg)
    # gate per SSD head: each of the G schedule groups spans H // G
    # consecutive heads; the scan is gated per (sample, head) inside
    # apply_ssd (kernel or masked mix) before the D-residual shortcut.
    rep = H // G
    gf_h = jnp.repeat(g_f, rep, axis=1).astype(jnp.float32)
    gb_h = jnp.repeat(g_b, rep, axis=1).astype(jnp.float32)
    kernel_bounds = None
    if live_bounds is not None:
        kernel_bounds = (live_bounds[0] * rep, live_bounds[1] * rep)
    return ssm_mod.apply_ssd(p, h, cfg.d_model, cfg.ssm,
                             gates=(gf_h, gb_h), use_kernel=use_kernel,
                             live_bounds=kernel_bounds)


def _apply_rglru_inner(p, h, cfg: ModelConfig, layer_gates,
                      use_kernel: bool = False, live_bounds=None):
    if layer_gates is None:
        return rglru_mod.apply_rglru(p, h, cfg.rglru)
    g_f, g_b = layer_gates
    G = g_f.shape[-1]
    W = cfg.rglru.lru_width or cfg.d_model
    if W % G != 0:
        # width doesn't tile into gate groups: coarse run-twice mix
        if use_kernel:
            kernel_contract.report_fallback(
                "rglru", f"lru width={W} not divisible by G={G} gate groups")
        full = rglru_mod.apply_rglru(p, h, cfg.rglru)
        sg = jax.lax.stop_gradient(full)
        gf = g_f[:, :1].mean(-1)[:, None, None]
        gb = g_b[:, :1].mean(-1)[:, None, None]
        return gf * (gb * full + (1 - gb) * sg)
    # gates stay at (sample, group) granularity: the G groups slice the LRU
    # width into contiguous channel bands (the kernel's slice axis is B*G,
    # so schedule live bounds pass through unscaled)
    gf = g_f.astype(jnp.float32)
    gb = g_b.astype(jnp.float32)
    return rglru_mod.apply_rglru(p, h, cfg.rglru, gates=(gf, gb),
                                 use_kernel=use_kernel,
                                 live_bounds=live_bounds)


# the named scope of each token-mixer kind in the compiled step's metadata
MIXER_SCOPE = {ATTN_GLOBAL: "attn", ATTN_LOCAL: "attn", SSD: "ssd",
               RGLRU: "rglru"}


def apply_block(p, x, kind: str, cfg: ModelConfig, layer_gates=None,
                policy=None, use_kernel: bool = False, live_bounds=None,
                tp=None):
    """Pre-norm residual block. Returns (x, aux_losses or None).

    tp: optional (axis_name, T) shard_map tensor-parallel spec — attention
    heads and FFN columns shard over the axis, SSD/RG-LRU/MoE blocks run
    replicated (their grads stay replicated, so no psum is needed).

    Each half runs under a ``jax.named_scope``: the token mixer under
    ``attn`` (or ``ssd``, ``rglru``), the FFN under ``mlp``, each with its
    norm and residual add."""
    with jax.named_scope(MIXER_SCOPE[kind]):
        h = apply_norm(p["norm1"], x, cfg.norm)
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            c = _apply_attn_inner(p["attn"], h, kind, cfg, layer_gates,
                                  policy, use_kernel, live_bounds, tp)
        elif kind == SSD:
            c = _apply_ssd_inner(p["ssd"], h, cfg, layer_gates, use_kernel,
                                 live_bounds)
        elif kind == RGLRU:
            c = _apply_rglru_inner(p["rglru"], h, cfg, layer_gates,
                                   use_kernel, live_bounds)
        if policy is not None:
            # constrain the CONTRIBUTION before the residual add so GSPMD
            # emits a reduce-scatter of the partial-sum projection instead
            # of all-reduce + slice (Megatron sequence-parallel; §Perf
            # iter q2)
            c = policy.residual(c)
        x = x + c
        if policy is not None:
            x = policy.residual(x)
    aux = None
    if "norm2" in p:
        with jax.named_scope("mlp"):
            h2 = apply_norm(p["norm2"], x, cfg.norm)
            y, aux = _apply_ffn(p, h2, cfg, layer_gates, policy, use_kernel,
                                live_bounds, tp)
            if policy is not None:
                y = policy.residual(y)
            x = x + y
            if policy is not None:
                x = policy.residual(x)
    return x, aux


# ========================================================== layer grouping
def layer_groups(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...], Tuple[str, ...]]:
    """Returns (n_cycles, pattern, remainder_kinds)."""
    pat = cfg.block_pattern
    n_cycles = cfg.n_layers // len(pat)
    rem = cfg.layer_kinds[n_cycles * len(pat):]
    return n_cycles, pat, rem


# ================================================================ model init
def init_model(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    n_cycles, pat, rem = layer_groups(cfg)
    keys = jax.random.split(key, 4 + len(rem))
    params = {"embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype),
              "final_norm": init_norm(cfg.norm, cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)
    if cfg.frontend != "none":
        params["frontend_proj"] = dense_init(
            keys[2], cfg.frontend_dim, cfg.d_model, dtype)

    def init_cycle(ck):
        cks = jax.random.split(ck, len(pat))
        return [_init_block(cks[i], pat[i], cfg, dtype) for i in range(len(pat))]

    if n_cycles > 0:
        cycle_keys = jax.random.split(keys[3], n_cycles)
        stacked = jax.vmap(init_cycle)(cycle_keys)   # leading dim n_cycles
        params["cycles"] = stacked
    params["rest"] = [
        _init_block(keys[4 + i], rem[i], cfg, dtype) for i in range(len(rem))]
    return params


# ============================================================ model forward
def forward(params, cfg: ModelConfig, tokens=None, features=None,
            gates=None, policy=None, remat: bool = False,
            use_kernel: bool = False, live_bounds=None, tp=None):
    """Returns (logits, aux) — logits [B, S, vocab].

    tokens: [B, S_text] int32 (None for pure-audio encoders)
    features: [B, T_f, frontend_dim] stub frontend embeddings (audio/vlm)
    gates: optional (g_f, g_b) of shape [n_layers, B, G]
    use_kernel: route attention blocks through the Pallas gated flash
        kernel (gate-aware custom VJP) instead of the masked dense path.
    live_bounds: optional static (live_fwd, live_bwd) per-layer max live
        (sample, group) slice counts from ``core.schedule
        .live_slice_bounds`` — enables the kernel path's compaction
        dispatch (one shared bound so scan compiles a single body).
    tp: optional (axis_name, T) shard_map tensor-parallel spec (must be
        traced inside shard_map over a mesh with that axis; see
        ``apply_block``). Embedding/norm/logits compute stays replicated.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("embed"):
        parts = []
        if features is not None:
            parts.append(features.astype(cdt)
                         @ params["frontend_proj"].astype(cdt))
        if tokens is not None:
            from repro.models.layers import apply_embedding
            parts.append(apply_embedding(params["embed"], tokens).astype(cdt))
        x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
        if policy is not None:
            x = policy.residual(x)

    n_cycles, pat, rem = layer_groups(cfg)
    P = len(pat)
    aux_sum = jnp.zeros((), jnp.float32)

    # the layer stack's own ops (gate and stacked-parameter slices, the
    # scan's saved residuals, per-cycle gradient assembly) under "layers";
    # each block's compute under its own scopes (apply_block)
    with jax.named_scope("layers"):
        if gates is not None:
            g_f, g_b = gates
            g_f_c = g_f[:n_cycles * P].reshape(n_cycles, P, *g_f.shape[1:])
            g_b_c = g_b[:n_cycles * P].reshape(n_cycles, P, *g_b.shape[1:])
            g_rest = (g_f[n_cycles * P:], g_b[n_cycles * P:])
        else:
            g_f_c = g_b_c = g_rest = None

        if n_cycles > 0:
            def cycle_body(carry, xs):
                x, aux = carry
                if gates is not None:
                    blocks, gfc, gbc = xs
                else:
                    (blocks,) = xs
                for i in range(P):
                    lg = (gfc[i], gbc[i]) if gates is not None else None
                    x, a = apply_block(blocks[i], x, pat[i], cfg, lg, policy,
                                       use_kernel, live_bounds, tp)
                    if a is not None:
                        aux = aux + a["load_balance"] + a["router_z"]
                return (x, aux), None

            body = cycle_body
            if remat:
                body = jax.checkpoint(cycle_body, prevent_cse=False)
            xs = (params["cycles"],) if gates is None else (
                params["cycles"], g_f_c, g_b_c)
            if n_cycles <= 2:
                # Unrolled: XLA's cost_analysis counts a while body ONCE
                # regardless of trip count, so the dry-run's depth-1/depth-2
                # FLOP extrapolation needs shallow models fully inlined.
                for c in range(n_cycles):
                    xs_c = jax.tree.map(lambda a: a[c], xs)
                    (x, aux_sum), _ = body((x, aux_sum), xs_c)
            else:
                (x, aux_sum), _ = jax.lax.scan(body, (x, aux_sum), xs)

        for i, kind in enumerate(rem):
            lg = None
            if gates is not None:
                lg = (g_rest[0][i], g_rest[1][i])
            x, a = apply_block(params["rest"][i], x, kind, cfg, lg, policy,
                               use_kernel, live_bounds, tp)
            if a is not None:
                aux_sum = aux_sum + a["load_balance"] + a["router_z"]

    with jax.named_scope("head"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
        if cfg.tie_embeddings:
            logits = x @ params["embed"]["table"].T.astype(cdt)
        else:
            logits = x @ params["unembed"].astype(cdt)
        if policy is not None:
            logits = policy.logits(logits)
        logits = softcap(logits, cfg.logit_softcap)
    return logits, {"aux_loss": aux_sum}


# ================================================================== decoding
def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Per-layer caches, stacked per cycle position (mirrors params)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    n_cycles, pat, rem = layer_groups(cfg)

    def one(kind):
        if kind == ATTN_GLOBAL:
            return attn.init_kv_cache(batch, max_len, cfg.n_kv_heads,
                                      cfg.resolved_head_dim, 0, dtype)
        if kind == ATTN_LOCAL:
            return attn.init_kv_cache(batch, max_len, cfg.n_kv_heads,
                                      cfg.resolved_head_dim, cfg.window, dtype)
        if kind == SSD:
            return ssm_mod.init_ssd_cache(batch, cfg.d_model, cfg.ssm, dtype)
        if kind == RGLRU:
            return rglru_mod.init_rglru_cache(batch, cfg.d_model, cfg.rglru, dtype)
        raise ValueError(kind)

    cache = {}
    if n_cycles > 0:
        cache["cycles"] = [
            jax.tree.map(lambda a: jnp.broadcast_to(a, (n_cycles,) + a.shape),
                         one(k)) for k in pat]
    cache["rest"] = [one(k) for k in rem]
    return cache


def _decode_block(p, c, x, kind, cfg: ModelConfig, t):
    h = apply_norm(p["norm1"], x, cfg.norm)
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        hd = cfg.resolved_head_dim
        window = cfg.window if kind == ATTN_LOCAL else 0
        y, c = attn.decode_attention(
            p["attn"], c, h, t=t, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=hd, window=window,
            rope=cfg.rope, rope_theta=cfg.rope_theta)
    elif kind == SSD:
        y, c = ssm_mod.decode_ssd(p["ssd"], c, h, cfg.d_model, cfg.ssm)
    elif kind == RGLRU:
        y, c = rglru_mod.decode_rglru(p["rglru"], c, h, cfg.rglru)
    x = x + y
    if "norm2" in p:
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        if "moe" in p:
            y2, _ = moe_mod.apply_moe(p["moe"], h2, cfg.moe, act=cfg.mlp_act)
        else:
            from repro.models.layers import apply_mlp
            y2 = apply_mlp(p["mlp"], h2, cfg.mlp_act, cfg.mlp_gated)
        x = x + y2
    return x, c


def decode_step(params, cache, cfg: ModelConfig, token, t, policy=None):
    """One decode step. token: [B,1] int32; t: scalar — tokens already cached.
    Returns (logits [B,1,vocab], new_cache)."""
    from repro.models.layers import apply_embedding
    cdt = jnp.dtype(cfg.compute_dtype)
    x = apply_embedding(params["embed"], token).astype(cdt)
    n_cycles, pat, rem = layer_groups(cfg)
    P = len(pat)

    new_cache = {"rest": []}
    if n_cycles > 0:
        def cycle_body(x, xs):
            blocks = xs[0]
            caches = xs[1]
            new_cs = []
            for i in range(P):
                x, nc = _decode_block(blocks[i], caches[i], x, pat[i], cfg, t)
                new_cs.append(nc)
            return x, new_cs

        if n_cycles <= 2:
            # unrolled for dry-run cost extrapolation (see forward())
            emitted = []
            for c in range(n_cycles):
                xs_c = jax.tree.map(lambda a: a[c],
                                    (params["cycles"], cache["cycles"]))
                x, nc = cycle_body(x, xs_c)
                emitted.append(nc)
            new_cache["cycles"] = jax.tree.map(
                lambda *leaves: jnp.stack(leaves), *emitted)
        else:
            x, new_cycle_cache = jax.lax.scan(
                cycle_body, x, (params["cycles"], cache["cycles"]))
            new_cache["cycles"] = new_cycle_cache
    for i, kind in enumerate(rem):
        x, nc = _decode_block(params["rest"][i], cache["rest"][i], x, kind,
                              cfg, t)
        new_cache["rest"].append(nc)

    x = apply_norm(params["final_norm"], x, cfg.norm)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].T.astype(cdt)
    else:
        logits = x @ params["unembed"].astype(cdt)
    if policy is not None:
        logits = policy.logits(logits)
    return softcap(logits, cfg.logit_softcap), new_cache


# ====================================================== prefill (cache dump)
def _prefill_block(p, x, kind: str, cfg: ModelConfig, max_len: int,
                   raw_kv: bool):
    """One block of the batched prefill: the dense forward computation of
    ``apply_block`` (ungated, unsharded) plus the decode-cache entry the
    block leaves behind — post-rope K/V for attention, final conv/recurrent
    state for SSD / RG-LRU. Returns (x, cache_entry)."""
    h = apply_norm(p["norm1"], x, cfg.norm)
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        window = cfg.window if kind == ATTN_LOCAL else 0
        c, k, v = attn.apply_attention(
            p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, causal=cfg.causal, window=window,
            rope=cfg.rope, rope_theta=cfg.rope_theta, return_kv=True)
        entry = {"k": k, "v": v} if raw_kv else \
            attn.kv_prefill_cache(k, v, window, max_len)
    elif kind == SSD:
        c, entry = ssm_mod.apply_ssd(p["ssd"], h, cfg.d_model, cfg.ssm,
                                     return_state=True)
    elif kind == RGLRU:
        c, entry = rglru_mod.apply_rglru(p["rglru"], h, cfg.rglru,
                                         return_state=True)
    else:
        raise ValueError(kind)
    x = x + c
    if "norm2" in p:
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        y, _ = _apply_ffn(p, h2, cfg, None, None)
        x = x + y
    return x, entry


def prefill_forward(params, cfg: ModelConfig, tokens, max_len: int = 0,
                    *, raw_kv: bool = False):
    """Batched serving prefill: ONE teacher-forced ``forward()`` pass over
    the whole prompt that also dumps the decode caches — O(1) launches
    instead of the O(S) sequential decode-path loop.

    tokens: [B, S] int32. Returns (logits [B, S, vocab], cache) where cache
    matches ``init_cache(cfg, B, max_len)`` structurally and decode can
    continue from position S. With ``raw_kv=True`` attention entries are
    instead the full post-rope history ``{"k","v"}: [B, S, n_kv, hd]`` —
    what the paged serving engine slices into fixed-size pages
    (``serving/engine.py``).

    Layers are unrolled (no scan): serving compiles once per engine and
    needs per-layer cache capture, not O(1)-in-depth HLO like training.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    from repro.models.layers import apply_embedding
    B, S = tokens.shape
    max_len = max_len or S
    x = apply_embedding(params["embed"], tokens).astype(cdt)

    n_cycles, pat, rem = layer_groups(cfg)
    P = len(pat)
    cache = {"rest": []}
    cycle_entries = []
    for c in range(n_cycles):
        blocks = jax.tree.map(lambda a: a[c], params["cycles"])
        ents = []
        for i in range(P):
            x, e = _prefill_block(blocks[i], x, pat[i], cfg, max_len, raw_kv)
            ents.append(e)
        cycle_entries.append(ents)
    if n_cycles > 0:
        cache["cycles"] = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                                       *cycle_entries)
    for i, kind in enumerate(rem):
        x, e = _prefill_block(params["rest"][i], x, kind, cfg, max_len,
                              raw_kv)
        cache["rest"].append(e)

    x = apply_norm(params["final_norm"], x, cfg.norm)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].T.astype(cdt)
    else:
        logits = x @ params["unembed"].astype(cdt)
    return softcap(logits, cfg.logit_softcap), cache


# ============================================================== loss helpers
@jax.custom_vjp
def fused_xent(logits, labels):
    """Mean token cross-entropy with a hand-written backward.

    Forward never materializes a float32 [B,S,V] buffer (label logit is
    gathered first; logsumexp reduces with fused elementwise ops) and the
    backward emits the cotangent (softmax - onehot) directly in the logits
    dtype — the naive log_softmax formulation kept several f32 logits-sized
    temps alive, which dominated HBM in the dry run.
    """
    loss, _ = _xent_fwd_impl(logits, labels)
    return loss


def _xent_fwd_impl(logits, labels):
    label_logit = jnp.take_along_axis(logits, labels[..., None],
                                      axis=-1)[..., 0].astype(jnp.float32)
    m = jnp.max(logits, axis=-1)
    # exp stays in the logits dtype; the reduce accumulates in f32 (no f32
    # [B,S,V] materialization even on backends with weak fusion)
    lse = m.astype(jnp.float32) + jnp.log(jnp.sum(
        jnp.exp(logits - m[..., None]), axis=-1, dtype=jnp.float32))
    loss = jnp.mean(lse - label_logit)
    return loss, (logits, labels, m, lse)


def _xent_bwd_impl(res, g):
    logits, labels, m, lse = res
    n = logits.size // logits.shape[-1]
    # softmax in the logits dtype; exp fuses with the subtraction
    z = (lse - m.astype(jnp.float32)).astype(logits.dtype)
    gn = (g / n).astype(logits.dtype)
    dlogits = jnp.exp(logits - m[..., None] - z[..., None]) * gn
    # subtract g/n at the label position by scatter — avoids materializing a
    # [B, S, V] onehot buffer
    b_idx = jnp.arange(dlogits.shape[0])[:, None]
    s_idx = jnp.arange(dlogits.shape[1])[None, :]
    dlogits = dlogits.at[b_idx, s_idx, labels].add(-gn)
    return dlogits, None


fused_xent.defvjp(lambda logits, labels: _xent_fwd_impl(logits, labels),
                  _xent_bwd_impl)


def lm_loss(params, cfg: ModelConfig, tokens, labels, features=None,
            gates=None, policy=None, remat: bool = False,
            use_kernel: bool = False, live_bounds=None, tp=None):
    """Next-token (or frame-classification) cross-entropy."""
    logits, aux = forward(params, cfg, tokens=tokens, features=features,
                          gates=gates, policy=policy, remat=remat,
                          use_kernel=use_kernel, live_bounds=live_bounds,
                          tp=tp)
    with jax.named_scope("head"):
        if features is not None and tokens is not None:
            # VLM: loss only over the text region (labels align to text
            # tokens)
            logits = logits[:, -labels.shape[1]:]
        loss = fused_xent(logits, labels)
        return loss + aux["aux_loss"], {"ce": loss, "aux": aux["aux_loss"]}
