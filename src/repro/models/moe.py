"""Mixture-of-Experts layer with sort-based static-capacity dispatch.

Dispatch is the MaxText-style sort/scatter form (no [T,E,C] one-hot blow-up):
tokens are sorted by expert id, positioned within their expert segment,
dropped beyond capacity, gathered into a dense [E, C, d] buffer, processed
with a batched expert einsum, and combined with a scatter-add.

Sharding: the expert dim is annotated by an optional ``shard_fn`` supplied by
the caller (expert-parallel when n_experts % model_axis == 0, otherwise
TP-within-expert on the hidden dim). This module stays mesh-agnostic.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.models.layers import dense_init, _act
from repro.configs.base import MoEConfig


def init_moe(key, d_model: int, cfg: MoEConfig, dtype):
    ks = jax.random.split(key, 6)
    E, F = cfg.n_experts, cfg.d_ff
    p = {
        "router": dense_init(ks[0], d_model, E, dtype, scale=0.02),
        "w_up": (jax.random.normal(ks[1], (E, d_model, F)) / jnp.sqrt(d_model)).astype(dtype),
        "w_gate": (jax.random.normal(ks[2], (E, d_model, F)) / jnp.sqrt(d_model)).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, F, d_model)) / jnp.sqrt(F)).astype(dtype),
    }
    if cfg.n_shared_experts > 0:
        SF = cfg.n_shared_experts * F
        p["shared_up"] = dense_init(ks[4], d_model, SF, dtype)
        p["shared_gate"] = dense_init(ks[5], d_model, SF, dtype)
        p["shared_down"] = dense_init(ks[0], SF, d_model, dtype)
    return p


def apply_moe(params, x, cfg: MoEConfig, act: str = "silu",
              shard_fn: Optional[Callable] = None,
              gates: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              use_kernel: bool = False,
              live_tokens: Optional[int] = None,
              live_bwd_tokens: Optional[int] = None,
              block_c: int = 128):
    """x: [B, S, d]. Returns (y, aux) where aux has load-balance/z losses.

    gates: optional per-sample D2FT gates (g_f, g_b), each [B] in {0, 1}
    with g_b <= g_f — the schedule gate intersects the router's top-k:
    assignments from g_f == 0 samples are dropped *at dispatch* (they never
    occupy capacity slots), and within each expert segment g_b == 1
    assignments sort first so backward-live slots pack into a prefix of
    capacity blocks. The caller's gate_mix still owns the stop-gradient
    semantics on the combined output. use_kernel routes the expert FFN
    through the doubly-sparse Pallas kernel (``ops.gated_moe_ffn``) with
    slot-occupancy masks; ``live_tokens`` is the schedule's static upper
    bound on forward-live tokens (live samples x S), bounding live capacity
    slots at ``live_tokens * top_k`` for compaction-style block truncation.
    ``live_bwd_tokens`` is the matching *backward*-live bound (g_b samples
    x S): because bwd-live assignments pack first per expert segment, it
    truncates the kernel's backward grid separately — a g_b < g_f mix
    stops dispatching capacity blocks that only hold p_o slots.
    """
    B, S, D = x.shape
    T = B * S
    E, K, C_f = cfg.n_experts, cfg.top_k, cfg.capacity_factor
    xt = x.reshape(T, D)

    logits = (xt @ params["router"]).astype(jnp.float32)        # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)                      # [T, K]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # ---- flatten (token, k) assignments and sort by expert id
    e_flat = top_e.reshape(T * K)
    tok_flat = jnp.repeat(jnp.arange(T), K)
    w_flat = top_w.reshape(T * K)
    if gates is None:
        live_a = bwd_a = None
        order = jnp.argsort(e_flat, stable=True)
        counts = jnp.bincount(e_flat, length=E)                 # [E]
    else:
        g_f, g_b = gates
        gf_t = jnp.repeat(g_f.reshape(B), S)                    # [T]
        gb_t = jnp.repeat(g_b.reshape(B), S)
        live_a = gf_t[tok_flat] > 0                             # [T*K]
        bwd_a = gb_t[tok_flat] > 0
        # sort key: (expert, bwd-dead-last) for live assignments; dead ones
        # past every expert so they never claim a capacity slot
        key = jnp.where(live_a,
                        2 * e_flat + (1 - bwd_a.astype(e_flat.dtype)),
                        2 * E)
        order = jnp.argsort(key, stable=True)
        counts = jnp.bincount(jnp.where(live_a, e_flat, E),
                              length=E + 1)[:E]
    e_s, tok_s, w_s = e_flat[order], tok_flat[order], w_flat[order]

    offsets = jnp.cumsum(counts) - counts                       # exclusive
    pos = jnp.arange(T * K) - offsets[e_s]                      # rank in segment
    capacity = int(max(1, round(T * K / E * C_f)))
    keep = pos < capacity
    if live_a is not None:
        keep = keep & live_a[order]
    pos_c = jnp.where(keep, pos, capacity)                      # OOB -> drop

    buf = jnp.zeros((E, capacity + 1, D), x.dtype)
    buf = buf.at[e_s, pos_c].set(xt[tok_s])
    buf = buf[:, :capacity]                                     # [E, C, D]
    if shard_fn is not None:
        buf = shard_fn(buf)

    if use_kernel and gates is not None and shard_fn is None:
        keep_f = keep.astype(jnp.float32)
        keep_b = (keep & bwd_a[order]).astype(jnp.float32)
        fwd_slots = jnp.zeros((E, capacity + 1), jnp.float32)
        fwd_slots = fwd_slots.at[e_s, pos_c].add(keep_f)[:, :capacity]
        bwd_slots = jnp.zeros((E, capacity + 1), jnp.float32)
        bwd_slots = bwd_slots.at[e_s, pos_c].add(keep_b)[:, :capacity]
        live_slots = (min(capacity, int(live_tokens) * K)
                      if live_tokens is not None else None)
        live_bwd_slots = (min(capacity, int(live_bwd_tokens) * K)
                          if live_bwd_tokens is not None else None)
        out_e = kernel_ops.gated_moe_ffn(
            buf, params["w_up"], params["w_gate"], params["w_down"],
            fwd_slots, bwd_slots, act=act, block_c=block_c,
            live_slots=live_slots, live_bwd_slots=live_bwd_slots)
        out_e = out_e.astype(x.dtype)
    else:
        h = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
        g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
        h = _act(act)(g) * h
        out_e = jnp.einsum("ecf,efd->ecd", h, params["w_down"])  # [E, C, D]
        if shard_fn is not None:
            out_e = shard_fn(out_e)

    y = jnp.zeros((T, D), x.dtype)
    contrib = out_e[e_s, jnp.minimum(pos_c, capacity - 1)]
    contrib = contrib * (w_s * keep).astype(x.dtype)[:, None]
    y = y.at[tok_s].add(contrib)

    if cfg.n_shared_experts > 0:
        hs = xt @ params["shared_up"]
        gs = _act(act)(xt @ params["shared_gate"])
        y = y + (hs * gs) @ params["shared_down"]

    # ---- aux losses (GShard/Switch style)
    frac_tokens = jnp.mean(jax.nn.one_hot(top_e[:, 0], E), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux_lb = E * jnp.sum(frac_tokens * frac_probs) * cfg.aux_loss
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    aux_z = jnp.mean(z ** 2) * cfg.router_z_loss
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    aux = {"load_balance": aux_lb, "router_z": aux_z, "drop_frac": dropped}
    return y.reshape(B, S, D), aux


# ===================================================== expert parallel (EP)
def apply_moe_ep(params, x, cfg: MoEConfig, act: str, mesh, batch_axes,
                 model_axis: str = "model", seq_sharded: bool = True,
                 expert_parallel: bool = True):
    """Sharded MoE via shard_map with explicit collectives.

    The global sort-based dispatch above is correct single-device JAX, but
    its data-dependent gather/scatter defeats GSPMD: the compiler
    replicates the token buffers. Production dispatch is explicit. Two modes:

    * expert_parallel (E % model_size == 0): each device routes its LOCAL
      tokens, builds a per-expert send buffer, all-to-alls over ``model``
      (experts live there), computes its local experts, all-to-alls back.
    * TP-within-expert (e.g. mixtral's 8 experts on a 16-wide axis): every
      device holds an F-slice of ALL experts; dispatch is local, the expert
      down-projection is partial and psum'd over ``model``.

    x: [B, S, D] (batch over batch_axes, seq over model). Fixed local
    capacity C_l = ceil(T_l * K / E) * capacity_factor.
    """
    from jax.sharding import PartitionSpec as P

    E, K = cfg.n_experts, cfg.top_k
    M = mesh.shape[model_axis]
    E_l = E // M if expert_parallel else E

    def local_fn(xl, router, w_up, w_gate, w_down):
        # xl: [B_l, S_l, D]; w_*: [E_l, D, F] (EP) or [E, D, F/M] (TP)
        B_l, S_l, D = xl.shape
        T_l = B_l * S_l
        xt = xl.reshape(T_l, D)
        logits = (xt @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, K)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        e_flat = top_e.reshape(T_l * K)
        tok_flat = jnp.repeat(jnp.arange(T_l), K)
        w_flat = top_w.reshape(T_l * K)
        order = jnp.argsort(e_flat, stable=True)
        e_s, tok_s, w_s = e_flat[order], tok_flat[order], w_flat[order]
        counts = jnp.bincount(e_flat, length=E)
        offsets = jnp.cumsum(counts) - counts
        pos = jnp.arange(T_l * K) - offsets[e_s]
        C_l = int(max(1, -(-T_l * K // E) * cfg.capacity_factor))
        keep = pos < C_l
        pos_c = jnp.where(keep, pos, C_l)

        send = jnp.zeros((E, C_l + 1, D), xl.dtype)
        send = send.at[e_s, pos_c].set(xt[tok_s])[:, :C_l]    # [E, C_l, D]
        if expert_parallel:
            # ---- all-to-all: expert dim -> devices; add source-device dim
            recv = jax.lax.all_to_all(
                send.reshape(M, E_l, C_l, D), model_axis, split_axis=0,
                concat_axis=0, tiled=False)                   # [M,E_l,C_l,D]
            buf = recv.transpose(1, 0, 2, 3).reshape(E_l, M * C_l, D)
        else:
            buf = send                                        # [E, C_l, D]

        h = jnp.einsum("ecd,edf->ecf", buf, w_up)
        g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
        out_e = jnp.einsum("ecf,efd->ecd", _act(act)(g) * h, w_down)

        if expert_parallel:
            back = out_e.reshape(E_l, M, C_l, D).transpose(1, 0, 2, 3)
            got = jax.lax.all_to_all(back, model_axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            got = got.reshape(E, C_l, D)
        else:
            got = jax.lax.psum(out_e, model_axis)             # partial sums

        y = jnp.zeros((T_l, D), xl.dtype)
        contrib = got[e_s, jnp.minimum(pos_c, C_l - 1)]
        contrib = contrib * (w_s * keep).astype(xl.dtype)[:, None]
        y = y.at[tok_s].add(contrib)

        frac_tokens = jnp.mean(jax.nn.one_hot(top_e[:, 0], E), axis=0)
        frac_probs = jnp.mean(probs, axis=0)
        all_axes = tuple(mesh.axis_names)
        aux_lb = E * jnp.sum(
            jax.lax.pmean(frac_tokens * frac_probs, all_axes)) * cfg.aux_loss
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        aux_z = jax.lax.pmean(jnp.mean(z ** 2), all_axes) * cfg.router_z_loss
        drop = jax.lax.pmean(1.0 - jnp.mean(keep.astype(jnp.float32)),
                             all_axes)
        return y.reshape(B_l, S_l, D), {"load_balance": aux_lb,
                                        "router_z": aux_z, "drop_frac": drop}

    # TP-within-expert psums partial-F outputs per token, so every model
    # rank must see the SAME tokens: sequence stays gathered in that mode.
    xspec = P(batch_axes,
              model_axis if (seq_sharded and expert_parallel) else None,
              None)
    if expert_parallel:
        up_spec = gate_spec = down_spec = P(model_axis, None, None)
    else:
        up_spec = gate_spec = P(None, None, model_axis)
        down_spec = P(None, model_axis, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(xspec, P(None, None), up_spec, gate_spec, down_spec),
        out_specs=(xspec, {"load_balance": P(), "router_z": P(),
                           "drop_frac": P()}),
        check_vma=False)
    y, aux = fn(x, params["router"], params["w_up"], params["w_gate"],
                params["w_down"])

    if cfg.n_shared_experts > 0:
        B, S, D = x.shape
        xt = x.reshape(B * S, D)
        hs = xt @ params["shared_up"]
        gs = _act(act)(xt @ params["shared_gate"])
        y = y + ((hs * gs) @ params["shared_down"]).reshape(B, S, D)
    return y, aux
