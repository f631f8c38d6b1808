"""GQA attention: global/causal, bidirectional, sliding-window (block-local
subquadratic), plus single-token decode against full-length or ring KV caches.

D2FT head-group gating happens in transformer.py at the block level, on
the output projection of this module's attention.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init

NEG_INF = -2.0 ** 30


# ------------------------------------------------------------------- params
def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qkv_bias: bool, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def _project_qkv(params, x, n_heads, n_kv_heads, head_dim):
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv_heads, head_dim)
    v = v.reshape(B, S, n_kv_heads, head_dim)
    return q, k, v


def _repeat_kv(k, Hq: int):
    """[B,S,Hkv,hd] -> [B,S,Hq,hd]. Keeping the einsum 4-D (instead of a
    5-D [B,S,Hkv,G,hd] grouping) lets GSPMD shard the head dim cleanly —
    the grouped form forced involuntary full rematerialization when
    Hkv % model_axis != 0."""
    Hkv = k.shape[2]
    if Hkv == Hq:
        return k
    return jnp.repeat(k, Hq // Hkv, axis=2)


def gated_kernel_attention(q, k, v, g_f, g_b, *, causal: bool,
                           window: int = 0,
                           interpret: Optional[bool] = None,
                           live_bounds: Optional[Tuple[int, int]] = None):
    """Pallas-kernel attention with D2FT (g_f, g_b) head gates.

    q: [B,S,Hq,hd]; k, v: [B,S,Hkv,hd] (GQA expanded here); g_f, g_b:
    [B,Hq] in {0,1}. Returns [B,S,Hq,hd]. Forward output is g_f-gated (p_s
    heads are zeros and skip the MXU); the custom-VJP backward skips every
    (sample, head) slice with g_b == 0 inside the kernel (p_o and p_s), so
    forward-only micro-batches never pay attention-backward FLOPs.

    live_bounds: optional static (live_fwd, live_bwd) upper bounds on the
    g_f != 0 / g_b != 0 (sample, head) slice counts — enables compaction
    dispatch (the kernel grid's leading dim becomes the bound instead of
    B*Hq and gated-off slices stop paying DMA). Schedule-derived, see
    ``core.schedule.live_slice_bounds``.
    """
    from repro.kernels.ops import gated_attention
    Hq = q.shape[2]
    k = _repeat_kv(k, Hq)
    v = _repeat_kv(v, Hq)
    live_f, live_b = live_bounds if live_bounds is not None else (None, None)
    out = gated_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), g_f, g_b, causal=causal,
                          window=window, interpret=interpret,
                          live_fwd=live_f, live_bwd=live_b)
    return out.transpose(0, 2, 1, 3)


def _sdpa(q, k, v, mask):
    """q: [B,Sq,Hq,hd]; k,v: [B,Sk,Hkv,hd]; mask: broadcastable
    [B,1,Sq,Sk] boolean (True = attend). GQA via KV head repetition."""
    B, Sq, Hq, hd = q.shape
    k = _repeat_kv(k, Hq)
    v = _repeat_kv(v, Hq)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    logits = jnp.where(mask, logits.astype(jnp.float32), NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out


def _causal_mask(Sq, Sk, offset=0):
    # True where key position <= query position (+offset aligns positions)
    qpos = jnp.arange(Sq)[:, None] + offset
    kpos = jnp.arange(Sk)[None, :]
    return (kpos <= qpos)[None, None]


def _window_mask(Sq, Sk, window, offset=0):
    qpos = jnp.arange(Sq)[:, None] + offset
    kpos = jnp.arange(Sk)[None, :]
    return ((kpos <= qpos) & (kpos > qpos - window))[None, None]


def pad_attention_params(p, n_heads, n_kv, head_dim, Hp, Hkvp):
    """Zero-pad head dims: wq/bq to Hp heads, wk/wv/bk/bv to Hkvp, wo rows
    to Hp. Exact — padded heads' wo rows are zero so their contribution
    vanishes; real head h keeps kv head h // (Hp/Hkvp) (ratio preserved).
    Enables head-TP sharding when the true head count is not divisible by
    the model axis (``ShardingPolicy.pad_heads``)."""
    dq = (Hp - n_heads) * head_dim
    dkv = (Hkvp - n_kv) * head_dim
    out = dict(p)
    out["wq"] = jnp.pad(p["wq"], ((0, 0), (0, dq)))
    out["wk"] = jnp.pad(p["wk"], ((0, 0), (0, dkv)))
    out["wv"] = jnp.pad(p["wv"], ((0, 0), (0, dkv)))
    out["wo"] = jnp.pad(p["wo"], ((0, dq), (0, 0)))
    for b, d in (("bq", dq), ("bk", dkv), ("bv", dkv)):
        if b in p:
            out[b] = jnp.pad(p[b], (0, d))
    return out


def _chunked_sdpa(q, k, v, chunk_q: int, causal: bool, window: int = 0):
    """Exact causal/window attention with the query dim processed in
    chunks via lax.map — caps the [B,H,cq,Sk] scores buffer instead of
    materializing [B,H,Sq,Sk] (a memory lever, FLOPs unchanged)."""
    B, S, Hq, hd = q.shape
    k = _repeat_kv(k, Hq)
    v = _repeat_kv(v, Hq)
    n = S // chunk_q
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    kpos = jnp.arange(S)[None, :]

    def one(ci):
        qc = jax.lax.dynamic_slice_in_dim(q, ci * chunk_q, chunk_q, axis=1)
        qpos = ci * chunk_q + jnp.arange(chunk_q)[:, None]
        mask = jnp.ones((chunk_q, S), bool)
        if causal:
            mask &= kpos <= qpos
        if window and window > 0:
            mask &= kpos > qpos - window
        logits = jnp.einsum("bqhd,bkhd->bhqk", qc * scale, k)
        logits = jnp.where(mask[None, None], logits.astype(jnp.float32),
                           NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    chunks = jax.lax.map(one, jnp.arange(n))          # [n,B,cq,H,hd]
    return jnp.moveaxis(chunks, 0, 1).reshape(B, S, Hq, hd)


# ----------------------------------------------------------- train / prefill
def apply_attention(params, x, *, n_heads: int, n_kv_heads: int, head_dim: int,
                    causal: bool, window: int = 0, rope: bool = True,
                    rope_theta: float = 10_000.0,
                    positions: Optional[jnp.ndarray] = None,
                    return_kv: bool = False):
    """Returns attention block output [B,S,d_model].

    window > 0 selects sliding-window attention; when S > 2*window a
    block-local (chunked) subquadratic implementation is used.
    return_kv: additionally return the post-rope (k, v) [B,S,n_kv,hd] —
    the serving prefill captures them into the KV cache (forward() + cache
    dump instead of a sequential decode loop, see serving/decode.py).
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    if rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    if window and window > 0 and S > 2 * window and S % window == 0:
        out = _block_local_attention(q, k, v, window)
    else:
        if window and window > 0:
            mask = _window_mask(S, S, window)
        elif causal:
            mask = _causal_mask(S, S)
        else:
            mask = jnp.ones((1, 1, S, S), bool)
        out = _sdpa(q, k, v, mask)

    out = out.reshape(B, S, n_heads * head_dim) @ params["wo"]
    if return_kv:
        return out, k, v
    return out


def kv_prefill_cache(k, v, window: int, max_len: int) -> dict:
    """Full-history prefill K/V [B,S,n_kv,hd] -> the ``init_kv_cache``
    decode layout, so ``decode_attention`` can continue from position S.

    Global layers get the zero-padded [B, max_len, ...] cache. Local layers
    get the [B, W, ...] ring buffer: slot ``p % W`` holds position ``p`` for
    the last ``min(S, W)`` positions — exactly the state a sequential
    decode-path prefill would have left, including the invariant that the
    next decode step (t = S) overwrites the slot whose position just fell
    out of the window."""
    B, S = k.shape[:2]
    if window and window > 0:
        L = window
        m = min(S, L)
        pos = jnp.arange(S - m, S)
        kc = jnp.zeros((B, L) + k.shape[2:], k.dtype).at[:, pos % L].set(
            k[:, pos])
        vc = jnp.zeros((B, L) + v.shape[2:], v.dtype).at[:, pos % L].set(
            v[:, pos])
        return {"k": kc, "v": vc}
    assert S <= max_len, f"prompt length {S} exceeds cache max_len {max_len}"
    pad = ((0, 0), (0, max_len - S)) + ((0, 0),) * (k.ndim - 2)
    return {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}


def _block_local_attention(q, k, v, window: int):
    """Subquadratic sliding-window attention: chunk queries by `window`;
    each chunk attends to itself + the previous chunk under an exact
    (kpos <= qpos) & (kpos > qpos - window) mask. O(S * 2W) work."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    C = S // window
    qc = q.reshape(B, C, window, Hq, hd)
    kc = k.reshape(B, C, window, Hkv, hd)
    vc = v.reshape(B, C, window, Hkv, hd)
    # previous chunk (zeros for the first chunk)
    kprev = jnp.pad(kc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    vprev = jnp.pad(vc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    kcat = jnp.concatenate([kprev, kc], axis=2)   # [B,C,2W,Hkv,hd]
    vcat = jnp.concatenate([vprev, vc], axis=2)
    kcat = _repeat_kv(kcat.reshape(B, C * 2 * window, Hkv, hd), Hq)\
        .reshape(B, C, 2 * window, Hq, hd)
    vcat = _repeat_kv(vcat.reshape(B, C * 2 * window, Hkv, hd), Hq)\
        .reshape(B, C, 2 * window, Hq, hd)
    qpos = jnp.arange(window)[:, None] + window   # within [W, 2W)
    kpos = jnp.arange(2 * window)[None, :]
    mask = (kpos <= qpos) & (kpos > qpos - window)      # [W, 2W]
    # first chunk: mask out the zero-padded "previous" half
    first = (kpos >= window) & mask
    mask_all = jnp.broadcast_to(mask, (C, window, 2 * window))
    mask_all = mask_all.at[0].set(first)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    logits = jnp.einsum("bcqhd,bckhd->bchqk", qc * scale, kcat)
    logits = jnp.where(mask_all[None, :, None], logits.astype(jnp.float32),
                       NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bchqk,bckhd->bcqhd", probs, vcat)
    return out.reshape(B, S, Hq, hd)


# -------------------------------------------------------------------- decode
def init_kv_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
                  window: int, dtype) -> dict:
    L = window if window and window > 0 else max_len
    return {
        "k": jnp.zeros((batch, L, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, L, n_kv_heads, head_dim), dtype),
    }


def decode_attention(params, cache: dict, x, *, t, n_heads: int,
                     n_kv_heads: int, head_dim: int, window: int = 0,
                     rope: bool = True, rope_theta: float = 10_000.0
                     ) -> Tuple[jnp.ndarray, dict]:
    """One-token decode. x: [B, 1, d_model]; t: scalar int32 — number of
    tokens already in the cache (the new token has position t). Global
    caches are [B, max_len, ...]; local caches are ring buffers [B, W, ...].
    """
    B = x.shape[0]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    pos = jnp.full((B, 1), t, jnp.int32)
    if rope:
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
    L = cache["k"].shape[1]
    if window and window > 0:
        slot = t % L                       # ring buffer
    else:
        slot = jnp.minimum(t, L - 1)       # full-length cache
    kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    # valid positions: ring buffer slot s holds absolute position
    #   global: s ; local: the latest absolute position congruent to s mod L
    idx = jnp.arange(L)
    if window and window > 0:
        # absolute position stored in slot s after writing token t:
        #   p(s) = t - ((t - s) mod L)
        abs_pos = t - jnp.mod(t - idx, L)
        valid = (abs_pos >= 0) & (abs_pos <= t) & (abs_pos > t - window)
    else:
        valid = idx <= t
    mask = valid[None, None, None, :]                  # [1,1,1,L]
    out = _sdpa(q, kc, vc, mask)                       # [B,1,Hq,hd]
    out = out.reshape(B, 1, n_heads * head_dim) @ params["wo"]
    return out, {"k": kc, "v": vc}
