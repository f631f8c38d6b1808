"""jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute with interpret=True; on TPU the
same pallas_call lowers to Mosaic. ``interpret=None`` auto-detects.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.d2ft_attention import (d2ft_flash_attention,
                                          gated_flash_attention,
                                          pad_to_blocks)
from repro.kernels import d2ft_moe as _moe
from repro.kernels import d2ft_rglru as _rglru
from repro.kernels import d2ft_ssd as _ssd
from repro.kernels.lora_matmul import lora_matmul
from repro.kernels.paged_decode import paged_flash_decode
from repro.kernels import ref


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _concrete(x):
    """np array when x is a concrete value, None when it is a tracer."""
    try:
        return np.asarray(x)
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        return None


def _validate_gates(g_f, g_b, B: int, H: int, live_fwd, live_bwd):
    """Shape check always; two value contracts are checked whenever the
    gates are concrete — i.e. at every direct call; inside an outer jit the
    gates are tracers and the checks are skipped (the schedule construction
    upholds them):

    * the documented ``g_b <= g_f`` invariant (a p_s head cannot run its
      backward);
    * ``live_fwd``/``live_bwd`` being true *upper* bounds on the live gate
      counts — an undersized bound would silently truncate the compaction
      gather and zero live slices' outputs/gradients (the classic mistake
      is passing per-(sample, group) schedule bounds without the
      heads-per-group scaling the model stack applies).
    """
    if g_f.shape != (B, H) or g_b.shape != (B, H):
        raise ValueError(
            f"gates must be [B={B}, H={H}], got {g_f.shape} / {g_b.shape}")
    cf, cb = _concrete(g_f), _concrete(g_b)
    if cf is None or cb is None:
        return
    if np.any(cb > cf):
        bad = np.argwhere(cb > cf)
        raise ValueError(
            "g_b <= g_f violated (a gated-off forward cannot have a live "
            f"backward): g_b > g_f at (sample, head) {bad[:8].tolist()}"
            f"{' ...' if len(bad) > 8 else ''}")
    for name, bound, live in (("live_fwd", live_fwd, int((cf != 0).sum())),
                              ("live_bwd", live_bwd, int((cb != 0).sum()))):
        if bound is not None and bound < live:
            raise ValueError(
                f"{name}={bound} is below the live gate count {live}: the "
                "compaction bound must be an upper bound or live slices "
                "would be silently dropped (did you forget the H//G "
                "heads-per-group scaling?)")


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret",
                                             "live_fwd", "live_bwd"))
def _gated_attention_impl(q, k, v, g_f, g_b, *, causal, window, block_q,
                          block_k, interpret, live_fwd, live_bwd):
    q, k, v, bq, bk, S, Sp = pad_to_blocks(q, k, v, block_q, block_k)
    out = gated_flash_attention(q, k, v, g_f, g_b, causal, window, bq, bk,
                                _auto_interpret(interpret), S, live_fwd,
                                live_bwd)
    return out[:, :, :S] if Sp != S else out


def gated_attention(q, k, v, g_f, g_b=None, *, causal: bool = True,
                    window: int = 0, block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    live_fwd: Optional[int] = None,
                    live_bwd: Optional[int] = None):
    """D2FT-gated flash attention with a gate-aware backward (custom VJP).

    q, k, v: [B, H, S, hd]; g_f, g_b: [B, H] float {0,1} with g_b <= g_f
    elementwise (checked whenever the gates are concrete). g_f gates the
    forward (0 -> zeros and no forward MXU work: p_s); g_b gates the
    backward kernel (0 -> zero dq/dk/dv and no backward MXU work: p_o and
    p_s). Omitting g_b uses g_b = g_f, i.e. the fully differentiable p_f
    path (back-compat with the forward-only API).

    live_fwd / live_bwd: optional *static* upper bounds on the number of
    g_f != 0 / g_b != 0 (sample, head) slices — e.g. B*H scaled by the
    Schedule's p_f/p_o micro-batch counts (``core.schedule
    .live_slice_bounds``). When given, the kernels run on a compacted grid
    of that many slices (live slices gathered front via a stable argsort of
    the gates, results scattered back with zeros elsewhere) so gated-off
    slices pay neither grid steps nor DMA. Bounds must be >= the actual
    live counts for the gates passed; None dispatches all B*H slices.

    Sequences of at most 256 rows run as one whole tile per slice, with no
    padding (the short path; the block sizes do not apply). Longer ones
    that don't divide the tiles either shrink the tiles (near-divisor
    case) or zero-pad S (select_blocks); padded rows/tiles are masked via
    the kernels' seq_len bound and sliced off, and jnp.pad's VJP routes the
    padding out of the gradients.
    """
    if g_b is None:
        g_b = g_f
    B, H, S, _ = q.shape
    _validate_gates(g_f, g_b, B, H, live_fwd, live_bwd)
    return _gated_attention_impl(q, k, v, g_f, g_b, causal=causal,
                                 window=window, block_q=block_q,
                                 block_k=block_k, interpret=interpret,
                                 live_fwd=live_fwd, live_bwd=live_bwd)


# ------------------------------------------------------- gated SSD / RG-LRU
def _scan_pad(S: int, chunk: int):
    """(Q, Sp): chunk size actually used and the padded length. Scan-shaped
    inputs can't shrink tiles the way attention's select_blocks does (the
    chunk is the recurrence granularity), so odd lengths always zero-pad up
    to the next chunk multiple — safe because a padded row carries zero
    log-decay (identity state update) and zero input."""
    Q = min(chunk, S)
    return Q, -(-S // Q) * Q


@functools.partial(jax.jit, static_argnames=("chunk", "interpret",
                                             "live_fwd", "live_bwd"))
def _gated_ssd_impl(x, da, Bm, Cm, g_f, g_b, *, chunk, interpret, live_fwd,
                    live_bwd):
    S = x.shape[1]
    Q, Sp = _scan_pad(S, chunk)
    if Sp != S:
        pad = (0, Sp - S)
        x = jnp.pad(x, ((0, 0), pad, (0, 0), (0, 0)))
        da = jnp.pad(da, ((0, 0), pad, (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), pad, (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), pad, (0, 0)))
    y = _ssd.gated_ssd_scan(x, da, Bm, Cm, g_f, g_b, Q,
                            _auto_interpret(interpret), live_fwd, live_bwd)
    return y[:, :S] if Sp != S else y


def gated_ssd_scan(x, da, Bm, Cm, g_f, g_b=None, *, chunk: int,
                   live_fwd: Optional[int] = None,
                   live_bwd: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """D2FT-gated SSD chunked scan with a gate-aware backward (custom VJP).

    x: [B,S,H,P] dt-weighted input, da: [B,S,H] per-step log-decay
    (``dt * A``), Bm/Cm: [B,S,N] (shared across heads); g_f, g_b: [B,H]
    float {0,1} with g_b <= g_f per (sample, head) — g_f == 0 heads produce
    zeros and skip the forward chunk loop (p_s), g_b == 0 heads skip every
    backward matmul and get zero dx/ddA/dB/dC (p_o and p_s). Omitting g_b
    uses g_b = g_f. live_fwd / live_bwd are static live-slice upper bounds
    enabling compaction dispatch (``core.schedule.live_slice_bounds``
    scaled by heads-per-group). S that doesn't divide the chunk is
    zero-padded (identity decay) and sliced back — the recurrent-arch
    analogue of attention's select_blocks pad path.
    """
    if g_b is None:
        g_b = g_f
    B, S, H, P = x.shape
    _validate_gates(g_f, g_b, B, H, live_fwd, live_bwd)
    return _gated_ssd_impl(x, da, Bm, Cm, g_f, g_b, chunk=chunk,
                           interpret=interpret, live_fwd=live_fwd,
                           live_bwd=live_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret",
                                             "live_fwd", "live_bwd"))
def _gated_rglru_impl(la, b, g_f, g_b, *, chunk, interpret, live_fwd,
                      live_bwd):
    S = la.shape[1]
    Q, Sp = _scan_pad(S, chunk)
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0))
        la, b = jnp.pad(la, pad), jnp.pad(b, pad)
    h = _rglru.gated_rglru_scan(la, b, g_f, g_b, Q,
                                _auto_interpret(interpret), live_fwd,
                                live_bwd)
    return h[:, :S] if Sp != S else h


def gated_rglru_scan(la, b, g_f, g_b=None, *, chunk: int = 128,
                     live_fwd: Optional[int] = None,
                     live_bwd: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """D2FT-gated RG-LRU scan h_t = exp(la_t) h_{t-1} + b_t (custom VJP).

    la, b: [B,S,W] (la <= 0); g_f, g_b: [B,G] float {0,1} with g_b <= g_f
    per (sample, channel-group), W % G == 0 — the W channels split into G
    contiguous bands gating independently. Returns h [B,S,W] f32 with
    g_f-dead bands exactly zero; g_b-dead bands contribute zero dla/db and
    skip every backward contraction. live_fwd / live_bwd enable compaction
    dispatch over the (B*G) slice axis. Odd S zero-pads to the chunk
    (identity decay) and slices back.
    """
    if g_b is None:
        g_b = g_f
    B, S, W = la.shape
    G = g_f.shape[1]
    if W % G != 0:
        raise ValueError(f"lru width {W} not divisible by G={G} gate groups")
    _validate_gates(g_f, g_b, B, G, live_fwd, live_bwd)
    return _gated_rglru_impl(la, b, g_f, g_b, chunk=chunk,
                             interpret=interpret, live_fwd=live_fwd,
                             live_bwd=live_bwd)


# ------------------------------------------------------------ gated MoE FFN
@functools.partial(jax.jit, static_argnames=("act", "block_c", "live_slots",
                                             "live_bwd_slots", "interpret"))
def _gated_moe_impl(xb, w_up, w_gate, w_down, fwd_slots, bwd_slots, *, act,
                    block_c, live_slots, live_bwd_slots, interpret):
    E, C, D = xb.shape
    bc = min(block_c, C)
    Cp = -(-C // bc) * bc
    n_cb = Cp // bc
    # static capacity truncation: trailing blocks beyond the schedule's
    # live-slot bound are provably empty — don't launch or stream them
    if live_slots is not None and live_slots < Cp:
        n_cb = min(n_cb, -(-max(1, int(live_slots)) // bc))
    # the backward truncates independently, on the g_b bound: the dispatch
    # packs backward-live slots into a capacity prefix per expert, so a
    # g_b < g_f mix shrinks the backward grid below the forward's
    n_cb_b = n_cb
    if live_bwd_slots is not None:
        n_cb_b = min(n_cb, -(-max(1, int(live_bwd_slots)) // bc))
    Cr = n_cb * bc
    pad = ((0, 0), (0, max(0, Cr - C)))
    xs = jnp.pad(xb, pad + ((0, 0),))[:, :Cr]
    fm = jnp.pad(fwd_slots, pad)[:, :Cr].reshape(E, n_cb, bc)
    bm = jnp.pad(bwd_slots, pad)[:, :Cr].reshape(E, n_cb, bc)
    fm = (fm.sum(-1) > 0).astype(jnp.float32)
    bm = (bm.sum(-1) > 0).astype(jnp.float32)
    y = _moe.gated_moe_ffn(xs, w_up, w_gate, w_down, fm, bm, act, bc,
                           n_cb_b, _auto_interpret(interpret))
    if Cr < C:
        y = jnp.pad(y, ((0, 0), (0, C - Cr), (0, 0)))
    return y[:, :C]


def gated_moe_ffn(xb, w_up, w_gate, w_down, fwd_slots, bwd_slots=None, *,
                  act: str = "silu", block_c: int = 128,
                  live_slots: Optional[int] = None,
                  live_bwd_slots: Optional[int] = None,
                  interpret: Optional[bool] = None):
    """Doubly-sparse MoE expert FFN over a capacity buffer (custom VJP).

    xb: [E, C, D] front-packed capacity buffer (see models/moe.py's
    gate-aware dispatch), w_up/w_gate: [E, D, F], w_down: [E, F, D];
    fwd_slots / bwd_slots: [E, C] float {0,1} slot-occupancy masks
    (bwd <= fwd elementwise — a slot's backward can't be live if its
    forward isn't). Slots are grouped into capacity blocks of ``block_c``;
    a block computes only when it holds at least one live slot
    (``@pl.when`` skip otherwise). ``live_slots`` is a static upper bound
    on live slots per expert (schedule live-sample bound x top_k): blocks
    beyond it are truncated from the grid entirely — the MoE analogue of
    compaction dispatch. ``live_bwd_slots`` bounds the *backward-live*
    slots separately (g_b bound x top_k): the dispatch packs p_f slots
    into a capacity prefix per expert, so the backward grid truncates to
    this smaller bound even when the forward must cover every p_o slot.
    Omitting it shares the forward's bound (every backward-live slot is
    forward-live, so ``live_slots`` always covers it). Omitting bwd_slots
    uses bwd = fwd.
    """
    if bwd_slots is None:
        bwd_slots = fwd_slots
    E, C, D = xb.shape
    if fwd_slots.shape != (E, C) or bwd_slots.shape != (E, C):
        raise ValueError(
            f"slot masks must be [E={E}, C={C}], got {fwd_slots.shape} / "
            f"{bwd_slots.shape}")
    cf, cb = _concrete(fwd_slots), _concrete(bwd_slots)
    if cf is not None and cb is not None:
        if np.any(cb > cf):
            raise ValueError("bwd_slots <= fwd_slots violated: a slot with "
                             "no live forward cannot have a live backward")
        if live_slots is not None:
            occupied = np.argwhere(cf != 0)
            top = int(occupied[:, 1].max()) + 1 if occupied.size else 0
            if live_slots < top:
                raise ValueError(
                    f"live_slots={live_slots} is below the highest occupied "
                    f"slot {top}: the capacity-truncation bound must cover "
                    "every live slot or their outputs would be zeroed")
        if live_bwd_slots is not None:
            occ_b = np.argwhere(cb != 0)
            top_b = int(occ_b[:, 1].max()) + 1 if occ_b.size else 0
            if live_bwd_slots < top_b:
                raise ValueError(
                    f"live_bwd_slots={live_bwd_slots} is below the highest "
                    f"occupied backward slot {top_b}: the backward "
                    "truncation bound must cover every backward-live slot "
                    "or their gradients would be zeroed")
    return _gated_moe_impl(xb, w_up, w_gate, w_down, fwd_slots, bwd_slots,
                           act=act, block_c=block_c, live_slots=live_slots,
                           live_bwd_slots=live_bwd_slots,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _paged_decode_impl(q, k_pages, v_pages, page_table, lengths, g_f, *,
                       window, interpret):
    return paged_flash_decode(q, k_pages, v_pages, page_table, lengths, g_f,
                              window=window,
                              interpret=_auto_interpret(interpret))


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           g_f=None, *, window: int = 0,
                           interpret: Optional[bool] = None):
    """Decode-mode entry to the gated attention kernel family: one token per
    sequence against a paged KV cache, pages streamed by table indirection
    from scalar prefetch (kernels/paged_decode.py).

    q: [B, H, hd] post-rope queries (position ``lengths[b]``); k_pages,
    v_pages: [n_pages, page_size, n_kv, hd] shared pools (GQA un-expanded —
    the kernel's index map resolves head groups); page_table: [B, n_pmax]
    int32, padded with the null page 0 (every entry must be a valid page id:
    index maps run before block-skip predicates); lengths: [B] int32 tokens
    already cached. g_f: optional [B, H] forward gates in {0,1} — serving is
    schedule-free so the default is all-ones; gated-off heads write zeros
    and skip the MXU like the training kernel's p_s path. Returns [B,H,hd].
    """
    B, H, hd = q.shape
    if q.shape[-1] != k_pages.shape[-1]:
        raise ValueError(f"q head_dim {hd} != pool head_dim "
                         f"{k_pages.shape[-1]}")
    if k_pages.shape != v_pages.shape:
        raise ValueError(f"k/v pool shapes differ: {k_pages.shape} vs "
                         f"{v_pages.shape}")
    if page_table.shape[0] != B or lengths.shape != (B,):
        raise ValueError(
            f"page_table/lengths batch mismatch: {page_table.shape}, "
            f"{lengths.shape}, B={B}")
    if g_f is None:
        g_f = jnp.ones((B, H), jnp.float32)
    elif g_f.shape != (B, H):
        raise ValueError(f"g_f must be [B={B}, H={H}], got {g_f.shape}")
    ct = _concrete(page_table)
    if ct is not None:
        n_pages = k_pages.shape[0]
        if ct.min() < 0 or ct.max() >= n_pages:
            raise ValueError(
                f"page_table entries must be valid page ids in [0, "
                f"{n_pages}): got range [{ct.min()}, {ct.max()}]")
    return _paged_decode_impl(q, k_pages, v_pages, page_table, lengths, g_f,
                              window=window, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "block_m", "block_n",
                                             "interpret"))
def lora_linear(x, w, a, b, scale: float = 1.0, *, block_m: int = 256,
                block_n: int = 256, interpret: Optional[bool] = None):
    """Fused y = x·W + scale·(x·A)·B for 2-D or 3-D x."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y = lora_matmul(x2, w, a, b, scale, block_m=block_m, block_n=block_n,
                    interpret=_auto_interpret(interpret))
    return y.reshape(*shape[:-1], w.shape[-1])
