"""Pallas TPU kernels: D2FT-gated flash attention, forward *and* backward.

The paper skips a subnet's work per micro-batch: p_s (shortcut) skips the
subnet entirely, p_o (forward-only) runs the forward but skips the backward.
On a GPU cluster the subnet's device simply idles; the TPU analogue is a
flash-attention kernel family with per-(sample, head) gate operands:

* forward kernel, gate ``g_f``: when ``g_f == 0`` the whole online-softmax
  KV loop for that (batch, head) slice is skipped with ``@pl.when`` and
  zeros are written once, so the MXU never sees the block (p_s).
* fused backward kernel, gate ``g_b``: when ``g_b == 0`` every backward
  matmul for the slice is skipped the same way and zero gradients are
  written once (p_o *and* p_s) — this is where the paper's headline ~40%
  training-compute saving lives, since the backward is ~60% of attention
  FLOPs.

Two dispatch-level optimisations make the *launched* work proportional to
the *live* work instead of merely skipping the MXU:

1. **Compaction dispatch** — the (B, H) axes are flattened into one slice
   axis and, when the caller supplies a static live-count upper bound
   (derived from the Schedule's p_f/p_o counts), the live slices are
   gathered front via a stable argsort permutation computed from the gates.
   The kernels then run on a grid whose leading dim is ``n_live`` instead of
   ``B*H`` and the results are scattered back with zeros elsewhere — so
   gated-off slices cost neither sequential grid steps nor HBM→VMEM DMA.
2. **Fused one-pass backward** — a single kernel computes ``s`` and ``dp``
   once per tile and emits dq, dk and dv together: 5 matmuls per live tile
   instead of the 7 the previous split dq / transposed-grid dkv pair paid,
   one launch instead of two, and one read of q/k/v/do/lse/delta instead of
   two. dq is accumulated across kv steps in a per-slice output block whose
   index map ignores the inner grid dims, so it stays resident in VMEM for
   the whole slice (no recomputation, no input/output aliasing — which the
   interpreter does not honour for read-back accumulation).

Supports causal and sliding-window masks (the assigned archs' local
-attention layers).

Two tilings of the same algorithm, chosen by the sequence length
(``attention_geometry``):

* **short path** — a sequence of at most ``SHORT_SEQ_ROWS`` (256) rows,
  rounded up to the 8-row sublane, is one whole, unpadded tile per slice
  (ViT-S/16's 197 tokens). The grid is 1-D over the dispatched slices, and
  each step takes ``spb`` slices as [spb, S, hd] blocks (lse and delta as
  [spb, S, 1] columns). ``slices_per_step`` derives ``spb`` from (S,
  head_dim, itemsize) so that the double-buffered blocks fill half the
  default scoped VMEM (``SHORT_VMEM``), counting head_dim and the columns
  at whole 128-lane widths: 8 forward and 4 backward slices a step at
  S=197, head_dim 64. A step loops over its slices, each under its own
  gate's ``@pl.when``. The forward is a plain full-row softmax (no m/l
  scratch, no rescaling); the backward one pass per slice with s, p, dp
  and ds in VMEM — the same 5 matmuls, no dq residency across steps. The
  launch rounds the dispatch count up to whole steps with dead (gate 0)
  slices. Causal and window masks are applied element-wise only: the
  masked triangle is computed, which at this length costs less than the
  grid steps a tiling would add.
* **flash path** — longer sequences, described below.

Tiling: q tiles [block_q, head_dim], kv tiles [block_k, head_dim] — both
MXU-aligned (multiples of 128 for fp32/bf16 lanes). Forward scratch: the
fp32 accumulator (block_q × head_dim) plus m/l online-softmax statistics in
VMEM; the KV axis is the innermost (sequential) grid dim so scratch carries
across kv steps. The forward additionally emits the logsumexp residual
[B, H, S] consumed by the backward kernel (the paper-standard o/lse-residual
flash backward — s and p are recomputed blockwise instead of materializing
[S, S]). Fully-masked causal/window blocks are skipped with ``@pl.when`` in
every kernel.

``gated_flash_attention`` is the differentiable custom-VJP entry point;
``d2ft_flash_attention`` remains the forward-only op. The jit'd public
wrapper with interpret auto-detection is ``repro.kernels.ops
.gated_attention``; the pure-jnp oracles live in ``repro.kernels.ref``.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import contract as _contract

NEG_INF = -2.0 ** 30
# logsumexp stored for rows that never saw a live key: large *positive* so
# exp(s - LSE_MASKED) underflows to exactly 0 in the backward for any score.
LSE_MASKED = 2.0 ** 30

# Test hook: when set to a callable, the backward kernel invokes it (via
# jax.debug.callback) once per *executed* compute block. Lets tests assert
# that g_b == 0 slices do no backward matmul work — static HLO FLOP counts
# cannot see the skip because interpret mode lowers the grid to a loop whose
# body XLA counts once regardless of trip count or taken branches. The hook
# is read at trace time: set it before the first trace of the function under
# test (avoid pre-cached jits).
on_backward_block = None

# Test hook: when set to a callable, every pallas_call built by _forward /
# _backward reports its dispatch as ``on_dispatch(kind, grid)`` with kind in
# {"fwd", "bwd"} at TRACE time. Lets tests assert the compacted grid's
# leading dim equals the live-slice bound instead of B*H. Same caveat as
# on_backward_block: set it before the first trace (jit caches skip tracing).
on_dispatch = None


def _maybe_count_block():
    if on_backward_block is not None:
        jax.debug.callback(on_backward_block)


def _report_dispatch(kind: str, grid):
    if on_dispatch is not None:
        on_dispatch(kind, tuple(grid))


def _block_live(qpos0, kpos0, block_q: int, block_k: int, causal: bool,
                window: int, seq_len: int):
    """Whether the (iq, ik) tile contains any unmasked in-bounds entry
    (tiles fully in the seq_len padding region are skipped too)."""
    live = jnp.logical_and(qpos0 < seq_len, kpos0 < seq_len)
    if causal:
        live &= kpos0 <= qpos0 + block_q - 1
    if window and window > 0:
        live &= kpos0 + block_k - 1 > qpos0 - window
    return live


def _tile_mask(qpos0, kpos0, block_q: int, block_k: int, seq_len: int,
               causal: bool, window: int):
    qpos = qpos0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_len
    if causal:
        mask &= kpos <= qpos
    if window and window > 0:
        mask &= kpos > qpos - window
    return mask


# ==================================================== compaction dispatch
# Shared across every gated kernel (ssd / rglru / moe speak the same
# contract); canonical definitions live in repro.kernels.contract.
_dispatch_count = _contract.dispatch_count
_live_permutation = _contract.live_permutation


def launch_shape(n_disp: int, spb_max: int):
    """(grid steps, slices per step) for ``n_disp`` dispatched slices at
    most ``spb_max`` to a step: the fewest steps, then the fewest slices
    per step that cover ``n_disp`` — so fewer than one dead slice per step
    pads the launch."""
    steps = -(-n_disp // spb_max)
    return steps, -(-n_disp // steps)


def _launch(g, live, N: int, spb_max: int):
    """(idx, spb): the gather permutation of the launched slices (None for
    all N in order) and the slices per grid step. The launch is the
    dispatch count rounded up to whole steps: past the live bound the
    stable permutation holds only dead slices, and past N the indices are
    out of range, which ``_gather`` fills with zeros (gate 0) and
    ``_scatter`` drops. No live work is added either way."""
    steps, spb = launch_shape(_dispatch_count(live, N), spb_max)
    n_launch = steps * spb
    if n_launch == N:
        return None, spb
    idx = _live_permutation(g, min(n_launch, N))
    if n_launch > N:
        idx = jnp.concatenate(
            [idx, jnp.arange(N, n_launch, dtype=idx.dtype)])
    return idx, spb


def _gather(a, idx):
    return jnp.take(a, idx, axis=0, mode="fill", fill_value=0)


def _scatter(base, idx, a):
    return base.at[idx].set(a, mode="drop", unique_indices=True)


# ================================================================== forward
def _fwd_kernel(gate_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, scale: float, causal: bool, window: int,
                block_q: int, block_k: int, n_k: int, seq_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    gate = gate_ref[pl.program_id(0)]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level skip: gate==0 (p_s subnet) or fully-masked block
    qpos0 = iq * block_q
    kpos0 = ik * block_k
    run = jnp.logical_and(
        gate != 0, _block_live(qpos0, kpos0, block_q, block_k, causal,
                               window, seq_len))

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [bq, hd]
        k = k_ref[0].astype(jnp.float32)               # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q * scale, k,
                                (((1,), (1,)), ((), ())))   # [bq, bk]
        mask = _tile_mask(qpos0, kpos0, block_q, block_k, seq_len, causal,
                          window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                            # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = l_ref[...]                                 # [bq, 1]
        safe = jnp.where(l > 0, l, 1.0)
        out = jnp.where(l > 0, acc_ref[...] / safe, 0.0)
        out = out * (gate != 0).astype(jnp.float32)
        o_ref[0] = out.astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l > 0, m_ref[...] + jnp.log(safe),
                               LSE_MASKED)


def _fwd_short_kernel(gate_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      scale: float, causal: bool, window: int, seq_len: int,
                      spb: int):
    """Short-sequence forward, grid (n_steps,): ``spb`` slices per step,
    each one whole [S, hd] tile. A plain full-row softmax per slice — the
    whole score row is in VMEM, so no m/l scratch and no rescaling. A
    gated-off slice writes zeros and LSE_MASKED without a matmul."""
    S = q_ref.shape[1]
    base = pl.program_id(0) * spb

    def one_slice(j, carry):
        gate = gate_ref[base + j]

        @pl.when(gate != 0)
        def _compute():
            q = q_ref[j].astype(jnp.float32)           # [S, hd]
            k = k_ref[j].astype(jnp.float32)
            v = v_ref[j].astype(jnp.float32)
            s = jax.lax.dot_general(q * scale, k,
                                    (((1,), (1,)), ((), ())))   # [S, S]
            s = jnp.where(_tile_mask(0, 0, S, S, seq_len, causal, window),
                          s, NEG_INF)
            m = jnp.max(s, axis=1, keepdims=True)      # [S, 1]
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
            o_ref[j] = (acc / l).astype(o_ref.dtype)
            lse_ref[j] = m + jnp.log(l)

        @pl.when(gate == 0)
        def _skip():
            o_ref[j] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)
            lse_ref[j] = jnp.full(lse_ref.shape[1:], LSE_MASKED, jnp.float32)

        return carry

    jax.lax.fori_loop(0, spb, one_slice, 0)


def _fwd_short_call(gate, q, k, v, *, spb: int, scale: float, causal: bool,
                    window: int, seq_len: int, interpret: bool):
    n, S, hd = q.shape
    grid = (n // spb,)
    _report_dispatch("fwd", grid)
    mat = pl.BlockSpec((spb, S, hd), lambda i, g: (i, 0, 0))
    col = pl.BlockSpec((spb, S, 1), lambda i, g: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_short_kernel, scale=scale, causal=causal,
                          window=window, seq_len=seq_len, spb=spb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                   # g_f
            grid=grid, in_specs=[mat, mat, mat], out_specs=[mat, col]),
        out_shape=[
            jax.ShapeDtypeStruct((n, S, hd), q.dtype),
            jax.ShapeDtypeStruct((n, S, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="d2ft_attn_fwd_short",
    )(gate, q, k, v)


def _fwd_flash_call(gate, q, k, v, *, block_q: int, block_k: int,
                    scale: float, causal: bool, window: int, seq_len: int,
                    interpret: bool):
    n, S, hd = q.shape
    n_q = S // block_q
    n_k = S // block_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k, seq_len=seq_len)

    grid = (n, n_q, n_k)
    _report_dispatch("fwd", grid)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                   # g_f
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, hd),
                             lambda s, iq, ik, g: (s, iq, 0)),
                pl.BlockSpec((1, block_k, hd),
                             lambda s, iq, ik, g: (s, ik, 0)),
                pl.BlockSpec((1, block_k, hd),
                             lambda s, iq, ik, g: (s, ik, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, hd),
                             lambda s, iq, ik, g: (s, iq, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda s, iq, ik, g: (s, iq, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, hd), jnp.float32),   # acc
                pltpu.VMEM((block_q, 1), jnp.float32),    # m
                pltpu.VMEM((block_q, 1), jnp.float32),    # l
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((n, S, hd), q.dtype),
            jax.ShapeDtypeStruct((n, S, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="d2ft_attn_fwd_flash",
    )(gate, q, k, v)


def _forward(q, k, v, g_f, *, causal: bool, window: int, block_q: int,
             block_k: int, interpret: bool, seq_len: int = 0,
             live: int = None):
    """Returns (o [B,H,S,hd], lse [B,H,S,1] f32). seq_len is the true length
    when the arrays carry tile padding (0 means unpadded). ``live`` is the
    static live-slice upper bound enabling compaction dispatch. One
    whole-sequence tile (``is_short``) takes the short kernel, any other
    tiling the flash kernel."""
    B, H, S, hd = q.shape
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    seq_len = seq_len or S
    scale = 1.0 / (hd ** 0.5)

    N = B * H
    q, k, v = (a.reshape(N, S, hd) for a in (q, k, v))
    g = g_f.reshape(N)
    short = is_short(S, block_q, block_k)
    spb_max = (slices_per_step(S, hd, q.dtype.itemsize, "fwd") if short
               else 1)
    idx, spb = _launch(g, live, N, spb_max)
    if idx is not None:
        q, k, v, g = (_gather(a, idx) for a in (q, k, v, g))

    common = dict(scale=scale, causal=causal, window=window,
                  seq_len=seq_len, interpret=interpret)
    gate = _contract.gate_operand(g)
    if short:
        o, lse = _fwd_short_call(gate, q, k, v, spb=spb, **common)
    else:
        o, lse = _fwd_flash_call(gate, q, k, v, block_q=block_q,
                                 block_k=block_k, **common)

    if idx is not None:
        # scatter live results back; dead (never-dispatched) slices are the
        # zero-fill, dispatched-but-gated-off padding slices wrote zeros /
        # LSE_MASKED themselves so the set() is a no-op value-wise.
        o = _scatter(jnp.zeros((N, S, hd), o.dtype), idx, o)
        lse = _scatter(jnp.full((N, S, 1), LSE_MASKED, jnp.float32), idx,
                       lse)
    return o.reshape(B, H, S, hd), lse.reshape(B, H, S, 1)


def d2ft_flash_attention(q, k, v, gates, *, causal: bool = True,
                         window: int = 0, block_q: int = 128,
                         block_k: int = 128, interpret: bool = False,
                         live: int = None):
    """Forward-only gated flash attention (no VJP registered).

    q, k, v: [B, H, S, hd] (kv heads already expanded to H);
    gates: [B, H] float {0,1}. Returns [B, H, S, hd]. The tiles come from
    the same ``attention_geometry`` wrapper as ``ops.gated_attention``
    (a short sequence is one whole tile; padded rows are masked via the
    kernel's seq_len bound and sliced off). ``live`` optionally enables
    compaction dispatch with a static live-slice upper bound. For the
    differentiable path use ``gated_flash_attention`` / ``ops
    .gated_attention``.
    """
    q, k, v, bq, bk, S, Sp = pad_to_blocks(q, k, v, block_q, block_k)
    out = _forward(q, k, v, gates, causal=causal, window=window,
                   block_q=bq, block_k=bk, interpret=interpret,
                   seq_len=S, live=live)[0]
    return out[:, :, :S] if Sp != S else out


# ================================================================= backward
def _bwd_fused_kernel(gate_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      scale: float, causal: bool, window: int, block_q: int,
                      block_k: int, n_q: int, seq_len: int):
    """Fused one-pass backward, grid (n_slices, n_k, n_q) — q innermost.

    Per live tile: 5 matmuls (``s``, ``p^T·do``, ``do·v^T``, ``ds·k``,
    ``ds^T·q``); ``s`` and ``dp`` are computed once and shared between the
    dq and dk paths (the split-kernel design recomputed them, 3 + 4 = 7).
    dk/dv accumulate in VMEM scratch while the kv tile stays resident and
    flush at the end of each q sweep. dq accumulates *in the output block
    itself*: its index map ignores (ik, iq), so the whole [S, hd] per-slice
    dq tile stays resident in VMEM across the slice's grid steps and is
    flushed to HBM exactly once — cross-step accumulation without
    recomputation or input/output aliasing. ``g_b == 0`` skips every matmul;
    zeros are written once per slice."""
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    gate = gate_ref[pl.program_id(0)]

    @pl.when(jnp.logical_and(ik == 0, iq == 0))
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(iq == 0)
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    qpos0 = iq * block_q
    kpos0 = ik * block_k
    run = jnp.logical_and(
        gate != 0, _block_live(qpos0, kpos0, block_q, block_k, causal,
                               window, seq_len))

    @pl.when(run)
    def _compute():
        _maybe_count_block()
        q = q_ref[0].astype(jnp.float32)               # [bq, hd]
        k = k_ref[0].astype(jnp.float32)               # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)             # [bq, hd]
        lse = lse_ref[0]                               # [bq, 1]
        delta = delta_ref[0]                           # [bq, 1]
        s = jax.lax.dot_general(q * scale, k,
                                (((1,), (1,)), ((), ())))   # [bq, bk]
        mask = _tile_mask(qpos0, kpos0, block_q, block_k, seq_len, causal,
                          window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                           # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta)                          # [bq, bk]
        rows = pl.ds(pl.multiple_of(qpos0, 8), block_q)
        dq_ref[0, rows, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ()))) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ()))) * scale

    @pl.when(iq == n_q - 1)
    def _flush():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_short_kernel(gate_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dk_ref, dv_ref, *, scale: float,
                      causal: bool, window: int, seq_len: int, spb: int):
    """Short-sequence backward, grid (n_steps,): ``spb`` slices per step,
    each one pass over its whole [S, hd] tile with ``s``, ``p``, ``dp`` and
    ``ds`` all in VMEM — the same 5 matmuls as the flash kernel's tile, and
    no dq residency across grid steps. ``g_b == 0`` skips every matmul and
    writes zeros."""
    S = q_ref.shape[1]
    base = pl.program_id(0) * spb

    def one_slice(j, carry):
        gate = gate_ref[base + j]

        @pl.when(gate != 0)
        def _compute():
            _maybe_count_block()
            q = q_ref[j].astype(jnp.float32)           # [S, hd]
            k = k_ref[j].astype(jnp.float32)
            v = v_ref[j].astype(jnp.float32)
            do = do_ref[j].astype(jnp.float32)
            lse = lse_ref[j]                           # [S, 1]
            delta = delta_ref[j]                       # [S, 1]
            s = jax.lax.dot_general(q * scale, k,
                                    (((1,), (1,)), ((), ())))   # [S, S]
            s = jnp.where(_tile_mask(0, 0, S, S, seq_len, causal, window),
                          s, NEG_INF)
            p = jnp.exp(s - lse)
            dv = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
            ds = p * (dp - delta)
            dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ()))) * scale
            dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ()))) * scale
            dq_ref[j] = dq.astype(dq_ref.dtype)
            dk_ref[j] = dk.astype(dk_ref.dtype)
            dv_ref[j] = dv.astype(dv_ref.dtype)

        @pl.when(gate == 0)
        def _skip():
            for ref in (dq_ref, dk_ref, dv_ref):
                ref[j] = jnp.zeros(ref.shape[1:], ref.dtype)

        return carry

    jax.lax.fori_loop(0, spb, one_slice, 0)


def _bwd_short_call(gate, q, k, v, do, lse, delta, *, spb: int,
                    scale: float, causal: bool, window: int, seq_len: int,
                    interpret: bool):
    n, S, hd = q.shape
    grid = (n // spb,)
    _report_dispatch("bwd", grid)
    mat = pl.BlockSpec((spb, S, hd), lambda i, g: (i, 0, 0))
    col = pl.BlockSpec((spb, S, 1), lambda i, g: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_short_kernel, scale=scale, causal=causal,
                          window=window, seq_len=seq_len, spb=spb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                   # g_b
            grid=grid, in_specs=[mat, mat, mat, mat, col, col],
            out_specs=[mat, mat, mat]),
        out_shape=[
            jax.ShapeDtypeStruct((n, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((n, S, hd), k.dtype),
            jax.ShapeDtypeStruct((n, S, hd), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="d2ft_attn_bwd_short",
    )(gate, q, k, v, do, lse, delta)


def _bwd_flash_call(gate, q, k, v, do, lse, delta, *, block_q: int,
                    block_k: int, scale: float, causal: bool, window: int,
                    seq_len: int, interpret: bool):
    n, S, hd = q.shape
    n_q = S // block_q
    n_k = S // block_k
    grid = (n, n_k, n_q)
    _report_dispatch("bwd", grid)
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          n_q=n_q, seq_len=seq_len),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                   # g_b
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, hd),
                             lambda s, ik, iq, g: (s, iq, 0)),       # q
                pl.BlockSpec((1, block_k, hd),
                             lambda s, ik, iq, g: (s, ik, 0)),       # k
                pl.BlockSpec((1, block_k, hd),
                             lambda s, ik, iq, g: (s, ik, 0)),       # v
                pl.BlockSpec((1, block_q, hd),
                             lambda s, ik, iq, g: (s, iq, 0)),       # do
                pl.BlockSpec((1, block_q, 1),
                             lambda s, ik, iq, g: (s, iq, 0)),       # lse
                pl.BlockSpec((1, block_q, 1),
                             lambda s, ik, iq, g: (s, iq, 0)),       # delta
            ],
            out_specs=[
                # dq: per-slice block, VMEM-resident across the whole
                # slice — S*hd*4 bytes of VMEM (~16MB/core caps S around
                # 16-32k at hd=128: docs/kernels.md)
                pl.BlockSpec((1, S, hd), lambda s, ik, iq, g: (s, 0, 0)),
                pl.BlockSpec((1, block_k, hd),
                             lambda s, ik, iq, g: (s, ik, 0)),
                pl.BlockSpec((1, block_k, hd),
                             lambda s, ik, iq, g: (s, ik, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                            pltpu.VMEM((block_k, hd), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((n, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((n, S, hd), k.dtype),
            jax.ShapeDtypeStruct((n, S, hd), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="d2ft_attn_bwd_flash",
    )(gate, q, k, v, do, lse, delta)


def _backward(q, k, v, g_b, o, lse, do, *, causal: bool, window: int,
              block_q: int, block_k: int, interpret: bool, seq_len: int = 0,
              live: int = None):
    B, H, S, hd = q.shape
    seq_len = seq_len or S
    scale = 1.0 / (hd ** 0.5)

    N = B * H
    q, k, v, o, do = (a.reshape(N, S, hd) for a in (q, k, v, o, do))
    lse = lse.reshape(N, S, 1)
    g = g_b.reshape(N)
    short = is_short(S, block_q, block_k)
    spb_max = (slices_per_step(S, hd, q.dtype.itemsize, "bwd") if short
               else 1)
    idx, spb = _launch(g, live, N, spb_max)
    if idx is not None:
        q, k, v, o, do, lse, g = (_gather(a, idx)
                                  for a in (q, k, v, o, do, lse, g))
    # delta_i = sum_d dO_id * O_id — cheap elementwise reduce, done outside
    # the kernel (standard flash-bwd preprocessing) on the *compacted*
    # operands so gated-off slices don't pay it either.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)

    common = dict(scale=scale, causal=causal, window=window,
                  seq_len=seq_len, interpret=interpret)
    gate = _contract.gate_operand(g)
    if short:
        dq, dk, dv = _bwd_short_call(gate, q, k, v, do, lse, delta, spb=spb,
                                     **common)
    else:
        dq, dk, dv = _bwd_flash_call(gate, q, k, v, do, lse, delta,
                                     block_q=block_q, block_k=block_k,
                                     **common)

    dq = dq.astype(q.dtype)
    if idx is not None:
        dq, dk, dv = (_scatter(jnp.zeros((N, S, hd), a.dtype), idx, a)
                      for a in (dq, dk, dv))
    return (dq.reshape(B, H, S, hd), dk.reshape(B, H, S, hd),
            dv.reshape(B, H, S, hd))


# =============================================================== custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11,
                                                    12))
def gated_flash_attention(q, k, v, g_f, g_b, causal, window, block_q,
                          block_k, interpret, seq_len=0, live_fwd=None,
                          live_bwd=None):
    """Differentiable gated flash attention core.

    Forward output is ``g_f``-gated (p_s heads produce zeros, MXU skipped);
    the registered backward returns dq/dk/dv that are *computed* only where
    ``g_b != 0`` — p_o / p_s slices skip every backward matmul via
    ``@pl.when`` and write zeros once. Gates receive zero cotangents (they
    are schedule constants). seq_len is the true length when the operands
    carry tile padding (0 = unpadded). ``live_fwd`` / ``live_bwd`` are
    static upper bounds on the number of g_f != 0 / g_b != 0 slices: when
    given, the kernels dispatch a compacted grid of that many slices instead
    of B*H (gather live front / scatter back — see the module docstring);
    None dispatches everything. Prefer the jit'd ``ops.gated_attention``,
    which also picks tile sizes and padding.
    """
    o, _ = _forward(q, k, v, g_f, causal=causal, window=window,
                    block_q=block_q, block_k=block_k, interpret=interpret,
                    seq_len=seq_len, live=live_fwd)
    return o


def _vjp_fwd(q, k, v, g_f, g_b, causal, window, block_q, block_k, interpret,
             seq_len=0, live_fwd=None, live_bwd=None):
    o, lse = _forward(q, k, v, g_f, causal=causal, window=window,
                      block_q=block_q, block_k=block_k, interpret=interpret,
                      seq_len=seq_len, live=live_fwd)
    return o, (q, k, v, g_f, g_b, o, lse)


def _vjp_bwd(causal, window, block_q, block_k, interpret, seq_len, live_fwd,
             live_bwd, res, do):
    q, k, v, g_f, g_b, o, lse = res
    dq, dk, dv = _backward(q, k, v, g_b, o, lse, do, causal=causal,
                           window=window, block_q=block_q, block_k=block_k,
                           interpret=interpret, seq_len=seq_len,
                           live=live_bwd)
    return dq, dk, dv, jnp.zeros_like(g_f), jnp.zeros_like(g_b)


gated_flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


# ======================================================= tile selection
SUBLANE = 8   # f32 sublane tile: every q/k tile is a multiple of it
LANE = 128    # lane width: a VMEM block's last dim is padded up to it

# Sequences of at most this many rows (S rounded up to SUBLANE) run the
# short kernels: one whole-sequence tile per slice. Their backward holds a
# slice's [S, S] f32 scores, p, dp and ds in VMEM at once — 1 MiB at 256
# rows beside the double-buffered blocks of several slices (SHORT_VMEM), in
# the 16 MiB of scoped VMEM a v5e kernel gets by default. At 512 rows those
# four would take 4 MiB and leave room for about one slice a step, while
# the flash tiling's 128x128 tiles already amortise its per-step overhead.
SHORT_SEQ_ROWS = 256
# VMEM for the short kernels' double-buffered blocks: half the default
# scoped VMEM, the other half for one slice's score-sized temporaries.
SHORT_VMEM = 8 * 2 ** 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def is_short(S: int, block_q: int, block_k: int) -> bool:
    """Whether the kernels run the short path: one tile spans the whole
    (padded) sequence of at most ``SHORT_SEQ_ROWS`` rows."""
    return block_q == block_k == S <= SHORT_SEQ_ROWS


def slices_per_step(S: int, hd: int, itemsize: int, kind: str) -> int:
    """Most slices one short-kernel grid step takes (``kind`` "fwd" or
    "bwd") so that their double-buffered blocks fit ``SHORT_VMEM``. A block
    [S, hd] occupies S rows of hd rounded up to whole lanes; the f32
    [S, 1] columns (lse, delta) a whole lane each. Forward blocks: q, k, v,
    o and the lse column; backward: q, k, v, do, dk, dv, the f32 dq, and
    the lse and delta columns."""
    lanes = _round_up(hd, LANE)
    n_mat, n_f32, n_col = {"fwd": (4, 0, 1), "bwd": (6, 1, 2)}[kind]
    row = lanes * (n_mat * itemsize + n_f32 * 4) + n_col * LANE * 4
    return max(1, SHORT_VMEM // (2 * _round_up(S, SUBLANE) * row))


def _largest_divisor(S: int, block: int) -> int:
    """Largest multiple of SUBLANE <= block dividing S (0 if none)."""
    b = block - block % SUBLANE
    while b and S % b:
        b -= SUBLANE
    return b


def select_blocks(S: int, block_q: int, block_k: int):
    """(block_q, block_k, padded_S) of the flash path, for sequences longer
    than ``SHORT_SEQ_ROWS`` (``attention_geometry`` picks the path).

    Tiles are always multiples of the 8-row sublane tile, as the TPU
    lowering requires of a block's second-minor dim. Exact fit when S
    divides the requested tiles; otherwise shrink to a multiple-of-8
    divisor if one exists within 2x of the request (stays near MXU width);
    otherwise keep the requested tiles and pad S up to a common multiple —
    never degenerate slivers (e.g. S=257 pads to 384 with 128-tiles, S=5
    pads to one 8-row tile)."""
    cap = _round_up(S, SUBLANE)
    bq = min(block_q, cap)
    bk = min(block_k, cap)
    if S % bq == 0 and S % bk == 0:
        return bq, bk, S
    dq_ = _largest_divisor(S, bq)
    dk_ = _largest_divisor(S, bk)
    if dq_ >= bq // 2 and dk_ >= bk // 2:
        return dq_, dk_, S
    m = math.lcm(bq, bk)
    return bq, bk, -(-S // m) * m


def attention_geometry(S: int, block_q: int, block_k: int):
    """(block_q, block_k, padded_S) used by ``ops.gated_attention``,
    ``d2ft_flash_attention`` AND the FLOP/DMA accounting below — one source
    of truth for tile geometry. A sequence of at most ``SHORT_SEQ_ROWS``
    rows, once rounded up to the sublane, is one whole unpadded tile (the
    short path): a block equal to the array's full extent needs no
    multiple of 8, and the TPU lowering pads the VMEM tile itself (ViT-S's
    S=197). A longer one takes ``select_blocks``' flash tiles."""
    if _round_up(S, SUBLANE) <= SHORT_SEQ_ROWS:
        return S, S, S
    return select_blocks(S, block_q, block_k)


def pad_to_blocks(q, k, v, block_q: int, block_k: int):
    """Shared attention_geometry + zero-pad step for every kernel entry
    point (``ops.gated_attention`` and the forward-only
    ``d2ft_flash_attention``).

    Returns (q, k, v, bq, bk, S, Sp): operands padded along the sequence
    axis to Sp when S doesn't divide the chosen tiles (padded rows are
    masked inside the kernels via their seq_len bound; callers slice
    outputs back to S, and jnp.pad's VJP keeps the padding out of the
    gradients)."""
    S = q.shape[2]
    bq, bk, Sp = attention_geometry(S, block_q, block_k)
    if Sp != S:
        pad = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    return q, k, v, bq, bk, S, Sp


# ======================================================== analytic accounting
def live_block_count(S: int, block_q: int, block_k: int, causal: bool,
                     window: int, seq_len: int = 0) -> int:
    """Number of (iq, ik) tiles the kernels execute per live (batch, head)
    slice — the same block-granular predicate as the ``@pl.when`` skip.
    S is the (possibly padded) grid extent; seq_len the true length. The
    short path's single whole-sequence tile always counts 1: it computes
    the masked entries too."""
    seq_len = seq_len or S
    n_q, n_k = S // block_q, S // block_k
    return sum(
        bool(_block_live(iq * block_q, ik * block_k, block_q, block_k,
                         causal, window, seq_len))
        for iq in range(n_q) for ik in range(n_k))


FWD_MATMULS_PER_TILE = 2   # qk^T, pv
BWD_MATMULS_PER_TILE = 5   # s, p^T·do, do·v^T, ds·k, ds^T·q (fused one-pass)


def gated_attention_flops(g_f, g_b, S: int, hd: int, *, causal: bool = True,
                          window: int = 0, block_q: int = 128,
                          block_k: int = 128):
    """Executed MXU FLOPs (fwd, bwd) of the kernel path under concrete gates.

    Uses the same tile geometry as ``ops.gated_attention``
    (attention_geometry, including padding) and the same block-granular
    skip predicate: 2 matmuls per live tile forward (qk^T, pv); 5 backward
    — the fused one-pass kernel computes ``s`` and ``dp`` once per tile and
    emits dq/dk/dv together (the former split dq / dkv kernels paid 3 + 4 =
    7, recomputing both). Each matmul is 2·bq·bk·hd FLOPs; the short path's
    tile is the whole padded sequence. Static HLO FLOP counts can't report
    this (interpret mode lowers the grid to a loop whose body XLA counts
    once), hence this mirror of the kernel's own skip logic.
    """
    bq, bk, Sp = attention_geometry(S, block_q, block_k)
    tiles = live_block_count(Sp, bq, bk, causal, window, seq_len=S)
    per_matmul = 2 * bq * bk * hd
    fwd = float(np.sum(np.asarray(g_f) != 0)) \
        * tiles * FWD_MATMULS_PER_TILE * per_matmul
    bwd = float(np.sum(np.asarray(g_b) != 0)) \
        * tiles * BWD_MATMULS_PER_TILE * per_matmul
    return fwd, bwd


def gated_attention_dispatched_bytes(g_f, g_b, S: int, hd: int, *,
                                     causal: bool = True, window: int = 0,
                                     block_q: int = 128, block_k: int = 128,
                                     live_fwd: int = None,
                                     live_bwd: int = None,
                                     itemsize: int = 4):
    """(fwd_bytes, bwd_bytes) the BlockSpec pipelines stream HBM<->VMEM for
    one fwd / one bwd ``pallas_call`` under the given dispatch.

    Mirrors the kernels' grids and index maps: a block is (re)fetched only
    when its index-map output changes between consecutive grid steps. On
    the short path every launched slice streams each of its blocks once:
    q, k, v in and o, lse out forward; q, k, v, do, lse, delta in and dq,
    dk, dv out backward. The launch is the dispatch count rounded up to
    whole steps of ``slices_per_step`` slices (``launch_shape``). On the
    flash path, per dispatched slice the forward streams q once per q-tile,
    k/v once per (iq, ik) step and writes o/lse once; the fused backward
    keeps k/v resident per kv sweep, streams q/do/lse/delta once per (ik,
    iq) step, writes dk/dv once per kv tile and the VMEM-resident dq block
    exactly once. The ``@pl.when`` gate/mask skip does NOT skip this
    traffic — only compaction dispatch does: without
    ``live_fwd``/``live_bwd`` every one of the B*H slices is streamed; with
    bounds, only the compacted grid's slices are. Gate scalars and the
    jnp-level gather/scatter/pad copies are not modelled (they are O(live)
    and fuse outside the kernels).
    """
    bq, bk, Sp = attention_geometry(S, block_q, block_k)
    N = int(np.asarray(g_f).size)
    assert int(np.asarray(g_b).size) == N
    disp_f = _dispatch_count(live_fwd, N)
    disp_b = _dispatch_count(live_bwd, N)
    if is_short(Sp, bq, bk):
        disp_f = math.prod(launch_shape(
            disp_f, slices_per_step(Sp, hd, itemsize, "fwd")))
        disp_b = math.prod(launch_shape(
            disp_b, slices_per_step(Sp, hd, itemsize, "bwd")))
        return (disp_f * (4 * Sp * hd + Sp) * itemsize,
                disp_b * (7 * Sp * hd + 2 * Sp) * itemsize)
    n_q, n_k = Sp // bq, Sp // bk
    fwd_slice = (n_q * bq * hd                 # q: fetched once per q tile
                 + 2 * n_q * n_k * bk * hd    # k, v: refetched per (iq, ik)
                 + n_q * bq * hd              # o written once per q tile
                 + n_q * bq)                  # lse
    bwd_slice = (2 * n_k * bk * hd            # k, v: resident per kv sweep
                 + 2 * n_k * n_q * bq * hd    # q, do: refetched per (ik, iq)
                 + 2 * n_k * n_q * bq         # lse, delta
                 + Sp * hd                    # dq block: written once/slice
                 + 2 * n_k * bk * hd)         # dk, dv: written once per tile
    return disp_f * fwd_slice * itemsize, disp_b * bwd_slice * itemsize
