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


def _forward(q, k, v, g_f, *, causal: bool, window: int, block_q: int,
             block_k: int, interpret: bool, seq_len: int = 0,
             live: int = None):
    """Returns (o [B,H,S,hd], lse [B,H,S,1] f32). seq_len is the true length
    when the arrays carry tile padding (0 means unpadded). ``live`` is the
    static live-slice upper bound enabling compaction dispatch."""
    B, H, S, hd = q.shape
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    seq_len = seq_len or S
    n_q = S // block_q
    n_k = S // block_k
    scale = 1.0 / (hd ** 0.5)

    N = B * H
    q, k, v = (a.reshape(N, S, hd) for a in (q, k, v))
    g = g_f.reshape(N)
    n_disp = _dispatch_count(live, N)
    idx = None
    if n_disp < N:
        idx = _live_permutation(g, n_disp)
        q, k, v, g = (jnp.take(a, idx, axis=0) for a in (q, k, v, g))

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k, seq_len=seq_len)

    grid = (n_disp, n_q, n_k)
    _report_dispatch("fwd", grid)
    o, lse = pl.pallas_call(
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
            jax.ShapeDtypeStruct((n_disp, S, hd), q.dtype),
            jax.ShapeDtypeStruct((n_disp, S, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(_contract.gate_operand(g), q, k, v)

    if idx is not None:
        # scatter live results back; dead (never-dispatched) slices are the
        # zero-fill, dispatched-but-gated-off padding slices wrote zeros /
        # LSE_MASKED themselves so the set() is a no-op value-wise.
        o = jnp.zeros((N, S, hd), o.dtype).at[idx].set(
            o, unique_indices=True)
        lse = jnp.full((N, S, 1), LSE_MASKED, jnp.float32).at[idx].set(
            lse, unique_indices=True)
    return o.reshape(B, H, S, hd), lse.reshape(B, H, S, 1)


def d2ft_flash_attention(q, k, v, gates, *, causal: bool = True,
                         window: int = 0, block_q: int = 128,
                         block_k: int = 128, interpret: bool = False,
                         live: int = None):
    """Forward-only gated flash attention (no VJP registered).

    q, k, v: [B, H, S, hd] (kv heads already expanded to H);
    gates: [B, H] float {0,1}. Returns [B, H, S, hd]. Sequence lengths that
    don't divide the tiles go through the same ``select_blocks`` shrink-or
    -pad wrapper as ``ops.gated_attention`` (padded rows are masked via the
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


def _backward(q, k, v, g_b, o, lse, do, *, causal: bool, window: int,
              block_q: int, block_k: int, interpret: bool, seq_len: int = 0,
              live: int = None):
    B, H, S, hd = q.shape
    seq_len = seq_len or S
    n_q = S // block_q
    n_k = S // block_k
    scale = 1.0 / (hd ** 0.5)

    N = B * H
    q, k, v, o, do = (a.reshape(N, S, hd) for a in (q, k, v, o, do))
    lse = lse.reshape(N, S, 1)
    g = g_b.reshape(N)
    n_disp = _dispatch_count(live, N)
    idx = None
    if n_disp < N:
        idx = _live_permutation(g, n_disp)
        q, k, v, o, do = (jnp.take(a, idx, axis=0)
                          for a in (q, k, v, o, do))
        lse, g = jnp.take(lse, idx, axis=0), jnp.take(g, idx, axis=0)
    # delta_i = sum_d dO_id * O_id — cheap elementwise reduce, done outside
    # the kernel (standard flash-bwd preprocessing) on the *compacted*
    # operands so gated-off slices don't pay it either.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)

    grid = (n_disp, n_k, n_q)
    _report_dispatch("bwd", grid)
    dq, dk, dv = pl.pallas_call(
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
            jax.ShapeDtypeStruct((n_disp, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((n_disp, S, hd), k.dtype),
            jax.ShapeDtypeStruct((n_disp, S, hd), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(_contract.gate_operand(g), q, k, v, do, lse, delta)

    dq = dq.astype(q.dtype)
    if idx is not None:
        dq, dk, dv = (jnp.zeros((N, S, hd), a.dtype).at[idx].set(
            a, unique_indices=True) for a in (dq, dk, dv))
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


def _largest_divisor(S: int, block: int) -> int:
    """Largest multiple of SUBLANE <= block dividing S (0 if none)."""
    b = block - block % SUBLANE
    while b and S % b:
        b -= SUBLANE
    return b


def select_blocks(S: int, block_q: int, block_k: int):
    """(block_q, block_k, padded_S) used by ``ops.gated_attention``,
    ``d2ft_flash_attention`` AND the FLOP/DMA accounting below — one source
    of truth for tile geometry.

    Tiles are always multiples of the 8-row sublane tile, as the TPU
    lowering requires of a block's second-minor dim. Exact fit when S
    divides the requested tiles; otherwise shrink to a multiple-of-8
    divisor if one exists within 2x of the request (stays near MXU width);
    otherwise keep the requested tiles and pad S up to a common multiple —
    never degenerate slivers (e.g. S=257 pads to 384 with 128-tiles, S=5
    pads to one 8-row tile)."""
    cap = -(-S // SUBLANE) * SUBLANE
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


def pad_to_blocks(q, k, v, block_q: int, block_k: int):
    """Shared select_blocks + zero-pad step for every kernel entry point
    (``ops.gated_attention`` and the forward-only ``d2ft_flash_attention``).

    Returns (q, k, v, bq, bk, S, Sp): operands padded along the sequence
    axis to Sp when S doesn't divide the chosen tiles (padded rows are
    masked inside the kernels via their seq_len bound; callers slice
    outputs back to S, and jnp.pad's VJP keeps the padding out of the
    gradients)."""
    S = q.shape[2]
    bq, bk, Sp = select_blocks(S, block_q, block_k)
    if Sp != S:
        pad = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    return q, k, v, bq, bk, S, Sp


# ======================================================== analytic accounting
def live_block_count(S: int, block_q: int, block_k: int, causal: bool,
                     window: int, seq_len: int = 0) -> int:
    """Number of (iq, ik) tiles the kernels execute per live (batch, head)
    slice — the same block-granular predicate as the ``@pl.when`` skip.
    S is the (possibly padded) grid extent; seq_len the true length."""
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

    Uses the same tile geometry as ``ops.gated_attention`` (select_blocks,
    including padding) and the same block-granular skip predicate: 2 matmuls
    per live tile forward (qk^T, pv); 5 backward — the fused one-pass kernel
    computes ``s`` and ``dp`` once per tile and emits dq/dk/dv together
    (the former split dq / dkv kernels paid 3 + 4 = 7, recomputing both).
    Each matmul is 2·bq·bk·hd FLOPs. Static HLO FLOP counts can't report
    this (interpret mode lowers the grid to a loop whose body XLA counts
    once), hence this mirror of the kernel's own skip logic.
    """
    bq, bk, Sp = select_blocks(S, block_q, block_k)
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
    when its index-map output changes between consecutive grid steps, so per
    dispatched slice the forward streams q once per q-tile, k/v once per
    (iq, ik) step and writes o/lse once; the fused backward keeps k/v
    resident per kv sweep, streams q/do/lse/delta once per (ik, iq) step,
    writes dk/dv once per kv tile and the VMEM-resident dq block exactly
    once. The ``@pl.when`` gate/mask skip does NOT skip this traffic — only
    compaction dispatch does: without ``live_fwd``/``live_bwd`` every one of
    the B*H slices is streamed; with bounds, only the compacted grid's
    slices are. Gate scalars and the jnp-level gather/scatter/pad copies are
    not modelled (they are O(live) and fuse outside the kernels).
    """
    bq, bk, Sp = select_blocks(S, block_q, block_k)
    n_q, n_k = Sp // bq, Sp // bk
    N = int(np.asarray(g_f).size)
    assert int(np.asarray(g_b).size) == N
    disp_f = _dispatch_count(live_fwd, N)
    disp_b = _dispatch_count(live_bwd, N)
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
