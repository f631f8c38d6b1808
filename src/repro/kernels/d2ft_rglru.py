"""Pallas TPU kernels: D2FT-gated RG-LRU recurrence, forward *and* backward.

Implements the gated block kernel contract (``repro.kernels.contract``,
docs/kernels.md) for the RecurrentGemma RG-LRU block
``h_t = a_t * h_{t-1} + b_t``. The subnet axis is the flattened
(sample, channel-group) pair: the recurrence is elementwise per channel,
so the schedule's G groups slice the ``lru_width`` into G contiguous
``Wg = W // G`` channel bands that gate independently — the same
slice-major compacted grid as the attention and SSD kernels.

The scan is chunked in log space: with ``lc = cumsum(log_a)`` inside a
chunk, ``h_q = sum_{k<=q} exp(lc_q - lc_k) b_k + exp(lc_q) * h_prev``
(every exponent <= 0 since log_a <= 0, so this is stable), and the last
row carries to the next chunk in VMEM scratch. The backward walks chunks
in reverse carrying the cotangent of the incoming state; per chunk it
needs only the inputs and the forward's *output* h (g_b = 1 implies
g_f = 1, so the gated output equals h on every backward-live slice):

    db_k   = sum_{q>=k} exp(lc_q - lc_k) dh_q
    dprev  = sum_q exp(lc_q) dh_q
    dlc_q  = dh_q * h_q - b_q * db_q          (diagonal terms cancel)
    dla    = reverse_cumsum(dlc)

``g_f == 0`` slices skip the forward body via ``@pl.when`` and write
zeros; ``g_b == 0`` slices skip every backward tensor contraction and
write zero dla/db. Compaction dispatch under static ``live_fwd`` /
``live_bwd`` bounds is shared via ``contract``. ``S % chunk != 0`` is
handled by the jit'd wrapper zero-padding (log_a = 0 is the identity
decay, b = 0 adds nothing; pad rows are sliced off and jnp.pad's VJP
drops their gradients).

The jit'd public wrapper with interpret auto-detection is
``repro.kernels.ops.gated_rglru_scan``; the pure-jnp oracle is
``repro.kernels.ref.gated_rglru_ref``.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import contract as _contract

# Test hooks — same contract as d2ft_attention / d2ft_ssd.
on_backward_block = None
on_dispatch = None


def _maybe_count_block():
    if on_backward_block is not None:
        jax.debug.callback(on_backward_block)


def _report_dispatch(kind: str, grid):
    if on_dispatch is not None:
        on_dispatch(kind, tuple(grid))


def _decay_matrix(lc):
    """Lm[q, k, w] = exp(lc_q - lc_k) masked causal (diag = 1); lc [Q,Wg]."""
    Q = lc.shape[0]
    diff = lc[:, None, :] - lc[None, :, :]
    tril = jnp.tril(jnp.ones((Q, Q), jnp.bool_))[:, :, None]
    return jnp.where(tril, jnp.exp(diff), 0.0)


# ================================================================== forward
def _fwd_kernel(gate_ref, la_ref, b_ref, h_ref, carry_ref):
    j = pl.program_id(1)
    gate = gate_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    prev = carry_ref[...]                                   # [1, Wg] f32

    @pl.when(gate != 0)
    def _compute():
        la = la_ref[0].astype(jnp.float32)                  # [Q, Wg]
        b = b_ref[0].astype(jnp.float32)
        Q = la.shape[0]
        lc = jnp.cumsum(la, axis=0)
        h = jnp.sum(_decay_matrix(lc) * b[None, :, :], axis=1)
        h = h + jnp.exp(lc) * prev
        h_ref[0] = h.astype(h_ref.dtype)
        carry_ref[...] = h[Q - 1:Q]

    @pl.when(gate == 0)
    def _dead():
        h_ref[0] = jnp.zeros_like(h_ref[0])


def _slice_major(a, G: int):
    """[B,S,W] -> [B*G, S, Wg]: contiguous channel bands per group."""
    B, S, W = a.shape
    Wg = W // G
    return a.reshape(B, S, G, Wg).transpose(0, 2, 1, 3).reshape(B * G, S, Wg)


def _unslice(a, B: int, G: int):
    NS, S, Wg = a.shape
    return a.reshape(B, G, S, Wg).transpose(0, 2, 1, 3).reshape(B, S, G * Wg)


def _forward(la, b, g_f, *, chunk: int, interpret: bool, live=None):
    """la, b: [B,S,W]; g_f: [B,G] with W % G == 0. Returns h [B,S,W] f32
    (g_f-gated: dead channel-groups are exact zeros)."""
    B, S, W = la.shape
    G = g_f.shape[1]
    Wg = W // G
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    NS = B * G
    las, bs = _slice_major(la, G), _slice_major(b, G)
    g = g_f.reshape(NS)
    n_disp = _contract.dispatch_count(live, NS)
    idx = None
    if n_disp < NS:
        idx = _contract.live_permutation(g, n_disp)
        las, bs, g = (jnp.take(a, idx, axis=0) for a in (las, bs, g))

    grid = (n_disp, nc)
    _report_dispatch("fwd", grid)
    h = pl.pallas_call(
        _fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                 # g_f
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, Q, Wg), lambda s, j, g: (s, j, 0)),   # log_a
                pl.BlockSpec((1, Q, Wg), lambda s, j, g: (s, j, 0)),   # b
            ],
            out_specs=pl.BlockSpec((1, Q, Wg), lambda s, j, g: (s, j, 0)),
            scratch_shapes=[pltpu.VMEM((1, Wg), jnp.float32)]),    # carry
        out_shape=jax.ShapeDtypeStruct((n_disp, S, Wg), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_contract.gate_operand(g), las, bs)

    if idx is not None:
        h = jnp.zeros((NS, S, Wg), h.dtype).at[idx].set(
            h, unique_indices=True)
    return _unslice(h, B, G)


# ================================================================= backward
def _bwd_kernel(gate_ref, la_ref, b_ref, h_ref, dy_ref, dla_ref, db_ref,
                dcarry_ref):
    j = pl.program_id(1)
    gate = gate_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        dcarry_ref[...] = jnp.zeros_like(dcarry_ref)

    @pl.when(gate != 0)
    def _compute():
        _maybe_count_block()
        la = la_ref[0].astype(jnp.float32)                  # [Q, Wg]
        b = b_ref[0].astype(jnp.float32)
        h = h_ref[0].astype(jnp.float32)                    # fwd output
        dy = dy_ref[0].astype(jnp.float32)
        Q = la.shape[0]
        lc = jnp.cumsum(la, axis=0)
        last = (jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1)
        dh = dy + jnp.where(last, dcarry_ref[...], 0.0)
        Lm = _decay_matrix(lc)
        db = jnp.sum(Lm * dh[:, None, :], axis=0)           # [Q, Wg]
        dprev = jnp.sum(jnp.exp(lc) * dh, axis=0, keepdims=True)  # [1, Wg]
        dlc = dh * h - b * db
        dla = jnp.cumsum(dlc[::-1], axis=0)[::-1]           # cumsum adjoint
        dla_ref[0] = dla.astype(dla_ref.dtype)
        db_ref[0] = db.astype(db_ref.dtype)
        dcarry_ref[...] = dprev

    @pl.when(gate == 0)
    def _dead():
        dla_ref[0] = jnp.zeros_like(dla_ref[0])
        db_ref[0] = jnp.zeros_like(db_ref[0])


def _backward(la, b, g_b, h, dy, *, chunk: int, interpret: bool, live=None):
    B, S, W = la.shape
    G = g_b.shape[1]
    Wg = W // G
    Q = min(chunk, S)
    nc = S // Q
    NS = B * G
    las, bs, hs, dys = (_slice_major(a, G) for a in (la, b, h, dy))
    g = g_b.reshape(NS)
    n_disp = _contract.dispatch_count(live, NS)
    idx = None
    if n_disp < NS:
        idx = _contract.live_permutation(g, n_disp)
        las, bs, hs, dys, g = (jnp.take(a, idx, axis=0)
                               for a in (las, bs, hs, dys, g))

    rev = nc - 1
    grid = (n_disp, nc)
    _report_dispatch("bwd", grid)

    def rmap(s, j, g):
        return (s, rev - j, 0)

    dla, db = pl.pallas_call(
        _bwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                  # g_b
            grid=grid,
            in_specs=[pl.BlockSpec((1, Q, Wg), rmap)] * 4,  # log_a, b, h, dy
            out_specs=[pl.BlockSpec((1, Q, Wg), rmap)] * 2,  # dla, db
            scratch_shapes=[pltpu.VMEM((1, Wg), jnp.float32)]),     # dcarry
        out_shape=[
            jax.ShapeDtypeStruct((n_disp, S, Wg), jnp.float32),
            jax.ShapeDtypeStruct((n_disp, S, Wg), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_contract.gate_operand(g), las, bs, hs, dys)

    if idx is not None:
        dla, db = (jnp.zeros((NS, S, Wg), a.dtype).at[idx].set(
            a, unique_indices=True) for a in (dla, db))
    return (_unslice(dla, B, G).astype(la.dtype),
            _unslice(db, B, G).astype(b.dtype))


# =============================================================== custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def gated_rglru_scan(la, b, g_f, g_b, chunk, interpret, live_fwd=None,
                     live_bwd=None):
    """Differentiable gated RG-LRU scan core.

    la: [B,S,W] per-step log-decay (<= 0), b: [B,S,W] input, g_f/g_b:
    [B,G] float {0,1} with g_b <= g_f and W % G == 0. Returns h [B,S,W]
    f32, ``g_f``-gated per channel-group; the backward computes dla/db
    only where ``g_b != 0`` (gates receive zero cotangents). S must be a
    multiple of ``chunk`` (the jit'd wrapper pads). Prefer
    ``ops.gated_rglru_scan``.
    """
    return _forward(la, b, g_f, chunk=chunk, interpret=interpret,
                    live=live_fwd)


def _vjp_fwd(la, b, g_f, g_b, chunk, interpret, live_fwd=None,
             live_bwd=None):
    h = _forward(la, b, g_f, chunk=chunk, interpret=interpret,
                 live=live_fwd)
    return h, (la, b, g_f, g_b, h)


def _vjp_bwd(chunk, interpret, live_fwd, live_bwd, res, dy):
    la, b, g_f, g_b, h = res
    dla, db = _backward(la, b, g_b, h, dy, chunk=chunk, interpret=interpret,
                        live=live_bwd)
    return dla, db, jnp.zeros_like(g_f), jnp.zeros_like(g_b)


gated_rglru_scan.defvjp(_vjp_fwd, _vjp_bwd)


# ======================================================== analytic accounting
def gated_rglru_flops(g_f, g_b, S: int, Wg: int, *, chunk: int):
    """Executed FLOPs (fwd, bwd) of the kernel path under concrete gates:
    the dominant [Q,Q,Wg] intra-chunk contraction (2*Q*Q*Wg MACs) per live
    chunk — one in the forward (h_intra), one in the backward (db)."""
    Q = min(chunk, S)
    nc = -(-S // Q)
    per = 2 * Q * Q * Wg
    return (float(np.sum(np.asarray(g_f) != 0)) * nc * per,
            float(np.sum(np.asarray(g_b) != 0)) * nc * per)


def gated_rglru_dispatched_bytes(g_f, g_b, S: int, Wg: int, *, chunk: int,
                                 live_fwd: int = None, live_bwd: int = None,
                                 itemsize: int = 4):
    """(fwd_bytes, bwd_bytes) streamed per pallas_call: every block's index
    map advances each chunk step, so per dispatched slice each operand
    streams exactly once (fwd: la + b read, h written; bwd: la, b, h, dy
    read, dla + db written). Only compaction skips this traffic."""
    NS = int(np.asarray(g_f).size)
    disp_f = _contract.dispatch_count(live_fwd, NS)
    disp_b = _contract.dispatch_count(live_bwd, NS)
    return (disp_f * 3 * S * Wg * itemsize,
            disp_b * 6 * S * Wg * itemsize)
