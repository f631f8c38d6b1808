"""Pallas TPU kernel: paged flash decode for the gated attention family.

Single-token decode against a *paged* KV cache (serving/pages.py): each
sequence's history lives in fixed-size pages of a shared pool
``[n_pages, page_size, n_kv, hd]``, addressed through a per-sequence page
table. The kernel streams pages HBM→VMEM **by table indirection**: the page
table and sequence lengths ride in scalar-prefetch SMEM operands
(``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index maps can
compute the source page id ``table[b, p]`` before each grid step's DMA —
no gathered contiguous copy of the history is ever materialized (the jnp
``jnp.take`` reference in ``serving/paged_decode.py`` is exactly that copy,
kept as the parity oracle).

Grid: ``(B, H, n_pmax)`` with the page axis innermost/sequential, carrying
the online-softmax scratch (f32 acc/m/l at block_q = 1 — one query row per
(slot, head)). Per-page block skip with ``@pl.when``:

* pages past the sequence length (``p * page_size > t``) — covers table
  padding, which points at the null page 0;
* pages wholly outside a sliding window (local-attention layers keep full
  history in pages; the window is enforced here by masking);
* gated-off heads (``g_f == 0``) — serving is schedule-free so the default
  gates are all-ones, but gate-elided adapters route through the same entry
  (mirrors the training kernel's p_s semantics: zeros written, MXU idle).

Padded table entries MUST hold a valid page id (the null page): index maps
run for every grid step regardless of ``@pl.when``, so the DMA source must
be in bounds even for skipped blocks. ``PageManager.table_array`` upholds
this.

The jit'd public wrapper with interpret auto-detection is
``repro.kernels.ops.paged_decode_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30

# Test hook: when set to a callable, ``paged_flash_decode`` reports its
# dispatch as ``on_dispatch(grid)`` at TRACE time (set before the first
# trace; jit caches skip tracing — same caveat as d2ft_attention's hooks).
on_dispatch = None


def _paged_decode_kernel(tbl_ref, len_ref, gate_ref, q_ref, k_ref, v_ref,
                         o_ref, acc_ref, m_ref, l_ref, *, scale: float,
                         page_size: int, n_pmax: int, window: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    p = pl.program_id(2)
    t = len_ref[b]                      # query position == tokens cached
    gate = gate_ref[b, h]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level skip: gated-off head, page past the valid length (incl.
    # null-page table padding), or page wholly left of the window
    run = jnp.logical_and(gate != 0, p * page_size <= t)
    if window and window > 0:
        run = jnp.logical_and(run, (p + 1) * page_size - 1 > t - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # [1, hd]
        k = k_ref[0, :, 0, :].astype(jnp.float32)        # [page_size, hd]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q * scale, k,
                                (((1,), (1,)), ((), ())))  # [1, page_size]
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        mask = pos <= t
        if window and window > 0:
            mask = jnp.logical_and(mask, pos > t - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pr, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + \
            jax.lax.dot_general(pr, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(p == n_pmax - 1)
    def _finalize():
        l = l_ref[...]                                   # [1, 1]
        safe = jnp.where(l > 0, l, 1.0)
        out = jnp.where(l > 0, acc_ref[...] / safe, 0.0)
        out = out * gate.astype(jnp.float32)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_flash_decode(q, k_pages, v_pages, page_table, lengths, gates, *,
                       window: int = 0, interpret: bool = False):
    """One decode step of paged attention.

    q: [B, H, hd] (post-rope query at position ``lengths[b]``);
    k_pages, v_pages: [n_pages, page_size, n_kv, hd] (this step's K/V
    already written); page_table: [B, n_pmax] int32, null-padded;
    lengths: [B] int32; gates: [B, H] float. Returns [B, H, hd].
    GQA is resolved in the index map (head h reads kv head h // (H//n_kv)),
    so the pools stay un-expanded in HBM.
    """
    B, H, hd = q.shape
    n_pages, page_size, n_kv, _ = k_pages.shape
    n_pmax = page_table.shape[1]
    assert H % n_kv == 0, (H, n_kv)
    rep = H // n_kv
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               page_size=page_size, n_pmax=n_pmax,
                               window=window)
    grid = (B, H, n_pmax)
    if on_dispatch is not None:
        on_dispatch(tuple(grid))

    def kv_map(b, h, p, tbl, ln, g):
        return (tbl[b, p], 0, h // rep, 0)

    def q_map(b, h, p, tbl, ln, g):      # q / o laid out [B*H, 1, hd]
        return (b * H + h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # page_table, lengths, gates
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, hd), q_map),
            pl.BlockSpec((1, page_size, 1, hd), kv_map),
            pl.BlockSpec((1, page_size, 1, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),    # acc
            pltpu.VMEM((1, 1), jnp.float32),     # m
            pltpu.VMEM((1, 1), jnp.float32),     # l
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      gates.astype(jnp.float32), q.reshape(B * H, 1, hd), k_pages,
      v_pages).reshape(B, H, hd)
