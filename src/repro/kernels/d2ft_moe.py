"""Pallas TPU kernels: D2FT-gated MoE expert FFN, forward *and* backward.

Implements the gated block kernel contract (``repro.kernels.contract``,
docs/kernels.md) for the MoE expert path. The schedule gate composes with
router sparsity *upstream*, in ``models/moe.py``'s dispatch: gate-dead
(token, k) routing entries are dropped before the capacity sort, so each
expert's ``[C, D]`` capacity buffer is front-packed with live tokens only
(p_f slots packed before p_o slots within each expert segment). This
kernel then runs the doubly-sparse expert einsum over that buffer on a
grid of (expert, capacity-block) tiles:

* forward: a tile runs only when its ``fwd_mask`` bit is set (some live
  token occupies one of its slots) — empty and gate-dead tiles write
  zeros via ``@pl.when`` and the gated-MLP matmuls are skipped.
* fused backward: a tile runs only when its ``bwd_mask`` bit is set (some
  p_f token occupies it); h and the gate pre-activation are recomputed
  from x, and dx plus the per-expert dW accumulators are emitted in one
  pass. dW tiles use the attention kernel's dq pattern: their output
  index map ignores the capacity-block dim so they stay VMEM-resident per
  expert and flush once.

The masks are per (expert, capacity-block) in {0, 1} with bwd <= fwd and
receive zero cotangents. The analogue of compaction dispatch is *static
capacity truncation*: the wrapper (``ops.gated_moe_ffn``) shrinks the
capacity axis to the schedule-derived live-slot bound before launching,
so provably-empty trailing blocks cost neither grid steps nor DMA. The
two passes truncate independently — the forward to the g_f bound, the
backward to the (smaller or equal) g_b bound via the ``bwd_blocks``
nondiff argument, which works because the dispatch sorts backward-live
assignments into a capacity prefix per expert.

The jit'd public wrapper with interpret auto-detection is
``repro.kernels.ops.gated_moe_ffn``; the pure-jnp oracle is
``repro.kernels.ref.gated_moe_ffn_ref``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import contract as _contract

# Test hooks — same contract as d2ft_attention / d2ft_ssd / d2ft_rglru.
on_backward_block = None
on_dispatch = None


def _maybe_count_block():
    if on_backward_block is not None:
        jax.debug.callback(on_backward_block)


def _report_dispatch(kind: str, grid):
    if on_dispatch is not None:
        on_dispatch(kind, tuple(grid))


def act_pair(name: str):
    """(f, df) for the expert activation — the backward kernel needs an
    explicit derivative (no autodiff inside a Pallas body). Matches
    ``models.layers._act``: silu, gelu (tanh approximation — jax.nn.gelu's
    default), relu."""
    if name == "silu":
        def df(g):
            s = jax.nn.sigmoid(g)
            return s * (1.0 + g * (1.0 - s))
        return jax.nn.silu, df
    if name == "gelu":
        c = math.sqrt(2.0 / math.pi)

        def df(g):
            t = jnp.tanh(c * (g + 0.044715 * g ** 3))
            return 0.5 * (1.0 + t) + \
                0.5 * g * (1.0 - t ** 2) * c * (1.0 + 3 * 0.044715 * g ** 2)
        return jax.nn.gelu, df
    if name == "relu":
        return jax.nn.relu, lambda g: (g > 0).astype(g.dtype)
    raise ValueError(f"unknown activation {name!r}")


# ================================================================== forward
def _block_mask(m_ref):
    """This (expert, capacity-block) tile's mask bit from the flattened
    [E * n_cb] scalar-prefetch operand (``contract.gate_operand``)."""
    return m_ref[pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)]


def _fwd_kernel(fm_ref, x_ref, wu_ref, wg_ref, wd_ref, y_ref, *, act: str):
    f, _ = act_pair(act)
    live = _block_mask(fm_ref)

    @pl.when(live != 0)
    def _compute():
        x = x_ref[0].astype(jnp.float32)                    # [bc, D]
        wu = wu_ref[0].astype(jnp.float32)                  # [D, F]
        wg = wg_ref[0].astype(jnp.float32)
        wd = wd_ref[0].astype(jnp.float32)                  # [F, D]
        h = jax.lax.dot_general(x, wu, (((1,), (0,)), ((), ())))
        g = jax.lax.dot_general(x, wg, (((1,), (0,)), ((), ())))
        y = jax.lax.dot_general(f(g) * h, wd, (((1,), (0,)), ((), ())))
        y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(live == 0)
    def _dead():
        y_ref[0] = jnp.zeros_like(y_ref[0])


def _forward(xb, w_up, w_gate, w_down, fm, *, act: str, block_c: int,
             interpret: bool):
    E, C, D = xb.shape
    F = w_up.shape[-1]
    assert C % block_c == 0, (C, block_c)
    n_cb = C // block_c
    grid = (E, n_cb)
    _report_dispatch("fwd", grid)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                  # fm
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_c, D), lambda e, ic, m: (e, ic, 0)),
                pl.BlockSpec((1, D, F), lambda e, ic, m: (e, 0, 0)),  # w_up
                pl.BlockSpec((1, D, F), lambda e, ic, m: (e, 0, 0)),  # w_gate
                pl.BlockSpec((1, F, D), lambda e, ic, m: (e, 0, 0)),  # w_down
            ],
            out_specs=pl.BlockSpec((1, block_c, D),
                                   lambda e, ic, m: (e, ic, 0))),
        out_shape=jax.ShapeDtypeStruct((E, C, D), xb.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_contract.gate_operand(fm.reshape(-1)), xb, w_up, w_gate,
      w_down)


# ================================================================= backward
def _bwd_kernel(bm_ref, x_ref, wu_ref, wg_ref, wd_ref, dy_ref, dx_ref,
                dwu_ref, dwg_ref, dwd_ref, *, act: str):
    """Fused one-pass backward over (expert, capacity-block) tiles. dW
    outputs accumulate in per-expert blocks whose index map ignores the
    capacity-block dim (VMEM-resident per expert, init at ic == 0)."""
    f, df = act_pair(act)
    ic = pl.program_id(1)
    live = _block_mask(bm_ref)

    @pl.when(ic == 0)
    def _init():
        dwu_ref[...] = jnp.zeros_like(dwu_ref)
        dwg_ref[...] = jnp.zeros_like(dwg_ref)
        dwd_ref[...] = jnp.zeros_like(dwd_ref)

    @pl.when(live != 0)
    def _compute():
        _maybe_count_block()
        x = x_ref[0].astype(jnp.float32)                    # [bc, D]
        wu = wu_ref[0].astype(jnp.float32)
        wg = wg_ref[0].astype(jnp.float32)
        wd = wd_ref[0].astype(jnp.float32)
        dy = dy_ref[0].astype(jnp.float32)                  # [bc, D]
        h = jax.lax.dot_general(x, wu, (((1,), (0,)), ((), ())))
        g = jax.lax.dot_general(x, wg, (((1,), (0,)), ((), ())))
        a = f(g)
        dmid = jax.lax.dot_general(dy, wd, (((1,), (1,)), ((), ())))
        dwd_ref[0] += jax.lax.dot_general(a * h, dy, (((0,), (0,)), ((), ())))
        dh = dmid * a
        dgpre = dmid * h * df(g)
        dx = jax.lax.dot_general(dh, wu, (((1,), (1,)), ((), ()))) + \
            jax.lax.dot_general(dgpre, wg, (((1,), (1,)), ((), ())))
        dx_ref[0] = dx.astype(dx_ref.dtype)
        dwu_ref[0] += jax.lax.dot_general(x, dh, (((0,), (0,)), ((), ())))
        dwg_ref[0] += jax.lax.dot_general(x, dgpre, (((0,), (0,)), ((), ())))

    @pl.when(live == 0)
    def _dead():
        dx_ref[0] = jnp.zeros_like(dx_ref[0])


def _backward(xb, w_up, w_gate, w_down, bm, dy, *, act: str, block_c: int,
              interpret: bool):
    E, C, D = xb.shape
    F = w_up.shape[-1]
    n_cb = C // block_c
    grid = (E, n_cb)
    _report_dispatch("bwd", grid)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                  # bm
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_c, D), lambda e, ic, m: (e, ic, 0)),
                pl.BlockSpec((1, D, F), lambda e, ic, m: (e, 0, 0)),
                pl.BlockSpec((1, D, F), lambda e, ic, m: (e, 0, 0)),
                pl.BlockSpec((1, F, D), lambda e, ic, m: (e, 0, 0)),
                pl.BlockSpec((1, block_c, D),
                             lambda e, ic, m: (e, ic, 0)),          # dy
            ],
            out_specs=[
                pl.BlockSpec((1, block_c, D),
                             lambda e, ic, m: (e, ic, 0)),          # dx
                pl.BlockSpec((1, D, F), lambda e, ic, m: (e, 0, 0)),  # dwu
                pl.BlockSpec((1, D, F), lambda e, ic, m: (e, 0, 0)),  # dwg
                pl.BlockSpec((1, F, D), lambda e, ic, m: (e, 0, 0)),  # dwd
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((E, C, D), jnp.float32),
            jax.ShapeDtypeStruct((E, D, F), jnp.float32),
            jax.ShapeDtypeStruct((E, D, F), jnp.float32),
            jax.ShapeDtypeStruct((E, F, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_contract.gate_operand(bm.reshape(-1)), xb, w_up, w_gate,
      w_down, dy)


# =============================================================== custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def gated_moe_ffn(xb, w_up, w_gate, w_down, fm, bm, act, block_c,
                  bwd_blocks, interpret):
    """Differentiable doubly-sparse MoE expert FFN core.

    xb: [E, C, D] capacity buffer (front-packed live tokens — see
    models/moe.py), w_up/w_gate: [E, D, F], w_down: [E, F, D], fm/bm:
    [E, C // block_c] float {0,1} per-(expert, capacity-block) masks with
    bm <= fm. Forward skips fm == 0 tiles; backward skips bm == 0 tiles
    and returns zero gradients there (masks get zero cotangents).
    bwd_blocks: static capacity-block count for the backward grid, keyed
    on the g_b bound *separately* from the forward's truncation — the
    dispatch packs backward-live slots into a capacity prefix per expert
    (models/moe.py sorts p_f assignments before p_o), so when g_b < g_f
    every bm bit beyond the first ``bwd_blocks`` blocks is zero and the
    backward launches a (E, bwd_blocks) grid over sliced operands instead
    of re-walking the forward's capacity; dx zero-pads back to C. Pass
    ``None`` (or >= C // block_c) for the full grid. C must be a multiple
    of block_c (the wrapper pads + truncates). Prefer
    ``ops.gated_moe_ffn``.
    """
    return _forward(xb, w_up, w_gate, w_down, fm, act=act, block_c=block_c,
                    interpret=interpret)


def _vjp_fwd(xb, w_up, w_gate, w_down, fm, bm, act, block_c, bwd_blocks,
             interpret):
    y = _forward(xb, w_up, w_gate, w_down, fm, act=act, block_c=block_c,
                 interpret=interpret)
    return y, (xb, w_up, w_gate, w_down, fm, bm)


def _vjp_bwd(act, block_c, bwd_blocks, interpret, res, dy):
    xb, w_up, w_gate, w_down, fm, bm = res
    E, C, _ = xb.shape
    n_cb = C // block_c
    nb = n_cb if bwd_blocks is None else min(int(bwd_blocks), n_cb)
    if nb < n_cb:
        # backward-live slots are front-packed: the truncated tail holds
        # only bm == 0 blocks, whose dx is zero and whose dW tiles are
        # @pl.when-skipped anyway — slicing them off the grid makes the
        # backward's steps and DMA scale with g_b, not g_f.
        cr = nb * block_c
        dx, dwu, dwg, dwd = _backward(
            xb[:, :cr], w_up, w_gate, w_down, bm[:, :nb], dy[:, :cr],
            act=act, block_c=block_c, interpret=interpret)
        dx = jnp.pad(dx, ((0, 0), (0, C - cr), (0, 0)))
    else:
        dx, dwu, dwg, dwd = _backward(xb, w_up, w_gate, w_down, bm, dy,
                                      act=act, block_c=block_c,
                                      interpret=interpret)
    return (dx.astype(xb.dtype), dwu.astype(w_up.dtype),
            dwg.astype(w_gate.dtype), dwd.astype(w_down.dtype),
            jnp.zeros_like(fm), jnp.zeros_like(bm))


gated_moe_ffn.defvjp(_vjp_fwd, _vjp_bwd)


# ======================================================== analytic accounting
FWD_MATMULS_PER_TILE = 3   # x·w_up, x·w_gate, (act·h)·w_down
BWD_MATMULS_PER_TILE = 8   # h, g recompute; dmid; dwd; dx (2); dwu; dwg


def gated_moe_flops(fm, bm, block_c: int, D: int, F: int):
    """Executed MXU FLOPs (fwd, bwd) under concrete block masks: live tiles
    x matmuls/tile x 2·bc·D·F each — the kernel's own skip, mirrored."""
    per = 2 * block_c * D * F
    return (float(np.sum(np.asarray(fm) != 0)) * FWD_MATMULS_PER_TILE * per,
            float(np.sum(np.asarray(bm) != 0)) * BWD_MATMULS_PER_TILE * per)


def gated_moe_dispatched_bytes(E: int, n_cb: int, block_c: int, D: int,
                               F: int, *, itemsize: int = 4,
                               n_cb_bwd: Optional[int] = None):
    """(fwd_bytes, bwd_bytes) streamed for grids of (E, n_cb): expert
    weights fetch once per expert (their index maps ignore the capacity
    dim), x/y/dy/dx once per tile, dW written once per expert. Capacity
    truncation (the wrapper's n_cb) is what shrinks this — ``@pl.when``
    alone does not. ``n_cb_bwd`` prices the backward's separate g_b-keyed
    truncation (defaults to the shared grid)."""
    nb = n_cb if n_cb_bwd is None else n_cb_bwd
    wb = 3 * D * F * itemsize
    tile = block_c * D * itemsize
    fwd = E * (wb + n_cb * 2 * tile)               # x read + y written
    bwd = E * (wb + nb * 3 * tile + wb)            # x, dy read; dx, dW out
    return fwd, bwd
