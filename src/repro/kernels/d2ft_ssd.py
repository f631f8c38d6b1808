"""Pallas TPU kernels: D2FT-gated SSD chunked scan, forward *and* backward.

Implements the gated block kernel contract (``repro.kernels.contract``,
docs/kernels.md) for the Mamba-2 SSD block: the subnet axis is the
flattened (sample, head) pair, matching the per-head decay ``dA`` of
``models/ssm.ssd_chunked``. Per live slice the kernel runs the exact
chunked algorithm of the jnp reference — intra-chunk quadratic term,
inter-chunk recurrence carried in f32 VMEM scratch — so the kernel and
masked paths agree to float-associativity:

* forward, gate ``g_f``: ``g_f == 0`` slices skip the whole chunk loop
  body with ``@pl.when`` (no MXU work) and write zeros once per chunk;
  the recurrent state scratch stays at its zero init.
* fused backward, gate ``g_b``: dead slices skip every backward matmul
  and write zero dx / ddA / dB / dC. The backward walks chunks in
  *reverse* grid order (the output index maps flip the chunk index)
  carrying the state cotangent in VMEM scratch; everything else is
  recomputed per chunk from the saved inputs plus the per-chunk incoming
  states (``prevs``) the forward emits as a residual.

Compaction dispatch is shared with the attention kernel: live slices are
gathered front via ``contract.live_permutation`` under a static
``live_fwd`` / ``live_bwd`` bound and the grid's leading dim shrinks to
the bound; results scatter back with zeros elsewhere.

B/C are shared across heads in the model (single B/C group); the wrapper
broadcasts them per slice before compaction and the VJP sums the
per-head dB/dC back. ``S % chunk != 0`` is handled by the *caller*
(``models/ssm.apply_ssd``) zero-padding the scan inputs — a padded row
has ``dA = 0`` (identity decay) and ``xbar = 0`` (no state
contribution), so no in-kernel length masking is needed and the pad
rows' outputs/grads are sliced/dropped outside.

The jit'd public wrapper with interpret auto-detection is
``repro.kernels.ops.gated_ssd_scan``; the pure-jnp oracle is
``repro.kernels.ref.gated_ssd_ref``.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import contract as _contract

# Test hooks — same contract as d2ft_attention: on_backward_block fires once
# per *executed* backward chunk (via jax.debug.callback), on_dispatch fires
# per pallas_call at trace time as (kind, grid). Set before the first trace.
on_backward_block = None
on_dispatch = None


def _maybe_count_block():
    if on_backward_block is not None:
        jax.debug.callback(on_backward_block)


def _report_dispatch(kind: str, grid):
    if on_dispatch is not None:
        on_dispatch(kind, tuple(grid))


_HI = jax.lax.Precision.HIGHEST


def _tril(Q: int):
    """[Q, Q] causal mask, tril[q, k] = k <= q (diagonal included)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return col <= row


def _exp_lanes(x11, n: int):
    """exp of a [1, 1] value as a [1, n] row. Broadcasting a [1, 1] value
    straight onto a [P, N] tile is refused by the TPU lowering (sublanes
    and lanes at once): broadcast along lanes here, before the exp so the
    two broadcasts are not folded into one, and let the use site
    broadcast the row along sublanes."""
    return jnp.exp(jnp.broadcast_to(x11, (1, n)))


def _causal_decay(da):
    """da [Q, 1] -> (cum [Q, 1] inclusive cumsum, L [Q, Q]) with
    L[q, k] = exp(cum_q - cum_k) masked causal (diagonal = 1) — the
    reference's intra-chunk decay matrix. The cumsums are matmuls against
    the causal mask (column and row form), which the TPU lowering supports
    where a cumsum/transpose of a [Q, 1] column is not."""
    tril = _tril(da.shape[0])
    ones = tril.astype(jnp.float32)
    cum = jax.lax.dot_general(ones, da, (((1,), (0,)), ((), ())),
                              precision=_HI)                     # [Q, 1]
    cum_row = jax.lax.dot_general(da, ones, (((0,), (1,)), ((), ())),
                                  precision=_HI)                 # [1, Q]
    return cum, jnp.where(tril, jnp.exp(cum - cum_row), 0.0)


# ================================================================== forward
def _fwd_kernel(gate_ref, da_ref, x_ref, b_ref, c_ref, y_ref, prev_ref,
                state_ref):
    j = pl.program_id(1)
    gate = gate_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    prev = state_ref[...]                                   # [P, N] f32

    @pl.when(gate != 0)
    def _compute():
        x = x_ref[0].astype(jnp.float32)                    # [Q, P]
        da = da_ref[0].astype(jnp.float32)                  # [Q, 1]
        b = b_ref[0].astype(jnp.float32)                    # [Q, N]
        c = c_ref[0].astype(jnp.float32)                    # [Q, N]
        Q = da.shape[0]
        cum, L = _causal_decay(da)
        tot = cum[Q - 1:Q]                                  # [1, 1]
        cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))  # [Q, Q]
        N = b.shape[1]
        y = jax.lax.dot_general(cb * L, x, (((1,), (0,)), ((), ())))
        y = y + jax.lax.dot_general(
            c, prev, (((1,), (1,)), ((), ()))) * jnp.exp(cum)
        y_ref[0] = y.astype(y_ref.dtype)
        prev_ref[0, 0] = prev
        xw = x * jnp.exp(tot - cum)                         # decay-to-end
        state_ref[...] = _exp_lanes(tot, N) * prev + \
            jax.lax.dot_general(xw, b, (((0,), (0,)), ((), ())))

    @pl.when(gate == 0)
    def _dead():
        y_ref[0] = jnp.zeros_like(y_ref[0])
        prev_ref[0, 0] = jnp.zeros_like(prev_ref[0, 0])


def _slice_major(x, da, Bm, Cm):
    """[B,S,H,*] model layout -> slice-major [B*H, S, *] kernel layout,
    broadcasting the head-shared B/C per slice."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    xs = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    das = da.transpose(0, 2, 1).reshape(B * H, S, 1)
    Bs = jnp.broadcast_to(Bm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    Cs = jnp.broadcast_to(Cm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    return xs, das, Bs, Cs


def _forward(x, da, Bm, Cm, g_f, *, chunk: int, interpret: bool, live=None):
    """x: [B,S,H,P] (dt-weighted input), da: [B,S,H] (dt*A), Bm/Cm: [B,S,N],
    g_f: [B,H]. Returns (y [B,S,H,P], prevs [B*H, nc, P, N] f32 — the state
    entering each chunk, zeros for never-dispatched slices)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    NS = B * H
    xs, das, Bs, Cs = _slice_major(x, da, Bm, Cm)
    g = g_f.reshape(NS)
    n_disp = _contract.dispatch_count(live, NS)
    idx = None
    if n_disp < NS:
        idx = _contract.live_permutation(g, n_disp)
        xs, das, Bs, Cs, g = (jnp.take(a, idx, axis=0)
                              for a in (xs, das, Bs, Cs, g))

    grid = (n_disp, nc)
    _report_dispatch("fwd", grid)
    y, prevs = pl.pallas_call(
        _fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                  # g_f
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, Q, 1), lambda s, j, g: (s, j, 0)),    # da
                pl.BlockSpec((1, Q, P), lambda s, j, g: (s, j, 0)),    # x
                pl.BlockSpec((1, Q, N), lambda s, j, g: (s, j, 0)),    # B
                pl.BlockSpec((1, Q, N), lambda s, j, g: (s, j, 0)),    # C
            ],
            out_specs=[
                pl.BlockSpec((1, Q, P), lambda s, j, g: (s, j, 0)),    # y
                pl.BlockSpec((1, 1, P, N),
                             lambda s, j, g: (s, j, 0, 0)),            # prevs
            ],
            scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)]),      # state
        out_shape=[
            jax.ShapeDtypeStruct((n_disp, S, P), x.dtype),
            jax.ShapeDtypeStruct((n_disp, nc, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_contract.gate_operand(g), das, xs, Bs, Cs)

    if idx is not None:
        y = jnp.zeros((NS, S, P), y.dtype).at[idx].set(
            y, unique_indices=True)
        prevs = jnp.zeros((NS, nc, P, N), jnp.float32).at[idx].set(
            prevs, unique_indices=True)
    return y.reshape(B, H, S, P).transpose(0, 2, 1, 3), prevs


# ================================================================= backward
def _bwd_kernel(gate_ref, da_ref, x_ref, b_ref, c_ref, prev_ref, dy_ref,
                dx_ref, dda_ref, db_ref, dc_ref, dstate_ref):
    """Reverse chunk sweep (the index maps flip j); VMEM scratch carries the
    state cotangent. Per live chunk: recompute the decay/state quantities
    and emit dx / ddA / dB / dC plus the carry for the previous chunk."""
    j = pl.program_id(1)
    gate = gate_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    @pl.when(gate != 0)
    def _compute():
        _maybe_count_block()
        x = x_ref[0].astype(jnp.float32)                    # [Q, P]
        da = da_ref[0].astype(jnp.float32)                  # [Q, 1]
        b = b_ref[0].astype(jnp.float32)                    # [Q, N]
        c = c_ref[0].astype(jnp.float32)                    # [Q, N]
        prev = prev_ref[0, 0]                               # [P, N] f32
        dy = dy_ref[0].astype(jnp.float32)                  # [Q, P]
        ds = dstate_ref[...]                                # [P, N] f32
        Q = da.shape[0]
        cum, L = _causal_decay(da)                          # [Q, 1], [Q, Q]
        tot = cum[Q - 1:Q]                                  # [1, 1]
        e_cum = jnp.exp(cum)
        d2e = jnp.exp(tot - cum)
        e_tot = _exp_lanes(tot, b.shape[1])            # [1, N]
        cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))  # [Q, Q]

        # intra-chunk: y_intra = (CB * L) @ x
        gqk = jax.lax.dot_general(dy, x, (((1,), (1,)), ((), ())))
        dcb = gqk * L
        m = gqk * cb * L                                    # dL * L
        dc_intra = jax.lax.dot_general(dcb, b, (((1,), (0,)), ((), ())))
        db_intra = jax.lax.dot_general(dcb, c, (((0,), (0,)), ((), ())))
        dx_intra = jax.lax.dot_general(cb * L, dy, (((0,), (0,)), ((), ())))

        # inter-chunk output: y_inter = (c @ prev^T) * e_cum
        t1 = dy * e_cum
        dc_inter = jax.lax.dot_general(t1, prev, (((1,), (0,)), ((), ())))
        dprev_y = jax.lax.dot_general(t1, c, (((0,), (0,)), ((), ())))
        y_int = jax.lax.dot_general(
            c, prev, (((1,), (1,)), ((), ()))) * e_cum
        dcum_yint = jnp.sum(dy * y_int, axis=1, keepdims=True)

        # state update: state' = e_tot * prev + (x * d2e)^T @ b
        dprev_state = e_tot * ds
        dtot_state = jnp.exp(tot) * jnp.sum(ds * prev, keepdims=True)
        dxw = jax.lax.dot_general(b, ds, (((1,), (1,)), ((), ())))  # [Q, P]
        xw = x * d2e
        db_state = jax.lax.dot_general(xw, ds, (((1,), (0,)), ((), ())))
        dx_state = dxw * d2e
        dd2e = jnp.sum(dxw * x, axis=1, keepdims=True)      # [Q, 1]

        w = dd2e * d2e
        tril = _tril(Q).astype(jnp.float32)
        ones = jnp.ones((Q, 1), jnp.float32)
        m_col = jax.lax.dot_general(m, ones, (((0,), (0,)), ((), ())),
                                    precision=_HI)          # column sums
        dcum = jnp.sum(m, axis=1, keepdims=True) - m_col + dcum_yint - w
        dtot = dtot_state + jnp.sum(w, keepdims=True)
        # cumsum adjoint: reverse cumsum = tril^T @ dcum
        dda = jax.lax.dot_general(tril, dcum, (((0,), (0,)), ((), ())),
                                  precision=_HI) + dtot

        dx_ref[0] = (dx_intra + dx_state).astype(dx_ref.dtype)
        dda_ref[0] = dda.astype(dda_ref.dtype)
        db_ref[0] = (db_intra + db_state).astype(db_ref.dtype)
        dc_ref[0] = (dc_intra + dc_inter).astype(dc_ref.dtype)
        dstate_ref[...] = dprev_state + dprev_y

    @pl.when(gate == 0)
    def _dead():
        dx_ref[0] = jnp.zeros_like(dx_ref[0])
        dda_ref[0] = jnp.zeros_like(dda_ref[0])
        db_ref[0] = jnp.zeros_like(db_ref[0])
        dc_ref[0] = jnp.zeros_like(dc_ref[0])


def _backward(x, da, Bm, Cm, g_b, prevs, dy, *, chunk: int, interpret: bool,
              live=None):
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    nc = S // Q
    NS = B * H
    xs, das, Bs, Cs = _slice_major(x, da, Bm, Cm)
    dys = dy.transpose(0, 2, 1, 3).reshape(NS, S, P)
    g = g_b.reshape(NS)
    n_disp = _contract.dispatch_count(live, NS)
    idx = None
    if n_disp < NS:
        idx = _contract.live_permutation(g, n_disp)
        xs, das, Bs, Cs, dys, prevs, g = (
            jnp.take(a, idx, axis=0)
            for a in (xs, das, Bs, Cs, dys, prevs, g))

    rev = nc - 1
    grid = (n_disp, nc)
    _report_dispatch("bwd", grid)

    def rmap(s, j, g):
        return (s, rev - j, 0)

    dx, dda, db, dc = pl.pallas_call(
        _bwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                  # g_b
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, Q, 1), rmap),                      # da
                pl.BlockSpec((1, Q, P), rmap),                      # x
                pl.BlockSpec((1, Q, N), rmap),                      # B
                pl.BlockSpec((1, Q, N), rmap),                      # C
                pl.BlockSpec((1, 1, P, N),
                             lambda s, j, g: (s, rev - j, 0, 0)),   # prevs
                pl.BlockSpec((1, Q, P), rmap),                      # dy
            ],
            out_specs=[
                pl.BlockSpec((1, Q, P), rmap),                      # dx
                pl.BlockSpec((1, Q, 1), rmap),                      # dda
                pl.BlockSpec((1, Q, N), rmap),                      # dB
                pl.BlockSpec((1, Q, N), rmap),                      # dC
            ],
            scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)]),      # dstate
        out_shape=[
            jax.ShapeDtypeStruct((n_disp, S, P), jnp.float32),
            jax.ShapeDtypeStruct((n_disp, S, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_disp, S, N), jnp.float32),
            jax.ShapeDtypeStruct((n_disp, S, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_contract.gate_operand(g), das, xs, Bs, Cs, prevs, dys)

    if idx is not None:
        dx, dda, db, dc = (
            jnp.zeros((NS,) + a.shape[1:], a.dtype).at[idx].set(
                a, unique_indices=True) for a in (dx, dda, db, dc))
    dx = dx.reshape(B, H, S, P).transpose(0, 2, 1, 3).astype(x.dtype)
    dda = dda.reshape(B, H, S).transpose(0, 2, 1).astype(da.dtype)
    # B/C are shared across heads: sum the per-slice cotangents back
    db = db.reshape(B, H, S, N).sum(axis=1).astype(Bm.dtype)
    dc = dc.reshape(B, H, S, N).sum(axis=1).astype(Cm.dtype)
    return dx, dda, db, dc


# =============================================================== custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def gated_ssd_scan(x, da, Bm, Cm, g_f, g_b, chunk, interpret,
                   live_fwd=None, live_bwd=None):
    """Differentiable gated SSD chunked scan core.

    x: [B,S,H,P] dt-weighted input (``xh * dt``), da: [B,S,H] per-step
    log-decay (``dt * A``, negative), Bm/Cm: [B,S,N] shared across heads,
    g_f/g_b: [B,H] float {0,1} with g_b <= g_f. Returns y: [B,S,H,P],
    ``g_f``-gated; the registered backward computes dx/ddA/dB/dC only where
    ``g_b != 0`` (gates receive zero cotangents). ``live_fwd`` / ``live_bwd``
    are static live-slice bounds enabling compaction dispatch. S must be a
    multiple of ``chunk`` (the caller pads — see module docstring). Prefer
    the jit'd ``ops.gated_ssd_scan``.
    """
    y, _ = _forward(x, da, Bm, Cm, g_f, chunk=chunk, interpret=interpret,
                    live=live_fwd)
    return y


def _vjp_fwd(x, da, Bm, Cm, g_f, g_b, chunk, interpret, live_fwd=None,
             live_bwd=None):
    y, prevs = _forward(x, da, Bm, Cm, g_f, chunk=chunk, interpret=interpret,
                        live=live_fwd)
    return y, (x, da, Bm, Cm, g_f, g_b, prevs)


def _vjp_bwd(chunk, interpret, live_fwd, live_bwd, res, dy):
    x, da, Bm, Cm, g_f, g_b, prevs = res
    dx, dda, db, dc = _backward(x, da, Bm, Cm, g_b, prevs, dy, chunk=chunk,
                                interpret=interpret, live=live_bwd)
    return dx, dda, db, dc, jnp.zeros_like(g_f), jnp.zeros_like(g_b)


gated_ssd_scan.defvjp(_vjp_fwd, _vjp_bwd)


# ======================================================== analytic accounting
# matmuls per live chunk (FLOPs = 2 * m * n * k each):
#   fwd: CB [Q,Q,N], y_intra [Q,Q,P], y_inter [Q,P,N], state-add [Q,P,N]
#   bwd: gqk + dx_intra [Q,Q,P]*2, dc_intra + db_intra [Q,Q,N]*2,
#        dc_inter + dprev_y + y_int + dxw + db_state [Q,P,N]*5
def _chunk_flops(Q: int, P: int, N: int):
    fwd = 2 * (Q * Q * N + Q * Q * P + 2 * Q * P * N)
    bwd = 2 * (2 * Q * Q * P + 2 * Q * Q * N + 5 * Q * P * N)
    return fwd, bwd


def gated_ssd_flops(g_f, g_b, S: int, P: int, N: int, *, chunk: int):
    """Executed MXU FLOPs (fwd, bwd) of the kernel path under concrete
    gates: live slices x chunks x the per-chunk matmul list above. Mirrors
    the kernel's own ``@pl.when`` skip — static HLO counts cannot."""
    Q = min(chunk, S)
    nc = -(-S // Q)
    f, b = _chunk_flops(Q, P, N)
    return (float(np.sum(np.asarray(g_f) != 0)) * nc * f,
            float(np.sum(np.asarray(g_b) != 0)) * nc * b)


def gated_ssd_dispatched_bytes(g_f, g_b, S: int, P: int, N: int, *,
                               chunk: int, live_fwd: int = None,
                               live_bwd: int = None, itemsize: int = 4):
    """(fwd_bytes, bwd_bytes) the BlockSpec pipelines stream per pallas_call.

    Every input block's index map advances each chunk step, so per
    dispatched slice each operand streams exactly once: fwd reads
    x/da/B/C and writes y + the per-chunk prevs residual; bwd re-reads
    them plus prevs/dy and writes dx/dda/dB/dC. ``@pl.when`` does not
    skip this traffic — only compaction dispatch does."""
    Q = min(chunk, S)
    nc = -(-S // Q)
    NS = int(np.asarray(g_f).size)
    disp_f = _contract.dispatch_count(live_fwd, NS)
    disp_b = _contract.dispatch_count(live_bwd, NS)
    fwd_slice = (S * P + S + 2 * S * N        # x, da, B, C read
                 + S * P + nc * P * N)        # y, prevs written
    bwd_slice = (S * P + S + 2 * S * N + nc * P * N + S * P   # reads + dy
                 + S * P + S + 2 * S * N)     # dx, dda, dB, dC written
    return disp_f * fwd_slice * itemsize, disp_b * bwd_slice * itemsize
