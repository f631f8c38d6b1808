"""The gated block kernel contract shared by every D2FT Pallas kernel.

Every gated kernel in this package — attention (`d2ft_attention`), the SSD
chunked scan (`d2ft_ssd`), the RG-LRU recurrence (`d2ft_rglru`) and MoE
expert dispatch (`d2ft_moe`) — speaks the same interface:

* **gates** — a forward gate ``g_f`` and backward gate ``g_b`` over the
  kernel's *subnet axis* (flattened (sample, head) / (sample, group) /
  token slices), float {0, 1}, with the invariant ``g_b <= g_f``
  elementwise: p_f subnets have (1, 1), p_o (1, 0), p_s (0, 0). The
  forward output is ``g_f``-gated (dead subnets produce exact zeros and
  their compute blocks are skipped with ``@pl.when``); the registered
  backward computes gradients only where ``g_b != 0`` and writes exact
  zeros elsewhere. Gates are schedule constants and receive zero
  cotangents.
* **compaction bounds** — static live-slice upper bounds (``live_fwd``,
  ``live_bwd``) derived from ``core/schedule.live_slice_bounds``: when
  given, the kernel gathers live slices to the front via a stable argsort
  permutation of the gates (``live_permutation``) and launches a grid
  whose leading dim is ``dispatch_count(live, N)`` instead of N, then
  scatters results back with zeros elsewhere. Dead slices beyond the
  bound cost neither grid steps nor HBM->VMEM DMA.
* **dispatch hook** — each kernel module exposes ``on_dispatch(kind,
  grid)`` fired at trace time for every pallas_call it builds, and
  ``on_backward_block()`` fired (via jax.debug.callback) once per
  *executed* backward compute block. Tests assert executed work matches
  the schedule bounds exactly.
* **FLOP / DMA byte model** — each kernel module exports
  ``gated_*_flops(g_f, g_b, ...)`` and ``gated_*_dispatched_bytes(...)``
  mirroring its own grid, skip predicate and BlockSpec streams, since
  static HLO FLOP counts cannot see ``@pl.when`` skips in interpret mode.

``models/transformer.py`` routes every block type through a kernel
implementing this contract when ``use_kernel=True``; any route that falls
back to the dense stop-gradient mix reports itself through ``on_fallback``
so the config-zoo test can fail loudly instead of silently testing the
wrong path. See docs/kernels.md.
"""
from __future__ import annotations

import jax.numpy as jnp

# Hook: when set to a callable, every place that takes a non-kernel route
# despite use_kernel=True calls ``on_fallback(kind, reason)`` with kind the
# block type ("attn", "ssd", "rglru", "moe", ...). tests/test_config_zoo.py
# uses it to assert every block type in every config hits a real kernel.
on_fallback = None


def report_fallback(kind: str, reason: str):
    if on_fallback is not None:
        on_fallback(kind, reason)


def dispatch_count(live, N: int) -> int:
    """Static number of slices to launch: the live-count upper bound clamped
    to [1, N]; None disables compaction (dispatch all N slices)."""
    if live is None or live >= N:
        return N
    return max(1, int(live))


def live_permutation(gate_flat, n_dispatch: int):
    """First ``n_dispatch`` entries of the stable permutation that sorts
    live (gate != 0) slices to the front, preserving original order within
    each class. jit-compatible: the *values* are traced, the *size* is the
    static schedule-derived bound — any dead slices padding the tail carry
    gate 0 and are skipped block-level inside the kernels."""
    dead = (gate_flat == 0).astype(jnp.int32)
    return jnp.argsort(dead, stable=True)[:n_dispatch]


def gate_operand(gate_flat):
    """Gates as the kernels' int32 scalar-prefetch operand: the whole [n]
    vector sits in SMEM and each grid step reads its slice's gate as a
    scalar (``gate_ref[pl.program_id(0)]``) — the TPU lowering has no
    (1, 1) VMEM block of an (n, 1) array."""
    return (gate_flat != 0).astype(jnp.int32)
