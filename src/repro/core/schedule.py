"""Schedule table construction and gate materialization (paper Algorithm 1).

A ``ScheduleTable`` is an int8 array [K, N] over subnets k and micro-batches
i with entries  1 = p_f (full),  2 = p_o (forward-only),  3 = p_s (shortcut)
— the exact encoding of Algorithm 1.

Subnets are indexed k = l * G + g for layer l and head-group g; this module
converts tables to the (g_f, g_b) gate arrays consumed by
models.transformer.forward and to the kernel path's static live-slice
bounds.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np

import jax.numpy as jnp

P_F, P_O, P_S = 1, 2, 3


@dataclass
class Schedule:
    table: np.ndarray          # [K, N] int8 in {1,2,3}
    n_layers: int
    n_groups: int              # G (subnets per layer)

    @property
    def n_microbatches(self) -> int:
        return self.table.shape[1]

    def layer_group_view(self) -> np.ndarray:
        return self.table.reshape(self.n_layers, self.n_groups, -1)


def merge_tables(sel_pf: np.ndarray, sel_po: np.ndarray) -> np.ndarray:
    """Algorithm 1 lines 14-31. sel_pf, sel_po: [K, N] bool."""
    table = np.full(sel_pf.shape, P_S, np.int8)
    table[sel_po] = P_O
    table[sel_pf] = P_F            # p_f wins conflicts (line 23-25)
    return table


def build_schedule(backward_scores: np.ndarray, forward_scores: np.ndarray,
                   n_layers: int, n_groups: int, *, c_f: float, c_b: float,
                   cap_pf, cap_po, resolution: int = 100) -> Schedule:
    """Run the bi-level knapsack for every subnet (= device) independently.

    backward_scores / forward_scores: [K, N]; cap_pf / cap_po: scalar or [K]
    per-device capacities (heterogeneity support, paper §IV-D).
    """
    from repro.core.knapsack import bilevel_select
    K, N = backward_scores.shape
    cap_pf = np.broadcast_to(np.asarray(cap_pf, np.float64), (K,))
    cap_po = np.broadcast_to(np.asarray(cap_po, np.float64), (K,))
    sel_pf = np.zeros((K, N), bool)
    sel_po = np.zeros((K, N), bool)
    for k in range(K):
        sel_pf[k], sel_po[k] = bilevel_select(
            backward_scores[k], forward_scores[k], c_f, c_b,
            cap_pf[k], cap_po[k], resolution)
    return Schedule(merge_tables(sel_pf, sel_po), n_layers, n_groups)


# ------------------------------------------------------------------- gates
def gates_from_schedule(sched: Schedule, mb_of_sample: np.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize (g_f, g_b) of shape [n_layers, B, G] for the masked path.

    mb_of_sample: [B] micro-batch index of each sample in the batch.
    g_f = 1 where op in {p_f, p_o} (forward runs); g_b = 1 where op == p_f.
    """
    t = sched.layer_group_view()                         # [L, G, N]
    per_sample = t[:, :, mb_of_sample]                   # [L, G, B]
    g_f = jnp.asarray((per_sample != P_S).transpose(0, 2, 1), jnp.float32)
    g_b = jnp.asarray((per_sample == P_F).transpose(0, 2, 1), jnp.float32)
    return g_f, g_b


def live_slice_bounds(sched: Schedule, mb_of_sample: np.ndarray
                      ) -> Tuple[int, int]:
    """Static (live_fwd, live_bwd) upper bounds for compaction dispatch.

    Counts, per layer, the (sample, group) slices with g_f != 0 (op in
    {p_f, p_o}) and with g_b != 0 (op == p_f) and takes the max over layers
    — one static bound shared by every layer so scan/jit compile a single
    kernel dispatch. The kernel consumes per-(sample, head) gates, so
    multiply by heads-per-group (H // G) before passing to
    ``ops.gated_attention`` (models do this). These are Python ints derived
    from the host-side schedule table, never traced values.
    """
    per_sample = sched.layer_group_view()[:, :, mb_of_sample]   # [L, G, B]
    live_f = int((per_sample != P_S).sum(axis=(1, 2)).max())
    live_b = int((per_sample == P_F).sum(axis=(1, 2)).max())
    return live_f, live_b


def op_counts(sched: Schedule) -> dict:
    t = sched.table
    return {"p_f": int((t == P_F).sum()), "p_o": int((t == P_O).sum()),
            "p_s": int((t == P_S).sum())}
