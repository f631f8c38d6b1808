"""Schedule-masked gradient synchronization for the distributed D2FT step.

In data-parallel D2FT every device computes gradients only for its own
micro-batches, but the masked/kernel gated paths guarantee something
stronger: a (layer, head-group) subnet with **no p_f micro-batch anywhere
in the schedule** has *identically zero* gradient on every device — p_o
contributions are ``stop_gradient``-ed and p_s contributions are zeroed
before they enter the residual stream. All-reducing those zeros is pure
waste, and on commodity interconnects gradient all-reduce is the binding
constraint of distributed fine-tuning. This module turns the host-side
schedule table into a per-leaf *sync plan* that the shard_map train step
applies instead of a blanket ``pmean``:

* ``all``     — live backward somewhere in the leaf: full pmean.
* ``none``    — no live backward in any covered subnet: the psum is elided
                (every device already holds the exact, zero, global grad).
* ``sliced``  — the leaf has head-group structure along one axis (wq/wo
                columns/rows, gated-FFN up/down blocks): only the live
                groups' contiguous slices are pmean'd; dead slices ride
                along untouched. This is the fine granularity that makes
                the skip worth bytes even when a few groups stay live.
* ``stacked`` — scan-stacked ``cycles`` leaves carry one layer per leading
                index; each gets its own per-cycle spec.

Safety rails (always ``all``): embeddings / unembeddings / final norm
(grads flow through every sample), MoE subtrees and their ``norm2`` (the
router aux losses are *not* gated — they produce gradients regardless of
the schedule), and any leaf whose group axis is not divisible by G.

``sync_byte_report`` prices the plan (live vs total all-reduce bytes) so
the dry-run and the ``distributed_step`` bench can report the comm saving
without parsing HLO, and the HLO-parsed numbers can be cross-checked
against it.

ZeRO-1 form (``mode="zero"``)
-----------------------------
``grad_sync_plan(..., mode="zero", n_shards=k)`` replaces the masked pmean
with a partitioned sync: every leaf that can be evenly split over the data
mesh gets a ``zero`` spec that

* **reduce-scatters** only the runs of backward-live groups (dead runs are
  locally sliced — their gradient is identically zero on every device, so
  the device's own sub-chunk already holds the global value);
* hands each device one owned shard per leaf (for each (live, gather) run,
  device d owns the d-th sub-chunk of the run), on which the optimizer
  moments live and the update executes — per-device moment memory drops to
  ~1/k of the replicated baseline;
* **all-gathers** only the runs whose parameters can have changed: the
  backward-live runs plus any run that was ever live since the moments
  were last zero (``ever_live``). A dead run with zero moments has an
  identity update under an *elidable* optimizer (zero weight decay — see
  ``Optimizer.elidable``), so its params are still replicated-correct
  without the gather. Non-elidable optimizers force a full gather mask.

Wire-byte accounting is honest about the physics: reduce-scatter +
all-gather of a live run costs exactly what a ring all-reduce of the same
run costs (2·(k-1)/k bytes per element), so the zero mode *matches* the
masked psum's bytes at equal masks — its wins are the sharded optimizer
state, shard-sized update FLOPs, and that partially-live leaves never pay
a full-tensor collective even where whole-subnet elision can't fire.
Leaves with no evenly divisible axis fall back to their masked spec (and
keep replicated moments); ``sync_byte_report(..., n_shards=k)`` prices
both collectives separately plus the ring-wire total, and
``zero_state_byte_report`` prices the per-device moment memory.

ZeRO-3 form (``mode="zero3"``)
------------------------------
``grad_sync_plan(..., mode="zero3", n_shards=k)`` keeps the same run
partition but makes the *shards* the persistent parameter state: no device
holds a full replica between steps. Inside the step, full-leaf views exist
only transiently (``zero3_materialize``) and the gather mask becomes a
**forward** question — a (layer, head-group) run that is p_s on every
micro-batch of the schedule contributes *exactly zero* to the residual
stream (``gated_linear`` zeroes the group's input columns, g_f == 0), so
its parameters are never all-gathered at all: a zeros view is
bit-identical, for the forward and (trivially) the backward. Runs with any
p_f or p_o cell are gathered; protected leaves gather densely. Gradients
of backward-live runs reduce-scatter straight onto the owning shard
(the ZeRO-2 half), each device updates only its owned slice, and there is
no post-update gather — next step's materialization starts from the
updated shards. Unlike the ZeRO-1 gather elision this needs neither
``Optimizer.elidable`` nor ``ever_live`` bookkeeping: the shard is the
source of truth and is always updated, weight decay included.

``zero3_param_byte_report`` prices the residency-window memory model:
persistent bytes (owned shards + replicated fallback leaves) plus the
largest transiently materialized unit (one transformer block, or one
loss-path subtree) under the gather mask.

Streamed execution
------------------
``zero3_stream_materialize`` is the execution the residency model prices:
each residency unit (one cycle of one pattern position, one ``rest``
block, one loss-path subtree) is materialized by its own per-unit
all-gathers, and every leaf's reduce-scatter is fused into the
materialization's ``custom_vjp`` — the scatter is emitted exactly where
the backward releases that unit's gradient instead of in a serialized
post-backward pass. Values and wire bytes are identical to
``zero3_materialize`` + ``apply_zero_scatter`` (same collectives on the
same operands, split per unit); what changes is the *schedule*: XLA is
free to overlap unit i+1's gathers with unit i's compute and to free each
unit's views as its backward retires. A ``ResidencyRecorder`` passed to
the streamed materializer counts the gathered bytes of every unit at
trace time, and ``check_zero3_residency`` fails if that measurement
disagrees with ``zero3_param_byte_report``'s model beyond tolerance —
``per_device_peak_bytes`` is a measurement, not a promise.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.schedule import P_F, P_S, Schedule


def backward_live_groups(sched: Schedule) -> np.ndarray:
    """[L, G] bool — subnet (l, g) has a live backward (any p_f micro-batch).

    Note this is *schedule*-global, not per-device: a subnet live on any
    device needs the all-reduce on every device (SPMD runs one program)."""
    return (sched.layer_group_view() == P_F).any(axis=-1)


def forward_live_groups(sched: Schedule) -> np.ndarray:
    """[L, G] bool — subnet (l, g) has a live forward (any non-p_s cell).

    The complement is the ZeRO-3 gather-elision set: a subnet that is p_s
    on every micro-batch contributes exactly zero to the residual stream
    whatever its parameter values (``gated_linear`` zeroes its columns),
    so no device needs its params materialized that step. Superset of
    ``backward_live_groups`` (p_f cells are forward-live too), which keeps
    the zero-mode invariant gather ⊇ scatter intact."""
    return (sched.layer_group_view() != P_S).any(axis=-1)


@dataclass(frozen=True)
class SyncSpec:
    """Per-leaf gradient synchronization recipe (see module docstring)."""
    mode: str              # all | none | sliced | stacked | zero | zero_stacked
    axis: int = 0                              # sliced/zero: partition axis
    live: Tuple[bool, ...] = ()                # per-group backward liveness
    per_cycle: Tuple["SyncSpec", ...] = field(default=())   # (zero_)stacked
    gather: Tuple[bool, ...] = ()              # zero: param all-gather mask
    shards: int = 0                            # zero: data-mesh size k


_ALL = SyncSpec("all")
_NONE = SyncSpec("none")

# Leaf name -> axis holding the G contiguous head-group blocks: the groups
# of contiguous heads (wq/wk/wv columns, wo rows) and of MLP width
# (w_up/w_gate columns, w_down rows) whose outputs the gated projections
# (models/transformer.py _gated_proj of wo / w_down) gate.
_Q_AXIS = {"wq": 1, "bq": 0, "wo": 0}
_KV_AXIS = {"wk": 1, "bk": 0, "wv": 1, "bv": 0}
_FFN_AXIS = {"w_up": 1, "w_gate": 1, "w_down": 0}


def _sliceable_axis(name: str, shape: Tuple[int, ...], cfg: ModelConfig,
                    G: int):
    """Axis of the G group blocks in this leaf, or None (coarse leaf)."""
    axis = None
    if name in _Q_AXIS:
        axis = _Q_AXIS[name]
    elif name in _KV_AXIS:
        # KV columns align with query groups only when every group owns a
        # whole number of kv heads; shared kv heads (G % n_kv != 0) receive
        # gradients from several groups -> coarse.
        if cfg.n_kv_heads % G == 0:
            axis = _KV_AXIS[name]
    elif name in _FFN_AXIS and len(shape) == 2:
        axis = _FFN_AXIS[name]
    if axis is None or shape[axis] % G != 0:
        return None
    return axis


def _leaf_spec(name: str, shape: Tuple[int, ...], live_g: np.ndarray,
               cfg: ModelConfig, protected: bool) -> SyncSpec:
    """Spec for one unstacked block leaf given its layer's [G] liveness."""
    if protected:
        return _ALL
    if live_g.all():
        return _ALL
    if not live_g.any():
        return _NONE
    axis = _sliceable_axis(name, shape, cfg, len(live_g))
    if axis is None:
        return _ALL          # partially live, not group-sliceable
    return SyncSpec("sliced", axis=axis, live=tuple(bool(x) for x in live_g))


def _zero_axis(name: str, shape: Tuple[int, ...], cfg: ModelConfig, G: int,
               k: int):
    """(partition axis, groups along it) for a zero leaf, or None.

    Group-sliceable leaves keep mask granularity G when every group block
    splits evenly over the k shards; otherwise the leaf is partitioned
    coarse (one run spanning the largest evenly divisible axis)."""
    axis = _sliceable_axis(name, shape, cfg, G)
    if axis is not None and (shape[axis] // G) % k == 0:
        return axis, G
    divisible = [a for a in range(len(shape)) if shape[a] % k == 0]
    if not divisible:
        return None
    return max(divisible, key=lambda a: shape[a]), 1


def _zero_leaf_spec(name: str, shape: Tuple[int, ...], live_g: np.ndarray,
                    ever_g: np.ndarray, fwd_g: np.ndarray, cfg: ModelConfig,
                    protected: bool, k: int, elide_gather: bool,
                    zero3: bool) -> SyncSpec:
    """Zero-mode spec for one unstacked leaf: partition + (live, gather)
    masks; falls back to the masked spec when no axis splits evenly.

    zero3=True switches the gather mask to forward liveness: the full view
    is rebuilt from the owned shards every step, so the only question is
    whether any consumer of a run's params survives the forward gates —
    staleness (``ever_g``) and optimizer elidability cannot arise."""
    part = _zero_axis(name, shape, cfg, len(live_g), k)
    if part is None:
        return _leaf_spec(name, shape, live_g, cfg, protected)
    axis, groups = part
    if protected:
        live_g = np.ones_like(live_g)
    if zero3:
        gather_g = fwd_g | live_g
    else:
        gather_g = live_g | ever_g if elide_gather \
            else np.ones_like(live_g, bool)
    if groups == 1:
        # coarse partition: the mask collapses to the whole block — for
        # zero3 that is exactly the safety a non-group-sliceable leaf
        # (norms, shared-KV weights, SSD/RG-LRU params) needs: it is only
        # elidable when every group of its block is forward-dead.
        live_g = np.atleast_1d(live_g.any())
        gather_g = np.atleast_1d(gather_g.any())
    return SyncSpec("zero", axis=axis, shards=k,
                    live=tuple(bool(x) for x in live_g),
                    gather=tuple(bool(x) for x in gather_g))


def _block_plan(block, live_g: np.ndarray, cfg: ModelConfig,
                stack: int = 0, *, mode: str = "masked", n_shards: int = 0,
                ever_g: Optional[np.ndarray] = None,
                fwd_g: Optional[np.ndarray] = None,
                elide_gather: bool = True):
    """Plan for one block's param subtree. ``stack`` > 0 marks scan-stacked
    leaves whose leading dim holds one layer per index; ``live_g`` is then
    [stack, G] instead of [G]."""
    has_moe = isinstance(block, dict) and "moe" in block
    if ever_g is None:
        ever_g = np.zeros_like(live_g)
    if fwd_g is None:
        fwd_g = np.zeros_like(live_g)

    def leaf(name, shape, lg, eg, fg, prot):
        if mode in ("zero", "zero3"):
            return _zero_leaf_spec(name, shape, lg, eg, fg, cfg, prot,
                                   n_shards, elide_gather, mode == "zero3")
        return _leaf_spec(name, shape, lg, cfg, prot)

    def rec(tree, name, protected):
        if isinstance(tree, dict):
            return {k: rec(v, k, protected or k == "moe") for k, v in
                    tree.items()}
        if isinstance(tree, (list, tuple)):
            return [rec(v, name, protected) for v in tree]
        # MoE router aux losses are computed from norm2(x) regardless of
        # gating, so the whole FFN side of an MoE block keeps full sync.
        prot = protected or (has_moe and name == "norm2")
        if stack == 0:
            return leaf(name, tree.shape, live_g, ever_g, fwd_g, prot)
        per_cycle = tuple(leaf(name, tree.shape[1:], live_g[c], ever_g[c],
                               fwd_g[c], prot) for c in range(stack))
        if all(s == per_cycle[0] for s in per_cycle):
            s = per_cycle[0]
            if s.mode in ("all", "none"):
                return s
            # identical pattern in every cycle: operate on the stacked
            # leaf directly (partition axis shifts past the stack dim)
            return SyncSpec(s.mode, axis=s.axis + 1, live=s.live,
                            gather=s.gather, shards=s.shards)
        if all(s.mode == "zero" for s in per_cycle):
            # zero-vs-fallback is decided by (name, shape, cfg, k) alone,
            # so cycles never mix zero and masked specs: stack the
            # per-cycle masks under one shape-derived partition axis
            return SyncSpec("zero_stacked", axis=per_cycle[0].axis + 1,
                            shards=n_shards, per_cycle=per_cycle)
        return SyncSpec("stacked", per_cycle=per_cycle)

    return rec(block, None, False)


def _fill(tree, spec: SyncSpec):
    if isinstance(tree, dict):
        return {k: _fill(v, spec) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_fill(v, spec) for v in tree]
    return spec


def _fill_zero(tree, cfg, k):
    """Zero specs for loss-path leaves: fully live, fully gathered."""
    if isinstance(tree, dict):
        return {key: _fill_zero(v, cfg, k) for key, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_fill_zero(v, cfg, k) for v in tree]
    one = np.ones(1, bool)
    return _zero_leaf_spec("", tree.shape, one, one, one, cfg, True, k,
                           True, False)


def grad_sync_plan(params, cfg: ModelConfig, sched: Schedule, *,
                   mode: str = "masked", n_shards: int = 0,
                   ever_live: Optional[np.ndarray] = None,
                   elide_gather: bool = True):
    """Mirror of the params tree with a SyncSpec at every leaf.

    mode="masked" (default): the PR-3 masked-psum plan. mode="zero": the
    ZeRO-1 plan (requires ``n_shards`` = data-mesh size); ``ever_live``
    is an optional [L, G] bool of groups that were backward-live under any
    earlier plan since the moments were last zero — their moments may be
    non-zero, so their params must still be gathered; ``elide_gather=False``
    (non-elidable optimizer, e.g. weight decay) forces a full gather mask.
    mode="zero3": fully sharded params — same partition, but the gather
    mask is *forward* liveness (see module docstring); ``ever_live`` and
    ``elide_gather`` are ignored (the owned shards are always updated, so
    no staleness exists for the mask to track).

    Static and host-side (numpy over the schedule table, shapes from the
    params/eval_shape tree) — baked into the jitted distributed step, so a
    new schedule means a new plan and a re-jit, exactly like the compaction
    bounds."""
    from repro.models.transformer import layer_groups
    assert mode in ("masked", "zero", "zero3"), mode
    assert mode == "masked" or n_shards >= 1, f"{mode} mode needs n_shards"
    live = backward_live_groups(sched)                       # [L, G]
    ever = np.zeros_like(live) if ever_live is None \
        else np.asarray(ever_live, bool)
    assert ever.shape == live.shape, (ever.shape, live.shape)
    fwd = forward_live_groups(sched) if mode == "zero3" \
        else np.zeros_like(live)
    n_cycles, pat, rem = layer_groups(cfg)
    P = len(pat)
    assert live.shape[0] == cfg.n_layers, (live.shape, cfg.n_layers)
    kw = dict(mode=mode, n_shards=n_shards, elide_gather=elide_gather)
    plan = {}
    for key, sub in params.items():
        if key == "cycles":
            # sub[i]: block at pattern position i, leaves [n_cycles, ...];
            # cycle c holds layer c * P + i
            plan[key] = [
                _block_plan(sub[i],
                            live[[c * P + i for c in range(n_cycles)]],
                            cfg, stack=n_cycles,
                            ever_g=ever[[c * P + i for c in range(n_cycles)]],
                            fwd_g=fwd[[c * P + i for c in range(n_cycles)]],
                            **kw)
                for i in range(P)]
        elif key == "rest":
            plan[key] = [_block_plan(sub[i], live[n_cycles * P + i], cfg,
                                     ever_g=ever[n_cycles * P + i],
                                     fwd_g=fwd[n_cycles * P + i], **kw)
                         for i in range(len(sub))]
        else:
            # embed / unembed / final_norm / frontend_proj: gradients flow
            # through every sample's loss path — never skip (and in the
            # zero modes: always scattered, always gathered).
            plan[key] = _fill_zero(sub, cfg, n_shards) \
                if mode in ("zero", "zero3") else _fill(sub, _ALL)
    return plan


# ------------------------------------------------------------- application
def _runs(live: Tuple[bool, ...]):
    """Merge consecutive equal-liveness groups into (live, start, stop)."""
    out = []
    start = 0
    for g in range(1, len(live) + 1):
        if g == len(live) or live[g] != live[start]:
            out.append((live[start], start, g))
            start = g
    return out


def _sync_leaf(g, spec: SyncSpec, axis_name: str):
    if spec.mode == "none":
        return g
    if spec.mode == "all":
        return jax.lax.pmean(g, axis_name)
    if spec.mode == "stacked":
        return jnp.stack([_sync_leaf(g[c], s, axis_name)
                          for c, s in enumerate(spec.per_cycle)])
    blocks = len(spec.live)
    size = g.shape[spec.axis] // blocks
    parts = []
    for is_live, start, stop in _runs(spec.live):
        seg = jax.lax.slice_in_dim(g, start * size, stop * size,
                                   axis=spec.axis)
        parts.append(jax.lax.pmean(seg, axis_name) if is_live else seg)
    return jnp.concatenate(parts, axis=spec.axis) if len(parts) > 1 \
        else parts[0]


def apply_grad_sync(grads, plan, axis_name: str):
    """Masked pmean: all-reduce exactly the live slices of the grads tree.

    Must run inside shard_map over ``axis_name``. Skipped leaves/slices are
    identically zero on every device (see module docstring), so eliding
    their psum leaves them — correctly — at the global value."""
    if isinstance(plan, SyncSpec):
        return _sync_leaf(grads, plan, axis_name)
    if isinstance(plan, dict):
        return {k: apply_grad_sync(grads[k], plan[k], axis_name)
                for k in grads}
    return [apply_grad_sync(g, p, axis_name) for g, p in zip(grads, plan)]


# ------------------------------------------------------ lo-fi local sync
def stack_replicas(tree, n: int):
    """Replicated tree -> per-replica stacked tree ([n, ...] leaves): the
    state layout of ``sync_mode="local"``, where each replica fine-tunes
    its own copy with zero gradient sync between merges."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None],
                                                   (n,) + x.shape), tree)


def _merge_leaf(x, spec: SyncSpec):
    """[R, ...] stacked replica leaf -> merged single leaf.

    The lo-fi merge rule: slices with a live backward anywhere in the
    mask diverge across replicas and are averaged; dead slices received
    identically-zero grads on every replica, so their copies are still
    bit-identical and replica 0 IS the merged value — taking it (instead
    of a mean) keeps dead slices bit-stable and models the wire saving
    (dead slices never need to move)."""
    if spec.mode == "none":
        return x[0]
    if spec.mode == "all":
        return x.mean(axis=0)
    if spec.mode == "stacked":
        return jnp.stack([_merge_leaf(x[:, c], s)
                          for c, s in enumerate(spec.per_cycle)])
    assert spec.mode == "sliced", spec.mode
    blocks = len(spec.live)
    axis = spec.axis + 1                       # leaf axes shift past [R]
    size = x.shape[axis] // blocks
    parts = []
    for is_live, start, stop in _runs(spec.live):
        seg = jax.lax.slice_in_dim(x, start * size, stop * size, axis=axis)
        parts.append(seg.mean(axis=0) if is_live else seg[0])
    return jnp.concatenate(parts, axis=spec.axis) if len(parts) > 1 \
        else parts[0]


def lofi_merge(stacked, plan):
    """Merge per-replica stacked state under a masked-mode sync plan.

    ``plan`` should be built from the union of every schedule that was
    active since the replicas were last in sync (the elastic loop tracks
    that as ``live_since_merge``): a subnet live under ANY of them may
    have diverged and must be averaged; a subnet dead under all of them
    is still replica-identical and is passed through from replica 0.
    ``sync_byte_report(plan, params)`` prices the merge's wire bytes."""
    if isinstance(plan, SyncSpec):
        return _merge_leaf(stacked, plan)
    if isinstance(plan, dict):
        return {k: lofi_merge(stacked[k], plan[k]) for k in stacked}
    return [lofi_merge(x, p) for x, p in zip(stacked, plan)]


# --------------------------------------------------------- zero application
def _is_zero(spec) -> bool:
    return isinstance(spec, SyncSpec) and spec.mode in ("zero",
                                                        "zero_stacked")


def _zero_runs(spec: SyncSpec):
    """Merge consecutive groups with equal (live, gather) into
    (live, gather, start_group, stop_group) runs. Run boundaries define the
    shard layout: for each run, device d owns its d-th sub-chunk, and the
    device shard is the concatenation of those sub-chunks in run order."""
    out = []
    start = 0
    n = len(spec.live)
    for g in range(1, n + 1):
        if g == n or (spec.live[g], spec.gather[g]) != \
                (spec.live[start], spec.gather[start]):
            out.append((spec.live[start], spec.gather[start], start, g))
            start = g
    return out


def _map_zero(fn, tree_args, plan):
    """Apply fn(leaf_args..., spec) over the (tree_args..., plan) lockstep
    tree. zero_stacked leaves are lifted automatically: fn runs per cycle
    on the unstacked slices with the per-cycle spec and the results are
    re-stacked (spec-only calls — no tree_args — get the stacked spec
    itself, whose ``axis`` already includes the cycle dim)."""
    if isinstance(plan, SyncSpec):
        if plan.mode == "zero_stacked" and tree_args:
            return jnp.stack([fn(*[t[c] for t in tree_args], s)
                              for c, s in enumerate(plan.per_cycle)])
        return fn(*tree_args, plan)
    if isinstance(plan, dict):
        return {k: _map_zero(fn, [t[k] for t in tree_args], plan[k])
                for k in plan}
    return [_map_zero(fn, [t[i] for t in tree_args], p)
            for i, p in enumerate(plan)]


def _plan_leaves(tree, plan):
    """Yield (leaf, spec) pairs of a (params-like tree, plan) in lockstep
    — the one traversal all plan accounting shares."""
    if isinstance(plan, SyncSpec):
        yield tree, plan
    elif isinstance(plan, dict):
        for k in plan:
            yield from _plan_leaves(tree[k], plan[k])
    else:
        for t, s in zip(tree, plan):
            yield from _plan_leaves(t, s)


def _zero_scatter_leaf(g, spec: SyncSpec, axis_name: str):
    """Full local grad -> this device's reduced shard (pmean semantics).

    Live runs reduce-scatter (the only cross-device bytes); dead runs are
    identically zero everywhere, so the device's own sub-chunk is already
    the global value and is sliced locally."""
    if not _is_zero(spec):
        return _sync_leaf(g, spec, axis_name)
    k = spec.shards
    gs = g.shape[spec.axis] // len(spec.live)
    idx = jax.lax.axis_index(axis_name)
    parts = []
    for live, _, s, e in _zero_runs(spec):
        seg = jax.lax.slice_in_dim(g, s * gs, e * gs, axis=spec.axis)
        if live:
            parts.append(jax.lax.psum_scatter(
                seg, axis_name, scatter_dimension=spec.axis, tiled=True) / k)
        else:
            plen = (e - s) * gs // k
            parts.append(jax.lax.dynamic_slice_in_dim(
                seg, idx * plen, plen, axis=spec.axis))
    return jnp.concatenate(parts, axis=spec.axis) if len(parts) > 1 \
        else parts[0]


def _zero_shard_leaf(x, spec: SyncSpec, axis_name: str):
    """Replicated leaf -> this device's owned shard (no communication)."""
    if not _is_zero(spec):
        return x
    gs = x.shape[spec.axis] // len(spec.live)
    idx = jax.lax.axis_index(axis_name)
    parts = []
    for _, _, s, e in _zero_runs(spec):
        plen = (e - s) * gs // spec.shards
        seg = jax.lax.slice_in_dim(x, s * gs, e * gs, axis=spec.axis)
        parts.append(jax.lax.dynamic_slice_in_dim(
            seg, idx * plen, plen, axis=spec.axis))
    return jnp.concatenate(parts, axis=spec.axis) if len(parts) > 1 \
        else parts[0]


def _zero_gather_leaf(u, old, spec: SyncSpec, axis_name: str):
    """Updated shard + previous replicated leaf -> new replicated leaf.

    Runs outside the gather mask kept their old params on every device
    (zero grad, zero moments, elidable update — see module docstring)."""
    if not _is_zero(spec):
        return u
    k = spec.shards
    gs = old.shape[spec.axis] // len(spec.live)
    off = 0
    parts = []
    for _, gather, s, e in _zero_runs(spec):
        plen = (e - s) * gs // k
        if gather:
            piece = jax.lax.slice_in_dim(u, off, off + plen, axis=spec.axis)
            parts.append(jax.lax.all_gather(piece, axis_name,
                                            axis=spec.axis, tiled=True))
        else:
            parts.append(jax.lax.slice_in_dim(old, s * gs, e * gs,
                                              axis=spec.axis))
        off += plen
    return jnp.concatenate(parts, axis=spec.axis) if len(parts) > 1 \
        else parts[0]


def apply_zero_scatter(grads, plan, axis_name: str):
    """Local grads tree -> mixed tree: reduced shards at zero leaves,
    masked-pmean full leaves elsewhere. Must run inside shard_map."""
    return _map_zero(lambda g, s: _zero_scatter_leaf(g, s, axis_name),
                     [grads], plan)


def zero_shard_params(params, plan, axis_name: str):
    """Replicated params tree -> this device's owned shards (zero leaves)
    with non-partitioned leaves passed through."""
    return _map_zero(lambda x, s: _zero_shard_leaf(x, s, axis_name),
                     [params], plan)


def apply_zero_gather(updated, old_params, plan, axis_name: str):
    """Updated shard tree + previous replicated params -> new replicated
    params; only runs in the gather mask move bytes."""
    return _map_zero(lambda u, o, s: _zero_gather_leaf(u, o, s, axis_name),
                     [updated, old_params], plan)


def _zero3_materialize_leaf(shard, spec: SyncSpec, axis_name: str):
    """Owned shard -> transient full-leaf view for the step body.

    Runs in the gather mask are all-gathered (device d's piece is the d-th
    sub-chunk of the run, so a tiled gather restores the canonical run
    content); elided runs materialize as zeros — exact, because every
    consumer of their values is multiplied by g_f == 0 (see
    ``forward_live_groups``), and the persistent state is the shard, which
    the elision never touches."""
    if not _is_zero(spec):
        return shard
    k = spec.shards
    full = shard.shape[spec.axis] * k
    gs = full // len(spec.live)
    off = 0
    parts = []
    for _, gather, s, e in _zero_runs(spec):
        plen = (e - s) * gs // k
        if gather:
            piece = jax.lax.slice_in_dim(shard, off, off + plen,
                                         axis=spec.axis)
            parts.append(jax.lax.all_gather(piece, axis_name,
                                            axis=spec.axis, tiled=True))
        else:
            shape = list(shard.shape)
            shape[spec.axis] = (e - s) * gs
            parts.append(jnp.zeros(shape, shard.dtype))
        off += plen
    return jnp.concatenate(parts, axis=spec.axis) if len(parts) > 1 \
        else parts[0]


def zero3_materialize(params, plan, axis_name: str):
    """Sharded params tree -> full-param views for the forward/backward.

    Only runs in the gather mask move bytes; fallback (masked) leaves pass
    through replicated. Must run inside shard_map. The inverse direction
    needs no collective: grads reduce-scatter onto the shards
    (``apply_zero_scatter``) and the update runs shard-resident."""
    return _map_zero(lambda x, s: _zero3_materialize_leaf(x, s, axis_name),
                     [params], plan)


def zero_norm_sq(grads, plan):
    """(shard_sq, full_sq): squared-norm contributions of a mixed grads
    tree. ``shard_sq`` sums zero-leaf shards (disjoint across devices — a
    scalar psum completes them); ``full_sq`` sums the replicated masked
    leaves, identical on every device."""
    shard_sq = jnp.zeros((), jnp.float32)
    full_sq = jnp.zeros((), jnp.float32)
    for g, spec in _plan_leaves(grads, plan):
        sq = jnp.sum(g.astype(jnp.float32) ** 2)
        if _is_zero(spec):
            shard_sq = shard_sq + sq
        else:
            full_sq = full_sq + sq
    return shard_sq, full_sq


def zero_param_specs(plan, axis_name: str):
    """PartitionSpec tree for the sharded moment leaves: the partition axis
    is sharded over ``axis_name`` for zero leaves, replicated otherwise.
    The global array layout is the concatenation of device shards (each the
    run-ordered sub-chunks of ``_zero_runs``) along the partition axis."""
    from jax.sharding import PartitionSpec as P

    def leaf(spec):
        if _is_zero(spec):
            return P(*([None] * spec.axis + [axis_name]))
        return P()

    return _map_zero(leaf, [], plan)


# ------------------------------------------------ streamed materialization
class ResidencyRecorder:
    """Trace-time counter of the bytes each residency unit all-gathers.

    The streamed materializer reports every gather site it emits as
    ``record(unit, site, nbytes)``; sites are keyed (not summed) so
    re-tracing the same step is idempotent. ``unit_bytes()`` is the
    *measured* side of the residency model — ``check_zero3_residency``
    compares it against ``zero3_param_byte_report``."""

    def __init__(self):
        self.sites: dict = {}            # unit -> {site_key: bytes}

    def record(self, unit: str, site: str, nbytes: float):
        self.sites.setdefault(unit, {})[site] = float(nbytes)

    def unit_bytes(self) -> dict:
        return {u: float(sum(s.values())) for u, s in self.sites.items()}


def _cycle_spec(spec: SyncSpec, c: int) -> SyncSpec:
    """Spec for cycle c of a scan-stacked leaf (the uniform stacked forms
    carry the cycle dim in ``axis``; unstacking shifts it back)."""
    if spec.mode in ("zero_stacked", "stacked"):
        return spec.per_cycle[c]
    if spec.mode == "zero":
        return SyncSpec("zero", axis=spec.axis - 1, live=spec.live,
                        gather=spec.gather, shards=spec.shards)
    if spec.mode == "sliced":
        return SyncSpec("sliced", axis=spec.axis - 1, live=spec.live)
    return spec                          # all / none: per-cycle unchanged


def _stream_leaf(shard, spec: SyncSpec, axis_name: str, recorder=None,
                 unit: str = "", path: str = ""):
    """One leaf of the streamed schedule: forward all-gather and backward
    reduce-scatter fused into a ``custom_vjp``, so the scatter sits exactly
    where the backward releases this leaf's gradient. Fallback (masked)
    leaves stream too: identity forward, masked pmean in the vjp. Values
    match ``zero3_materialize`` + ``apply_zero_scatter`` bit-for-bit —
    the ``dist_zero3_streamed`` parity arm pins that."""
    if recorder is not None and _is_zero(spec):
        full_ax = shard.shape[spec.axis] * spec.shards
        gs = full_ax // len(spec.live)
        row = float(np.prod(shard.shape)) / shard.shape[spec.axis] \
            * np.dtype(shard.dtype).itemsize
        for _, gather, s, e in _zero_runs(spec):
            if gather:
                recorder.record(unit, f"{path}[{s}:{e}]",
                                (e - s) * gs * row)

    @jax.custom_vjp
    def mat(s):
        return _zero3_materialize_leaf(s, spec, axis_name)

    def mat_fwd(s):
        return _zero3_materialize_leaf(s, spec, axis_name), None

    def mat_bwd(_, ct):
        return (_zero_scatter_leaf(ct, spec, axis_name),)

    mat.defvjp(mat_fwd, mat_bwd)
    return mat(shard)


def _stream_block(tree, plan_t, axis_name: str, recorder, unit: str,
                  path: str = ""):
    if isinstance(plan_t, SyncSpec):
        return _stream_leaf(tree, plan_t, axis_name, recorder, unit, path)
    if isinstance(plan_t, dict):
        return {k: _stream_block(tree[k], plan_t[k], axis_name, recorder,
                                 unit, f"{path}/{k}") for k in plan_t}
    return [_stream_block(tree[i], p, axis_name, recorder, unit,
                          f"{path}/{i}") for i, p in enumerate(plan_t)]


def _stream_block_stacked(tree, plan_t, axis_name: str, recorder,
                          unit_prefix: str, path: str = ""):
    """Scan-stacked block: each cycle is its own residency unit, so every
    leaf is unstacked and materialized per cycle (one gather per cycle per
    run instead of one spanning the stack — same bytes, unit-granular
    scheduling) and re-stacked for the scan."""
    if isinstance(plan_t, SyncSpec):
        n_cycles = tree.shape[0]
        return jnp.stack([
            _stream_leaf(tree[c], _cycle_spec(plan_t, c), axis_name,
                         recorder, f"{unit_prefix}[{c}]", path)
            for c in range(n_cycles)])
    if isinstance(plan_t, dict):
        return {k: _stream_block_stacked(tree[k], plan_t[k], axis_name,
                                         recorder, unit_prefix,
                                         f"{path}/{k}") for k in plan_t}
    return [_stream_block_stacked(tree[i], p, axis_name, recorder,
                                  unit_prefix, f"{path}/{i}")
            for i, p in enumerate(plan_t)]


def zero3_stream_materialize(params, plan, axis_name: str, *,
                             recorder: Optional[ResidencyRecorder] = None):
    """Sharded params tree -> full views, one residency unit at a time.

    Drop-in replacement for ``zero3_materialize`` whose gradient is
    already the scattered mixed tree of ``apply_zero_scatter`` — take
    ``jax.grad`` of a loss over this and the reduce-scatters land at each
    unit's backward release point, with per-unit all-gathers the XLA
    scheduler can prefetch (double-buffer) against the previous unit's
    compute. Must run inside shard_map. ``recorder`` (optional) counts
    every gather site's bytes per unit at trace time."""
    out = {}
    for key in params:
        if key == "cycles":
            out[key] = [
                _stream_block_stacked(params[key][i], plan[key][i],
                                      axis_name, recorder, f"cycles[{i}]")
                for i in range(len(plan[key]))]
        elif key == "rest":
            out[key] = [
                _stream_block(params[key][i], plan[key][i], axis_name,
                              recorder, f"rest[{i}]")
                for i in range(len(plan[key]))]
        else:
            out[key] = _stream_block(params[key], plan[key], axis_name,
                                     recorder, key)
    return out


# --------------------------------------------------------------- accounting
def _mask_fraction(mask: Tuple[bool, ...]) -> float:
    return float(sum(mask)) / len(mask) if mask else 0.0


def _live_fraction(spec: SyncSpec) -> float:
    if spec.mode == "all":
        return 1.0
    if spec.mode == "none":
        return 0.0
    if spec.mode in ("stacked", "zero_stacked"):
        return float(np.mean([_live_fraction(s) for s in spec.per_cycle]))
    return _mask_fraction(spec.live)


def _gather_fraction(spec: SyncSpec) -> float:
    if spec.mode == "zero":
        return _mask_fraction(spec.gather)
    if spec.mode == "zero_stacked":
        return float(np.mean([_gather_fraction(s) for s in spec.per_cycle]))
    return 0.0


def sync_byte_report(plan, params, n_shards: Optional[int] = None) -> dict:
    """Price the plan. Works on concrete arrays or ShapeDtypeStructs.

    Masked leaves contribute live bytes to ``ar_bytes`` (all-reduce); zero
    leaves contribute scatter-live bytes to ``rs_bytes`` (reduce-scatter)
    and gather-mask bytes to ``ag_bytes`` (all-gather). ``synced_bytes``
    keeps the PR-3 meaning of all-reduce-*equivalent* bytes — a zero
    leaf's RS + AG pair counts as the mean of the two masks, so
    ``fraction`` stays comparable across modes. With ``n_shards`` the
    report adds ``wire``: per-device ring traffic by collective
    (2·(k-1)/k per all-reduce byte, (k-1)/k per RS/AG byte), the number
    the HLO-parsed ``launch.hlo.collective_bytes`` should reproduce."""
    totals = {"total_bytes": 0.0, "synced_bytes": 0.0, "ar_bytes": 0.0,
              "rs_bytes": 0.0, "ag_bytes": 0.0, "n_leaves": 0,
              "n_skipped": 0, "n_sliced": 0, "n_zero": 0}
    for p, spec in _plan_leaves(params, plan):
        size = float(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
        totals["total_bytes"] += size
        totals["n_leaves"] += 1
        if _is_zero(spec):
            rs, ag = _live_fraction(spec), _gather_fraction(spec)
            totals["rs_bytes"] += size * rs
            totals["ag_bytes"] += size * ag
            totals["synced_bytes"] += size * (rs + ag) / 2.0
            totals["n_zero"] += 1
            continue
        totals["ar_bytes"] += size * _live_fraction(spec)
        totals["synced_bytes"] += size * _live_fraction(spec)
        if spec.mode == "none":
            totals["n_skipped"] += 1
        elif spec.mode in ("sliced", "stacked"):
            totals["n_sliced"] += 1
    totals["fraction"] = (totals["synced_bytes"] / totals["total_bytes"]
                          if totals["total_bytes"] else 1.0)
    if n_shards is not None and n_shards > 1:
        k = n_shards
        wire = {
            "all_reduce": 2.0 * (k - 1) / k * totals["ar_bytes"],
            "reduce_scatter": (k - 1) / k * totals["rs_bytes"],
            "all_gather": (k - 1) / k * totals["ag_bytes"],
        }
        wire["total"] = sum(wire.values())
        totals["wire"] = wire
    return totals


def zero_state_byte_report(plan, params, n_shards: int,
                           n_moments: int = 1) -> dict:
    """Per-device optimizer-moment memory under the plan's partition.

    Zero leaves keep 1/k of each moment copy per device; fallback (masked)
    leaves stay replicated. ``fraction`` is per-device bytes over the
    replicated baseline — the ZeRO-1 memory claim."""
    totals = {"replicated_bytes": 0.0, "per_device_bytes": 0.0,
              "n_partitioned": 0, "n_replicated": 0}
    for p, spec in _plan_leaves(params, plan):
        size = float(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
        totals["replicated_bytes"] += size
        if _is_zero(spec):
            totals["per_device_bytes"] += size / n_shards
            totals["n_partitioned"] += 1
        else:
            totals["per_device_bytes"] += size
            totals["n_replicated"] += 1
    for key in ("replicated_bytes", "per_device_bytes"):
        totals[key] *= n_moments
    totals["n_shards"] = n_shards
    totals["fraction"] = (totals["per_device_bytes"]
                          / totals["replicated_bytes"]
                          if totals["replicated_bytes"] else 1.0)
    return totals


def _cycle_gather_fraction(spec: SyncSpec, c: int) -> Optional[float]:
    """Gather fraction of cycle c of a (possibly stacked) leaf spec, or
    None for fallback (masked/replicated) leaves."""
    if spec.mode == "zero_stacked":
        return _gather_fraction(spec.per_cycle[c])
    if _is_zero(spec):
        return _gather_fraction(spec)        # uniform across cycles
    return None


def _unit_gathered_bytes(tree, plan_t, cycle=None, n_cycles: int = 1):
    """Gathered bytes of one residency unit (cycle slice or whole block)
    under the plan's gather mask — the model side of what the streamed
    materializer's ``ResidencyRecorder`` measures at trace time."""
    u = 0.0
    for p, spec in _plan_leaves(tree, plan_t):
        if not _is_zero(spec):
            continue
        f = _cycle_gather_fraction(spec, 0 if cycle is None else cycle)
        u += float(np.prod(p.shape)) * np.dtype(p.dtype).itemsize \
            / n_cycles * f
    return u


def zero3_param_byte_report(plan, params, n_shards: int) -> dict:
    """Residency-window memory model of the ZeRO-3 partition.

    Persistent per-device bytes: the owned shards (1/k of every
    partitioned leaf) plus replicated fallback leaves. Transient bytes:
    the full-leaf views the step materializes, priced per *residency
    unit* — one transformer block (one cycle of one pattern position, or
    one ``rest`` block) or one loss-path subtree — under the plan's gather
    mask, with elided runs costing nothing (their zeros view folds into
    the gated-off consumer). ``per_device_peak_bytes`` prices the
    streaming schedule ``zero3_stream_materialize`` executes: one unit
    materialized at a time, freed after its backward retires. The model is
    cross-checked against that execution's trace-time gather counter by
    ``check_zero3_residency`` (and the distributed-step bench fails if
    they disagree beyond 5%); the double-buffered variant that also holds
    the prefetched next unit is priced by the overlap model in
    launch/diststep.py.

    ``fraction`` = peak / replicated is the ZeRO-3 memory claim;
    ``n_gather_elided`` counts runs whose all-gather the schedule killed
    (> 0 is the "the gates tell us which gathers are dead" acceptance)."""
    def size_of(p):
        return float(np.prod(p.shape)) * np.dtype(p.dtype).itemsize

    totals = {"replicated_bytes": 0.0, "shard_bytes": 0.0,
              "fallback_bytes": 0.0, "gathered_bytes": 0.0,
              "elided_bytes": 0.0, "n_runs": 0, "n_gather_elided": 0,
              "n_partitioned": 0, "n_fallback": 0}
    for p, spec in _plan_leaves(params, plan):
        size = size_of(p)
        totals["replicated_bytes"] += size
        if not _is_zero(spec):
            totals["fallback_bytes"] += size
            totals["n_fallback"] += 1
            continue
        totals["shard_bytes"] += size / n_shards
        totals["n_partitioned"] += 1
        subs = [(size / len(spec.per_cycle), s) for s in spec.per_cycle] \
            if spec.mode == "zero_stacked" else [(size, spec)]
        for sub_size, sub in subs:
            for _, gather, s, e in _zero_runs(sub):
                frac = (e - s) / len(sub.live)
                totals["n_runs"] += 1
                if gather:
                    totals["gathered_bytes"] += sub_size * frac
                else:
                    totals["n_gather_elided"] += 1
                    totals["elided_bytes"] += sub_size * frac

    units = dict(zero3_unit_schedule(plan, params))
    totals["peak_unit_bytes"] = max(units.values()) if units else 0.0
    totals["peak_unit"] = max(units, key=units.get) if units else ""
    totals["per_device_peak_bytes"] = (totals["shard_bytes"]
                                       + totals["fallback_bytes"]
                                       + totals["peak_unit_bytes"])
    totals["fraction"] = (totals["per_device_peak_bytes"]
                          / totals["replicated_bytes"]
                          if totals["replicated_bytes"] else 1.0)
    totals["n_shards"] = n_shards
    return totals


# Loss-path subtrees consumed before the layer stack in the forward —
# everything else non-block (final norm, unembed, ...) runs after it.
_HEAD_KEYS = ("embed", "frontend_proj")


def zero3_unit_schedule(plan, params):
    """Ordered [(unit_name, gathered_bytes)] in forward execution order.

    Head loss-path subtrees (embeddings) first, then the layer stack in
    layer order — layer l = c * P + i is unit ``cycles[i][c]``, followed
    by the ``rest`` blocks — then the remaining loss-path subtrees. This
    is the unit sequence the streamed materializer gathers and the order
    the overlap-window model (launch/diststep.py) prefetches in; names
    match ``zero3_param_byte_report``'s units and the keys the
    ``ResidencyRecorder`` measures."""
    head, tail = [], []
    for key in plan:
        if key in ("cycles", "rest"):
            continue
        entry = (key, _unit_gathered_bytes(params[key], plan[key]))
        (head if key in _HEAD_KEYS else tail).append(entry)
    blocks = []
    if "cycles" in plan and plan["cycles"]:
        leaves = jax.tree.leaves(params["cycles"][0])
        n_cycles = leaves[0].shape[0] if leaves else 1
        for c in range(n_cycles):
            for i in range(len(plan["cycles"])):
                blocks.append((f"cycles[{i}][{c}]", _unit_gathered_bytes(
                    params["cycles"][i], plan["cycles"][i], cycle=c,
                    n_cycles=n_cycles)))
    for i in range(len(plan.get("rest", []))):
        blocks.append((f"rest[{i}]", _unit_gathered_bytes(
            params["rest"][i], plan["rest"][i])))
    return head + blocks + tail


def check_zero3_residency(recorder: ResidencyRecorder, plan, params,
                          n_shards: int, *, tol: float = 0.05) -> dict:
    """Fail if the executed streaming schedule and the residency model
    disagree beyond ``tol`` (relative).

    ``recorder`` holds the gather bytes the streamed materializer actually
    emitted (counted at trace time, per residency unit);
    ``zero3_param_byte_report`` / ``zero3_unit_schedule`` hold what the
    model promises. Every unit is compared, units the model does not know
    about are an error, and the derived ``per_device_peak_bytes`` must
    agree — the measurement contract the distributed-step bench and the
    8-device parity suite enforce. Returns the measured-side summary for
    the bench's ``overlap`` block."""
    report = zero3_param_byte_report(plan, params, n_shards)
    measured = recorder.unit_bytes()
    model = dict(zero3_unit_schedule(plan, params))

    def close(a, b):
        return abs(a - b) <= tol * max(abs(a), abs(b), 1.0)

    unknown = set(measured) - set(model)
    assert not unknown, f"streamed schedule gathered unknown units {unknown}"
    for name, want in model.items():
        got = measured.get(name, 0.0)
        assert close(got, want), \
            f"unit {name}: measured {got:.0f}B vs model {want:.0f}B"
    peak_meas = max(measured.values()) if measured else 0.0
    assert close(peak_meas, report["peak_unit_bytes"]), \
        (peak_meas, report["peak_unit_bytes"])
    device_peak = (report["shard_bytes"] + report["fallback_bytes"]
                   + peak_meas)
    assert close(device_peak, report["per_device_peak_bytes"]), \
        (device_peak, report["per_device_peak_bytes"])
    return {
        "measured_peak_unit_bytes": peak_meas,
        "measured_peak_unit": (max(measured, key=measured.get)
                               if measured else ""),
        "measured_per_device_peak_bytes": device_peak,
        "model_per_device_peak_bytes": report["per_device_peak_bytes"],
        "peak_agreement": (device_peak / report["per_device_peak_bytes"]
                           if report["per_device_peak_bytes"] else 1.0),
        "n_units_measured": len(measured),
        "n_units_model": len(model),
    }


# ----------------------------------------------- layout / state resharding
def _zero_layout_perm(spec: SyncSpec, axis_len: int) -> np.ndarray:
    """perm[i] = canonical axis index held at position i of the global
    shard-concatenated layout (device-major, runs in order, d-th sub-chunk
    of each run per device). A bijection over range(axis_len)."""
    k = spec.shards
    gs = axis_len // len(spec.live)
    perm = np.empty(axis_len, np.int64)
    pos = 0
    for d in range(k):
        for _, _, s, e in _zero_runs(spec):
            plen = (e - s) * gs // k
            start = s * gs + d * plen
            perm[pos:pos + plen] = np.arange(start, start + plen)
            pos += plen
    assert pos == axis_len
    return perm


def _leaf_to_canonical(x: np.ndarray, spec: SyncSpec) -> np.ndarray:
    """Global shard-layout array -> canonical element order (numpy)."""
    if spec.mode == "zero_stacked":
        return np.stack([_leaf_to_canonical(x[c], s)
                         for c, s in enumerate(spec.per_cycle)])
    if not _is_zero(spec):
        return x
    perm = _zero_layout_perm(spec, x.shape[spec.axis])
    out = np.empty_like(x)
    idx = [slice(None)] * x.ndim
    idx[spec.axis] = perm
    out[tuple(idx)] = x
    return out


def _leaf_from_canonical(x: np.ndarray, spec: SyncSpec) -> np.ndarray:
    """Canonical array -> the global shard-concatenated layout (numpy)."""
    if spec.mode == "zero_stacked":
        return np.stack([_leaf_from_canonical(x[c], s)
                         for c, s in enumerate(spec.per_cycle)])
    if not _is_zero(spec):
        return x
    perm = _zero_layout_perm(spec, x.shape[spec.axis])
    return np.take(x, perm, axis=spec.axis)


# ------------------------------------------------ tensor-axis grad combine
# Leaves whose COMPUTE shards over the tensor axis (attention head blocks,
# FFN column blocks — models.transformer tp= path). Their local grads are
# disjoint slices of the true grad (zero outside this device's block), so
# a psum over the tensor axis reassembles the full tensor. Every other
# leaf's compute is replicated across the axis (identical grads after the
# _tp_copy backward all-reduces the activation cotangent), so it must NOT
# be psum'd. MoE reuses the w_up/w_gate/w_down names but runs replicated,
# hence the parent-key restriction.
_TP_SHARDED = {
    "attn": {"wq", "wk", "wv", "wo", "bq", "bk", "bv"},
    "mlp": {"w_up", "w_gate", "w_down"},
}


def apply_tensor_grad_sync(grads, axis_name: str):
    """psum the tensor-parallel-sharded grad leaves over ``axis_name``;
    replicated leaves pass through. Mirrors ``apply_grad_sync``'s role on
    the data axis — run this FIRST, so the data-axis sync (masked / ZeRO
    scatter) sees full per-data-shard grads."""
    def walk(tree, parent=None):
        if isinstance(tree, dict):
            return {k: (jax.lax.psum(v, axis_name)
                        if not isinstance(v, (dict, list, tuple))
                        and k in _TP_SHARDED.get(parent, ())
                        else walk(v, k))
                    for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return [walk(v, parent) for v in tree]
        return tree

    return walk(grads)


def zero_reshard(tree, old_plan, new_plan):
    """Re-layout a moments tree from one plan's shard layout to another's
    (host-side numpy; used when a schedule refresh changes the plan).
    Either plan may be None, meaning canonical/replicated layout — so this
    also converts masked <-> zero optimizer state."""
    def rec(x, old_s, new_s):
        if isinstance(x, (dict,)):
            return {k: rec(x[k],
                           old_s[k] if old_s is not None else None,
                           new_s[k] if new_s is not None else None)
                    for k in x}
        if isinstance(x, (list, tuple)):
            return [rec(xi,
                        old_s[i] if old_s is not None else None,
                        new_s[i] if new_s is not None else None)
                    for i, xi in enumerate(x)]
        arr = np.asarray(x)
        if old_s is not None:
            arr = _leaf_to_canonical(arr, old_s)
        if new_s is not None:
            arr = _leaf_from_canonical(arr, new_s)
        return jnp.asarray(arr)

    return rec(tree, old_plan, new_plan)
