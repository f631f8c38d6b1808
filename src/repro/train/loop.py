"""Training loops: standard fine-tuning and the D2FT-orchestrated variants.

`finetune` drives the masked or the kernel path on LLM backbones;
`finetune_vit` mirrors the paper's ViT experiments and is what the
paper-table benchmarks call.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import D2FTConfig, ModelConfig
from repro.core import d2ft as d2ft_mod
from repro.core.schedule import (Schedule, gates_from_schedule,
                                 live_slice_bounds)
from repro.core.scores import compute_scores, transformer_blocks, vit_blocks
from repro.data.synthetic import microbatch_assignment
from repro.models.transformer import lm_loss
from repro.models.vit import ViTConfig, vit_loss
from repro.optim.optimizers import (Optimizer, clip_by_global_norm,
                                    clip_scale)
from repro.train.spans import each_step, span


@dataclass
class TrainLog:
    losses: list = field(default_factory=list)
    metrics: list = field(default_factory=list)
    # per step: dispatch of the jitted step to its loss being ready
    step_times: list = field(default_factory=list)
    # distributed path: rebalance report + sync-plan byte report
    extras: dict = field(default_factory=dict)
    # per step: host phases and compile events (train.spans.StepRecord)
    steps: list = field(default_factory=list)
    # replans: schedules planned; step_builds: jitted steps made
    counters: dict = field(default_factory=lambda: {"replans": 0,
                                                    "step_builds": 0})

    def last(self, k: str):
        return self.metrics[-1][k] if self.metrics else None


def _read_back(log: TrainLog, metrics):
    """The step's time from its dispatch and wait spans, then its metrics
    on the host (the ``readback`` phase)."""
    log.step_times.append(log.steps[-1].seconds("dispatch", "wait"))
    with span(log, "readback"):
        log.losses.append(float(metrics["loss"]))
        log.metrics.append({k: float(v) for k, v in metrics.items()})


# ------------------------------------------------------------------ LLM path
def make_train_step(cfg: ModelConfig, opt: Optimizer, *, use_gates: bool,
                    policy=None, remat: bool = False,
                    clip: float = 1.0, use_kernel: bool = False,
                    live_bounds=None):
    """Returns jit-able step(params, opt_state, batch[, sched_args]).

    use_kernel: run attention through the Pallas gated flash kernel whose
    custom-VJP backward skips p_o / p_s (sample, head-group) slices.
    live_bounds: static (live_fwd, live_bwd) (sample, group) slice bounds
    from ``core.schedule.live_slice_bounds`` — enables the kernel path's
    compaction dispatch. Baked into the jitted step: re-make (and re-jit)
    the step when the schedule's live counts change.
    """

    def loss_of(params, batch, sched_args):
        gates = sched_args if use_gates else None
        return lm_loss(params, cfg, batch.get("tokens"), batch["labels"],
                       features=batch.get("features"), gates=gates,
                       policy=policy, remat=remat, use_kernel=use_kernel,
                       live_bounds=live_bounds if use_gates else None)

    def step(params, opt_state, batch, sched_args=None):
        (loss, metrics), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params, batch, sched_args)
        with jax.named_scope("clip"):
            grads, gnorm = clip_by_global_norm(grads, clip)
        with jax.named_scope("optimizer"):
            params, opt_state = opt.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return params, opt_state, metrics

    return step


def plan_from_scores(cfg: ModelConfig, d2: D2FTConfig, params,
                     score_batches, loss_fn) -> Schedule:
    """Scoring pass (paper: before fine-tuning) + bi-level knapsack."""
    G = d2.head_groups or max(cfg.n_heads, 1)
    bw, fw = compute_scores(loss_fn, params,
                            lambda t: transformer_blocks(t, cfg),
                            score_batches, G,
                            backward_metric=d2.backward_score,
                            forward_metric=d2.forward_score)
    return d2ft_mod.plan_schedule(d2, bw, fw, cfg.n_layers, G)


def finetune(params, cfg: ModelConfig, d2: Optional[D2FTConfig],
             opt: Optimizer, batches: Iterable, *, steps: int,
             use_kernel: bool = False,
             log: Optional[TrainLog] = None) -> tuple:
    """Fine-tune; if d2 is given, schedule ops per batch via D2FT."""
    log = log or TrainLog()
    opt_state = opt.init(params)
    # jitted steps cached per compaction bound pair: bounds are re-derived
    # every batch (batch size or schedule changes change the live counts),
    # identical counts reuse the cached trace
    step_fns = {}

    def get_step(bounds):
        if bounds not in step_fns:
            step_fns[bounds] = jax.jit(make_train_step(
                cfg, opt, use_gates=d2 is not None, use_kernel=use_kernel,
                live_bounds=bounds))
            log.counters["step_builds"] += 1
        return step_fns[bounds]

    sched = None
    for i, batch in each_step(log, batches, steps):
        if d2 is not None and sched is None:
            from repro.data.synthetic import split_microbatches
            with span(log, "plan"):
                mbs = split_microbatches(batch, d2.n_microbatches)
                sched = plan_from_scores(
                    cfg, d2, params, mbs,
                    lambda p, mb: lm_loss(p, cfg, mb.get("tokens"),
                                          mb["labels"],
                                          features=mb.get("features"))[0])
            log.counters["replans"] += 1
        with span(log, "prepare"):
            sched_args = None
            bounds = None
            if d2 is not None:
                B = batch["labels"].shape[0]
                mb_of = microbatch_assignment(B, d2.n_microbatches)
                sched_args = gates_from_schedule(sched, mb_of)
                if use_kernel:
                    bounds = live_slice_bounds(sched, mb_of)
            step_fn = get_step(bounds)
        with span(log, "dispatch"):
            params, opt_state, metrics = step_fn(params, opt_state, batch,
                                                 sched_args)
        with span(log, "wait"):
            jax.block_until_ready(metrics["loss"])
        _read_back(log, metrics)
    return params, opt_state, log


# ----------------------------------------------------------- distributed path
def _zero_state_specs(opt_state_shapes, plan, axis_name: str):
    """PartitionSpec tree for the optimizer state: params-shaped subtrees
    (same treedef as the plan — moments, EMA copies, anything updated
    leafwise from grad/param shards) get the plan's partition specs,
    everything else (step counters, fallback scalars) stays replicated.
    Callers must hand such subtrees over in the plan's shard layout
    (``sharding.sync.zero_reshard``; zero-init moments are
    layout-invariant)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.sync import SyncSpec, zero_param_specs

    pspecs = zero_param_specs(plan, axis_name)
    plan_def = jax.tree.structure(plan,
                                  is_leaf=lambda x: isinstance(x, SyncSpec))
    return {
        k: pspecs if jax.tree.structure(v) == plan_def
        else jax.tree.map(lambda _: P(), v)
        for k, v in opt_state_shapes.items()
    }


def _grad_anomaly(grads, thresh):
    """Per-subnet-block gradient anomaly detection (the pre-sync guard).

    Computes one squared grad norm per parameter block — each cycle of
    every scan-stacked ``cycles`` entry, each ``rest`` block, and each
    loss-path subtree — and flags blocks that are non-finite or whose
    norm exceeds ``thresh`` (pass +inf to disable the norm test).
    Returns (bad_any, n_bad_blocks): a scalar bool and a float count."""
    def block_sq(tree, stacked):
        leaves = jax.tree.leaves(tree)
        if stacked:
            tot = sum(jnp.sum(l.astype(jnp.float32) ** 2,
                              axis=tuple(range(1, l.ndim)))
                      for l in leaves)
        else:
            tot = sum(jnp.sum(l.astype(jnp.float32) ** 2) for l in leaves)
        return jnp.atleast_1d(tot)

    sqs = []
    for key, sub in grads.items():
        if key == "cycles":
            sqs.extend(block_sq(blk, True) for blk in sub)
        elif key == "rest":
            sqs.extend(block_sq(blk, False) for blk in sub)
        else:
            sqs.append(block_sq(sub, False))
    sq = jnp.concatenate(sqs)
    bad = ~jnp.isfinite(sq) | (jnp.sqrt(sq) > thresh)
    return bad.any(), bad.sum().astype(jnp.float32)


def _tree_where(cond, a, b):
    """Leafwise select: ``a`` where the scalar ``cond`` holds, else ``b``."""
    return jax.tree.map(lambda x, y: jnp.where(cond, x, y), a, b)


def _mesh_parallel(mesh):
    """The ``ParallelConfig`` of a caller that passes none: the mesh's own
    (data, stage, tensor) axis sizes, every option at its default."""
    from repro.launch.parallel import MeshSpec, ParallelConfig

    shape = dict(mesh.shape) if mesh is not None else {}
    return ParallelConfig(mesh=MeshSpec(
        *(int(shape.get(name, 1)) for name in MeshSpec().axis_names)))


def make_distributed_train_step(cfg: ModelConfig, opt: Optimizer, mesh,
                                sync_plan, *, parallel=None,
                                clip: float = 1.0, live_bounds=None,
                                params=None, n_replicas=None,
                                residency_recorder=None,
                                stage_assignment=None,
                                pipeline_recorder=None):
    """shard_map multi-axis gated train step (paper's *distributed* D2FT).

    ``parallel`` (a ``launch.parallel.ParallelConfig``) is the one knob
    bundle: mesh spec (data/stage/tensor sizes), sync_mode, streamed,
    opt_chunk, guard, use_kernel and pipeline microbatches. ``None`` takes
    the mesh's axis sizes with every option at its default.

    Axes beyond data compose around the same bodies:

    * ``stage > 1`` — GPipe microbatch pipeline (``train.pipeline``): each
      stage device runs its ``stage_assignment`` layer range (a
      ``core.assignment.StageAssignment``, required) on
      ``parallel.microbatches`` microbatches with ppermute handoffs; the
      per-stage partial loss/metrics/grads are psum-completed over the
      stage axis before any data-axis sync, so masked/ZeRO-1/ZeRO-3 see
      exactly the replicated full-batch grads they always did.
      ``pipeline_recorder`` (a ``train.pipeline.PipelineRecorder``) counts
      rounds/sends at trace time for the bubble cross-check.
    * ``tensor > 1`` — Megatron sharding of attention heads / FFN columns
      inside each block (``models.transformer`` ``tp=``); the TP-sharded
      leaf grads are disjoint slices, psum-reassembled over the tensor
      axis (``sharding.sync.apply_tensor_grad_sync``) so everything
      downstream again sees replicated full grads.

    Each device runs the masked/kernel gated path on its shard of the batch
    — its multiple-knapsack-assigned micro-batches after
    ``core.assignment.device_sample_order`` reordering — then gradients are
    combined per ``sync_mode``:

    * ``"masked"`` — ``sharding.sync.apply_grad_sync``: only parameters
      with a live backward somewhere in the schedule enter the pmean;
      p_o/p_s-only subnets contribute identically-zero grads on every
      device and their psum is elided. Params and optimizer state stay
      replicated.
    * ``"zero"`` — ZeRO-1: live runs are reduce-scattered, each device
      updates only its owned param shard with its shard of the optimizer
      moments (per-device moment memory ~1/n_devices), then updated params
      are all-gathered under the plan's gather mask. Requires a zero-mode
      ``sync_plan`` (``grad_sync_plan(mode="zero", n_shards=...)``) and
      ``params`` (a template for the optimizer-state structure); the
      returned step expects/returns the optimizer state in the plan's
      shard layout (``sharding.sync.zero_reshard`` converts).
    * ``"zero3"`` — fully sharded params: the step expects AND returns the
      params in the plan's shard layout (same layout the moments use), so
      no device holds a full replica between steps. The step body
      materializes full views via a *schedule-masked* all-gather
      (``sharding.sync.zero3_materialize``) — runs that are p_s on every
      micro-batch are never gathered, a zeros view being exact — takes
      grads against the views, reduce-scatters live runs straight onto
      the owning shards (ZeRO-2), and updates shard-resident. Requires a
      ``grad_sync_plan(mode="zero3", ...)`` plan and ``params``.
      ``streamed=True`` swaps in the per-residency-unit streamed schedule
      (``sharding.sync.zero3_stream_materialize``): one set of all-gathers
      per unit that the XLA scheduler can prefetch against the previous
      unit's compute, and each unit's reduce-scatter fused into its
      backward release point via ``custom_vjp`` instead of a serialized
      post-backward pass. Same collectives on the same operands — the
      ``dist_zero3_streamed`` parity arm pins value equality and the
      8-device suite pins wire-byte equality. ``opt_chunk=n`` streams the
      shard-resident update ``n`` elements at a time
      (``optim.optimizers.chunked``, equal to a few ulp);
      ``residency_recorder`` (a ``sharding.sync.ResidencyRecorder``)
      counts the streamed schedule's per-unit gather bytes at trace time
      for the ``check_zero3_residency`` cross-check. Both options require
      ``sync_mode="zero3"``, and ``streamed`` is incompatible with
      ``guard`` (the guard must zero anomalous local grads *before* any
      collective, which the vjp-embedded scatters make impossible).

    * ``"local"`` — the lo-fi communication-free mode: params and
      optimizer state arrive *per-replica stacked* ([n_replicas, ...]
      leaves, see ``sharding.sync.stack_replicas``) and every replica
      updates its own copy from its own batch shard with ZERO gradient
      sync (vmap over the replica axis — no collectives, no mesh
      needed). The caller merges replicas every K steps with
      ``sharding.sync.lofi_merge``. ``sync_plan``/``mesh`` are ignored;
      ``n_replicas`` sets the stack size (defaults to the mesh's data
      axis).

    guard=True arms the pre-sync non-finite-grad guard: the step takes
    two extra arguments ``(fault, thresh)`` — ``fault`` a [n_devices]
    float32 multiplier applied to each device's local grads (the fault
    injection seam, all-ones when healthy) and ``thresh`` a scalar
    per-block grad-norm anomaly threshold (+inf disables). After the
    backward, each device runs per-subnet-block anomaly detection
    (``_grad_anomaly``) on its LOCAL grads; anomalous devices zero their
    contribution *before* any collective (one bad replica cannot poison
    the pmean), a one-scalar psum counts bad devices, and if any device
    flagged, the whole update is skipped — params and optimizer state
    pass through unchanged. Metrics gain ``skipped`` (0/1),
    ``bad_devices`` and ``bad_blocks``. In local mode the guard is
    per-replica: only the anomalous replica skips its own update.

    sync_plan: per-leaf SyncSpec tree from ``sharding.sync.grad_sync_plan``.
    live_bounds: static per-device (live_fwd, live_bwd) compaction bounds
    (``core.assignment.distributed_live_bounds``) — each device dispatches
    only its local shard's live slices through the gated kernels.
    Returns jitted step(params, opt_state, batch, gates[, fault, thresh])
    with params replicated, batch sharded on the leading axis and gates
    [L, B, G] sharded on the sample axis.
    """
    from jax.sharding import PartitionSpec as P

    from repro.optim.optimizers import chunked
    from repro.sharding.sync import (apply_grad_sync, apply_tensor_grad_sync,
                                     apply_zero_gather, apply_zero_scatter,
                                     zero3_materialize,
                                     zero3_stream_materialize, zero_norm_sq,
                                     zero_param_specs, zero_shard_params)
    from repro.train.pipeline import pipeline_loss

    parallel = parallel or _mesh_parallel(mesh)
    axis_name = parallel.data_axis
    sync_mode, guard = parallel.sync_mode, parallel.guard
    streamed, opt_chunk = parallel.streamed, parallel.opt_chunk
    use_kernel = parallel.use_kernel
    S, T = parallel.mesh.stage, parallel.mesh.tensor
    tp = (parallel.tensor_axis, T) if T > 1 else None
    parallel.validate_model(cfg)
    if (S > 1 or T > 1) and mesh is not None:
        parallel.validate_mesh(mesh)
    if S > 1:
        assert stage_assignment is not None, \
            "stage > 1 needs a core.assignment.StageAssignment " \
            "(plan_stage_assignment on the current schedule)"
        assert stage_assignment.n_stages == S, \
            (stage_assignment.n_stages, S)
    upd_opt = chunked(opt, opt_chunk) if opt_chunk else opt

    def grads_of(params_full, batch, gates):
        """value_and_grad of the local loss. Under stage/tensor axes the
        per-device partials are psum-completed HERE, so every body below
        sees full-batch replicated (loss, metrics, grads) exactly as on a
        pure data mesh — masked/ZeRO sync tails compose unchanged."""
        if S > 1:
            assert batch.get("features") is None, \
                "the pipeline path is tokens-only"

            def fn(p):
                return pipeline_loss(
                    p, cfg, batch["tokens"], batch["labels"], gates,
                    boundaries=stage_assignment.boundaries,
                    n_microbatches=parallel.microbatches,
                    stage_axis=parallel.stage_axis, tp=tp,
                    recorder=pipeline_recorder)
        else:
            def fn(p):
                return lm_loss(p, cfg, batch.get("tokens"), batch["labels"],
                               features=batch.get("features"), gates=gates,
                               use_kernel=use_kernel,
                               live_bounds=live_bounds, tp=tp)
        (loss, metrics), grads = jax.value_and_grad(
            fn, has_aux=True)(params_full)
        if S > 1:
            # stage partials (each stage's own layers, last stage's head)
            # sum to the full-batch values; grad supports are disjoint per
            # layer, so the psum is a reassembly, not an average
            stage_ax = parallel.stage_axis
            loss = jax.lax.psum(loss, stage_ax)
            metrics = {k: jax.lax.psum(v, stage_ax)
                       for k, v in metrics.items()}
            grads = jax.tree.map(lambda g: jax.lax.psum(g, stage_ax), grads)
        if tp is not None:
            grads = apply_tensor_grad_sync(grads, tp[0])
        return (loss, metrics), grads

    def guard_local(grads, fault, thresh):
        """Fault-inject, then neutralize anomalous local grads BEFORE any
        collective; returns (clean grads, this-device bad flag, count)."""
        f = fault[0]          # this device's scalar multiplier
        grads = jax.tree.map(lambda g: g * f.astype(g.dtype), grads)
        bad, n_bad_blocks = _grad_anomaly(grads, thresh)
        grads = jax.tree.map(lambda g: jnp.where(bad, jnp.zeros_like(g), g),
                             grads)
        return grads, bad, n_bad_blocks

    def finish_guarded(old_params, old_state, new_params, new_state,
                       metrics, bad, n_bad_blocks):
        """Skip-step: if ANY device flagged, every device keeps its old
        params/state (the psum makes the decision replicated)."""
        n_bad = jax.lax.psum(bad.astype(jnp.float32), axis_name)
        skip = n_bad > 0
        return (_tree_where(skip, old_params, new_params),
                _tree_where(skip, old_state, new_state),
                dict(metrics, skipped=skip.astype(jnp.float32),
                     bad_devices=n_bad,
                     bad_blocks=jax.lax.psum(n_bad_blocks, axis_name)))

    def clip_shards(gsync):
        """Global-norm clip of grads in the plan's shard layout: zero-leaf
        shards tile their tensors disjointly across devices, so one scalar
        psum completes their square sum; fallback leaves are replicated
        and added locally."""
        with jax.named_scope("clip"):
            shard_sq, full_sq = zero_norm_sq(gsync, sync_plan)
            gnorm = jnp.sqrt(jax.lax.psum(shard_sq, axis_name) + full_sq)
            scale = clip_scale(gnorm, clip)
            return jax.tree.map(lambda g: g * scale, gsync), gnorm

    def local_step(params, opt_state, batch, gates, fault=None, thresh=None):
        (loss, metrics), grads = grads_of(params, batch, gates)
        if guard:
            grads, bad, n_bad_blocks = guard_local(grads, fault, thresh)
        grads = apply_grad_sync(grads, sync_plan, axis_name)
        loss = jax.lax.pmean(loss, axis_name)
        metrics = {k: jax.lax.pmean(v, axis_name) for k, v in metrics.items()}
        # post-sync grads are the global mean on every device, so the norm,
        # clip and optimizer update stay replicated without more collectives
        with jax.named_scope("clip"):
            grads, gnorm = clip_by_global_norm(grads, clip)
        with jax.named_scope("optimizer"):
            new_params, new_state = opt.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        if guard:
            return finish_guarded(params, opt_state, new_params, new_state,
                                  metrics, bad, n_bad_blocks)
        return new_params, new_state, metrics

    def local_step_zero(params, opt_state, batch, gates, fault=None,
                        thresh=None):
        (loss, metrics), grads = grads_of(params, batch, gates)
        if guard:
            grads, bad, n_bad_blocks = guard_local(grads, fault, thresh)
        # mixed tree: reduced shards at zero leaves (live runs
        # reduce-scattered, dead runs locally sliced), masked pmean
        # elsewhere
        gsync = apply_zero_scatter(grads, sync_plan, axis_name)
        loss = jax.lax.pmean(loss, axis_name)
        metrics = {k: jax.lax.pmean(v, axis_name) for k, v in metrics.items()}
        gsync, gnorm = clip_shards(gsync)
        # each device updates only its owned shard (moments arrive sharded
        # through in_specs); the schedule-masked all-gather re-replicates
        # exactly the runs whose params can have changed
        with jax.named_scope("optimizer"):
            pshard = zero_shard_params(params, sync_plan, axis_name)
            new_shard, new_state = opt.update(gsync, opt_state, pshard)
        new_params = apply_zero_gather(new_shard, params, sync_plan,
                                       axis_name)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        if guard:
            return finish_guarded(params, opt_state, new_params, new_state,
                                  metrics, bad, n_bad_blocks)
        return new_params, new_state, metrics

    def local_step_zero3(params, opt_state, batch, gates, fault=None,
                         thresh=None):
        # params arrive as owned shards (the plan's layout); full views
        # exist only between here and the update — the ZeRO-3 residency
        # window. Runs the schedule proves forward-dead are never gathered
        # (zeros view, exact: their every consumer is gated off).
        full = zero3_materialize(params, sync_plan, axis_name)
        (loss, metrics), grads = grads_of(full, batch, gates)
        if guard:
            grads, bad, n_bad_blocks = guard_local(grads, fault, thresh)
        gsync = apply_zero_scatter(grads, sync_plan, axis_name)
        loss = jax.lax.pmean(loss, axis_name)
        metrics = {k: jax.lax.pmean(v, axis_name) for k, v in metrics.items()}
        gsync, gnorm = clip_shards(gsync)
        # grads and params are both shard-resident at zero leaves: the
        # update never touches a full tensor and there is no post-update
        # gather — next step's materialization starts from the new shards.
        with jax.named_scope("optimizer"):
            new_params, new_state = upd_opt.update(gsync, opt_state, params)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        if guard:
            return finish_guarded(params, opt_state, new_params, new_state,
                                  metrics, bad, n_bad_blocks)
        return new_params, new_state, metrics

    def local_step_zero3_streamed(params, opt_state, batch, gates):
        # the streamed schedule: differentiate straight through the
        # per-unit materializer, whose custom_vjp scatters each unit's
        # grads onto the owning shards at that unit's backward release
        # point — grads arrive here already in shard layout, with the
        # gathers issued per unit so prefetch can hide them behind the
        # previous unit's compute.
        def fn(shards):
            full = zero3_stream_materialize(shards, sync_plan, axis_name,
                                            recorder=residency_recorder)
            return lm_loss(full, cfg, batch.get("tokens"), batch["labels"],
                           features=batch.get("features"), gates=gates,
                           use_kernel=use_kernel, live_bounds=live_bounds)

        (loss, metrics), gsync = jax.value_and_grad(
            fn, has_aux=True)(params)
        loss = jax.lax.pmean(loss, axis_name)
        metrics = {k: jax.lax.pmean(v, axis_name) for k, v in metrics.items()}
        gsync, gnorm = clip_shards(gsync)
        with jax.named_scope("optimizer"):
            new_params, new_state = upd_opt.update(gsync, opt_state, params)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return new_params, new_state, metrics

    if sync_mode == "local":
        # lo-fi: per-replica stacked state, zero collectives — a vmap over
        # the replica axis stands in for the mesh (each replica is a
        # device that lost its links but kept training).
        R = int(n_replicas) if n_replicas else mesh.shape[axis_name]

        def one_replica(params, opt_state, batch, gates, fault=None,
                        thresh=None):
            (loss, metrics), grads = grads_of(params, batch, gates)
            if guard:
                grads = jax.tree.map(
                    lambda g: g * fault.astype(g.dtype), grads)
                bad, n_bad_blocks = _grad_anomaly(grads, thresh)
                grads = jax.tree.map(
                    lambda g: jnp.where(bad, jnp.zeros_like(g), g), grads)
            with jax.named_scope("clip"):
                grads, gnorm = clip_by_global_norm(grads, clip)
            with jax.named_scope("optimizer"):
                new_params, new_state = opt.update(grads, opt_state, params)
            metrics = dict(metrics, loss=loss, grad_norm=gnorm)
            if guard:
                # per-replica skip: only the anomalous replica holds back
                new_params = _tree_where(bad, params, new_params)
                new_state = _tree_where(bad, opt_state, new_state)
                metrics = dict(metrics, skipped=bad.astype(jnp.float32),
                               bad_blocks=n_bad_blocks)
            return new_params, new_state, metrics

        in_axes = (0, 0, 0, (1, 1)) + ((0, None) if guard else ())
        vstep = jax.vmap(one_replica, in_axes=in_axes)

        def step(params_stack, opt_stack, batch, gates, *rest):
            batch = jax.tree.map(
                lambda a: a.reshape((R, a.shape[0] // R) + a.shape[1:]),
                batch)
            gates = tuple(
                g.reshape(g.shape[0], R, g.shape[1] // R, g.shape[2])
                for g in gates)
            new_p, new_s, metrics = vstep(params_stack, opt_stack, batch,
                                          gates, *rest)
            metrics = {
                k: v.sum() if k in ("skipped", "bad_blocks") else v.mean()
                for k, v in metrics.items()}
            if guard:
                metrics["bad_devices"] = metrics["skipped"]
            return new_p, new_s, metrics

        return jax.jit(step)

    # check_vma=False: skipped (dead-subnet) grad leaves are device-invariant
    # — identically zero everywhere — but shard_map's replication tracker
    # cannot prove that through an elided psum.
    param_specs = P()
    if sync_mode == "masked":
        state_specs = P()
        body = local_step
    elif sync_mode in ("zero", "zero3"):
        assert params is not None, f"{sync_mode} mode needs a params template"
        state_shapes = jax.eval_shape(opt.init, params)
        state_specs = _zero_state_specs(state_shapes, sync_plan, axis_name)
        if sync_mode == "zero":
            body = local_step_zero
        else:
            param_specs = zero_param_specs(sync_plan, axis_name)
            body = local_step_zero3_streamed if streamed \
                else local_step_zero3
    else:
        raise ValueError(f"unknown sync_mode {sync_mode!r}")
    in_specs = (param_specs, state_specs, P(axis_name),
                (P(None, axis_name), P(None, axis_name)))
    if guard:
        in_specs = in_specs + (P(axis_name), P())
    step = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(param_specs, state_specs, P()),
        check_vma=False)
    return jax.jit(step)


def _reshard_opt_state(opt_state, old_plan, new_plan):
    """Re-layout params-shaped state subtrees between zero-plan shard
    layouts; either plan may be None for canonical order (host-side;
    identity for masked plans and non-params-shaped state)."""
    from repro.sharding.sync import SyncSpec, zero_reshard
    ref = new_plan if new_plan is not None else old_plan
    if ref is None:
        return opt_state
    plan_def = jax.tree.structure(
        ref, is_leaf=lambda x: isinstance(x, SyncSpec))
    return {
        k: zero_reshard(v, old_plan, new_plan)
        if jax.tree.structure(v) == plan_def else v
        for k, v in opt_state.items()
    }


def finetune_distributed(params, cfg: ModelConfig, d2: D2FTConfig,
                         opt: Optimizer, batches: Iterable, *, steps: int,
                         mesh, parallel=None, clip: float = 1.0,
                         refresh_every: Optional[int] = None,
                         log: Optional[TrainLog] = None) -> tuple:
    """Distributed D2FT fine-tuning: plan, balance micro-batches over the
    mesh's data axis with the multiple-knapsack assigner, then drive the
    shard_map gated step. ``refresh_every=k`` re-plans the schedule every k
    steps from fresh scores — and re-runs the knapsack assigner, rebuilds
    the sync plan and (zero mode) reshards the optimizer moments, since an
    assignment balanced for a stale schedule un-balances the new one. The
    latest rebalance/sync reports land in ``log.extras`` and every refresh
    is appended to ``log.extras["refreshes"]``.

    ``parallel`` (``launch.parallel.ParallelConfig``) selects sync mode and
    extra mesh axes; ``None`` takes the mesh's axis sizes with every
    option at its default. With ``parallel.mesh.stage > 1`` every refresh also
    re-runs the schedule-aware stage assigner
    (``core.assignment.plan_stage_assignment``) — pipeline stages are
    packed by the NEW schedule's live FLOP cost and the jitted step is
    rebuilt around the new boundaries — and the per-refresh record gains a
    ``"stages"`` report (boundaries, loads, makespan vs layer-count
    packing, analytic bubble fraction).

    sync_mode="zero" runs the ZeRO-1 sync (sliced reduce-scatter +
    schedule-masked all-gather, optimizer moments sharded ~1/n_devices);
    the gather elision engages only for ``opt.elidable`` optimizers and
    groups that have never been backward-live since their moments were
    zero (tracked here as ``ever_live``). sync_mode="zero3" additionally
    shards the params themselves: between steps every device holds only
    its owned shards, full views are materialized inside the step under
    the schedule's *forward* mask, and the per-refresh record gains the
    ``zero3_params`` residency report. ``streamed=True`` /
    ``opt_chunk=n`` select the per-unit streamed schedule and the
    chunk-streamed update (zero3 only; see
    ``make_distributed_train_step``) — numerically identical, so replan,
    reshard and checkpointing via ``zero_reshard`` are unchanged. The returned params and opt_state
    are in canonical element order regardless of sync_mode (the in-loop
    shard layout is converted back on return), so they checkpoint/resume
    on any path."""
    from repro.core.assignment import (device_sample_order,
                                       distributed_live_bounds,
                                       plan_device_assignment,
                                       plan_stage_assignment)
    from repro.core.schedule import op_counts
    from repro.sharding.sync import (backward_live_groups, grad_sync_plan,
                                     sync_byte_report, zero3_param_byte_report,
                                     zero_reshard)
    from repro.train.pipeline import analytic_bubble_fraction

    parallel = parallel or _mesh_parallel(mesh)
    sync_mode, use_kernel = parallel.sync_mode, parallel.use_kernel
    S = parallel.mesh.stage
    parallel.validate_model(cfg)

    log = log or TrainLog()
    opt_state = opt.init(params)
    ndev = mesh.shape["data"]
    assert sync_mode in ("masked", "zero", "zero3"), sync_mode
    sched = assignment = stage_assign = sync_plan = step_fn = None
    ever_live = None

    def replan(batch):
        from repro.data.synthetic import split_microbatches
        nonlocal ever_live
        mbs = split_microbatches(batch, d2.n_microbatches)
        sched = plan_from_scores(
            cfg, d2, params, mbs,
            lambda p, mb: lm_loss(p, cfg, mb.get("tokens"), mb["labels"],
                                  features=mb.get("features"))[0])
        assignment, report = plan_device_assignment(sched, ndev)
        if sync_mode == "zero":
            prior = ever_live
            if ever_live is None:
                ever_live = np.zeros((cfg.n_layers, sched.n_groups), bool)
            sync_plan = grad_sync_plan(
                params, cfg, sched, mode="zero", n_shards=ndev,
                ever_live=prior, elide_gather=opt.elidable)
            ever_live = ever_live | backward_live_groups(sched)
        elif sync_mode == "zero3":
            sync_plan = grad_sync_plan(params, cfg, sched, mode="zero3",
                                       n_shards=ndev)
        else:
            sync_plan = grad_sync_plan(params, cfg, sched)
        record = {
            "rebalance": report,
            "sync": sync_byte_report(sync_plan, params, n_shards=ndev),
            "op_counts": op_counts(sched),
            "device_of": [int(x) for x in assignment.device_of],
        }
        if sync_mode == "zero3":
            record["zero3_params"] = zero3_param_byte_report(
                sync_plan, params, ndev)
        stage_assign = None
        if S > 1:
            # re-pack pipeline stages for the NEW schedule's live costs —
            # a balanced packing for a stale schedule un-balances this one
            stage_assign, stage_rep = plan_stage_assignment(sched, S)
            stage_rep["bubble_fraction"] = analytic_bubble_fraction(
                stage_assign.loads, parallel.microbatches)
            record["stages"] = stage_rep
        return sched, assignment, stage_assign, sync_plan, record

    for i, batch in each_step(log, batches, steps):
        if sched is None or (refresh_every and i % refresh_every == 0
                             and i > 0):
            with span(log, "plan"):
                old_plan = sync_plan
                if sync_mode == "zero3" and old_plan is not None:
                    # back to canonical before scoring: the scoring pass reads
                    # param values whose group structure the shard layout
                    # permutes
                    params = zero_reshard(params, old_plan, None)
                sched, assignment, stage_assign, sync_plan, record = \
                    replan(batch)
                if sync_mode == "zero":
                    # canonical -> shard layout at the first plan (zeros are
                    # layout-invariant, but a params-shaped state initialized
                    # from values, e.g. an EMA copy, is not), then between
                    # layouts on refresh
                    opt_state = _reshard_opt_state(opt_state, old_plan,
                                                   sync_plan)
                elif sync_mode == "zero3":
                    params = zero_reshard(params, None, sync_plan)
                    opt_state = _reshard_opt_state(opt_state, old_plan,
                                                   sync_plan)
                    log.extras["zero3_params"] = record["zero3_params"]
                record["step"] = i
                log.extras["rebalance"] = record["rebalance"]
                log.extras["sync"] = record["sync"]
                if "stages" in record:
                    log.extras["stages"] = record["stages"]
                log.extras.setdefault("refreshes", []).append(record)
            log.counters["replans"] += 1
            step_fn = None
        with span(log, "prepare"):
            B = batch["labels"].shape[0]
            mb_of = microbatch_assignment(B, d2.n_microbatches)
            perm = device_sample_order(assignment, mb_of)
            batch = jax.tree.map(lambda a: a[perm], batch)
            gates = gates_from_schedule(sched, mb_of[perm])
            if step_fn is None:
                bounds = distributed_live_bounds(sched, mb_of, assignment) \
                    if use_kernel else None
                step_fn = make_distributed_train_step(
                    cfg, opt, mesh, sync_plan, clip=clip,
                    live_bounds=bounds, params=params, parallel=parallel,
                    stage_assignment=stage_assign)
                log.counters["step_builds"] += 1
        with span(log, "dispatch"):
            params, opt_state, metrics = step_fn(params, opt_state, batch,
                                                 gates)
        with span(log, "wait"):
            jax.block_until_ready(metrics["loss"])
        _read_back(log, metrics)
    if sync_mode in ("zero", "zero3") and sync_plan is not None:
        # hand back canonical element order: the shard layout is an
        # internal representation a checkpoint or another path must not see
        opt_state = _reshard_opt_state(opt_state, sync_plan, None)
        if sync_mode == "zero3":
            params = zero_reshard(params, sync_plan, None)
    return params, opt_state, log


# ------------------------------------------------------------------ ViT path
def make_vit_step(cfg: ViTConfig, opt: Optimizer, use_gates: bool,
                  clip: float = 1.0, use_kernel: bool = False,
                  live_bounds=None):
    """live_bounds: static (live_fwd, live_bwd) compaction bounds baked
    into the jitted step (see make_train_step)."""
    def step(params, opt_state, images, labels, gates=None):
        def loss_of(p):
            return vit_loss(p, images, labels, cfg,
                            gates=gates if use_gates else None,
                            use_kernel=use_kernel,
                            live_bounds=live_bounds if use_gates else None)
        (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
        with jax.named_scope("clip"):
            grads, gnorm = clip_by_global_norm(grads, clip)
        with jax.named_scope("optimizer"):
            params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, dict(metrics, loss=loss, grad_norm=gnorm)
    return step


def finetune_vit(params, cfg: ViTConfig, opt: Optimizer, batches,
                 steps: int, schedule_fn: Optional[Callable] = None,
                 n_microbatches: int = 5, use_kernel: bool = False,
                 log: Optional[TrainLog] = None):
    """schedule_fn(step_idx, params, images, labels) -> Schedule or None.

    The schedule is rematerialized whenever schedule_fn returns a new one
    (supports dynamic-pruning baselines that refresh every k iterations).
    use_kernel routes attention through the Pallas gated flash kernel so
    the Schedule's (g_f, g_b) gates drive the gate-aware backward kernels.
    """
    log = log or TrainLog()
    opt_state = opt.init(params)
    use_gates = schedule_fn is not None
    # jitted steps cached per compaction bound pair — schedule refreshes
    # that change the live counts re-jit, identical counts reuse the trace
    step_fns = {}

    def get_step(bounds):
        if bounds not in step_fns:
            step_fns[bounds] = jax.jit(make_vit_step(
                cfg, opt, use_gates, use_kernel=use_kernel,
                live_bounds=bounds))
            log.counters["step_builds"] += 1
        return step_fns[bounds]

    step_fn = get_step(None)
    sched = None
    for i, (images, labels) in each_step(log, batches, steps):
        gates = None
        if schedule_fn is not None:
            with span(log, "plan") as plan:
                new = schedule_fn(i, params, images, labels)
                plan.keep = new is not None
            if new is not None:
                sched = new
                log.counters["replans"] += 1
            with span(log, "prepare"):
                mb_of = microbatch_assignment(images.shape[0],
                                              n_microbatches)
                gates = gates_from_schedule(sched, mb_of)
                if use_kernel:
                    step_fn = get_step(live_slice_bounds(sched, mb_of))
        with span(log, "h2d"):
            x, y = jnp.asarray(images), jnp.asarray(labels)
        with span(log, "dispatch"):
            params, opt_state, metrics = step_fn(params, opt_state, x, y,
                                                 gates)
        with span(log, "wait"):
            jax.block_until_ready(metrics["loss"])
        _read_back(log, metrics)
    return params, opt_state, log


def eval_vit(params, cfg: ViTConfig, batches, max_batches: int = 10) -> float:
    from repro.models.vit import vit_forward
    fwd = jax.jit(lambda p, x: vit_forward(p, x, cfg))
    correct = total = 0
    for i, (images, labels) in enumerate(batches):
        if i >= max_batches:
            break
        pred = np.asarray(jnp.argmax(fwd(params, jnp.asarray(images)), -1))
        correct += int((pred == labels).sum())
        total += len(labels)
    return correct / max(total, 1)
