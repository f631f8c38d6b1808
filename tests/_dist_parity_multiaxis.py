"""Subprocess body for the 8-device MULTI-AXIS parity test.

Same contract as tests/_dist_parity.py (fresh interpreter, forced host
device count, one PARITY_OK line on success) but over (data, stage,
tensor) meshes: every multi-axis arm must reproduce the single-device
masked gated reference trajectory to <= 1e-6 over 3 SGD steps.

Arms:
* (data=4, tensor=2)           — Megatron TP heads/columns, masked sync
* (data=4, tensor=2) + ZeRO-3  — TP composed with fully-sharded params
* (data=2, stage=2)            — GPipe pipeline, live-cost stage packing
* (data=2, stage=2, tensor=2)  — all three axes at once (8 devices)
* (data=4, tensor=2) + LoRA    — adapters-only grads through the TP path
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.assignment import plan_stage_assignment
from repro.core.lora import init_lora, merge_lora
from repro.core.schedule import P_F, P_O, P_S, Schedule, gates_from_schedule
from repro.data.synthetic import lm_batches, microbatch_assignment
from repro.launch.parallel import MeshSpec, ParallelConfig
from repro.models.transformer import init_model, lm_loss
from repro.optim.optimizers import sgd
from repro.sharding.sync import (SyncSpec, apply_grad_sync, grad_sync_plan,
                                 zero_reshard)
from repro.train.loop import make_distributed_train_step, make_train_step
from repro.train.pipeline import PipelineRecorder, analytic_bubble_fraction


def max_leaf_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.abs(x - y).max()), a, b)))


assert len(jax.devices()) == 8, jax.devices()

cfg = ModelConfig(name="multiaxis", arch_type="dense", n_layers=4,
                  d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                  vocab_size=256)
G, L, N, B, S = 4, 4, 16, 32, 16
STEPS, TOL = 3, 1e-6
rng = np.random.default_rng(0)
table = rng.choice([P_F, P_O, P_S], size=(L * G, N),
                   p=[.4, .3, .3]).astype(np.int8)
table[0:G] = np.where(table[0:G] == P_F, P_O, table[0:G])   # dead layer
table[2 * G:3 * G] = P_F                                    # live layer
sched = Schedule(table, L, G)

params = init_model(jax.random.PRNGKey(0), cfg)
opt = sgd(1e-2)
batch = next(lm_batches(0, cfg.vocab_size, B, S, 1))
mb_of = microbatch_assignment(B, N)
gates = gates_from_schedule(sched, mb_of)
plan = grad_sync_plan(params, cfg, sched)


def run(step_fn, p0):
    p, s = p0, opt.init(p0)
    for _ in range(STEPS):
        p, s, m = step_fn(p, s, batch, gates)
    return p, m


# ---- single-device masked reference --------------------------------------
ref_step = jax.jit(make_train_step(cfg, opt, use_gates=True))
p_ref, m_ref = run(ref_step, params)

# ---- (data=4, tensor=2): Megatron TP inside shard_map --------------------
spec_tp = MeshSpec(data=4, tensor=2)
mesh_tp = spec_tp.build()
step_tp = make_distributed_train_step(
    cfg, opt, mesh_tp, plan, parallel=ParallelConfig(mesh=spec_tp))
p_tp, m_tp = run(step_tp, params)
tp_diff = max_leaf_diff(p_tp, p_ref)
assert tp_diff <= TOL, f"(data=4,tensor=2) diverged: {tp_diff}"
assert abs(float(m_tp["loss"]) - float(m_ref["loss"])) <= 1e-5

# ---- (data=4, tensor=2) + ZeRO-3: TP composed with sharded params --------
plan3 = grad_sync_plan(params, cfg, sched, mode="zero3", n_shards=4)
step_z3 = make_distributed_train_step(
    cfg, opt, mesh_tp, plan3,
    parallel=ParallelConfig(mesh=spec_tp, sync_mode="zero3"), params=params)
p_z3, m_z3 = run(step_z3, zero_reshard(params, None, plan3))
z3_diff = max_leaf_diff(zero_reshard(p_z3, plan3, None), p_ref)
assert z3_diff <= TOL, f"(data=4,tensor=2)+zero3 diverged: {z3_diff}"

# ---- (data=2, stage=2): GPipe pipeline, schedule-balanced stages ---------
spec_pp = MeshSpec(data=2, stage=2)
mesh_pp = spec_pp.build()
stage_assign, stage_rep = plan_stage_assignment(sched, 2)
recorder = PipelineRecorder()
step_pp = make_distributed_train_step(
    cfg, opt, mesh_pp, plan,
    parallel=ParallelConfig(mesh=spec_pp, microbatches=4),
    stage_assignment=stage_assign, pipeline_recorder=recorder)
p_pp, m_pp = run(step_pp, params)
pp_diff = max_leaf_diff(p_pp, p_ref)
assert pp_diff <= TOL, f"(data=2,stage=2) diverged: {pp_diff}"
assert abs(float(m_pp["loss"]) - float(m_ref["loss"])) <= 1e-5
trace = recorder.report()
assert trace["trace_ok"], trace
bubble = analytic_bubble_fraction(stage_assign.loads, 4)
assert 0.0 <= bubble < 1.0, bubble

# ---- (data=2, stage=2, tensor=2): all three axes at once -----------------
spec_all = MeshSpec(data=2, stage=2, tensor=2)
mesh_all = spec_all.build()
step_all = make_distributed_train_step(
    cfg, opt, mesh_all, plan,
    parallel=ParallelConfig(mesh=spec_all, microbatches=4),
    stage_assignment=stage_assign)
p_all, m_all = run(step_all, params)
all_diff = max_leaf_diff(p_all, p_ref)
assert all_diff <= TOL, f"(data=2,stage=2,tensor=2) diverged: {all_diff}"

# ---- (data=4, tensor=2) + LoRA: adapters-only grads through TP -----------
from jax.sharding import PartitionSpec as P

lora0 = init_lora(jax.random.PRNGKey(3), params, rank=2)
lplan = jax.tree.map(lambda _: SyncSpec("all"), lora0)


def lora_ref_step(lora_p, st, batch, gates):
    def loss(lp):
        return lm_loss(merge_lora(params, lp, 1.0), cfg, batch["tokens"],
                       batch["labels"], gates=gates)[0]
    g = jax.grad(loss)(lora_p)
    return opt.update(g, st, lora_p)


def lora_tp_local(lora_p, st, batch, gates):
    def loss(lp):
        return lm_loss(merge_lora(params, lp, 1.0), cfg, batch["tokens"],
                       batch["labels"], gates=gates, tp=("tensor", 2))[0]
    g = jax.grad(loss)(lora_p)
    # adapter grads arrive through the device's merged-weight slice — the
    # tensor psum reassembles them before the usual data-axis sync
    g = jax.tree.map(lambda x: jax.lax.psum(x, "tensor"), g)
    g = apply_grad_sync(g, lplan, "data")
    return opt.update(g, st, lora_p)


lora_tp_step = jax.jit(jax.shard_map(
    lora_tp_local, mesh=mesh_tp,
    in_specs=(P(), P(), P("data"), (P(None, "data"), P(None, "data"))),
    out_specs=(P(), P()), check_vma=False))
jref = jax.jit(lora_ref_step)
p_lr, s_lr = lora0, opt.init(lora0)
p_lt, s_lt = lora0, opt.init(lora0)
for _ in range(STEPS):
    p_lr, s_lr = jref(p_lr, s_lr, batch, gates)
    p_lt, s_lt = lora_tp_step(p_lt, s_lt, batch, gates)
lora_diff = max_leaf_diff(p_lt, p_lr)
assert lora_diff <= TOL, f"(data=4,tensor=2)+LoRA diverged: {lora_diff}"

print(f"PARITY_OK tp={tp_diff:.2e} tp_zero3={z3_diff:.2e} "
      f"pipe={pp_diff:.2e} all3={all_diff:.2e} lora_tp={lora_diff:.2e} "
      f"boundaries={stage_rep['boundaries']} "
      f"makespan_ratio={stage_rep['makespan_ratio']:.3f} "
      f"bubble={bubble:.3f} rounds={trace['n_rounds']} "
      f"sends={trace['n_sends']}")
