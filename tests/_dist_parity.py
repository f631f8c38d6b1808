"""Subprocess body for the 8-host-device distributed parity test.

Run by tests/test_distributed.py with a fresh interpreter so the forced
host-device count does not disturb the rest of the suite (conftest pins it
to ONE CPU device; jax locks the count at first init). Prints a single
machine-readable PARITY_OK line on success; any assertion or crash fails
the calling test via the exit code.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.assignment import (device_sample_order,
                                   distributed_live_bounds,
                                   plan_device_assignment)
from repro.core.schedule import (P_F, P_O, P_S, Schedule,
                                 gates_from_schedule, live_slice_bounds)
from repro.data.synthetic import lm_batches, microbatch_assignment
from repro.launch.diststep import measure_distributed_step
from repro.launch.hlo import collective_bytes
from repro.launch.mesh import make_data_mesh
from repro.launch.parallel import MeshSpec, ParallelConfig
from repro.models.transformer import init_model
from repro.optim.optimizers import sgd
from repro.train.loop import make_distributed_train_step, make_train_step


def max_leaf_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.abs(x - y).max()), a, b)))


assert len(jax.devices()) == 8, jax.devices()

cfg = ModelConfig(name="parity", arch_type="dense", n_layers=4, d_model=64,
                  n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256)
G, L, N, B, S, K = 4, 4, 16, 32, 16, 8
rng = np.random.default_rng(0)
# iid mix stresses the sliced/stacked plan paths; then force one fully-dead
# layer (all p_o/p_s -> psum elided) and one fully-live layer (plain pmean)
table = rng.choice([P_F, P_O, P_S], size=(L * G, N),
                   p=[.4, .3, .3]).astype(np.int8)
table[0:G] = np.where(table[0:G] == P_F, P_O, table[0:G])
table[2 * G:3 * G] = P_F
sched = Schedule(table, L, G)

from repro.sharding.sync import (grad_sync_plan, sync_byte_report,
                                 zero3_param_byte_report, zero_reshard,
                                 zero_state_byte_report)

params = init_model(jax.random.PRNGKey(0), cfg)
opt = sgd(1e-2)       # linear in grads: parity is pure FP reordering noise
mesh = make_data_mesh(K)


def pc(**options):
    """The distributed step's options on the K-device data mesh."""
    return ParallelConfig(mesh=MeshSpec(data=K), **options)

batch = next(lm_batches(0, cfg.vocab_size, B, S, 1))
mb_of = microbatch_assignment(B, N)

assignment, report = plan_device_assignment(sched, K)
assert report["capacity_ok"] and len(set(report["counts"])) == 1, report
perm = device_sample_order(assignment, mb_of)
pbatch = jax.tree.map(lambda a: a[perm], batch)
gates = gates_from_schedule(sched, mb_of[perm])
plan = grad_sync_plan(params, cfg, sched)
assert sync_byte_report(plan, params)["fraction"] < 1.0

# ---- masked-path parity: 3 optimizer steps, distributed vs single device
step = make_distributed_train_step(cfg, opt, mesh, plan)
ref_step = jax.jit(make_train_step(cfg, opt, use_gates=True))
p_d, s_d = params, opt.init(params)
p_r, s_r = params, opt.init(params)
for _ in range(3):
    p_d, s_d, m_d = step(p_d, s_d, pbatch, gates)
    p_r, s_r, m_r = ref_step(p_r, s_r, pbatch, gates)
maxdiff = max_leaf_diff(p_d, p_r)
assert maxdiff <= 1e-6, f"masked-path params diverged: {maxdiff}"
assert abs(float(m_d["loss"]) - float(m_r["loss"])) <= 1e-5

# ---- kernel-path parity: compacted Pallas dispatch inside shard_map, with
# per-device live bounds vs the single-device step's global bounds
bounds = distributed_live_bounds(sched, mb_of, assignment)
gbounds = live_slice_bounds(sched, mb_of)
assert bounds[0] <= gbounds[0] and bounds[1] <= gbounds[1], (bounds, gbounds)
kstep = make_distributed_train_step(cfg, opt, mesh, plan,
                                    parallel=pc(use_kernel=True),
                                    live_bounds=bounds)
kref = jax.jit(make_train_step(cfg, opt, use_gates=True, use_kernel=True,
                               live_bounds=gbounds))
pk, sk, mk = kstep(params, opt.init(params), pbatch, gates)
pr, sr, mr = kref(params, opt.init(params), pbatch, gates)
kdiff = max_leaf_diff(pk, pr)
assert kdiff <= 1e-6, f"kernel-path params diverged: {kdiff}"

# ---- ZeRO-sync parity: sliced reduce-scatter + sharded moments + masked
# all-gather over 3 optimizer steps must match the same single-device
# reference as the masked path (p_r / s_r above)
zplan = grad_sync_plan(params, cfg, sched, mode="zero", n_shards=K,
                       elide_gather=opt.elidable)
zstep = make_distributed_train_step(cfg, opt, mesh, zplan,
                                    parallel=pc(sync_mode="zero"),
                                    params=params)
p_z, s_z = params, opt.init(params)
for _ in range(3):
    p_z, s_z, m_z = zstep(p_z, s_z, pbatch, gates)
zdiff = max_leaf_diff(p_z, p_r)
assert zdiff <= 1e-6, f"zero-sync params diverged: {zdiff}"
assert abs(float(m_z["loss"]) - float(m_r["loss"])) <= 1e-5
# the sharded momenta, restored to canonical layout, are the reference's
zmu = zero_reshard(s_z["mu"], zplan, None)
mudiff = max_leaf_diff(zmu, s_r["mu"])
assert mudiff <= 1e-6, f"sharded momenta diverged: {mudiff}"
# per-device moment memory is ~1/K of the replicated baseline
zmem = zero_state_byte_report(zplan, params, K)
assert zmem["fraction"] <= 1.0 / K + 0.05, zmem

# ---- ZeRO-3 parity: fully sharded params, schedule-masked forward gather.
# Run on the concentrated paper-mix (it HAS p_s-everywhere subnets), so the
# gather elision actually fires and the parity proves the zeros views are
# exact — then on the iid schedule used by the other paths above.
from repro.launch.diststep import paper_mix_schedule

mix_sched = paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), seed=0)
mix_assignment, _ = plan_device_assignment(mix_sched, K)
mix_perm = device_sample_order(mix_assignment, mb_of)
mix_batch = jax.tree.map(lambda a: a[mix_perm], batch)
mix_gates = gates_from_schedule(mix_sched, mb_of[mix_perm])
z3_elided = 0
for name, s, b, g in [("paper_mix", mix_sched, mix_batch, mix_gates),
                      ("iid", sched, pbatch, gates)]:
    z3plan = grad_sync_plan(params, cfg, s, mode="zero3", n_shards=K)
    z3rep = zero3_param_byte_report(z3plan, params, K)
    z3step = make_distributed_train_step(cfg, opt, mesh, z3plan,
                                         parallel=pc(sync_mode="zero3"),
                                         params=params)
    rstep = jax.jit(make_train_step(cfg, opt, use_gates=True))
    p_3, s_3 = zero_reshard(params, None, z3plan), opt.init(params)
    p_m, s_m = params, opt.init(params)
    for _ in range(3):
        p_3, s_3, m_3 = z3step(p_3, s_3, b, g)
        p_m, s_m, m_m = rstep(p_m, s_m, b, g)
    z3diff = max_leaf_diff(zero_reshard(p_3, z3plan, None), p_m)
    assert z3diff <= 1e-6, f"zero3 params diverged ({name}): {z3diff}"
    assert abs(float(m_3["loss"]) - float(m_m["loss"])) <= 1e-5, name
    mu3diff = max_leaf_diff(zero_reshard(s_3["mu"], z3plan, None), s_m["mu"])
    assert mu3diff <= 1e-6, f"zero3 momenta diverged ({name}): {mu3diff}"
    if name == "paper_mix":
        # acceptance: elision fires and the residency model is <= 0.5x
        assert z3rep["n_gather_elided"] > 0, z3rep
        assert z3rep["fraction"] <= 0.5, z3rep
        z3_elided = z3rep["n_gather_elided"]
        z3_residency = z3rep["fraction"]

# ---- streamed ZeRO-3: per-unit gathers with the reduce-scatter fused into
# each unit's backward release, plus the chunked shard-resident optimizer
# sweep. Must walk the SAME trajectory as unstreamed zero3 and the masked
# reference, carry identical collective bytes (overlap scheduling moves
# collectives against compute, never adds or removes wire), and its
# trace-time gather counter must agree with the residency model within 5%.
from repro.launch.hlo import compare_collective_bytes
from repro.sharding.sync import ResidencyRecorder, check_zero3_residency

z3plan = grad_sync_plan(params, cfg, mix_sched, mode="zero3", n_shards=K)
rec3 = ResidencyRecorder()
sstep = make_distributed_train_step(cfg, opt, mesh, z3plan,
                                    parallel=pc(sync_mode="zero3",
                                                streamed=True,
                                                opt_chunk=1024),
                                    params=params, residency_recorder=rec3)
ustep = make_distributed_train_step(cfg, opt, mesh, z3plan,
                                    parallel=pc(sync_mode="zero3"),
                                    params=params)
mref = jax.jit(make_train_step(cfg, opt, use_gates=True))
p_s3, s_s3 = zero_reshard(params, None, z3plan), opt.init(params)
p_u3, s_u3 = zero_reshard(params, None, z3plan), opt.init(params)
p_m3, s_m3 = params, opt.init(params)
for _ in range(3):
    p_s3, s_s3, m_s3 = sstep(p_s3, s_s3, mix_batch, mix_gates)
    p_u3, s_u3, m_u3 = ustep(p_u3, s_u3, mix_batch, mix_gates)
    p_m3, s_m3, m_m3 = mref(p_m3, s_m3, mix_batch, mix_gates)
sdiff = max_leaf_diff(p_s3, p_u3)            # both in shard layout
assert sdiff <= 1e-6, f"streamed vs unstreamed zero3 diverged: {sdiff}"
smdiff = max_leaf_diff(zero_reshard(p_s3, z3plan, None), p_m3)
assert smdiff <= 1e-6, f"streamed zero3 vs masked reference: {smdiff}"
assert abs(float(m_s3["loss"]) - float(m_m3["loss"])) <= 1e-5
res3 = check_zero3_residency(rec3, z3plan, params, K)
assert 0.95 <= res3["peak_agreement"] <= 1.05, res3
args3 = (zero_reshard(params, None, z3plan), opt.init(params), mix_batch,
         mix_gates)
wire_cmp = compare_collective_bytes(
    sstep.lower(*args3).compile().as_text(),
    ustep.lower(*args3).compile().as_text(), default_group_size=K)
assert 0.98 <= wire_cmp["ratio"] <= 1.02, wire_cmp

# ---- comm accounting: schedule x sync-mode matrix vs all-p_f baseline
rec = measure_distributed_step(K, time_steps=0)
frac = rec["all_reduce_fraction"]
base = rec["variants"]["all_pf_baseline"]["all_reduce_bytes"]
assert base > 0, rec
assert frac <= 0.60, f"all-reduce fraction {frac} above the paper target"

# byte-model consistency: the sync plan's ring-wire prediction must match
# the HLO-parsed collective bytes within 25% for every variant (all /
# sliced / zero plans; the none-dominated plan is checked below)
for name, var in rec["variants"].items():
    model = var["sync_plan"]["wire"]["total"]
    hlo = var["wire_bytes"]
    assert model > 0, (name, var["sync_plan"])
    ratio = hlo / model
    assert 0.75 <= ratio <= 1.25, \
        f"{name}: HLO {hlo:.3e} vs model {model:.3e} (ratio {ratio:.3f})"

# none-dominated plan: all-p_s schedule keeps only the loss-path leaves
ps_sched = Schedule(np.full((L * G, N), P_S, np.int8), L, G)
ps_plan = grad_sync_plan(params, cfg, ps_sched)
ps_step = make_distributed_train_step(cfg, opt, mesh, ps_plan)
ps_gates = gates_from_schedule(ps_sched, mb_of[perm])
ps_hlo = sum(collective_bytes(
    ps_step.lower(params, opt.init(params), pbatch, ps_gates)
    .compile().as_text(), default_group_size=K).values())
ps_model = sync_byte_report(ps_plan, params, n_shards=K)["wire"]["total"]
ps_ratio = ps_hlo / ps_model
assert 0.75 <= ps_ratio <= 1.25, (ps_hlo, ps_model)

# ZeRO acceptance: paper-mix RS+AG wire bytes match the masked psum's (the
# only extra collective is the scalar grad-norm psum), and the uniformly
# spread 50%-live schedule still saves strictly even though whole-subnet
# elision never fires there
z = rec["zero_sync"]
assert z["paper_mix_wire_fraction"] <= \
    z["paper_mix_masked_wire_fraction"] * 1.001, z
assert z["paper_mix_wire_fraction"] <= 0.60, z
assert z["uniform_masked_n_skipped"] == 0, z
assert z["uniform_wire_fraction"] <= 0.85, z
assert z["opt_memory_fraction"] <= 1.0 / K + 0.05, z

# ZeRO-3 acceptance: the lowered step really carries param all-gathers,
# pays less wire than the all-p_f baseline even counting them, elides
# forward-dead gathers, and the residency model is <= 0.5x replicated
z3 = rec["zero3"]
assert z3["n_all_gather_ops"] > 0, z3
assert z3["n_gather_elided"] > 0, z3
assert z3["residency_fraction"] <= 0.5, z3
assert z3["paper_mix_wire_fraction"] <= 0.75, z3
assert z3["opt_memory_fraction"] <= 1.0 / K + 0.05, z3

# streamed acceptance: the bench's overlap block — some collectives hidden
# behind compute, measured residency agrees with the model, wire bytes
# invariant to the overlap scheduling, and the plan-derived sync_byte_report
# is bit-identical between the streamed and unstreamed variants (it prices
# the plan, not the schedule that executes it)
ov = rec["overlap"]
assert ov["exposed_collective_fraction"] < 1.0, ov
assert 0.95 <= ov["peak_agreement"] <= 1.05, ov
assert 0.98 <= ov["wire_ratio_vs_unstreamed"] <= 1.02, ov
assert rec["variants"]["paper_mix_zero3_streamed"]["sync_plan"] == \
    rec["variants"]["paper_mix_zero3"]["sync_plan"], \
    "sync_byte_report must be invariant to overlap scheduling"

print(f"PARITY_OK maxdiff={maxdiff:.3e} kernel_maxdiff={kdiff:.3e} "
      f"zero_maxdiff={zdiff:.3e} "
      f"all_reduce_fraction={frac:.4f} "
      f"sync_model_fraction={rec['sync_model_fraction']:.4f} "
      f"zero_paper_mix_wire={z['paper_mix_wire_fraction']:.4f} "
      f"zero_uniform_wire={z['uniform_wire_fraction']:.4f} "
      f"zero_opt_memory={z['opt_memory_fraction']:.4f} "
      f"zero3_wire={z3['paper_mix_wire_fraction']:.4f} "
      f"zero3_residency={z3_residency:.4f} "
      f"zero3_elided={z3_elided} "
      f"streamed_maxdiff={sdiff:.3e} "
      f"streamed_exposed={ov['exposed_collective_fraction']:.4f} "
      f"streamed_peak_agreement={ov['peak_agreement']:.4f} "
      f"streamed_wire_ratio={wire_cmp['ratio']:.4f} "
      f"byte_model_ratio_none={ps_ratio:.3f} "
      f"per_device_bounds={bounds[0]},{bounds[1]} "
      f"global_bounds={gbounds[0]},{gbounds[1]}")
