"""Distributed D2FT execution: the schedule-masked gradient sync plan
(sharding/sync.py), the shard_map step's byte accounting, and an
8-host-device parity run in a subprocess (this process is pinned to one
CPU device by conftest, and jax locks the device count at first init)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.configs.base import ModelConfig
from repro.core.schedule import P_F, P_O, P_S, Schedule
from repro.launch.diststep import (all_pf_schedule, paper_mix_schedule,
                                   uniform_half_schedule)
from repro.models.transformer import init_model
from repro.sharding.sync import (SyncSpec, apply_grad_sync,
                                 backward_live_groups, forward_live_groups,
                                 grad_sync_plan, sync_byte_report,
                                 zero3_param_byte_report, zero_reshard,
                                 zero_state_byte_report)

CFG = ModelConfig(name="sync", arch_type="dense", n_layers=4, d_model=64,
                  n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256)
L, G, N = 4, 4, 8


def _params():
    return init_model(jax.random.PRNGKey(0), CFG)


def _mixed_schedule():
    rng = np.random.default_rng(0)
    table = rng.choice([P_F, P_O, P_S], size=(L * G, N),
                       p=[.4, .3, .3]).astype(np.int8)
    table[0:G] = P_O                       # layer 0: forward-only everywhere
    table[2 * G:3 * G] = P_F               # layer 2: fully live
    return Schedule(table, L, G)


def test_backward_live_groups():
    sched = _mixed_schedule()
    live = backward_live_groups(sched)
    assert live.shape == (L, G)
    assert not live[0].any() and live[2].all()


def test_plan_modes_and_protected_leaves():
    params = _params()
    plan = grad_sync_plan(params, CFG, _mixed_schedule())
    # loss-path leaves never skip
    assert plan["embed"]["table"].mode == "all"
    assert all(s.mode == "all" for s in jax.tree.leaves(
        plan["final_norm"], is_leaf=lambda x: isinstance(x, SyncSpec)))
    # the 4 layers are scan-stacked at pattern position 0 with differing
    # liveness, so attention weights get per-cycle specs
    wq = plan["cycles"][0]["attn"]["wq"]
    assert wq.mode == "stacked" and len(wq.per_cycle) == 4
    assert wq.per_cycle[0].mode == "none"          # layer 0: p_o only
    assert wq.per_cycle[2].mode == "all"           # layer 2: fully live
    assert wq.per_cycle[1].mode in ("sliced", "all", "none")


def test_plan_all_pf_is_full_sync():
    params = _params()
    plan = grad_sync_plan(params, CFG, all_pf_schedule(L, G, N))
    assert all(s.mode == "all" for s in jax.tree.leaves(
        plan, is_leaf=lambda x: isinstance(x, SyncSpec)))
    assert sync_byte_report(plan, params)["fraction"] == 1.0


def test_sync_bytes_paper_mix_under_target():
    params = _params()
    sched = paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), seed=0)
    rep = sync_byte_report(grad_sync_plan(params, CFG, sched), params)
    assert rep["fraction"] <= 0.60, rep
    assert rep["n_skipped"] + rep["n_sliced"] > 0


def test_sync_bytes_all_ps_only_loss_path():
    params = _params()
    sched = Schedule(np.full((L * G, N), P_S, np.int8), L, G)
    rep = sync_byte_report(grad_sync_plan(params, CFG, sched), params)
    # only embed/unembed/final_norm stay synced
    assert 0.0 < rep["fraction"] < 0.35
    assert rep["n_skipped"] > 0


def test_apply_grad_sync_structure_single_device():
    """On a 1-device mesh pmean is the identity, so applying the plan must
    return every leaf (incl. sliced/stacked reassembly) bit-identical."""
    from jax.sharding import Mesh, PartitionSpec as P

    params = _params()
    plan = grad_sync_plan(params, CFG, _mixed_schedule())
    fake_grads = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(1), a.shape), params)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    out = jax.jit(jax.shard_map(
        lambda g: apply_grad_sync(g, plan, "data"), mesh=mesh,
        in_specs=(P(),), out_specs=P(), check_vma=False))(fake_grads)
    for a, b in zip(jax.tree.leaves(fake_grads), jax.tree.leaves(out)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------- HLO byte parser
def test_collective_bytes_group_size_forms():
    """collective_bytes reads the group size from explicit-list, iota and
    async-pair (`-start` carries the attribute, `-done` the array shape)
    prints, and falls back to default_group_size on the empty print."""
    from repro.launch.hlo import collective_bytes

    explicit = ("%ar = f32[100]{0} all-reduce(f32[100] %x), channel_id=1, "
                "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%sum")
    iota = ("%rs = f32[100]{0} reduce-scatter(f32[800] %x), channel_id=2, "
            "replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%sum")
    async_pair = (
        "%ag-start = (f32[100], f32[800]) all-gather-start(f32[100] %x), "
        "channel_id=3, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n"
        "%ag-done = f32[800]{0} all-gather-done("
        "(f32[100], f32[800]) %ag-start), channel_id=3")
    empty = ("%ar2 = f32[100]{0} all-reduce(f32[100] %x), channel_id=4, "
             "replica_groups={}, to_apply=%sum")
    got = collective_bytes("\n".join([explicit, iota, async_pair]))
    assert got["all-reduce"] == pytest.approx(2 * 7 / 8 * 400)
    assert got["reduce-scatter"] == pytest.approx(7 * 400)
    assert got["all-gather"] == pytest.approx(7 / 8 * 3200)
    assert collective_bytes(empty, default_group_size=8)["all-reduce"] \
        == pytest.approx(2 * 7 / 8 * 400)


def test_collective_parser_counts_combined_tuple_results():
    """XLA combines several psums into one tuple-shaped all-reduce; every
    array of the tuple is priced and the instruction counts once. TPU
    layouts (`{1,0:T(8,128)}`) parse too."""
    from repro.launch.hlo import collective_bytes, collective_counts

    combined = ("%all-reduce = (f32[4,8]{1,0}, /*index=1*/f32[16]{0}) "
                "all-reduce(%a, %b), channel_id=1, "
                "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%sum")
    tpu = ("%all-gather = f32[8,128]{1,0:T(8,128)} all-gather("
           "f32[2,128]{1,0:T(2,128)} %p), channel_id=2, "
           "replica_groups=[1,4]<=[4], dimensions={0}")
    got = collective_bytes("\n".join([combined, tpu]))
    assert got["all-reduce"] == pytest.approx(2 * 7 / 8 * (32 + 16) * 4)
    assert got["all-gather"] == pytest.approx(3 / 4 * 8 * 128 * 4)
    assert collective_counts("\n".join([combined, tpu])) == {
        "all-reduce": 1, "all-gather": 1}


# ------------------------------------------------------------- ZeRO plans
def _spec_leaves(plan):
    return jax.tree.leaves(plan, is_leaf=lambda x: isinstance(x, SyncSpec))


def test_zero_plan_modes_and_masks():
    params = _params()
    plan = grad_sync_plan(params, CFG, _mixed_schedule(), mode="zero",
                          n_shards=8)
    specs = _spec_leaves(plan)
    assert all(isinstance(s, SyncSpec) for s in specs)
    # every leaf of this config splits evenly over 8 shards -> all zero
    assert all(s.mode in ("zero", "zero_stacked") for s in specs)
    # loss-path leaves: fully scattered and gathered
    emb = plan["embed"]["table"]
    assert emb.mode == "zero" and all(emb.live) and all(emb.gather)
    # stacked attention weights: per-cycle masks follow the layers
    wq = plan["cycles"][0]["attn"]["wq"]
    assert wq.mode == "zero_stacked" and len(wq.per_cycle) == 4
    assert not any(wq.per_cycle[0].live)      # layer 0: p_o only, no scatter
    assert all(wq.per_cycle[2].live)          # layer 2: fully live
    # gather mask covers the scatter mask everywhere
    for s in specs:
        for sub in (s.per_cycle or (s,)):
            if sub.mode == "zero":
                assert all(g or not l
                           for l, g in zip(sub.live, sub.gather))


def test_zero_plan_ever_live_and_decay_force_gather():
    params = _params()
    sched = _mixed_schedule()
    # a group that was live under an earlier plan keeps its gather bit even
    # when now dead (its moments may be non-zero)
    ever = np.ones((L, G), bool)
    plan = grad_sync_plan(params, CFG, sched, mode="zero", n_shards=8,
                          ever_live=ever)
    for s in _spec_leaves(plan):
        for sub in (s.per_cycle or (s,)):
            if sub.mode == "zero":
                assert all(sub.gather), sub
    # a non-elidable optimizer (weight decay) forces the same dense gather
    plan = grad_sync_plan(params, CFG, sched, mode="zero", n_shards=8,
                          elide_gather=False)
    for s in _spec_leaves(plan):
        for sub in (s.per_cycle or (s,)):
            if sub.mode == "zero":
                assert all(sub.gather), sub


def test_zero_plan_indivisible_falls_back_to_masked():
    """n_shards that divides no axis degrades every leaf to its masked
    spec (replicated moments, pmean sync) — never a crash."""
    params = _params()
    sched = _mixed_schedule()
    plan7 = grad_sync_plan(params, CFG, sched, mode="zero", n_shards=7)
    masked = grad_sync_plan(params, CFG, sched)
    assert plan7 == masked


def test_zero_wire_model_matches_masked_psum():
    """Ring physics: reduce-scatter + all-gather of the live runs costs
    exactly what the masked all-reduce of the same runs costs."""
    params = _params()
    for sched in (_mixed_schedule(),
                  paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), 0),
                  uniform_half_schedule(L, G, N)):
        masked = sync_byte_report(grad_sync_plan(params, CFG, sched),
                                  params, n_shards=8)
        zero = sync_byte_report(
            grad_sync_plan(params, CFG, sched, mode="zero", n_shards=8),
            params, n_shards=8)
        assert zero["wire"]["total"] == \
            pytest.approx(masked["wire"]["total"], rel=1e-9)
        assert zero["fraction"] == pytest.approx(masked["fraction"],
                                                 rel=1e-9)


def test_uniform_half_schedule_no_whole_subnet_elision():
    """The uniformly spread 50%-live schedule: every layer partially live,
    so the masked plan's whole-subnet elision (`none`) never fires yet the
    sliced/zero run masks still price below the full sync."""
    params = _params()
    sched = uniform_half_schedule(L, G, N)
    live = backward_live_groups(sched)
    assert live.any(axis=1).all() and not live.all(axis=1).any()
    rep = sync_byte_report(grad_sync_plan(params, CFG, sched), params)
    assert rep["n_skipped"] == 0
    assert rep["fraction"] < 1.0


def test_zero_state_memory_fraction():
    """Acceptance: per-device optimizer-moment bytes under the ZeRO
    partition are <= 1/n_devices + slack of the replicated baseline."""
    params = _params()
    for sched in (paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), 0),
                  all_pf_schedule(L, G, N)):
        plan = grad_sync_plan(params, CFG, sched, mode="zero", n_shards=8)
        rep = zero_state_byte_report(plan, params, 8, n_moments=2)
        assert rep["fraction"] <= 1.0 / 8 + 0.05, rep
        assert rep["n_partitioned"] > 0
        # doubling the moment copies (adam m+v vs sgd mu) scales both sides
        assert rep["replicated_bytes"] == pytest.approx(
            2 * zero_state_byte_report(plan, params, 8)["replicated_bytes"])


# ------------------------------------------------------------ ZeRO-3 plans
def _ps_row_schedule():
    """Mixed schedule with a known p_s-everywhere subnet: layer 3 group 1
    is frozen on every micro-batch (forward-dead), layer 0 is p_o-only
    (forward-live, backward-dead), layer 2 fully live."""
    sched = _mixed_schedule()
    table = sched.table.copy()
    table[3 * G + 1] = P_S
    return Schedule(table, L, G)


def test_zero3_gather_mask_is_forward_liveness():
    params = _params()
    sched = _ps_row_schedule()
    fwd, live = forward_live_groups(sched), backward_live_groups(sched)
    assert fwd[0].all() and not live[0].any()     # p_o: fwd yes, bwd no
    assert not fwd[3, 1]                          # p_s everywhere: dead
    plan = grad_sync_plan(params, CFG, sched, mode="zero3", n_shards=8)
    wq = plan["cycles"][0]["attn"]["wq"]
    # layer 0 (p_o everywhere): nothing to scatter, everything to gather —
    # the zero-1 plan would elide this gather, zero3 cannot (forward needs
    # the values), which is exactly the semantic difference between them
    per0 = wq.per_cycle[0] if wq.mode == "zero_stacked" else wq
    assert not any(per0.live) and all(per0.gather)
    # layer 3 group 1 (p_s everywhere): the gather is elided
    per3 = wq.per_cycle[3] if wq.mode == "zero_stacked" else wq
    assert not per3.gather[1] and not per3.live[1]
    # gather covers scatter on every leaf
    for s in jax.tree.leaves(plan, is_leaf=lambda x: isinstance(x, SyncSpec)):
        for sub in (s.per_cycle or (s,)):
            if sub.mode == "zero":
                assert all(g or not l for l, g in zip(sub.live, sub.gather))
    # zero3 ignores ever_live and elide_gather: staleness cannot arise when
    # the owned shards are the persistent state
    same = grad_sync_plan(params, CFG, sched, mode="zero3", n_shards=8,
                          ever_live=np.ones((L, G), bool),
                          elide_gather=False)
    assert same == plan


def test_zero3_param_residency_report():
    """Acceptance numbers of the residency-window model: elision fires on
    the concentrated paper-mix and peak residency is <= 0.5x replicated;
    the all-p_f schedule elides nothing but still beats replication (the
    streaming window holds one block at a time)."""
    params = _params()
    sched = paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), 0)
    plan = grad_sync_plan(params, CFG, sched, mode="zero3", n_shards=8)
    rep = zero3_param_byte_report(plan, params, 8)
    assert rep["n_gather_elided"] > 0, rep
    assert rep["fraction"] <= 0.5, rep
    assert rep["per_device_peak_bytes"] == pytest.approx(
        rep["shard_bytes"] + rep["fallback_bytes"] + rep["peak_unit_bytes"])
    assert rep["gathered_bytes"] + rep["elided_bytes"] <= \
        rep["replicated_bytes"] + 1e-6
    plan_f = grad_sync_plan(params, CFG, all_pf_schedule(L, G, N),
                            mode="zero3", n_shards=8)
    rep_f = zero3_param_byte_report(plan_f, params, 8)
    assert rep_f["n_gather_elided"] == 0
    assert rep_f["elided_bytes"] == 0.0
    assert rep_f["fraction"] < 1.0
    # elision only shrinks the window
    assert rep["fraction"] <= rep_f["fraction"] + 1e-9


def test_zero3_wire_adds_forward_gather_honestly():
    """zero3's synced-byte fraction must EXCEED the zero-1 fraction on the
    paper-mix (it gathers forward-live runs every step, zero-1 only
    backward-live ones) — the byte model must not hide the cost that buys
    the sharded residency."""
    params = _params()
    sched = paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), 0)
    z1 = sync_byte_report(grad_sync_plan(params, CFG, sched, mode="zero",
                                         n_shards=8), params, n_shards=8)
    z3 = sync_byte_report(grad_sync_plan(params, CFG, sched, mode="zero3",
                                         n_shards=8), params, n_shards=8)
    assert z3["ag_bytes"] > z1["ag_bytes"]
    assert z3["fraction"] > z1["fraction"]
    assert z3["rs_bytes"] == pytest.approx(z1["rs_bytes"])


def test_zero_reshard_roundtrip_and_cross_plan():
    """Shard-layout -> canonical -> shard-layout is exact, and resharding
    between two different plans preserves every element (pure
    permutations)."""
    params = _params()
    plan_a = grad_sync_plan(params, CFG, _mixed_schedule(), mode="zero",
                            n_shards=8)
    plan_b = grad_sync_plan(params, CFG,
                            paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), 0),
                            mode="zero", n_shards=8)
    tree = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(1), a.shape), params)
    canon = zero_reshard(tree, plan_a, None)
    back = zero_reshard(canon, None, plan_a)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    crossed = zero_reshard(zero_reshard(tree, plan_a, plan_b), plan_b,
                           plan_a)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(crossed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # permutation property: sorted content identical in any layout
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(canon)):
        np.testing.assert_array_equal(np.sort(np.asarray(a), axis=None),
                                      np.sort(np.asarray(b), axis=None))


def test_paper_mix_costs_stay_seed_dependent():
    """Guard for the invariant the assigner regression test below rests
    on: the concentrated paper-mix must keep a seed-dependent
    per-micro-batch cost vector even when the p_o budget divides into
    whole rows (no natural partial row). K=20, n_mb=16 hits exactly that:
    round(0.3*20*16) = 96 = 6 full rows."""
    from repro.core.assignment import microbatch_costs
    for L_, G_, n_mb in [(5, 4, 16), (4, 4, 16), (4, 4, 8)]:
        costs = [microbatch_costs(paper_mix_schedule(
            L_, G_, n_mb, (0.4, 0.3, 0.3), seed=seed)) for seed in (0, 3)]
        assert not np.array_equal(costs[0], costs[1]), (L_, G_, n_mb)
        # mix preserved by the partial-row spill
        t = paper_mix_schedule(L_, G_, n_mb, (0.4, 0.3, 0.3), seed=0).table
        assert (t == P_O).sum() == round(0.3 * L_ * G_ * n_mb)


# ------------------------------------------ refresh re-planning regression
def test_assignment_changes_with_schedule():
    """Regression (ROADMAP "keeps one assignment"): the knapsack assigner
    must be re-run per schedule refresh — different schedules produce
    different micro-batch placements."""
    from repro.core.assignment import plan_device_assignment
    a1, _ = plan_device_assignment(
        paper_mix_schedule(L, G, 16, (0.4, 0.3, 0.3), seed=0), 4)
    a2, _ = plan_device_assignment(
        paper_mix_schedule(L, G, 16, (0.4, 0.3, 0.3), seed=3), 4)
    assert not np.array_equal(a1.device_of, a2.device_of), \
        "re-assignment is a no-op for a changed schedule"
    # determinism: replanning the same schedule is a no-op
    a3, _ = plan_device_assignment(
        paper_mix_schedule(L, G, 16, (0.4, 0.3, 0.3), seed=0), 4)
    assert np.array_equal(a1.device_of, a3.device_of)


def test_finetune_distributed_replans_per_refresh():
    """finetune_distributed(refresh_every=k) re-plans schedule AND device
    assignment every k steps (one refresh record per replan, each carrying
    a fresh assignment), in all three sync modes. The zero3 arm also pins
    the params layout contract: shard layout inside the loop (reshard per
    refresh), canonical order on return."""
    from repro.configs.base import D2FTConfig
    from repro.data.synthetic import lm_batches
    from repro.launch.mesh import make_data_mesh
    from repro.launch.parallel import MeshSpec, ParallelConfig
    from repro.optim.optimizers import sgd
    from repro.train.loop import finetune_distributed

    cfg = ModelConfig(name="refresh", arch_type="dense", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
                      vocab_size=128)
    d2 = D2FTConfig(n_microbatches=4, n_pf=2, n_po=1,
                    head_groups=cfg.n_heads)
    mesh = make_data_mesh(1)
    finals = {}
    for sync_mode in ("masked", "zero", "zero3"):
        params = init_model(jax.random.PRNGKey(0), cfg)
        batches = lm_batches(0, cfg.vocab_size, 8, 8, 5)
        p, _, log = finetune_distributed(
            params, cfg, d2, sgd(1e-2), batches, steps=5, mesh=mesh,
            parallel=ParallelConfig(mesh=MeshSpec(data=1),
                                    sync_mode=sync_mode),
            refresh_every=2)
        refreshes = log.extras["refreshes"]
        assert [r["step"] for r in refreshes] == [0, 2, 4]
        for r in refreshes:
            assert len(r["device_of"]) == d2.n_microbatches
            assert "rebalance" in r and "sync" in r
            if sync_mode == "zero3":
                assert "zero3_params" in r
        assert len(log.losses) == 5
        assert all(np.isfinite(v) for v in log.losses)
        finals[sync_mode] = p
    # canonical-order contract: on a 1-device mesh every collective is the
    # identity, so all three modes walk the same trajectory — if zero3
    # returned shard-layout params this comparison would scramble
    for mode in ("zero", "zero3"):
        diff = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
            finals["masked"], finals[mode])))
        assert diff <= 1e-6, (mode, diff)


# ------------------------------------------------------- streamed ZeRO-3
def test_zero3_unit_schedule_matches_report():
    """The execution-ordered unit schedule is the report's unit set: names
    unique, head subtrees first, totals and peak agree with
    ``zero3_param_byte_report`` (the schedule is the model the streamed
    materializer is checked against, so the two must never drift)."""
    from repro.sharding.sync import zero3_unit_schedule
    params = _params()
    sched = paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), 0)
    plan = grad_sync_plan(params, CFG, sched, mode="zero3", n_shards=8)
    units = zero3_unit_schedule(plan, params)
    rep = zero3_param_byte_report(plan, params, 8)
    names = [n for n, _ in units]
    assert len(names) == len(set(names))
    assert names[0] == "embed", names
    assert sum(b for _, b in units) == pytest.approx(rep["gathered_bytes"])
    assert max(b for _, b in units) == pytest.approx(rep["peak_unit_bytes"])
    assert dict(units)[rep["peak_unit"]] == pytest.approx(
        rep["peak_unit_bytes"])
    # blocks appear in forward (cycle-major) order
    blocks = [n for n in names if n.startswith("cycles[")]
    assert blocks == sorted(blocks, key=lambda s: (
        int(s.split("][")[1][:-1]), int(s.split("[")[1].split("]")[0])))


def test_streamed_residency_counter_matches_model():
    """Lowering the streamed step on a 1-device mesh fills the trace-time
    gather counter; ``check_zero3_residency`` must accept it with peak
    agreement ~1.0 — the measured-vs-model contract of the bench."""
    from repro.core.schedule import gates_from_schedule
    from repro.data.synthetic import lm_batches, microbatch_assignment
    from repro.launch.mesh import make_data_mesh
    from repro.launch.parallel import ParallelConfig
    from repro.optim.optimizers import sgd
    from repro.sharding.sync import (ResidencyRecorder,
                                     check_zero3_residency)
    from repro.train.loop import make_distributed_train_step

    params = _params()
    sched = paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), 0)
    plan = grad_sync_plan(params, CFG, sched, mode="zero3", n_shards=1)
    opt = sgd(1e-2)
    rec = ResidencyRecorder()
    step = make_distributed_train_step(
        CFG, opt, make_data_mesh(1), plan,
        parallel=ParallelConfig(sync_mode="zero3", streamed=True,
                                opt_chunk=64),
        params=params, residency_recorder=rec)
    batch = next(lm_batches(0, CFG.vocab_size, 8, 8, 1))
    gates = gates_from_schedule(sched, microbatch_assignment(8, N))
    shards = zero_reshard(params, None, plan)
    step.lower(shards, opt.init(params), batch, gates)
    out = check_zero3_residency(rec, plan, params, 1)
    assert out["peak_agreement"] == pytest.approx(1.0, abs=0.05)
    assert out["n_units_measured"] > 0
    assert out["n_units_measured"] <= out["n_units_model"]


def test_streamed_mode_validation():
    """streamed / opt_chunk are ZeRO-3-only, and streamed cannot compose
    with the pre-sync NaN guard (the reduce-scatters are fused into the
    backward, so there is no point where local grads exist to zero)."""
    from repro.launch.mesh import make_data_mesh
    from repro.launch.parallel import ParallelConfig
    from repro.optim.optimizers import sgd
    from repro.train.loop import make_distributed_train_step

    params = _params()
    sched = paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), 0)
    mesh = make_data_mesh(1)
    plan = grad_sync_plan(params, CFG, sched, mode="zero3", n_shards=1)
    with pytest.raises(ValueError, match="guard"):
        make_distributed_train_step(
            CFG, sgd(1e-2), mesh, plan, params=params,
            parallel=ParallelConfig(sync_mode="zero3", streamed=True,
                                    guard=True))
    plan_m = grad_sync_plan(params, CFG, sched, mode="masked")
    with pytest.raises(AssertionError):
        make_distributed_train_step(
            CFG, sgd(1e-2), mesh, plan_m, params=params,
            parallel=ParallelConfig(sync_mode="masked", streamed=True))


def test_zero3_overlap_report_model():
    """Overlap-window model invariants: exposed < serialized on the
    paper-mix (some gathers hide behind the previous unit's compute), more
    compute hides more, zero compute exposes everything, and the
    double-buffered window dominates the single-unit one."""
    from repro.launch.diststep import zero3_overlap_report
    params = _params()
    sched = paper_mix_schedule(L, G, N, (0.4, 0.3, 0.3), 0)
    plan = grad_sync_plan(params, CFG, sched, mode="zero3", n_shards=8)
    rep = zero3_param_byte_report(plan, params, 8)
    ov = zero3_overlap_report(plan, params, 8)
    assert 0.0 < ov["exposed_fraction"] < 1.0, ov
    assert ov["exposed_gather_bytes"] <= ov["serialized_gather_bytes"]
    assert ov["double_buffer_peak_bytes"] >= \
        rep["per_device_peak_bytes"] - 1e-6
    assert ov["double_buffer_fraction"] >= rep["fraction"] - 1e-9
    ov4 = zero3_overlap_report(plan, params, 8, compute_ratio=4.0)
    assert ov4["exposed_fraction"] <= ov["exposed_fraction"] + 1e-12
    ov0 = zero3_overlap_report(plan, params, 8, compute_ratio=0.0)
    assert ov0["exposed_fraction"] == pytest.approx(1.0)


@pytest.mark.multidevice
def test_distributed_parity_8dev_subprocess():
    """Acceptance: 8-host-device shard_map step == single-device gated step
    (masked, ZeRO-1 and ZeRO-3 sync, and the compacted-kernel path) and
    paper-mix all-reduce bytes at <= 60% of the all-p_f baseline. Runs in a
    fresh interpreter because the host-device count must be set before jax
    initializes. ``-m multidevice``: this is the slowest test in the repo
    (it compiles the whole schedule x sync-mode matrix on 8 emulated
    devices) and CI runs it in its own job with its own wall-clock
    budget."""
    script = os.path.join(os.path.dirname(__file__), "_dist_parity.py")
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + ([os.environ["PYTHONPATH"]]
                      if "PYTHONPATH" in os.environ else [])))
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "PARITY_OK" in proc.stdout, proc.stdout
