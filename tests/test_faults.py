"""Elastic fault-tolerance tests (train/elastic.py, launch/faults.py).

Fast single-device units run in tier 1; the 8-emulated-device fault
matrix (``-m faults``) runs in its own CI lane via a subprocess, like the
distributed parity test — the forced host-device count must be set before
jax initializes.
"""
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import D2FTConfig, ModelConfig
from repro.core.assignment import (microbatch_costs, plan_device_assignment,
                                   speed_capacities, weighted_makespan)
from repro.core.schedule import P_F, P_O, P_S, Schedule
from repro.data.synthetic import lm_batches
from repro.launch.faults import NO_FAULTS, FaultPlan, random_fault_plan
from repro.launch.mesh import make_data_mesh
from repro.launch.parallel import ParallelConfig
from repro.models.transformer import init_model
from repro.optim.optimizers import adamw, sgd
from repro.sharding.sync import (grad_sync_plan, lofi_merge, stack_replicas)
from repro.train.elastic import (ElasticConfig, feasible_survivor_count,
                                 finetune_elastic)
from repro.train.loop import finetune_distributed

CFG = ModelConfig(name="elastic", arch_type="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=128)
D2 = D2FTConfig(n_microbatches=4, n_pf=2, n_po=1, head_groups=4)
TOL = 1e-6


def _batches(n, seed=0):
    return list(lm_batches(seed, CFG.vocab_size, batch=8, seq=16, steps=n))


def _maxdiff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.abs(x - y).max()), a, b)))


def _params():
    return init_model(jax.random.PRNGKey(0), CFG)


# ------------------------------------------------------------ fault plans
def test_fault_plan_queries():
    fp = FaultPlan(slowdowns=((3, 2.0),), slowdown_start=2,
                   dropout=(5, 1), grad_faults=((4, 0, float("nan")),),
                   dropped_syncs=(6, 7))
    assert fp.unit_times(0, 4).tolist() == [1, 1, 1, 1]   # not started yet
    assert fp.unit_times(2, 4).tolist() == [1, 1, 1, 2.0]
    assert fp.unit_times(2, 2).tolist() == [1, 1]         # device out of range
    v = fp.grad_fault_vector(4, 4)
    assert np.isnan(v[0]) and v[1:].tolist() == [1, 1, 1]
    assert fp.grad_fault_vector(3, 4).tolist() == [1, 1, 1, 1]
    assert fp.dropout_at(5) == 1 and fp.dropout_at(4) is None
    assert fp.sync_dropped(6) and not fp.sync_dropped(5)
    assert fp.any_faults() and not NO_FAULTS.any_faults()


def test_fault_plan_json_roundtrip():
    fp = FaultPlan(seed=3, slowdowns=((0, 1.5), (2, 2.25)),
                   slowdown_start=1, dropout=(4, 2),
                   grad_faults=((3, 1, float("inf")),), dropped_syncs=(2,))
    rt = FaultPlan.from_json(fp.to_json())
    assert rt.to_json() == fp.to_json()
    assert rt.slowdowns == fp.slowdowns and rt.dropout == fp.dropout


def test_random_fault_plan_deterministic():
    a = random_fault_plan(7, steps=20, n_devices=8, p_dropout=1.0)
    b = random_fault_plan(7, steps=20, n_devices=8, p_dropout=1.0)
    # NaN != NaN inside the tuples, so compare the canonical JSON forms
    assert a.to_json() == b.to_json() and a.dropout is not None
    other = random_fault_plan(8, steps=20, n_devices=8, p_dropout=1.0)
    assert a.to_json() != other.to_json()
    lo, hi = 20 // 4 + 1, 3 * 20 // 4
    assert lo <= a.dropout[0] < hi


# ------------------------------------------------- straggler capacities
def test_speed_capacities_shift_load():
    rng = np.random.default_rng(0)
    table = rng.choice([P_F, P_O, P_S], size=(8, 16),
                       p=[.4, .3, .3]).astype(np.int8)
    sched = Schedule(table, 2, 4)
    costs = microbatch_costs(sched)
    u = np.ones(8)
    u[3] = 2.0
    caps = speed_capacities(costs, u, slack=1.1)
    # straggler budget is half a healthy device's; total stays feasible
    assert caps[3] == pytest.approx(caps[0] / 2.0)
    assert caps.sum() == pytest.approx(1.1 * costs.sum())
    base, _ = plan_device_assignment(sched, 8, None)
    mit, _ = plan_device_assignment(sched, 8, caps)
    assert weighted_makespan(mit, u) < weighted_makespan(base, u)
    # equal_counts preserved under capacities (shard_map needs it)
    assert len(set(mit.counts.tolist())) == 1


def test_feasible_survivor_count():
    assert feasible_survivor_count(8, 16) == 4
    assert feasible_survivor_count(8, 8) == 4
    assert feasible_survivor_count(4, 12) == 3
    assert feasible_survivor_count(2, 7) == 1
    assert feasible_survivor_count(1, 8) == 1


# ------------------------------------------------------------ lo-fi merge
def test_lofi_merge_semantics():
    """Live slices average across replicas; dead slices pass through
    replica 0 bit-identically."""
    params = _params()
    table = np.full((2 * 4, 4), P_S, np.int8)
    table[4 + 2] = P_F                       # only layer 1 group 2 live
    sched = Schedule(table, 2, 4)
    plan = grad_sync_plan(params, CFG, sched)
    stacked = stack_replicas(params, 3)
    # make the replicas diverge everywhere
    stacked = jax.tree.map(
        lambda x: x + jnp.arange(3, dtype=x.dtype).reshape(
            (3,) + (1,) * (x.ndim - 1)), stacked)
    merged = lofi_merge(stacked, plan)
    # protected loss-path leaves ("all" specs) average: +0,+1,+2 -> +1
    assert _maxdiff(merged["embed"],
                    jax.tree.map(lambda x: x + 1.0, params["embed"])) < 1e-5
    # a dead subnet's wq slice is replica 0's copy (offset +0), untouched
    wq = np.asarray(merged["cycles"][0]["attn"]["wq"])
    ref = np.asarray(params["cycles"][0]["attn"]["wq"])
    G = 4
    gsize = wq.shape[-1] // G
    # layer 0 (cycle 0) is fully dead -> all its groups equal replica 0
    assert np.array_equal(wq[0], ref[0])
    # layer 1 (cycle 1): live group 2 averaged (+1), dead groups replica 0
    assert np.array_equal(wq[1][:, :2 * gsize], ref[1][:, :2 * gsize])
    live = wq[1][:, 2 * gsize:3 * gsize]
    assert np.allclose(live, ref[1][:, 2 * gsize:3 * gsize] + 1.0, atol=1e-5)


# ------------------------------------------------ guarded step (1 device)
def test_guard_skips_nan_burst():
    mesh = make_data_mesh(1)
    fp = FaultPlan(grad_faults=((1, 0, float("nan")),))
    el = ElasticConfig(ckpt_every=0, ckpt_dir=tempfile.mkdtemp())
    params = _params()
    p_f, _, log = finetune_elastic(params, CFG, D2, sgd(0.1), _batches(4),
                                   steps=4, mesh=mesh, faults=fp, elastic=el)
    events = log.extras["elastic"]["events"]
    assert [e["step"] for e in events if e["type"] == "guard_skip"] == [1]
    assert log.extras["elastic"]["guard_skips"] == 1
    assert all(np.isfinite(v) for v in log.losses)
    assert all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(p_f))
    # the skipped step must be a true no-op: a clean run with that batch
    # removed walks the same trajectory
    clean = _batches(4)
    del clean[1]
    el = ElasticConfig(ckpt_every=0, ckpt_dir=tempfile.mkdtemp())
    p_c, _, _ = finetune_elastic(_params(), CFG, D2, sgd(0.1), clean,
                                 steps=3, mesh=mesh, elastic=el)
    assert _maxdiff(p_f, p_c) <= TOL


def test_elastic_no_faults_matches_distributed():
    """Guard armed with an all-ones fault vector is numerically inert:
    the elastic loop without faults IS finetune_distributed."""
    mesh = make_data_mesh(1)
    for mode in ("masked", "zero", "zero3"):
        ref, _, _ = finetune_distributed(
            _params(), CFG, D2, sgd(0.1), _batches(5), steps=4, mesh=mesh,
            parallel=ParallelConfig(sync_mode=mode), refresh_every=3)
        el = ElasticConfig(refresh_every=3, ckpt_every=0,
                           ckpt_dir=tempfile.mkdtemp())
        got, _, _ = finetune_elastic(
            _params(), CFG, D2, sgd(0.1), _batches(5), steps=4, mesh=mesh,
            sync_mode=mode, elastic=el)
        assert _maxdiff(ref, got) <= TOL, mode


def test_lofi_fallback_after_dropped_syncs():
    mesh = make_data_mesh(1)
    fp = FaultPlan(dropped_syncs=(1, 2))
    el = ElasticConfig(ckpt_every=0, merge_every=2, sync_fault_threshold=2,
                       ckpt_dir=tempfile.mkdtemp())
    p, _, log = finetune_elastic(_params(), CFG, D2, sgd(0.1), _batches(7),
                                 steps=7, mesh=mesh, faults=fp, elastic=el)
    ev = log.extras["elastic"]
    kinds = [e["type"] for e in ev["events"]]
    assert kinds.count("sync_drop") == 2
    assert "lofi_fallback" in kinds and ev["final_mode"] == "local"
    assert ev["merges"] >= 1
    # dropped steps log no loss: 7 steps - 2 dropped
    assert len(log.losses) == 5
    assert all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(p))


# -------------------------------- checkpoint round-trip / resume parity
@pytest.mark.parametrize("mode", ["masked", "zero", "zero3", "local"])
def test_resume_parity(mode):
    """Satellite acceptance: save mid-run, resume from the step-level
    checkpoint, and match the uninterrupted run <= 1e-6 (params AND
    optimizer state) on every sync mode, refresh crossing included."""
    mesh = make_data_mesh(1)
    opt = adamw(1e-3)
    ck_dir = tempfile.mkdtemp()
    el = ElasticConfig(refresh_every=3, ckpt_every=1, merge_every=2,
                       ckpt_dir=ck_dir)
    p_full, s_full, _ = finetune_elastic(
        _params(), CFG, D2, opt, _batches(6), steps=5, mesh=mesh,
        sync_mode=mode, elastic=el)
    el2 = ElasticConfig(refresh_every=3, ckpt_every=1, merge_every=2,
                        ckpt_dir=tempfile.mkdtemp())
    p_res, s_res, _ = finetune_elastic(
        _params(), CFG, D2, opt, _batches(6), steps=5, mesh=mesh,
        sync_mode=mode, elastic=el2,
        resume_from=os.path.join(ck_dir, "ckpt_2.npz"))
    assert _maxdiff(p_full, p_res) <= TOL, mode
    assert _maxdiff(s_full, s_res) <= TOL, mode


def test_checkpoint_keeps_empty_containers():
    """A config with no remainder blocks has params["rest"] == []; the
    npz round-trip must preserve it (dropout recovery reloads params into
    code that indexes every top-level key)."""
    from repro.train.checkpoints import load_checkpoint, save_checkpoint
    tree = {"rest": [], "empty": {}, "x": np.ones(2)}
    path = os.path.join(tempfile.mkdtemp(), "ck.npz")
    save_checkpoint(path, tree)
    back = load_checkpoint(path)
    assert back["rest"] == [] and back["empty"] == {}
    assert np.array_equal(np.asarray(back["x"]), tree["x"])


def test_load_checkpoint_validates_template():
    from repro.train.checkpoints import load_checkpoint, save_checkpoint
    path = os.path.join(tempfile.mkdtemp(), "ck.npz")
    save_checkpoint(path, {"a": np.ones((2, 3)), "b": {"c": np.zeros(4)}})
    ok = load_checkpoint(path, template={"a": np.zeros((2, 3)),
                                         "b": {"c": np.zeros(4)}})
    assert np.asarray(ok["a"]).shape == (2, 3)
    with pytest.raises(ValueError, match=r"shape mismatch.*a"):
        load_checkpoint(path, template={"a": np.zeros((9, 9)),
                                        "b": {"c": np.zeros(4)}})
    with pytest.raises(ValueError, match=r"missing.*b/d"):
        load_checkpoint(path, template={"a": np.zeros((2, 3)),
                                        "b": {"c": np.zeros(4),
                                              "d": np.zeros(1)}})
    with pytest.raises(ValueError, match="unexpected"):
        load_checkpoint(path, template={"a": np.zeros((2, 3))})


def test_train_state_roundtrip():
    from repro.core.assignment import DeviceAssignment
    from repro.train.checkpoints import load_train_state, save_train_state
    params = _params()
    opt = adamw(1e-3)
    state = opt.init(params)
    table = np.full((2 * 4, 4), P_F, np.int8)
    sched = Schedule(table, 2, 4)
    assignment = DeviceAssignment(np.array([0, 1, 0, 1]),
                                  np.array([1.0, 2.0, 3.0, 4.0]), 2,
                                  np.array([5.0, 6.0]))
    rng = np.asarray(jax.random.PRNGKey(7))
    path = os.path.join(tempfile.mkdtemp(), "state.npz")
    save_train_state(path, step=11, params=params, opt_state=state,
                     sched=sched, assignment=assignment, rng=rng,
                     extra={"speeds": np.ones(4), "sync_faults": 2})
    back = load_train_state(path, params_template=params)
    assert back["step"] == 11
    assert _maxdiff(back["params"], params) == 0
    assert _maxdiff(back["opt_state"], state) == 0
    assert np.array_equal(back["schedule"].table, sched.table)
    a = back["assignment"]
    assert np.array_equal(a.device_of, assignment.device_of)
    assert np.array_equal(a.capacities, assignment.capacities)
    assert np.array_equal(back["rng"], rng)
    assert int(back["extra"]["sync_faults"]) == 2


# --------------------------------------------------- 8-device fault matrix
@pytest.mark.faults
def test_fault_matrix_8dev_subprocess():
    """Acceptance matrix on 8 emulated devices: straggler mitigation,
    dropout recovery parity, NaN-burst guard, lo-fi fallback. Runs in a
    fresh interpreter (host-device count must be set before jax init);
    ``-m faults``: its own CI lane with its own wall-clock budget."""
    script = os.path.join(os.path.dirname(__file__), "_fault_matrix.py")
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + ([os.environ["PYTHONPATH"]]
                      if "PYTHONPATH" in os.environ else [])))
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "FAULTS_OK" in proc.stdout, proc.stdout
