"""Unit tests for the unified multi-axis parallelism API: MeshSpec /
ParallelConfig validation, ``parallel=`` as the train-step factories'
one way in, the schedule-aware pipeline stage assigner and the bubble
model. The 8-device multi-axis parity (collectives actually moving
bytes) is the ``multidevice``-marked subprocess test at the bottom
(tests/_dist_parity_multiaxis.py)."""
import itertools
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import optional_hypothesis
from repro.configs.base import ModelConfig
from repro.core.assignment import (StageAssignment, assign_stages,
                                   layer_live_costs, plan_stage_assignment,
                                   stage_report)
from repro.core.schedule import P_F, P_O, P_S, Schedule, gates_from_schedule
from repro.data.synthetic import lm_batches, microbatch_assignment
from repro.launch.parallel import MeshSpec, ParallelConfig
from repro.models.transformer import init_model
from repro.optim.optimizers import sgd
from repro.sharding.sync import grad_sync_plan
from repro.train.loop import make_distributed_train_step, make_train_step
from repro.train.pipeline import PipelineRecorder, analytic_bubble_fraction

given, settings, st = optional_hypothesis()

CFG = ModelConfig(name="par", arch_type="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=128)
L, G, N, B, S = 2, 4, 4, 8, 8


def _schedule():
    rng = np.random.default_rng(7)
    table = rng.choice([P_F, P_O, P_S], size=(L * G, N),
                       p=[.4, .3, .3]).astype(np.int8)
    table[0, 0] = P_F
    return Schedule(table, L, G)


# ----------------------------------------------------------------- MeshSpec
def test_meshspec_parse():
    assert MeshSpec.parse("data=4,stage=2,tensor=1") == \
        MeshSpec(data=4, stage=2, tensor=1)
    assert MeshSpec.parse("data=8") == MeshSpec(data=8)
    assert MeshSpec.parse(" stage=2 , data=4 ") == MeshSpec(data=4, stage=2)
    assert MeshSpec.parse("").shape == (1, 1, 1)
    with pytest.raises(ValueError, match="axis=size"):
        MeshSpec.parse("data:4")
    with pytest.raises(ValueError, match="unknown mesh axis"):
        MeshSpec.parse("model=4")
    with pytest.raises(ValueError, match="twice"):
        MeshSpec.parse("data=4,data=2")
    with pytest.raises(ValueError, match="positive"):
        MeshSpec(data=0)


def test_meshspec_build():
    # default layout keeps all three axes, singletons included
    mesh = MeshSpec(data=1).build()
    assert tuple(mesh.axis_names) == ("data", "stage", "tensor")
    assert dict(mesh.shape) == {"data": 1, "stage": 1, "tensor": 1}
    # oversubscription is an error, not a silent truncation
    with pytest.raises(ValueError, match="device_count"):
        MeshSpec(data=2).build()
    # axis_names may only drop singleton axes
    with pytest.raises(ValueError, match="drops"):
        MeshSpec(data=1, stage=2).build(axis_names=("data",))
    legacy = MeshSpec(data=1).build(axis_names=("data",))
    assert tuple(legacy.axis_names) == ("data",)


def test_make_host_mesh_remainder_raises():
    from repro.launch.mesh import make_host_mesh
    # 1 local device (conftest pins it): model=3 would silently drop 1
    with pytest.raises(ValueError, match="silently drop"):
        make_host_mesh(model=3)
    mesh = make_host_mesh(model=1)
    assert dict(mesh.shape) == {"data": 1, "model": 1}


# ------------------------------------------------------------ ParallelConfig
def test_parallel_config_validation():
    with pytest.raises(ValueError, match="unknown sync_mode"):
        ParallelConfig(sync_mode="nope")
    with pytest.raises(AssertionError):
        ParallelConfig(sync_mode="masked", streamed=True)
    with pytest.raises(ValueError, match="guard"):
        ParallelConfig(sync_mode="zero3", streamed=True, guard=True)
    with pytest.raises(ValueError, match="communication-free"):
        ParallelConfig(mesh=MeshSpec(stage=2), sync_mode="local",
                       microbatches=2)
    with pytest.raises(ValueError, match="pure data mesh"):
        ParallelConfig(mesh=MeshSpec(tensor=2), guard=True)
    with pytest.raises(ValueError, match="use_kernel"):
        ParallelConfig(mesh=MeshSpec(stage=2), microbatches=2,
                       use_kernel=True)
    with pytest.raises(ValueError, match="microbatches"):
        ParallelConfig(mesh=MeshSpec(stage=2))       # pipeline needs M >= 1
    with pytest.raises(ValueError, match="stage"):
        ParallelConfig(microbatches=4)               # M without a pipeline
    ok = ParallelConfig(mesh=MeshSpec(data=2, stage=2, tensor=2),
                        microbatches=4)
    assert ok.stage_axis == "stage" and ok.tensor_axis == "tensor"
    assert ParallelConfig().stage_axis is None
    assert ParallelConfig().tensor_axis is None


def test_parallel_config_validate_model():
    cfg = ParallelConfig(mesh=MeshSpec(tensor=3))
    with pytest.raises(ValueError, match="n_heads"):
        cfg.validate_model(CFG)                      # 3 does not divide 4
    with pytest.raises(ValueError, match="layers"):
        ParallelConfig(mesh=MeshSpec(stage=4),
                       microbatches=2).validate_model(CFG)   # L=2 < S=4
    ParallelConfig(mesh=MeshSpec(tensor=2)).validate_model(CFG)


def test_parallel_config_validate_mesh():
    cfg = ParallelConfig(mesh=MeshSpec(data=1, tensor=2))
    legacy = MeshSpec(data=1).build(axis_names=("data",))
    with pytest.raises(ValueError, match="tensor"):
        cfg.validate_mesh(legacy)                    # mesh lacks the axis
    ParallelConfig().validate_mesh(legacy)


# ------------------------------------------------- one way to set options
def test_deprecated_kwargs_still_work():
    """What callers of the removed loose kwargs still get: ``parallel=None``
    builds the config from the mesh's axis sizes, and both it and an
    explicit ``ParallelConfig`` run the same step as ``make_train_step``."""
    sched = _schedule()
    params = init_model(jax.random.PRNGKey(0), CFG)
    batch = next(lm_batches(0, CFG.vocab_size, B, S, 1))
    gates = gates_from_schedule(sched, microbatch_assignment(B, N))
    plan = grad_sync_plan(params, CFG, sched)
    opt = sgd(1e-2)
    from repro.launch.mesh import make_data_mesh
    mesh = make_data_mesh(1)
    step_mesh = make_distributed_train_step(CFG, opt, mesh, plan)
    step_conf = make_distributed_train_step(
        CFG, opt, mesh, plan, parallel=ParallelConfig(mesh=MeshSpec(data=1)))
    ref = jax.jit(make_train_step(CFG, opt, use_gates=True))
    p_m, _, _ = step_mesh(params, opt.init(params), batch, gates)
    p_c, _, _ = step_conf(params, opt.init(params), batch, gates)
    p_r, _, _ = ref(params, opt.init(params), batch, gates)
    for p in (p_m, p_c):
        diff = max(jax.tree.leaves(jax.tree.map(
            lambda x, y: float(jnp.abs(x - y).max()), p, p_r)))
        assert diff <= 1e-6, diff


@pytest.mark.parametrize("names,sizes,want", [
    (("data",), (1,), MeshSpec()),
    (("data", "stage", "tensor"), (4, 1, 2), MeshSpec(data=4, tensor=2)),
    (("data", "model"), (2, 4), MeshSpec(data=2)),
], ids=["data", "three-axes", "gspmd-host"])
def test_parallel_none_takes_the_mesh_axis_sizes(names, sizes, want):
    """With ``parallel=None`` the entries run the mesh's (data, stage,
    tensor) sizes with default options; axes of other names (the GSPMD
    ``model`` axis) are not the distributed step's."""
    from jax.sharding import AbstractMesh

    from repro.train.loop import _mesh_parallel
    got = _mesh_parallel(AbstractMesh(sizes, names))
    assert got == ParallelConfig(mesh=want)


@pytest.mark.parametrize("entry", ["make_distributed_train_step",
                                   "finetune_distributed"])
def test_loose_option_kwargs_are_rejected(entry):
    """``parallel=`` is the only way to set the distributed options: a
    loose ``sync_mode=`` is an unknown argument, not a deprecated one."""
    from repro.configs.base import D2FTConfig
    from repro.launch.mesh import make_data_mesh
    from repro.train import loop

    params = init_model(jax.random.PRNGKey(0), CFG)
    mesh = make_data_mesh(1)
    plan = grad_sync_plan(params, CFG, _schedule())
    calls = {
        "make_distributed_train_step": lambda: loop.make_distributed_train_step(
            CFG, sgd(1e-2), mesh, plan, sync_mode="masked"),
        "finetune_distributed": lambda: loop.finetune_distributed(
            params, CFG, D2FTConfig(n_microbatches=N), sgd(1e-2),
            lm_batches(0, CFG.vocab_size, B, S, 1), steps=1, mesh=mesh,
            sync_mode="masked"),
    }
    with pytest.raises(TypeError, match="sync_mode"):
        calls[entry]()


# -------------------------------------------------------- stage assignment
def _oracle_makespan(costs, S, caps=None):
    """Brute-force best bottleneck over all contiguous partitions."""
    L = len(costs)
    best = float("inf")
    for cuts in itertools.combinations(range(1, L), S - 1):
        bounds = (0,) + cuts + (L,)
        loads = [sum(costs[lo:hi]) for lo, hi in zip(bounds, bounds[1:])]
        if caps is not None:
            loads = [l / c for l, c in zip(loads, caps)]
        best = min(best, max(loads))
    return best


def test_assign_stages_exact():
    rng = np.random.default_rng(0)
    for L_, S_ in [(4, 2), (6, 3), (8, 4), (5, 5), (7, 2)]:
        costs = rng.uniform(0, 10, L_)
        costs[rng.integers(L_)] = 0.0            # a near-free (p_s) layer
        a = assign_stages(costs, S_)
        assert a.boundaries[0] == 0 and a.boundaries[-1] == L_
        assert all(b2 > b1 for b1, b2 in zip(a.boundaries, a.boundaries[1:]))
        assert abs(float(a.loads.max())
                   - _oracle_makespan(costs, S_)) < 1e-9


def test_assign_stages_capacities():
    costs = np.array([4.0, 4.0, 4.0, 4.0])
    caps = np.array([3.0, 1.0])                  # stage 0 is 3x faster
    a = assign_stages(costs, 2, caps)
    # normalized optimum gives the fast stage 3 layers: max(12/3, 4/1) = 4
    assert a.boundaries == (0, 3, 4), a.boundaries
    with pytest.raises(ValueError):
        assign_stages(costs, 5)                  # more stages than layers


def test_plan_stage_assignment_report():
    sched = _schedule()
    a, rep = plan_stage_assignment(sched, 2)
    assert rep["boundaries"][0] == 0 and rep["boundaries"][-1] == L
    assert rep["makespan_ratio"] <= 1.0 + 1e-9
    costs = layer_live_costs(sched)
    assert np.allclose(a.costs, costs)
    # a top-heavy schedule where live cost beats layer count: layer 0
    # all-p_f (1.0), layers 1..3 all-p_o (0.4) -> best split [0,1),[1,4)
    # with makespan 1.2 vs the uniform split's 1.4
    t = np.full((4 * G, N), P_O, np.int8)
    t[0:G] = P_F
    a2, rep2 = plan_stage_assignment(Schedule(t, 4, G), 2)
    assert rep2["makespan_ratio"] < 1.0
    assert rep2["boundaries"] == [0, 1, 4]


# ------------------------------------------------------------- bubble model
def test_analytic_bubble_fraction():
    # uniform loads reduce to the classic (S-1)/(M+S-1)
    assert abs(analytic_bubble_fraction([2.0, 2.0], 4)
               - 1.0 / 5.0) < 1e-12
    assert abs(analytic_bubble_fraction([1.0, 1.0, 1.0], 6)
               - 2.0 / 8.0) < 1e-12
    assert analytic_bubble_fraction([0.0, 0.0], 4) == 0.0
    # imbalance only ever adds bubble
    assert analytic_bubble_fraction([1.0, 3.0], 4) > \
        analytic_bubble_fraction([2.0, 2.0], 4)


def test_pipeline_recorder_report():
    r = PipelineRecorder()
    r.setup((0, 2, 4), 4)
    for t in range(5):
        r.round(t)
    for _ in range(4):
        r.send()
    rep = r.report()
    assert rep["trace_ok"] and rep["expected_rounds"] == 5


# ------------------------------------------------- hypothesis property test
@given(st.data())
@settings(max_examples=30, deadline=None)
def test_stage_assignment_property(data):
    """Any (costs, n_stages, capacities): the DP returns a contiguous
    non-empty cover of all layers that matches the brute-force bottleneck
    optimum (normalized by capacities when given)."""
    L_ = data.draw(st.integers(min_value=1, max_value=9), label="L")
    S_ = data.draw(st.integers(min_value=1, max_value=L_), label="S")
    costs = data.draw(st.lists(
        st.floats(min_value=0.0, max_value=100.0, allow_nan=False),
        min_size=L_, max_size=L_), label="costs")
    caps = None
    if data.draw(st.booleans(), label="use_caps"):
        caps = data.draw(st.lists(
            st.floats(min_value=0.1, max_value=10.0, allow_nan=False),
            min_size=S_, max_size=S_), label="caps")
    a = assign_stages(costs, S_, caps)
    assert a.boundaries[0] == 0 and a.boundaries[-1] == L_
    assert all(b2 > b1 for b1, b2 in zip(a.boundaries, a.boundaries[1:]))
    assert len(a.boundaries) == S_ + 1
    assert (np.sort(np.unique(a.stage_of)) == np.arange(S_)).all()
    loads = a.loads if caps is None else a.loads / np.asarray(caps)
    assert float(max(loads)) <= _oracle_makespan(costs, S_, caps) + 1e-9
    rep = stage_report(a)
    assert rep["boundaries"] == list(a.boundaries)


# ------------------------------------------------ 8-device multi-axis arms
@pytest.mark.multidevice
def test_multiaxis_parity_8dev_subprocess():
    """Acceptance: (data=4, tensor=2) and (data=2, stage=2) — plus the
    all-three-axes and TP+ZeRO-3 and TP+LoRA compositions — match the
    single-device gated reference to <= 1e-6 over 3 steps on 8 emulated
    devices. Fresh interpreter (host-device count must precede jax init);
    ``-m multidevice``: compiles several shard_map variants, so it runs in
    the multidevice CI job's wall-clock budget."""
    script = os.path.join(os.path.dirname(__file__),
                          "_dist_parity_multiaxis.py")
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + ([os.environ["PYTHONPATH"]]
                      if "PYTHONPATH" in os.environ else [])))
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "PARITY_OK" in proc.stdout, proc.stdout
