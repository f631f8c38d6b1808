"""The control fails the check: the reference computed one precision step
below the configuration's, put in the program's place, reads above the
limits on at least one compared number; so do the half-batch fault and,
on ``plan_gap``, a plan with a wrong p_o pick.

Smoke sizes. On a TPU the configurations keep their stated float32 at
``highest``, whose control runs at ``high`` (three bf16 passes). The CPU
ignores matmul precision, so there the smoke configurations state
``default``, whose control is bfloat16: that exercises the same
machinery. The chip readings at the cells' own sizes are in PERF.md."""
import json
import time

import jax
import numpy as np
import pytest

from bench import calibrate, correct, harness
from bench.tests.smoke import smoke_layout

CELLS = [w["name"] for w in harness.Layout().spec()["workloads"]
         if w["chips"] == 1]
# cells that plan a schedule, and so have a plan_gap
PLANNED = [c for c in CELLS
           if harness.Layout().traffic(c).get("d2ft", True)]


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    lay = smoke_layout(tmp_path_factory.mktemp("layout"))
    if jax.default_backend() != "tpu":
        for path in (lay.root / "configs").glob("*.json"):
            c = json.loads(path.read_text())
            c["precision"]["matmul"] = "default"
            path.write_text(json.dumps(c))
    return lay


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_half_batch_fail_a_limit(layout, cell):
    run = harness.drive(layout, cell, 17, 0.2, None, time.perf_counter(),
                        harness.CompileCounter())
    limits = layout.traffic(cell)["limits"]
    for got in (calibrate.reading(layout, run),
                calibrate.reading(layout, run, half=True)):
        assert any(got[k] > limits[k] for k in limits), (got, limits)


@pytest.mark.parametrize("cell", PLANNED)
def test_misplan_fails_plan_gap(layout, cell):
    run = harness.drive(layout, cell, 19, 0.2, None, time.perf_counter(),
                        harness.CompileCounter())
    checks = correct.check(layout, run)
    assert checks["plan_gap"]["value"] <= checks["plan_gap"]["limit"]
    wrong = correct.plan_gap(correct.misplan(run.table),
                             run.reference["scores"], run.traffic)
    assert wrong > layout.traffic(cell)["limits"]["plan_gap"]


def test_plan_gap_by_hand():
    P_F, P_O, P_S = correct.P_F, correct.P_O, correct.P_S
    t = {"n_pf": 2, "n_po": 1}
    back = np.array([[1.0]])                        # [L, G], one subnet
    fwd = np.array([[[5.0, 4.0, 3.0, 2.0]]])        # [L, G, M]
    best = np.array([[[P_F, P_F, P_O, P_S]]])
    assert correct.plan_gap(best, (back, fwd), t) == 0.0
    # p_o on the micro-batch scoring 2 where 3 was free: short by 1/3
    worse = np.array([[[P_F, P_F, P_S, P_O]]])
    assert correct.plan_gap(worse, (back, fwd), t) == pytest.approx(1 / 3)
    assert (correct.misplan(best) == worse).all()


def test_reference_group_sums_decide_the_plan_scores():
    """A reference module that defines ``group_sums`` splits its own
    layers among the groups, and ``plan_gap`` is held to those scores; a
    module without it gets the column and row rule (a weight named in
    neither goes whole to every group)."""
    import types

    import jax.numpy as jnp

    def own(block, G, power):       # column g of the fused weight is g's
        return jnp.abs(block["w_in"][0]) ** power

    def module(**extra):
        return types.SimpleNamespace(
            blocks=lambda p: [p], rows=lambda b: b.shape[0],
            take=lambda b, lo, hi: b[lo], **extra)

    params = {"w_in": jnp.array([[1.0, 3.0]])}
    batch = np.array([[[2.0, 0.0]], [[0.0, 1.0]]])   # one row a micro-batch
    gates = (np.ones((1, 2, 2)), np.ones((1, 2, 2)))

    def vg(p, b, gf, gb):           # the "gradient" of micro-batch b
        return 0.0, {"w_in": p["w_in"] * b}

    back, fwd = correct.subnet_scores(module(group_sums=own), vg, params,
                                      batch, gates, 2, 2)
    np.testing.assert_allclose(back, [[1.0, 3.0]])
    np.testing.assert_allclose(fwd, [[[4.0, 0.0], [0.0, 9.0]]])
    plain = correct.subnet_scores(module(), vg, params, batch, gates, 2, 2)
    np.testing.assert_allclose(plain[1], [[[4.0, 9.0], [4.0, 9.0]]])
    # p_o on micro-batch 1 in both groups: group 0's best was micro-batch 0
    P_O, P_S = correct.P_O, correct.P_S
    table = np.array([[[P_S, P_O], [P_S, P_O]]])
    t = {"n_pf": 0, "n_po": 1}
    assert correct.plan_gap(table, (back, fwd), t) == 1.0
    assert correct.plan_gap(table, plain, t) == 0.0
