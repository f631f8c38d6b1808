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


@pytest.mark.parametrize("cell", CELLS)
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
