"""Every cell end to end at smoke size on the CPU, and its faults.

Each run is a subprocess (``smoke.run_smoke``) so that it gets a fresh JAX
with as many virtual devices as the cell asks for chips. A fault planted under
the harness has to turn ``correct`` false; an unbroken run has to read
true, with every compared number printed beside its limit."""
import pytest

from bench.tests.smoke import run_smoke, smoke_cells

WORKLOADS = smoke_cells()
CELLS = [w["name"] for w in WORKLOADS]
CHIPS = {w["name"]: w["chips"] for w in WORKLOADS}
FAULTS = [(c, "frozen") for c in CELLS] + \
    [(c, "half_batch") for c in CELLS] + \
    [(c, "no_exchange") for c in CELLS if CHIPS[c] > 1]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    res, err = run_smoke(cell, 2 ** 33 + 7)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"step_ms", "peak_hbm_gb", "setup_s"}
    assert "compilations in the window: 0" in err
    for name, ch in res["checks"].items():
        assert f"check {name}:" in err


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_turns_correct_false(cell, fault):
    res, _ = run_smoke(cell, 5, fault)
    assert res["correct"] is False, res["checks"]
