"""Every cell end to end at smoke size on the CPU, and its faults.

Each run is a subprocess (``run_smoke``) so that it gets a fresh JAX with
as many virtual devices as the cell asks for chips. A fault planted under
the harness has to turn ``correct`` false; an unbroken run has to read
true, with every compared number printed beside its limit."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.smoke import SMOKE_ONLY

WORKLOADS = harness.Layout().spec()["workloads"] + [c for c, _ in SMOKE_ONLY]
CELLS = [w["name"] for w in WORKLOADS]
CHIPS = {w["name"]: w["chips"] for w in WORKLOADS}
FAULTS = [(c, "frozen") for c in CELLS] + \
    [(c, "half_batch") for c in CELLS] + \
    [(c, "no_exchange") for c in CELLS if CHIPS[c] > 1]


def run_smoke(cell, seed, fault=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{CHIPS[cell]}")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.run_smoke", cell, str(seed),
         fault], cwd=harness.REPO, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


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
