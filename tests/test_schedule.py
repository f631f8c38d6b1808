"""Schedule construction, gates, cost model."""
import numpy as np

from repro.configs.base import D2FTConfig
from repro.core.cost_model import (comm_cost, compute_cost, per_device_load,
                                   workload_variance)
from repro.core.d2ft import capacities, plan_schedule
from repro.core.baselines import (dpruning_schedule, gshard_schedule,
                                  random_schedule)
from repro.core.schedule import (P_F, P_O, P_S, gates_from_schedule,
                                 op_counts)


def _sched(L=4, G=4, N=5, n_pf=3, n_po=1, seed=0):
    rng = np.random.default_rng(seed)
    d2 = D2FTConfig(n_microbatches=N, n_pf=n_pf, n_po=n_po)
    bw = np.repeat(rng.random((L * G, 1)) + .1, N, axis=1)   # magnitude-like
    fw = rng.random((L * G, N)) + .1
    return plan_schedule(d2, bw, fw, L, G), d2


def test_knapsack_schedule_is_balanced():
    sched, d2 = _sched()
    assert workload_variance(sched.table) == 0.0
    counts = op_counts(sched)
    K = sched.table.shape[0]
    assert counts["p_f"] == 3 * K and counts["p_o"] == 1 * K


def test_costs_match_paper_budgets():
    sched, _ = _sched()
    # 3 p_f + 1 p_o of 5 micro-batches: compute (3 + .4)/5 = 68%, comm 70%
    assert abs(compute_cost(sched.table) - 0.68) < 1e-9
    assert abs(comm_cost(sched.table) - 0.70) < 1e-9


def test_gates_shapes_and_semantics():
    sched, _ = _sched(L=3, G=2, N=5)
    mb_of = np.repeat(np.arange(5), 2)
    g_f, g_b = gates_from_schedule(sched, mb_of)
    assert g_f.shape == (3, 10, 2) and g_b.shape == (3, 10, 2)
    t = sched.layer_group_view()
    # g_b only where p_f; g_f where p_f or p_o
    for l in range(3):
        for g in range(2):
            for b in range(10):
                op = t[l, g, mb_of[b]]
                assert float(g_b[l, b, g]) == (1.0 if op == P_F else 0.0)
                assert float(g_f[l, b, g]) == (1.0 if op != P_S else 0.0)


def test_random_baseline_unbalanced_dpruning_balanced_full():
    rng = np.random.default_rng(0)
    rs = random_schedule(rng, 6, 6, 5, 3, 1)
    assert workload_variance(rs.table) > 0.0          # paper Table I
    dp = dpruning_schedule(rng.random(36), 6, 6, 5, keep_fraction=0.6)
    loads = per_device_load(dp.table)
    assert set(np.unique(loads)) <= {0.0, 5.0}        # all-or-nothing
    gs = gshard_schedule(rng, rng.random((36, 5)), 6, 6, capacity=2)
    assert (gs.table != P_S).sum() <= 6 * 6 * 2       # capacity enforced
