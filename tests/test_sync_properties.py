"""Property-based invariants for the sync plan and the device assigner
(hypothesis; skipped cleanly when it is not installed — CI installs it via
requirements.txt, see conftest.optional_hypothesis).

* ``grad_sync_plan`` covers every param leaf exactly once, whatever the
  schedule, in masked, zero and zero3 modes;
* zero-partition slices tile the axis: the shard layout is a bijection of
  the canonical element order, shards are equal-sized, runs cover every
  group exactly once;
* the zero3 partition + schedule-masked gather round-trips every leaf
  bit-exactly: reassembling the per-device shards of every gathered run
  reproduces the canonical content, elided runs are exactly the
  forward-dead ones, and the gather mask covers the scatter mask;
* the knapsack assigner respects capacities whenever they are feasible and
  places every micro-batch exactly once.
"""
import numpy as np

import jax

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.configs.base import ModelConfig
from repro.core.assignment import assign_microbatches
from repro.core.schedule import P_F, P_O, P_S, Schedule
from repro.models.transformer import init_model
from repro.sharding.sync import (SyncSpec, _zero_layout_perm, _zero_runs,
                                 grad_sync_plan, sync_byte_report)

CFG = ModelConfig(name="prop", arch_type="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=128)
L, G = 2, 4
PARAMS = init_model(jax.random.PRNGKey(0), CFG)
IS_SPEC = dict(is_leaf=lambda x: isinstance(x, SyncSpec))


@st.composite
def schedule_tables(draw):
    n_mb = draw(st.integers(1, 6))
    cells = draw(st.lists(st.sampled_from([P_F, P_O, P_S]),
                          min_size=L * G * n_mb, max_size=L * G * n_mb))
    return Schedule(np.asarray(cells, np.int8).reshape(L * G, n_mb), L, G)


@settings(max_examples=40, deadline=None)
@given(schedule_tables(), st.sampled_from(["masked", "zero", "zero3"]),
       st.sampled_from([1, 2, 4, 8]))
def test_plan_covers_every_leaf_exactly_once(sched, mode, n_shards):
    plan = grad_sync_plan(PARAMS, CFG, sched, mode=mode, n_shards=n_shards)
    specs = jax.tree.leaves(plan, **IS_SPEC)
    assert all(isinstance(s, SyncSpec) for s in specs)
    # same treedef as the params: one spec per leaf, no leaf missed
    assert jax.tree.structure(plan, **IS_SPEC) == jax.tree.structure(PARAMS)
    rep = sync_byte_report(plan, PARAMS, n_shards=n_shards)
    assert rep["n_leaves"] == len(jax.tree.leaves(PARAMS))
    assert 0.0 <= rep["fraction"] <= 1.0


@settings(max_examples=40, deadline=None)
@given(schedule_tables(), st.sampled_from([1, 2, 4, 8]),
       st.booleans())
def test_zero_partition_tiles_every_axis(sched, n_shards, elide):
    plan = grad_sync_plan(PARAMS, CFG, sched, mode="zero",
                          n_shards=n_shards, elide_gather=elide)

    def check(spec, shape):
        gs = shape[spec.axis] // len(spec.live)
        runs = _zero_runs(spec)
        # runs tile the group axis exactly once, in order
        assert [r[2] for r in runs][0] == 0
        assert all(a[3] == b[2] for a, b in zip(runs, runs[1:]))
        assert runs[-1][3] == len(spec.live)
        assert gs * len(spec.live) == shape[spec.axis]
        # the shard layout is a bijection of the canonical order
        perm = _zero_layout_perm(spec, shape[spec.axis])
        assert np.array_equal(np.sort(perm), np.arange(shape[spec.axis]))
        # equal shards: every device owns exactly 1/k of the axis
        assert shape[spec.axis] % spec.shards == 0

    def rec(p, spec):
        if isinstance(spec, SyncSpec):
            if spec.mode == "zero":
                check(spec, p.shape)
            elif spec.mode == "zero_stacked":
                for sub in spec.per_cycle:
                    check(sub, p.shape[1:])
            return
        if isinstance(spec, dict):
            for k in spec:
                rec(p[k], spec[k])
        else:
            for pi, si in zip(p, spec):
                rec(pi, si)

    rec(PARAMS, plan)


@settings(max_examples=40, deadline=None)
@given(schedule_tables(), st.sampled_from([1, 2, 4, 8]))
def test_zero3_partition_gather_roundtrips_bit_exact(sched, n_shards):
    """Host-side emulation of the zero3 dataflow on every leaf: lay the
    canonical array out in shard order (``_zero_layout_perm``, what
    ``zero_reshard`` applies), split it into the k device shards, then
    rebuild the full view the way ``zero3_materialize`` does — walking the
    shard by run offsets, concatenating the k sub-chunks of gathered runs,
    zeros for elided runs. The result must equal the canonical array with
    exactly the elided runs zeroed, bit for bit — this pins the run-offset
    arithmetic of the runtime gather against the layout permutation the
    resharder uses. Also: gather ⊇ scatter on every leaf."""
    plan = grad_sync_plan(PARAMS, CFG, sched, mode="zero3",
                          n_shards=n_shards)

    def emulate(x, spec):
        ax, k = spec.axis, spec.shards
        n = x.shape[ax]
        gs = n // len(spec.live)
        layout = np.take(x, _zero_layout_perm(spec, n), axis=ax)
        shard_len = n // k
        shards = [np.take(layout, np.arange(d * shard_len,
                                            (d + 1) * shard_len), axis=ax)
                  for d in range(k)]
        parts, off = [], 0
        expect = x.copy()
        for _, gather, s, e in _zero_runs(spec):
            plen = (e - s) * gs // k
            if gather:
                parts.append(np.concatenate(
                    [np.take(sh, np.arange(off, off + plen), axis=ax)
                     for sh in shards], axis=ax))
            else:
                shape = list(x.shape)
                shape[ax] = (e - s) * gs
                parts.append(np.zeros(shape, x.dtype))
                idx = [slice(None)] * x.ndim
                idx[ax] = slice(s * gs, e * gs)
                expect[tuple(idx)] = 0
            off += plen
        got = np.concatenate(parts, axis=ax) if len(parts) > 1 else parts[0]
        np.testing.assert_array_equal(got, expect)
        assert all(g or not l for l, g in zip(spec.live, spec.gather))

    def rec(p, spec):
        if isinstance(spec, SyncSpec):
            if spec.mode == "zero":
                emulate(np.asarray(p), spec)
            elif spec.mode == "zero_stacked":
                arr = np.asarray(p)
                for c, sub in enumerate(spec.per_cycle):
                    emulate(arr[c], sub)
            return
        if isinstance(spec, dict):
            for k in spec:
                rec(p[k], spec[k])
        else:
            for pi, si in zip(p, spec):
                rec(pi, si)

    rec(PARAMS, plan)


# chunked() vs the whole-shard update, in ulp of each leaf's largest
# magnitude (see the test below for why it is not zero)
CHUNKED_ULPS = 4


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(["sgd", "adamw"]), st.integers(1, 97),
       st.integers(0, 2 ** 16), st.integers(1, 3))
def test_chunked_optimizer_update_bit_identical(kind, chunk, seed, steps):
    """``chunked(opt, chunk)`` must match ``opt`` — params and every
    moment — to within ``CHUNKED_ULPS`` ulp of each leaf's largest
    magnitude, for any chunk size (divisor or not: the zero-padded tail
    chunk must not perturb anything) over multiple steps. This is the
    correctness contract of the streamed ZeRO-3 shard-resident optimizer
    sweep: chunking is a memory schedule, and the update formula is the
    same elementwise one. It is not bit-identical: under ``jax.lax.map``
    XLA fuses the elementwise update differently (e.g. whether
    ``b1 * m + (1 - b1) * g`` becomes a fused multiply-add), which moves
    a result by one rounding; on the CPU that measured at most 0.6 ulp of
    the leaf scale over sgd/adamw, chunks 1-64 and 3 steps. The step
    counter and any integer state stay exact."""
    from repro.optim.optimizers import adamw, chunked, sgd
    opt = sgd(1e-2, momentum=0.9, weight_decay=1e-3) if kind == "sgd" \
        else adamw(1e-3, weight_decay=1e-2)
    copt = chunked(opt, chunk)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(3,), (5, 7), (2, 3, 4)]
    params = {"a": [jax.random.normal(keys[i], s)
                    for i, s in enumerate(shapes)],
              "b": jax.random.normal(keys[3], (11,))}
    p_ref, s_ref = params, opt.init(params)
    p_chk, s_chk = params, copt.init(params)
    for t in range(steps):
        grads = jax.tree.map(
            lambda _, k=keys[4 + t % 2], t=t:
                jax.random.normal(jax.random.fold_in(k, t), _.shape),
            params)
        p_ref, s_ref = opt.update(grads, s_ref, p_ref)
        p_chk, s_chk = copt.update(grads, s_chk, p_chk)

    def close(x, y):
        x, y = np.asarray(x), np.asarray(y)
        if not np.issubdtype(x.dtype, np.floating):
            return bool((x == y).all())
        scale = np.finfo(x.dtype).eps * float(np.abs(x).max())
        return float(np.abs(x - y).max()) <= CHUNKED_ULPS * scale

    assert all(jax.tree.leaves(jax.tree.map(close, p_ref, p_chk)))
    assert all(jax.tree.leaves(jax.tree.map(close, s_ref, s_chk)))


@st.composite
def assignment_instances(draw):
    n_dev = draw(st.integers(1, 4))
    n_items = draw(st.integers(1, 12))
    costs = draw(st.lists(st.floats(0.0, 5.0), min_size=n_items,
                          max_size=n_items))
    return np.asarray(costs), n_dev


@settings(max_examples=40, deadline=None)
@given(assignment_instances())
def test_assigner_places_every_item_within_feasible_capacity(inst):
    costs, n_dev = inst
    # generous per-device budget: total cost fits on every single device,
    # so the LPT seed can never be forced into a violation
    cap = float(costs.sum()) + 1.0
    a = assign_microbatches(costs, n_dev, capacities=cap)
    assert a.device_of.min() >= 0 and a.device_of.max() < n_dev
    assert len(a.device_of) == len(costs)                # each item placed
    assert np.allclose(a.loads.sum(), costs.sum())       # exactly once
    assert (a.loads <= cap + 1e-9).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(1, 4),
       st.lists(st.floats(0.0, 5.0), min_size=1, max_size=4))
def test_assigner_equal_counts(n_dev, per_dev, base_costs):
    n_items = n_dev * per_dev
    costs = np.resize(np.asarray(base_costs), n_items)
    a = assign_microbatches(costs, n_dev, equal_counts=True)
    assert (a.counts == per_dev).all()
