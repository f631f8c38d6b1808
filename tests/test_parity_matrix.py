"""Cross-path parity matrix: every D2FT execution path must produce the
same optimizer trajectory as the masked reference path.

One parametrized test replaces the per-PR parity spot checks: the masked
(gate_mix) path is the semantic definition, and the Pallas kernel path,
the compacted kernel dispatch, the shard_map distributed step (masked and
ZeRO sync, on a 1-device mesh where every collective is the identity) and
the LoRA variants must all match it to <= 1e-6 over 3 SGD steps. The
8-device distributed parity (where collectives actually move bytes) lives
in tests/_dist_parity.py — this matrix pins the *path* semantics, that
test pins the *collective* semantics.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.lora import init_lora, merge_lora
from repro.core.schedule import (P_F, P_O, P_S, Schedule,
                                 gates_from_schedule, live_slice_bounds)
from repro.data.synthetic import lm_batches, microbatch_assignment
from repro.models.transformer import lm_loss, init_model
from repro.optim.optimizers import sgd
from repro.sharding.sync import SyncSpec, grad_sync_plan
from repro.train.loop import make_distributed_train_step, make_train_step

CFG = ModelConfig(name="matrix", arch_type="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=128)
L, G, N, B, S = 2, 4, 4, 8, 8
STEPS, TOL = 3, 1e-6


def _schedule():
    """Mixed table: a dead subnet, a fully live subnet, partial layers —
    exercises none / sliced / stacked / zero specs and the gate logic."""
    rng = np.random.default_rng(7)
    table = rng.choice([P_F, P_O, P_S], size=(L * G, N),
                       p=[.4, .3, .3]).astype(np.int8)
    table[0] = P_O                          # layer 0 group 0: never backward
    table[G + 2] = P_F                      # layer 1 group 2: fully live
    return Schedule(table, L, G)


@pytest.fixture(scope="module")
def setup():
    sched = _schedule()
    params = init_model(jax.random.PRNGKey(0), CFG)
    batch = next(lm_batches(0, CFG.vocab_size, B, S, 1))
    mb_of = microbatch_assignment(B, N)
    gates = gates_from_schedule(sched, mb_of)
    bounds = live_slice_bounds(sched, mb_of)
    return sched, params, batch, gates, bounds


def _max_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.abs(x - y).max()), a, b)))


def _run(step_fn, params, opt, batch, gates):
    p, s = params, opt.init(params)
    for _ in range(STEPS):
        p, s, _ = step_fn(p, s, batch, gates)
    return p


@pytest.fixture(scope="module")
def reference(setup):
    """Masked gated path — the semantic definition all paths must match."""
    _, params, batch, gates, _ = setup
    opt = sgd(1e-2)
    step = jax.jit(make_train_step(CFG, opt, use_gates=True))
    return _run(step, params, opt, batch, gates)


@pytest.mark.parametrize("path", ["kernel", "compacted", "dist_masked",
                                  "dist_zero", "dist_zero3",
                                  "dist_zero3_streamed"])
def test_parity_matrix(path, setup, reference):
    sched, params, batch, gates, bounds = setup
    opt = sgd(1e-2)
    if path == "kernel":
        step = jax.jit(make_train_step(CFG, opt, use_gates=True,
                                       use_kernel=True))
    elif path == "compacted":
        step = jax.jit(make_train_step(CFG, opt, use_gates=True,
                                       use_kernel=True, live_bounds=bounds))
    else:
        from repro.launch.mesh import make_data_mesh
        from repro.launch.parallel import MeshSpec, ParallelConfig
        mesh = make_data_mesh(1)
        mode = {"dist_masked": "masked", "dist_zero": "zero",
                "dist_zero3": "zero3",
                "dist_zero3_streamed": "zero3"}[path]
        # the streamed arm also runs the chunked shard-resident optimizer
        # sweep (non-divisor chunk so the zero-padding path is exercised)
        streamed = path == "dist_zero3_streamed"
        plan = grad_sync_plan(params, CFG, sched, mode=mode, n_shards=1,
                              elide_gather=opt.elidable)
        step = make_distributed_train_step(
            CFG, opt, mesh, plan, params=params,
            parallel=ParallelConfig(mesh=MeshSpec(data=1), sync_mode=mode,
                                    streamed=streamed,
                                    opt_chunk=48 if streamed else None))
        if mode == "zero3":
            # zero3 holds the params in the plan's shard layout between
            # steps; run layout-in, layout-out and compare canonically
            from repro.sharding.sync import zero_reshard
            got = _run(step, zero_reshard(params, None, plan), opt, batch,
                       gates)
            got = zero_reshard(got, plan, None)
            diff = _max_diff(got, reference)
            assert diff <= TOL, f"{path} diverged from reference: {diff}"
            return
    got = _run(step, params, opt, batch, gates)
    diff = _max_diff(got, reference)
    assert diff <= TOL, f"{path} diverged from masked reference: {diff}"


# ----------------------------------------------------------------- LoRA arm
def _make_lora_step(base, opt, use_kernel, cfg=CFG):
    def step(lora_p, st, batch, gates):
        def loss(lp):
            merged = merge_lora(base, lp, 1.0)
            return lm_loss(merged, cfg, batch["tokens"], batch["labels"],
                           gates=gates, use_kernel=use_kernel)[0]
        g = jax.grad(loss)(lora_p)
        return opt.update(g, st, lora_p)
    return jax.jit(step)


@pytest.fixture(scope="module")
def lora_reference(setup):
    _, params, batch, gates, _ = setup
    opt = sgd(1e-2)
    lora = init_lora(jax.random.PRNGKey(3), params, rank=2)
    step = _make_lora_step(params, opt, use_kernel=False)
    p, s = lora, opt.init(lora)
    for _ in range(STEPS):
        p, s = step(p, s, batch, gates)
    return lora, p


@pytest.mark.parametrize("path", ["lora_kernel", "lora_dist",
                                  "lora_dist_streamed"])
def test_parity_matrix_lora(path, setup, lora_reference):
    """LoRA arm: adapters-only gradients through the gated paths. The
    distributed variant runs the same adapter loss inside shard_map with a
    full-sync plan over the adapter tree (adapters have no head-group
    axis, so they never skip). The streamed variant additionally holds the
    frozen base in the ZeRO-3 shard layout and stream-materializes it
    under the schedule's gather mask before the merge — streaming must
    compose with adapters-only training."""
    sched, params, batch, gates, _ = setup
    lora0, ref = lora_reference
    opt = sgd(1e-2)
    if path == "lora_kernel":
        step = _make_lora_step(params, opt, use_kernel=True)
        p, s = lora0, opt.init(lora0)
        for _ in range(STEPS):
            p, s = step(p, s, batch, gates)
    else:
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_data_mesh
        from repro.sharding.sync import (apply_grad_sync, zero_reshard,
                                         zero3_stream_materialize)

        plan = jax.tree.map(lambda _: SyncSpec("all"), lora0)
        mesh = make_data_mesh(1)
        streamed = path == "lora_dist_streamed"
        if streamed:
            plan3 = grad_sync_plan(params, CFG, sched, mode="zero3",
                                   n_shards=1, elide_gather=opt.elidable)
            base_shards = zero_reshard(params, None, plan3)

        def local(lora_p, st, batch, gates):
            base = zero3_stream_materialize(base_shards, plan3, "data") \
                if streamed else params

            def loss(lp):
                merged = merge_lora(base, lp, 1.0)
                return lm_loss(merged, CFG, batch["tokens"],
                               batch["labels"], gates=gates)[0]
            g = jax.grad(loss)(lora_p)
            g = apply_grad_sync(g, plan, "data")
            return opt.update(g, st, lora_p)

        step = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P("data"), (P(None, "data"), P(None, "data"))),
            out_specs=(P(), P()), check_vma=False))
        p, s = lora0, opt.init(lora0)
        for _ in range(STEPS):
            p, s = step(p, s, batch, gates)
    diff = _max_diff(p, ref)
    assert diff <= TOL, f"{path} diverged from LoRA masked reference: {diff}"


# ------------------------------------------------ block-kernel arch matrix
# The dense matrix above pins the attention kernel; this arm pins the SSD
# (mamba2), RG-LRU (recurrentgemma) and MoE (olmoe) gated block kernels on
# real zoo configs: kernel and compacted dispatch must match the masked
# reference trajectory to <= 1e-6 over 3 SGD steps, with and without LoRA.
BLOCK_ARCHS = ["mamba2-130m", "recurrentgemma-2b", "olmoe-1b-7b"]


def _arch_schedule(L):
    rng = np.random.default_rng(13)
    table = rng.choice([P_F, P_O, P_S], size=(L * G, N),
                       p=[.4, .3, .3]).astype(np.int8)
    table[0, 0] = P_F                       # at least one live backward
    return Schedule(table, L, G)


@pytest.fixture(scope="module", params=BLOCK_ARCHS)
def arch_setup(request):
    from repro.configs import get_smoke_config
    cfg = get_smoke_config(request.param)
    sched = _arch_schedule(cfg.n_layers)
    params = init_model(jax.random.PRNGKey(0), cfg)
    batch = next(lm_batches(0, cfg.vocab_size, B, 16, 1))
    mb_of = microbatch_assignment(B, N)
    gates = gates_from_schedule(sched, mb_of)
    bounds = live_slice_bounds(sched, mb_of)
    opt = sgd(1e-2)
    ref_step = jax.jit(make_train_step(cfg, opt, use_gates=True))
    ref = _run(ref_step, params, opt, batch, gates)
    return cfg, params, batch, gates, bounds, ref


@pytest.mark.parametrize("path", ["kernel", "compacted"])
def test_block_arch_parity(path, arch_setup):
    cfg, params, batch, gates, bounds, ref = arch_setup
    opt = sgd(1e-2)
    step = jax.jit(make_train_step(
        cfg, opt, use_gates=True, use_kernel=True,
        live_bounds=bounds if path == "compacted" else None))
    got = _run(step, params, opt, batch, gates)
    diff = _max_diff(got, ref)
    assert diff <= TOL, (f"{cfg.name} {path} diverged from masked "
                         f"reference: {diff}")


def test_block_arch_parity_lora(arch_setup):
    cfg, params, batch, gates, _, _ = arch_setup
    opt = sgd(1e-2)
    # default targets are attention-only; add the SSD/RG-LRU/MoE in/out
    # projections so adapter grads flow through every gated block kernel
    lora0 = init_lora(jax.random.PRNGKey(3), params, rank=2,
                      targets=("wq", "wk", "wv", "w_in", "w_out", "w_up"))
    ref_step = _make_lora_step(params, opt, use_kernel=False, cfg=cfg)
    ker_step = _make_lora_step(params, opt, use_kernel=True, cfg=cfg)
    p_ref, s_ref = lora0, opt.init(lora0)
    p_ker, s_ker = lora0, opt.init(lora0)
    for _ in range(STEPS):
        p_ref, s_ref = ref_step(p_ref, s_ref, batch, gates)
        p_ker, s_ker = ker_step(p_ker, s_ker, batch, gates)
    diff = _max_diff(p_ker, p_ref)
    assert diff <= TOL, (f"{cfg.name} lora_kernel diverged from LoRA "
                         f"masked reference: {diff}")
