"""Host spans, compile counts and device scopes of the fine-tuning loops
(train/spans.py): every step's record holds its phases in loop order,
``plan`` only where a schedule is planned, compilations where they happen;
the compiled steps' fusions carry the program's named scopes."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import D2FTConfig, ModelConfig
from repro.core.d2ft import plan_schedule
from repro.data.synthetic import image_batches, lm_batches, make_image_task
from repro.models.transformer import init_model
from repro.models.vit import ViTConfig, init_vit
from repro.optim.optimizers import adamw, sgd
from repro.train.loop import (TrainLog, finetune, finetune_distributed,
                              finetune_vit, make_train_step, make_vit_step)
from repro.train.spans import PHASES, StepRecord, each_step, span

LM = ModelConfig(name="spans", arch_type="dense", n_layers=2, d_model=32,
                 n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=64)
VIT = ViTConfig(n_layers=2, d_model=32, n_heads=4, d_ff=64, patch=8,
                image_size=16, n_classes=4)
D2 = D2FTConfig(n_microbatches=4, n_pf=2, n_po=1, head_groups=4)
SCOPES = ("embed", "layers", "attn", "mlp", "head", "clip", "optimizer")


def check_records(log: TrainLog, steps: int, plan_steps):
    """Phases in loop order without overlaps, spans covering the loop
    after step 0, ``plan`` exactly on ``plan_steps``, compilations on step
    0 and none on the last step, one step time per step."""
    assert len(log.steps) == len(log.step_times) == len(log.losses) == steps
    for i, rec in enumerate(log.steps):
        names = [n for n, _, _ in rec.spans]
        assert names == sorted(names, key=PHASES.index), (i, names)
        assert names[0] == "batch" and names[-3:] == [
            "dispatch", "wait", "readback"], (i, names)
        assert ("plan" in names) == (i in plan_steps), (i, names)
        ends = [t for _, s, e in rec.spans for t in (s, e)]
        assert ends == sorted(ends), (i, rec.spans)
        assert log.step_times[i] == pytest.approx(
            rec.seconds("dispatch", "wait"))
    later = [sp for rec in log.steps[1:] for sp in rec.spans]
    inside = sum(e - s for _, s, e in later)
    assert inside >= 0.95 * (later[-1][2] - later[0][1])
    assert log.steps[0].n_compiles > 0 and log.steps[0].compile_s > 0
    assert log.steps[-1].n_compiles == 0 and log.steps[-1].compiles == []


def test_vit_records():
    params = init_vit(jax.random.PRNGKey(0), VIT)
    task = make_image_task(0, n_classes=4, image_size=16, noise=0.3)
    scores = np.random.default_rng(0).random((2 * 4, 4))  # [L*G, N]
    sched = plan_schedule(D2, scores, scores, 2, 4)

    def schedule_fn(i, p, images, labels):
        return sched if i in (0, 3) else None

    _, _, log = finetune_vit(params, VIT, sgd(0.05),
                             image_batches(task, 1, 8, 8), steps=6,
                             schedule_fn=schedule_fn, n_microbatches=4)
    check_records(log, 6, plan_steps=(0, 3))
    assert all("h2d" in [n for n, _, _ in r.spans] for r in log.steps)
    assert log.counters == {"replans": 2, "step_builds": 1}


@pytest.mark.parametrize("use_kernel", [False, True])
def test_lm_records(use_kernel):
    """The LM loop on the masked and on the kernel path: the kernel path
    derives its live-slice bounds each step and reuses the one step built
    for them."""
    params = init_model(jax.random.PRNGKey(0), LM)
    batches = lm_batches(0, LM.vocab_size, batch=8, seq=8, steps=5)
    _, _, log = finetune(params, LM, D2, sgd(0.1), batches, steps=4,
                         use_kernel=use_kernel)
    check_records(log, 4, plan_steps=(0,))
    assert not any("h2d" in [n for n, _, _ in r.spans] for r in log.steps)
    assert log.counters == {"replans": 1, "step_builds": 1}


def test_distributed_records_replan_on_refresh():
    from repro.launch.mesh import make_data_mesh
    from repro.launch.parallel import MeshSpec, ParallelConfig

    params = init_model(jax.random.PRNGKey(0), LM)
    batches = lm_batches(0, LM.vocab_size, batch=8, seq=8, steps=6)
    _, _, log = finetune_distributed(
        params, LM, D2, sgd(0.1), batches, steps=6, mesh=make_data_mesh(1),
        parallel=ParallelConfig(mesh=MeshSpec(data=1)), refresh_every=2)
    check_records(log, 6, plan_steps=(0, 2, 4))
    assert [r["step"] for r in log.extras["refreshes"]] == [0, 2, 4]
    assert log.counters == {"replans": 3, "step_builds": 3}


def test_loop_stops_at_steps_and_at_the_end_of_its_batches():
    log = TrainLog()
    assert [i for i, _ in each_step(log, "abc", 2)] == [0, 1]
    assert len(log.steps) == 2
    log = TrainLog()
    assert [b for _, b in each_step(log, "ab", 5)] == ["a", "b"]
    assert [[n for n, _, _ in r.spans] for r in log.steps] == [["batch"]] * 2


def test_span_can_be_left_out_and_compiles_union():
    log = TrainLog(steps=[StepRecord()])
    with span(log, "plan") as s:
        s.keep = False
    with span(log, "prepare"):
        pass
    assert [n for n, _, _ in log.steps[0].spans] == ["prepare"]
    rec = StepRecord(compiles=[("trace", "f", 0, 10), ("trace", "g", 2, 5),
                               ("compile", "f", 20, 30)])
    assert rec.n_compiles == 1
    assert rec.compile_s == pytest.approx(20e-9)


# ------------------------------------------------------------ device scopes
def _scope_shares(compiled_text: str):
    """(fusions with op_name metadata, those under a program scope). The
    CPU backend wraps some single instructions in fusions that carry no
    metadata at all; those name no scope either way."""
    named = scoped = 0
    for line in compiled_text.splitlines():
        if " fusion(" not in line or "calls=" not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        if not m:
            continue
        named += 1
        parts = re.findall(r"[\w.-]+", m.group(1))
        scoped += any(p in SCOPES for p in parts)
    return named, scoped


def test_compiled_fusions_carry_program_scopes():
    opt = sgd(0.1, momentum=0.9)
    p = init_vit(jax.random.PRNGKey(0), VIT)
    gates = (jnp.ones((2, 8, 4)), jnp.ones((2, 8, 4)))
    vit = jax.jit(make_vit_step(VIT, opt, True)).lower(
        p, opt.init(p), jnp.zeros((8, 16, 16, 3)), jnp.zeros((8,), jnp.int32),
        gates).compile().as_text()
    opt = adamw(1e-3)
    p = init_model(jax.random.PRNGKey(0), LM)
    batch = {"tokens": jnp.zeros((8, 8), jnp.int32),
             "labels": jnp.zeros((8, 8), jnp.int32)}
    lm = jax.jit(make_train_step(LM, opt, use_gates=True)).lower(
        p, opt.init(p), batch, gates).compile().as_text()
    for text in (vit, lm):
        named, scoped = _scope_shares(text)
        assert named > 20 and scoped >= 0.9 * named, (named, scoped)
        for scope in ("embed", "attn", "mlp", "head", "clip", "optimizer"):
            assert f"{scope}/" in text or f"({scope})" in text, scope
    assert "transpose(jvp(attn))" in vit
