"""End-to-end behaviour: the paper's central claims at test scale.

1. D2FT at a 60-70% compute budget fine-tunes better than Random scheduling
   at the same budget (Fig. 1/2 ordering).
2. D2FT workload variance is 0; Random/GShard > 0 (Table I).
3. Sharded-model parity: the policy-constrained model on a host mesh equals
   the unsharded model (run in a subprocess with fake devices).
4. The dry run's perf levers (padded heads, q-chunked attention) are exact
   rewrites.

The kernel path's equivalence to the masked reference is
tests/test_kernel_grads.py's.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import D2FTConfig
from repro.core.baselines import random_schedule
from repro.core.cost_model import compute_cost, workload_variance
from repro.core.d2ft import plan_schedule
from repro.core.schedule import gates_from_schedule
from repro.core.scores import compute_scores, vit_blocks
from repro.data.synthetic import image_batches, make_image_task
from repro.models.vit import ViTConfig, init_vit, vit_loss
from repro.optim.optimizers import sgd
from repro.train.loop import eval_vit, finetune_vit

CFG = ViTConfig(n_layers=2, d_model=96, n_heads=6, d_ff=192, patch=8,
                image_size=32, n_classes=4)
N_MB = 5


def _pretrained(task, steps=25):
    params = init_vit(jax.random.PRNGKey(0), CFG)
    params, _, _ = finetune_vit(params, CFG, sgd(0.05),
                                image_batches(task, 11, 40, steps),
                                steps=steps)
    return params


def _d2ft_schedule_fn(d2):
    def fn(step, params, images, labels):
        if step % 16 != 0:
            return None
        mbs = list(zip(np.split(images, N_MB), np.split(labels, N_MB)))

        def loss_fn(p, mb):
            return vit_loss(p, jnp.asarray(mb[0]), jnp.asarray(mb[1]),
                            CFG)[0]

        bw, fw = compute_scores(loss_fn, params, vit_blocks, mbs,
                                CFG.n_heads)
        return plan_schedule(d2, bw, fw, CFG.n_layers, CFG.n_heads)
    return fn


def test_d2ft_beats_random_at_same_budget():
    task = make_image_task(3, n_classes=4, image_size=32, noise=0.35)
    base = _pretrained(task)
    d2 = D2FTConfig(n_microbatches=N_MB, n_pf=2, n_po=1)
    steps = 30

    p1, _, _ = finetune_vit(jax.tree.map(jnp.copy, base), CFG, sgd(0.05),
                            image_batches(task, 5, 40, steps), steps=steps,
                            schedule_fn=_d2ft_schedule_fn(d2),
                            n_microbatches=N_MB)
    acc_d2ft = eval_vit(p1, CFG, image_batches(task, 7, 40, 5))

    rng = np.random.default_rng(0)
    def random_fn(step, params, images, labels):
        return random_schedule(rng, CFG.n_layers, CFG.n_heads, N_MB, 2, 1)
    p2, _, _ = finetune_vit(jax.tree.map(jnp.copy, base), CFG, sgd(0.05),
                            image_batches(task, 5, 40, steps), steps=steps,
                            schedule_fn=random_fn, n_microbatches=N_MB)
    acc_rand = eval_vit(p2, CFG, image_batches(task, 7, 40, 5))
    assert acc_d2ft >= acc_rand - 0.02, (acc_d2ft, acc_rand)


def test_schedule_budget_and_balance():
    rng = np.random.default_rng(0)
    d2 = D2FTConfig(n_microbatches=5, n_pf=3, n_po=1)
    bw = np.repeat(rng.random((12, 1)) + .1, 5, 1)
    fw = rng.random((12, 5)) + .1
    sched = plan_schedule(d2, bw, fw, 2, 6)
    assert workload_variance(sched.table) == 0.0
    assert abs(compute_cost(sched.table) - 0.68) < 1e-9
    rs = random_schedule(rng, 2, 6, 5, 3, 1)
    assert workload_variance(rs.table) > 0.0


SHARDED_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig, MoEConfig
from jax.sharding import AxisType
from repro.models.transformer import init_model, lm_loss
from repro.sharding.policy import ShardingPolicy

cfg = ModelConfig(name="t", arch_type="moe", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=0, vocab_size=64,
                  moe=MoEConfig(n_experts=4, top_k=2, d_ff=32,
                                capacity_factor=4.0))
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
params = init_model(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
l0, _ = lm_loss(params, cfg, toks, toks)
policy = ShardingPolicy(mesh, cfg)
with mesh:
    pspecs = policy.param_specs(params)
    fn = jax.jit(lambda p, t: lm_loss(p, cfg, t, t, policy=policy)[0],
                 in_shardings=(pspecs, policy.batch_spec(toks.shape)))
    l1 = fn(params, toks)
err = abs(float(l0) - float(l1))
assert err < 2e-3, err
print("sharded parity OK", err)
"""


def test_sharded_model_parity_subprocess():
    """EP MoE + policy-constrained forward == unsharded (8 fake devices)."""
    import os
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    # force CPU: xla_force_host_platform_device_count needs the host
    # platform, and autodetect burns minutes in TPU init when libtpu is
    # installed without hardware
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", SHARDED_PARITY], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "sharded parity OK" in out.stdout


LEVER_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig
from jax.sharding import AxisType
from repro.models.transformer import init_model, forward
from repro.sharding.policy import ShardingPolicy

cfg = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=32,
                  n_heads=5, n_kv_heads=5, d_ff=64, vocab_size=64)
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
params = init_model(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
l0, _ = forward(params, cfg, tokens=toks)
with mesh:
    pol = ShardingPolicy(mesh, cfg, pad_heads=True, max_pad_overhead=2.0)
    assert pol.head_padding() == (8, 8), pol.head_padding()
    l1 = jax.jit(lambda p, t: forward(p, cfg, tokens=t, policy=pol)[0])(
        params, toks)
    pol2 = ShardingPolicy(mesh, cfg, attn_q_chunk=4)
    l2 = jax.jit(lambda p, t: forward(p, cfg, tokens=t, policy=pol2)[0])(
        params, toks)
e1 = float(jnp.max(jnp.abs(l0 - l1)))
e2 = float(jnp.max(jnp.abs(l0 - l2)))
assert e1 < 1e-4 and e2 < 1e-4, (e1, e2)
print("lever parity OK", e1, e2)
"""


def test_perf_lever_parity_subprocess():
    """Padded-head TP and q-chunked attention are EXACT rewrites."""
    import os
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["JAX_PLATFORMS"] = "cpu"       # see test_sharded_model_parity
    out = subprocess.run([sys.executable, "-c", LEVER_PARITY], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "lever parity OK" in out.stdout
