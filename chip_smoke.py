"""On-chip smoke run: D2FT fine-tuning through its entry points on a TPU.

  python chip_smoke.py                # one chip: the vit and llm phases
  python chip_smoke.py --four-chips   # four chips: the distributed phase only

Every phase runs in this one process (no JAX children) and goes through
the functions a user calls — ``finetune_vit``, ``finetune`` and
``finetune_distributed`` — with compiled Pallas kernels:

* ``vit``: the paper's own model, ViT-S/16 at its published widths
  (``configs/vit_small_paper.py``: 12 layers, d_model 384, 6 heads, d_ff
  1536, 224x224 images, patch 16, 10 classes). Synthetic images from
  ``--seed``; the scoring pass and the bi-level knapsack plan the schedule
  (``D2FTConfig`` defaults: 5 micro-batches, 3 p_f + 1 p_o); SGD with
  momentum, as in the paper.
* ``llm``: stablelm-3b at its published widths (2560 wide, 32 heads of 80,
  d_ff 6912, vocab 50304) with the depth cut from 32 to 4 layers, so that
  fp32 weights, grads and AdamW state plus the activations of 5 x 400
  tokens fit one 16 GB v5e (a compile for a described v5e puts the masked
  step at 14.5 GiB). AdamW; the schedule comes from ``finetune``'s own
  scoring pass.
* ``four-chips`` (only with ``--four-chips``): the llm model through
  ``finetune_distributed`` on a 4-way data mesh with the kernel route, once
  with ``sync_mode="masked"`` and once with ``"zero3"``, each against
  single-device ``finetune`` on the same global batch and schedule. SGD, so
  the update is linear in the gradients, as in tests/_dist_parity.py.

Each phase first checks that JAX runs on a TPU, makes any fallback off the
kernel route raise, and checks that the compiled step holds a
``tpu_custom_call`` (a compiled Pallas kernel, not an interpreted one).
Then it takes 3 steps on the kernel route and the same steps on
the masked reference route, and compares them:

* tolerance: both routes compute in fp32 at ``highest`` matmul precision
  (the kernels' in-kernel dots inherit it), so they differ only in the
  order of floating-point sums: the online softmax over tiles against one
  softmax, the data-parallel mean against one device's. Each step's loss
  must agree to ``LOSS_RTOL`` (relative) and the final params to
  ``UPDATE_RTOL``, measured as the norm of their difference over the norm
  of the reference's update. A max-abs test on params would fail on
  rounding alone under AdamW: an element whose gradient is zero up to
  rounding gets a full-size step of either sign, while the update as a
  whole agrees.

Informational lines (device, parameter count, compile and warm step
times, losses, parity, peak device memory) go to stdout as JSON, one per
phase. A failing check raises: the process exits non-zero and prints no
result. The last line, on success, is the result object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import stablelm_3b, vit_small_paper  # noqa: E402
from repro.configs.base import D2FTConfig  # noqa: E402
from repro.core.d2ft import plan_schedule  # noqa: E402
from repro.core.schedule import (gates_from_schedule,  # noqa: E402
                                 live_slice_bounds)
from repro.core.scores import compute_scores, vit_blocks  # noqa: E402
from repro.data.synthetic import (image_batches, lm_batches,  # noqa: E402
                                  make_image_task, microbatch_assignment,
                                  split_microbatches)
from repro.kernels import contract  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.hlo import collective_counts  # noqa: E402
from repro.models.transformer import init_model, lm_loss  # noqa: E402
from repro.models.vit import init_vit, vit_loss  # noqa: E402
from repro.optim.optimizers import adamw, sgd  # noqa: E402
from repro.train.loop import (finetune, finetune_distributed,  # noqa: E402
                              finetune_vit, make_distributed_train_step,
                              make_train_step, make_vit_step,
                              plan_from_scores)

LOSS_RTOL = 1e-4
UPDATE_RTOL = 1e-3
PRECISION = "highest"

VIT_LR = 0.01
LLM_LR = 1e-4
FOUR_CHIP_LR = 0.05
LLM_LAYERS = 4          # of stablelm-3b's 32: fits one 16 GB v5e with AdamW
# 8 micro-batches: the 4-way data mesh needs equal micro-batch counts per
# device; 5 p_f + 2 p_o of 8 is the defaults' budget (3 + 1 of 5) rounded
FOUR_CHIP_D2FT = D2FTConfig(n_microbatches=8, n_pf=5, n_po=2)


# ------------------------------------------------------------------ checks
def require_tpu(n_chips: int = 1):
    """The device JAX runs on; fails unless it is a TPU with enough chips."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke needs {n_chips} chips; JAX found "
                         f"{len(devices)}")
    return devices[0]


@contextlib.contextmanager
def no_fallback():
    """Any route that leaves the kernel despite use_kernel=True raises."""
    def fail(kind, reason):
        raise AssertionError(f"{kind} fell back off the kernel: {reason}")

    prev, contract.on_fallback = contract.on_fallback, fail
    try:
        yield
    finally:
        contract.on_fallback = prev


def require(ok: bool, what):
    """A check of the run that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(what)


def compile_step(jitted, args, on_chip: bool) -> dict:
    """Compile the step a phase runs; on the chip, it must hold a kernel."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    secs = time.perf_counter() - t0
    hlo = compiled.as_text()
    if on_chip:
        require("tpu_custom_call" in hlo,
                "the compiled step holds no Pallas kernel (tpu_custom_call)")
    out = {"compile_s": secs, "hlo": hlo}
    mem = compiled.memory_analysis()
    if mem is not None:
        out["compiled_bytes"] = int(
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return out


def n_params(tree) -> int:
    return int(sum(np.prod(np.shape(a)) for a in jax.tree.leaves(tree)))


def parity(p0, p_ker, p_ref, losses_ker, losses_ref) -> dict:
    """Kernel route vs reference: per-step loss and final-param agreement
    (see the module docstring for why params compare by update norm)."""
    require(len(losses_ker) == len(losses_ref), (losses_ker, losses_ref))
    require(np.all(np.isfinite(losses_ker)), losses_ker)
    diff_sq = upd_sq = max_abs = 0.0
    for z, k, r in zip(*(jax.tree.leaves(t) for t in (p0, p_ker, p_ref))):
        z, k, r = (np.asarray(a, np.float64) for a in (z, k, r))
        diff_sq += float(np.sum((k - r) ** 2))
        upd_sq += float(np.sum((r - z) ** 2))
        max_abs = max(max_abs, float(np.max(np.abs(k - r))))
    rec = {
        "loss_rel_diff": max(abs(a - b) / max(abs(b), 1e-30)
                             for a, b in zip(losses_ker, losses_ref)),
        "update_rel_diff": np.sqrt(diff_sq) / max(np.sqrt(upd_sq), 1e-30),
        "param_max_abs_diff": max_abs,
    }
    require(rec["loss_rel_diff"] <= LOSS_RTOL, rec)
    require(rec["update_rel_diff"] <= UPDATE_RTOL, rec)
    return rec


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_to_host(run):
    """Run one fine-tune; return (params on the host, log). Nothing of the
    run stays on the device: at the llm widths its optimizer state alone
    would not leave room for the next run's scoring pass."""
    params, _, log = run()
    return jax.device_get(params), log


def warm_step_s(log):
    return float(np.median(log.step_times[1:])) if len(log.step_times) > 1 \
        else None


# ------------------------------------------------------------------ phases
def vit_phase(cfg=None, *, batch: int = 40, steps: int = 3, seed: int = 0,
              on_chip: bool = True) -> dict:
    """ViT-S/16 through ``finetune_vit``: kernel route vs masked route."""
    cfg = cfg or vit_small_paper.CONFIG
    d2 = D2FTConfig()
    M = d2.n_microbatches
    task = make_image_task(seed, n_classes=cfg.n_classes,
                           image_size=cfg.image_size)
    data = list(image_batches(task, seed + 1, batch, steps))
    params = init_vit(jax.random.PRNGKey(seed), cfg)
    opt = sgd(VIT_LR)

    images, labels = data[0]
    mbs = list(zip(np.split(images, M), np.split(labels, M)))

    def loss_fn(p, mb):
        return vit_loss(p, jnp.asarray(mb[0]), jnp.asarray(mb[1]), cfg)[0]

    with jax.default_matmul_precision(PRECISION):
        bw, fw = compute_scores(loss_fn, params, vit_blocks, mbs, cfg.n_heads)
    sched = plan_schedule(d2, bw, fw, cfg.n_layers, cfg.n_heads)
    mb_of = microbatch_assignment(batch, M)
    bounds = live_slice_bounds(sched, mb_of)

    def schedule_fn(i, *_):
        return sched if i == 0 else None

    with jax.default_matmul_precision(PRECISION), no_fallback():
        step = jax.jit(make_vit_step(cfg, opt, True, use_kernel=True,
                                     live_bounds=bounds))
        comp = compile_step(step, (params, opt.init(params),
                                   jnp.asarray(images), jnp.asarray(labels),
                                   gates_from_schedule(sched, mb_of)),
                            on_chip)
        p_k, log_k = run_to_host(lambda: finetune_vit(
            params, cfg, opt, iter(data), steps, schedule_fn=schedule_fn,
            n_microbatches=M, use_kernel=True))
        p_r, log_r = run_to_host(lambda: finetune_vit(
            params, cfg, opt, iter(data), steps, schedule_fn=schedule_fn,
            n_microbatches=M, use_kernel=False))
    rec = parity(params, p_k, p_r, log_k.losses, log_r.losses)
    return {"phase": "vit", "params": n_params(params), "batch": batch,
            "steps": steps, "compile_s": comp["compile_s"],
            "compiled_bytes": comp.get("compiled_bytes"),
            "warm_step_s": warm_step_s(log_k), "losses": log_k.losses,
            "ref_losses": log_r.losses, **rec,
            "peak_bytes_in_use": peak_bytes()}


def _llm_cfg(layers: int):
    return stablelm_3b.CONFIG.replace(n_layers=layers)


def _scored_schedule(cfg, d2, params, batch):
    """The schedule ``finetune`` plans on its first batch (same call)."""
    return plan_from_scores(
        cfg, d2, params, split_microbatches(batch, d2.n_microbatches),
        lambda p, mb: lm_loss(p, cfg, mb.get("tokens"), mb["labels"])[0])


def llm_phase(cfg=None, *, batch: int = 5, seq: int = 400, steps: int = 3,
              seed: int = 0, on_chip: bool = True) -> dict:
    """stablelm-3b widths through ``finetune``: kernel vs masked route.

    The params are re-made from the seed for each route instead of being
    held across them: at these widths one extra copy of the weights does
    not fit beside a step's working set."""
    cfg = cfg or _llm_cfg(LLM_LAYERS)
    d2 = D2FTConfig()
    data = list(lm_batches(seed, cfg.vocab_size, batch, seq, steps))
    opt = adamw(LLM_LR)
    key = jax.random.PRNGKey(seed)

    params = init_model(key, cfg)
    p0 = jax.device_get(params)
    with jax.default_matmul_precision(PRECISION), no_fallback():
        sched = _scored_schedule(cfg, d2, params, data[0])
        mb_of = microbatch_assignment(batch, d2.n_microbatches)
        step = jax.jit(make_train_step(
            cfg, opt, use_gates=True, use_kernel=True,
            live_bounds=live_slice_bounds(sched, mb_of)))
        comp = compile_step(step, (params, jax.eval_shape(opt.init, params),
                                   data[0], gates_from_schedule(sched, mb_of)),
                            on_chip)
        del params
        p_k, log_k = run_to_host(lambda: finetune(
            init_model(key, cfg), cfg, d2, opt, iter(data), steps=steps,
            use_kernel=True))
        p_r, log_r = run_to_host(lambda: finetune(
            init_model(key, cfg), cfg, d2, opt, iter(data), steps=steps,
            use_kernel=False))
    rec = parity(p0, p_k, p_r, log_k.losses, log_r.losses)
    return {"phase": "llm", "layers": cfg.n_layers, "params": n_params(p0),
            "batch": batch, "seq": seq, "steps": steps,
            "compile_s": comp["compile_s"],
            "compiled_bytes": comp.get("compiled_bytes"),
            "warm_step_s": warm_step_s(log_k), "losses": log_k.losses,
            "ref_losses": log_r.losses, **rec,
            "peak_bytes_in_use": peak_bytes()}


def _distributed_hlo(cfg, d2, opt, params, batch, sync_mode, mesh,
                     parallel) -> str:
    """Compiled HLO of the step ``finetune_distributed`` builds for its
    first batch (same planning calls, same step factory)."""
    from repro.core.assignment import (device_sample_order,
                                       distributed_live_bounds,
                                       plan_device_assignment)
    from repro.sharding.sync import grad_sync_plan, zero_reshard

    ndev = mesh.shape["data"]
    sched = _scored_schedule(cfg, d2, params, batch)
    assignment, _ = plan_device_assignment(sched, ndev)
    B = batch["labels"].shape[0]
    mb_of = microbatch_assignment(B, d2.n_microbatches)
    perm = device_sample_order(assignment, mb_of)
    pbatch = jax.tree.map(lambda a: a[perm], batch)
    gates = gates_from_schedule(sched, mb_of[perm])
    kw = {"mode": "zero3", "n_shards": ndev} if sync_mode == "zero3" else {}
    plan = grad_sync_plan(params, cfg, sched, **kw)
    step = make_distributed_train_step(
        cfg, opt, mesh, plan, parallel=parallel, params=params,
        live_bounds=distributed_live_bounds(sched, mb_of, assignment))
    pvar = zero_reshard(params, None, plan) if sync_mode == "zero3" \
        else params
    return step.lower(pvar, opt.init(params), pbatch, gates).compile() \
        .as_text()


def four_chip_phase(cfg=None, *, batch: int = 8, seq: int = 400,
                    steps: int = 3, seed: int = 0, on_chip: bool = True,
                    n_devices: int = 4) -> dict:
    """``finetune_distributed`` on a 4-way data mesh with the kernel route,
    masked and zero3 sync, each against single-device ``finetune``."""
    from repro.launch.mesh import make_data_mesh
    from repro.launch.parallel import MeshSpec, ParallelConfig

    cfg = cfg or _llm_cfg(LLM_LAYERS)
    d2 = FOUR_CHIP_D2FT
    data = list(lm_batches(seed, cfg.vocab_size, batch, seq, steps))
    opt = sgd(FOUR_CHIP_LR)
    key = jax.random.PRNGKey(seed)
    mesh = make_data_mesh(n_devices)

    p0 = jax.device_get(init_model(key, cfg))
    out = {"phase": "four-chips", "layers": cfg.n_layers,
           "params": n_params(p0), "n_devices": n_devices, "batch": batch,
           "seq": seq, "steps": steps}
    with jax.default_matmul_precision(PRECISION), no_fallback():
        p_r, log_r = run_to_host(lambda: finetune(
            init_model(key, cfg), cfg, d2, opt, iter(data), steps=steps,
            use_kernel=True))
        out["ref_losses"] = log_r.losses
        for mode in ("masked", "zero3"):
            pc = ParallelConfig(mesh=MeshSpec(data=n_devices),
                                sync_mode=mode, use_kernel=True)
            t0 = time.perf_counter()
            hlo = _distributed_hlo(cfg, d2, opt, init_model(key, cfg),
                                   data[0], mode, mesh, pc)
            plan_compile_s = time.perf_counter() - t0
            if on_chip:
                require("tpu_custom_call" in hlo,
                        f"the {mode} step holds no Pallas kernel "
                        "(tpu_custom_call)")
            counts = collective_counts(hlo)
            if mode == "masked":
                require(counts.get("all-reduce", 0) > 0, counts)
            else:
                require(counts.get("reduce-scatter", 0) > 0
                        and counts.get("all-gather", 0) > 0, counts)
            p_d, log_d = run_to_host(lambda: finetune_distributed(
                init_model(key, cfg), cfg, d2, opt, iter(data), steps=steps,
                mesh=mesh, parallel=pc))
            out[mode] = {"collectives": counts,
                         "plan_compile_s": plan_compile_s,
                         "warm_step_s": warm_step_s(log_d),
                         "losses": log_d.losses,
                         **parity(p0, p_d, p_r, log_d.losses, log_r.losses)}
    out["peak_bytes_in_use"] = peak_bytes()
    return out


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip distributed phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic data and the weights")
    args = ap.parse_args(argv)

    n_chips = 4 if args.four_chips else 1
    dev = require_tpu(n_chips)
    enable_compile_cache()
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "devices": len(jax.devices()), "jax": jax.__version__}),
          flush=True)
    if args.four_chips:
        phases = [lambda: four_chip_phase(seed=args.seed, n_devices=n_chips)]
    else:
        phases = [lambda: vit_phase(seed=args.seed),
                  lambda: llm_phase(seed=args.seed)]
    for phase in phases:
        require_tpu(n_chips)
        print(json.dumps(phase()), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
