"""Train launcher.

  python -m repro.launch.train --arch stablelm-3b --d2ft --n-pf 3 \
      --n-po 1 --steps 500 --ckpt ckpt.npz

Without --full it runs the arch's reduced smoke config (CPU tests and quick
drives; Pallas kernels in interpret mode off the TPU). --full runs the
published config on the local devices: a data mesh over ``jax.devices()``
unless --mesh says otherwise. The 256-chip production mesh is the dry-run's
(``launch/dryrun.py``), not this launcher's.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.configs.base import D2FTConfig
from repro.data.synthetic import lm_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_data_mesh, make_host_mesh
from repro.launch.parallel import MeshSpec, ParallelConfig
from repro.models.transformer import init_model
from repro.optim.optimizers import adamw, sgd
from repro.sharding.policy import ShardingPolicy
from repro.train.checkpoints import save_checkpoint
from repro.train.loop import finetune, finetune_distributed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", choices=("sgd", "adamw"), default="adamw")
    ap.add_argument("--d2ft", action="store_true")
    ap.add_argument("--distributed", action="store_true",
                    help="data-parallel D2FT over the mesh's data axis: "
                         "multiple-knapsack device assignment + shard_map "
                         "gated step with the schedule-masked grad psum "
                         "(requires --d2ft)")
    ap.add_argument("--kernel", action="store_true",
                    help="route attention through the compacted Pallas "
                         "gated kernel path (single-device or per-shard "
                         "with --distributed; interpret mode on CPU)")
    ap.add_argument("--mesh", default=None, metavar="data=D,stage=S,tensor=T",
                    help="multi-axis device mesh (launch.parallel.MeshSpec "
                         "syntax, unlisted axes default 1): stage>1 runs "
                         "the GPipe microbatch pipeline with live-cost "
                         "stage packing, tensor>1 shards attention heads / "
                         "FFN columns Megatron-style; requires "
                         "--distributed and enough local devices "
                         "(default: all-data mesh)")
    ap.add_argument("--sync-mode",
                    choices=("masked", "zero", "zero3", "local"),
                    default="masked",
                    help="distributed gradient sync: 'masked' = schedule-"
                         "masked psum (replicated optimizer state), "
                         "'zero' = ZeRO-1 sliced reduce-scatter/all-gather "
                         "with optimizer moments sharded ~1/n_devices, "
                         "'zero3' = fully sharded params with the "
                         "schedule-masked (gate-elided) forward gather, "
                         "'local' = lo-fi zero-sync replicas merged every "
                         "--merge-every steps (requires --elastic; see "
                         "docs/robustness.md)")
    ap.add_argument("--refresh-every", type=int, default=None,
                    help="re-plan the schedule (and re-run the knapsack "
                         "device assigner, rebuild the sync plan) every "
                         "k steps")
    ap.add_argument("--n-pf", type=int, default=3)
    ap.add_argument("--n-po", type=int, default=1)
    ap.add_argument("--n-microbatches", type=int, default=4)
    ap.add_argument("--full", action="store_true",
                    help="published (full-size) config on a data mesh over "
                         "the local devices (--mesh overrides)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--elastic", action="store_true",
                    help="run the fault-tolerant elastic loop "
                         "(train.elastic.finetune_elastic): straggler-"
                         "aware replanning, dropout recovery from step-"
                         "level checkpoints, NaN-burst gradient guard, "
                         "lo-fi sync fallback (docs/robustness.md); "
                         "requires --distributed")
    ap.add_argument("--faults", default=None, metavar="PATH.json",
                    help="inject a deterministic FaultPlan from a JSON "
                         "file (launch.faults.FaultPlan.to_json) into the "
                         "elastic loop — slowdowns, a device dropout, "
                         "gradient bursts, dropped sync rounds")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="elastic step-level checkpoint cadence (steps); "
                         "0 disables periodic checkpoints")
    ap.add_argument("--ckpt-dir", default=None,
                    help="directory for elastic step-level checkpoints "
                         "(default: a fresh temp dir)")
    ap.add_argument("--merge-every", type=int, default=4,
                    help="lo-fi local-mode weight-merge cadence (steps)")
    ap.add_argument("--resume-from", default=None, metavar="CKPT.npz",
                    help="resume the elastic loop from a step-level "
                         "checkpoint (save_train_state format), on the "
                         "original mesh size or a shrunk one")
    args = ap.parse_args()
    enable_compile_cache()

    spec = MeshSpec.parse(args.mesh) if args.mesh else None
    if spec is not None and not args.distributed:
        raise SystemExit("--mesh only applies to the --distributed path")
    if spec is not None and args.elastic and \
            (spec.stage > 1 or spec.tensor > 1):
        raise SystemExit("--elastic runs on a pure data mesh; use "
                         "--mesh data=N (stage=tensor=1)")
    if args.full:
        cfg = get_config(args.arch)
        mesh = spec.build() if spec is not None else make_data_mesh()
    else:
        cfg = get_smoke_config(args.arch)
        mesh = spec.build() if spec is not None else make_host_mesh()
    print(f"arch={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"mesh={dict(mesh.shape)}")

    if cfg.frontend != "none":
        raise SystemExit("text-training launcher; audio/vlm archs use the "
                         "example drivers (examples/)")
    if not args.distributed and (args.sync_mode != "masked"
                                 or args.refresh_every is not None):
        raise SystemExit("--sync-mode/--refresh-every only apply to the "
                         "--distributed path")
    if not args.elastic and (args.faults or args.resume_from
                             or args.sync_mode == "local"):
        raise SystemExit("--faults/--resume-from/--sync-mode local require "
                         "--elastic (the plain distributed loop has no "
                         "fault handling)")
    if args.elastic and not args.distributed:
        raise SystemExit("--elastic requires --distributed")

    d2 = None
    if args.d2ft:
        d2 = D2FTConfig(n_microbatches=args.n_microbatches, n_pf=args.n_pf,
                        n_po=args.n_po,
                        head_groups=max(cfg.n_heads, 1))
        print(f"D2FT: {args.n_pf} p_f + {args.n_po} p_o of "
              f"{args.n_microbatches} micro-batches "
              f"(compute {100 * (args.n_pf + 0.4 * args.n_po) / args.n_microbatches:.0f}%)")

    params = init_model(jax.random.PRNGKey(0), cfg)
    opt = adamw(args.lr) if args.optimizer == "adamw" else sgd(args.lr)
    batches = lm_batches(0, cfg.vocab_size, args.batch, args.seq,
                         args.steps)
    t0 = time.time()
    if args.distributed:
        if d2 is None:
            raise SystemExit("--distributed requires --d2ft")
        if spec is None:
            spec = MeshSpec(data=int(mesh.shape["data"]))
        pconf = ParallelConfig(
            mesh=spec, sync_mode=args.sync_mode, use_kernel=args.kernel,
            microbatches=args.n_microbatches if spec.stage > 1 else 0)
        ndev = mesh.shape["data"]
        if spec.stage > 1 and (args.batch // ndev) % args.n_microbatches:
            raise SystemExit(
                f"pipeline needs the per-data-shard batch divisible by the "
                f"microbatch count: ({args.batch} / {ndev}) % "
                f"{args.n_microbatches} != 0")
        if args.n_microbatches % ndev:
            raise SystemExit(
                f"--distributed needs --n-microbatches divisible by the "
                f"data-mesh size: {args.n_microbatches} % {ndev} != 0 "
                "(equal-sized shard_map shards)")
        if args.batch % args.n_microbatches:
            raise SystemExit(
                f"--batch must be divisible by --n-microbatches: "
                f"{args.batch} % {args.n_microbatches} != 0")
        if args.elastic:
            from repro.launch.faults import FaultPlan
            from repro.train.elastic import ElasticConfig, finetune_elastic
            fp = None
            if args.faults:
                with open(args.faults) as f:
                    fp = FaultPlan.from_json(f.read())
            el = ElasticConfig(refresh_every=args.refresh_every,
                               ckpt_every=args.ckpt_every,
                               ckpt_dir=args.ckpt_dir,
                               merge_every=args.merge_every)
            params, opt_state, log = finetune_elastic(
                params, cfg, d2, opt, batches, steps=args.steps,
                mesh=mesh, sync_mode=args.sync_mode, faults=fp,
                elastic=el, use_kernel=args.kernel,
                resume_from=args.resume_from)
            ev = log.extras["elastic"]
            print(f"elastic: final_mode={ev['final_mode']} "
                  f"devices={ev['n_devices']} "
                  f"guard_skips={ev['guard_skips']} "
                  f"sync_faults={ev['sync_faults']} "
                  f"merges={ev['merges']}")
            for e in ev["events"]:
                print(f"  event: {e}")
            print(f"last checkpoint: {ev['last_ckpt']}")
        else:
            params, opt_state, log = finetune_distributed(
                params, cfg, d2, opt, batches, steps=args.steps,
                mesh=mesh, parallel=pconf,
                refresh_every=args.refresh_every)
        rep, sync = log.extras["rebalance"], log.extras.get("sync")
        print(f"assignment: loads {rep['loads']} spread {rep['spread']} "
              f"imbalance {rep['imbalance']:.3f} "
              f"({len(log.extras.get('refreshes', []))} replans)")
        stages = log.extras.get("stages")
        if stages is not None:
            print(f"pipeline: boundaries {stages['boundaries']} "
                  f"loads {stages['loads']} "
                  f"makespan_ratio {stages['makespan_ratio']:.3f} "
                  f"(vs layer-count {stages['layer_count_boundaries']}) "
                  f"bubble {stages['bubble_fraction']:.3f}")
        if sync is None:
            print("grad sync: none (lo-fi local replicas, merged "
                  f"every {args.merge_every} steps)")
        elif args.sync_mode in ("zero", "zero3"):
            print(f"grad sync ({args.sync_mode}): {sync['fraction']:.0%} "
                  f"all-reduce-equivalent bytes ({sync['n_zero']} leaves "
                  f"partitioned over {ndev} shards, "
                  f"rs {sync['rs_bytes']:.2e}B / "
                  f"ag {sync['ag_bytes']:.2e}B)")
            z3 = log.extras.get("zero3_params")
            if args.sync_mode == "zero3" and z3 is not None:
                print(f"param residency (zero3): "
                      f"{z3['fraction']:.0%} of replicated peak "
                      f"({z3['n_gather_elided']} forward-dead gathers "
                      f"elided, peak unit {z3['peak_unit']})")
        else:
            print(f"grad sync: {sync['fraction']:.0%} of param bytes "
                  f"all-reduced ({sync['n_skipped']} leaves skipped, "
                  f"{sync['n_sliced']} group-sliced)")
    else:
        params, opt_state, log = finetune(params, cfg, d2, opt, batches,
                                          steps=args.steps,
                                          use_kernel=args.kernel)
    dt = time.time() - t0
    print(f"{args.steps} steps in {dt:.1f}s — loss "
          f"{log.losses[0]:.3f} -> {log.losses[-1]:.3f}")
    if args.ckpt:
        save_checkpoint(args.ckpt, {"params": params})
        print(f"saved {args.ckpt}")


if __name__ == "__main__":
    main()
