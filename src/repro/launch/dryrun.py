import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × input shape × mesh).

The lines above MUST run before any other import — jax locks the platform
and device count on first init. The dry-run lowers onto 512 host CPU
devices, so it pins the CPU platform even on a machine with a TPU (whose
backend would otherwise start and then fail to build a 512-device mesh).
Never set those flags globally (smoke tests and benches must see 1 device).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every live pair
  ... [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --distributed-step \
      [--n-devices 8]    # shard_map gated step: per-device collective bytes

Per pair this produces a JSON artifact with:
  * memory_analysis (arg/output/temp bytes per device) of the FULL-depth
    compile — proves the config fits and shards;
  * cost_analysis FLOPs with scan-depth extrapolation (XLA counts a while
    body once, so we lower 1-cycle and 2-cycle variants and extrapolate:
    total = f1 + (f2 - f1) * (n_cycles - 1));
  * per-type collective bytes parsed from compiled HLO (same extrapolation),
    converted to per-device ICI traffic;
  * the sharding-policy report (incl. fallbacks).
"""
import argparse
import json
import time
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import INPUT_SHAPES, SKIPS, get_config, live_pairs
from repro.configs.base import InputShape, ModelConfig
from repro.launch.hlo import collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as S
from repro.models.transformer import layer_groups
from repro.sharding.policy import ShardingPolicy


def _reduced(cfg: ModelConfig, n_cycles: int) -> ModelConfig:
    _, pat, rem = layer_groups(cfg)
    return cfg.replace(n_layers=len(pat) * n_cycles + len(rem))


def _opt_state_shardings(policy: ShardingPolicy, pspecs):
    rep = policy.ns()
    return {"m": pspecs, "v": pspecs, "step": rep}


def lower_pair(cfg: ModelConfig, shape: InputShape, mesh,
               seq_parallel: bool = True, fsdp: bool = True,
               compute_dtype: str = "bfloat16", pad_heads: bool = False,
               attn_q_chunk: int = 0, max_pad_overhead: float = 1.5,
               capacity_factor: float = 0.0):
    """Build (lower-ready jit, args, policy) for one (arch, shape, mesh).

    pad_heads / attn_q_chunk / capacity_factor are the memory and
    collective levers a dry run can try on a published config.
    """
    cfg = cfg.replace(param_dtype=compute_dtype, compute_dtype=compute_dtype)
    if capacity_factor and cfg.moe is not None:
        import dataclasses
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=capacity_factor))
    policy = ShardingPolicy(mesh, cfg, seq_parallel=seq_parallel, fsdp=fsdp,
                            pad_heads=pad_heads, attn_q_chunk=attn_q_chunk,
                            max_pad_overhead=max_pad_overhead)
    if pad_heads and policy.head_padding() is not None:
        # Materialize the padding at "checkpoint load": zero-padded wq/wo
        # head blocks keep the function exact while letting every attention
        # weight shard head-aligned on `model` (trace-time padding leaves
        # the weights replicated and resharded per layer). The dry-run
        # lowers the padded config.
        Hp, Hkvp = policy.head_padding()
        cfg = cfg.replace(n_heads=Hp, n_kv_heads=Hkvp,
                          head_dim=cfg.resolved_head_dim)
        policy = ShardingPolicy(mesh, cfg, seq_parallel=seq_parallel,
                                fsdp=fsdp, attn_q_chunk=attn_q_chunk)
    params = S.param_shapes(cfg)
    pspecs = policy.param_specs(params)

    if shape.kind == "train":
        step, opt = S.make_train_step_fn(cfg, policy)
        opt_state = jax.eval_shape(opt.init, params)
        ospecs = _opt_state_shardings(policy, pspecs)
        batch = S.batch_specs(cfg, shape)
        bspecs = {k: policy.batch_spec(v.shape) for k, v in batch.items()}
        jitted = jax.jit(step, in_shardings=(pspecs, ospecs, bspecs),
                         out_shardings=(pspecs, ospecs, None))
        args = (params, opt_state, batch)
    elif shape.kind == "prefill":
        step = S.make_prefill_fn(cfg, policy)
        batch = S.batch_specs(cfg, shape)
        bspecs = {k: policy.batch_spec(v.shape) for k, v in batch.items()}
        jitted = jax.jit(step, in_shardings=(pspecs, bspecs))
        args = (params, batch)
    else:  # decode
        step = S.make_serve_fn(cfg, policy)
        cache, token, t = S.decode_specs(cfg, shape)
        cspecs = policy.cache_specs(cache)
        jitted = jax.jit(step, in_shardings=(
            pspecs, cspecs, policy.batch_spec(token.shape), policy.ns()),
            out_shardings=(None, cspecs))
        args = (params, cache, token, t)
    return jitted, args, policy


def run_pair(arch: str, shape_name: str, multi_pod: bool = False,
             seq_parallel: bool = True, fsdp: bool = True,
             extrapolate: bool = True, verbose: bool = True,
             variant: str = "baseline", **lever_kw) -> Dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    record: Dict = {"arch": arch, "shape": shape_name,
                    "mesh": "2x16x16" if multi_pod else "16x16",
                    "kind": shape.kind, "seq_parallel": seq_parallel,
                    "fsdp": fsdp, "variant": variant, "levers": {
                        k: str(v) for k, v in lever_kw.items()}}
    with mesh:
        # ---- full-depth compile: memory + proof of lowering
        t0 = time.time()
        jitted, args, policy = lower_pair(cfg, shape, mesh, seq_parallel,
                                          fsdp, **lever_kw)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t0, 1)
        mem = compiled.memory_analysis()
        record["memory"] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        }
        ca = compiled.cost_analysis() or {}
        record["flops_raw"] = float(ca.get("flops", 0.0))
        record["bytes_raw"] = float(ca.get("bytes accessed", 0.0))
        # empty replica_groups={} prints mean "all participants": the
        # model axis is the group for the dominant tensor-parallel
        # collectives, a conservative default for the rest
        ndev_default = mesh.shape.get("model", mesh.size)
        record["collectives_raw"] = collective_bytes(
            compiled.as_text(), default_group_size=ndev_default)
        record["policy"] = policy.report()

        # ---- depth extrapolation (scan bodies counted once by XLA)
        n_cycles, pat, rem = layer_groups(cfg)
        record["n_cycles"] = n_cycles
        if extrapolate and n_cycles > 2:
            per_depth = {}
            for k in (1, 2):
                cfg_k = _reduced(cfg, k)
                jk, ak, _ = lower_pair(cfg_k, shape, mesh, seq_parallel,
                                       fsdp, **lever_kw)
                ck = jk.lower(*ak).compile()
                cak = ck.cost_analysis() or {}
                per_depth[k] = {
                    "flops": float(cak.get("flops", 0.0)),
                    "bytes": float(cak.get("bytes accessed", 0.0)),
                    "coll": collective_bytes(
                        ck.as_text(), default_group_size=ndev_default),
                }
            f1, f2 = per_depth[1]["flops"], per_depth[2]["flops"]
            b1, b2 = per_depth[1]["bytes"], per_depth[2]["bytes"]
            record["flops"] = max(f1 + (f2 - f1) * (n_cycles - 1),
                                  record["flops_raw"])
            record["bytes"] = max(b1 + (b2 - b1) * (n_cycles - 1),
                                  record["bytes_raw"])
            coll = {}
            keys = set(per_depth[1]["coll"]) | set(per_depth[2]["coll"])
            for key in keys:
                c1 = per_depth[1]["coll"].get(key, 0.0)
                c2 = per_depth[2]["coll"].get(key, 0.0)
                raw = record["collectives_raw"].get(key, 0.0)
                # clamp: depth-1/2 compiles can differ structurally
                coll[key] = max(c1 + (c2 - c1) * (n_cycles - 1), raw, 0.0)
            record["collectives"] = coll
            record["per_depth"] = per_depth
        else:
            record["flops"] = record["flops_raw"]
            record["bytes"] = record["bytes_raw"]
            record["collectives"] = record["collectives_raw"]

    if verbose:
        print(f"[{arch} × {shape_name} × {record['mesh']}] "
              f"compile {record['compile_s']}s  "
              f"temp/device {record['memory']['temp_bytes']/2**30:.2f} GiB  "
              f"args/device {record['memory']['argument_bytes']/2**30:.2f} GiB  "
              f"flops/device {record['flops']:.3e}  "
              f"coll bytes {sum(record['collectives'].values()):.3e}")
        print("  " + record["policy"].replace("\n", "\n  "))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-extrapolate", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    help="tag for the output filename (hillclimb runs)")
    ap.add_argument("--pad-heads", action="store_true")
    ap.add_argument("--max-pad-overhead", type=float, default=1.5)
    ap.add_argument("--attn-q-chunk", type=int, default=0)
    ap.add_argument("--capacity-factor", type=float, default=0.0,
                    help="override MoE capacity factor (hillclimb lever)")
    ap.add_argument("--distributed-step", action="store_true",
                    help="lower the shard_map distributed D2FT step on a "
                         "data mesh carved from the host devices and report "
                         "per-device collective bytes (paper-mix schedule "
                         "vs all-p_f baseline)")
    ap.add_argument("--n-devices", type=int, default=8,
                    help="data-mesh size for --distributed-step")
    args = ap.parse_args()

    if args.distributed_step:
        from repro.launch.diststep import measure_distributed_step
        rec = measure_distributed_step(args.n_devices)
        os.makedirs(args.out, exist_ok=True)
        path = os.path.join(args.out,
                            f"distributed_step_{args.n_devices}dev.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        for name, var in rec["variants"].items():
            print(f"[distributed_step × {name} × {args.n_devices}dev] "
                  f"wire bytes {var['wire_bytes']:.3e}  "
                  f"sync-plan fraction {var['sync_plan']['fraction']:.3f}  "
                  f"load spread {var['rebalance']['spread']}")
        z = rec["zero_sync"]
        z3 = rec["zero3"]
        print(f"paper-mix all-reduce bytes at "
              f"{rec['all_reduce_fraction']:.1%} of the all-p_f baseline "
              f"(sync-plan model: {rec['sync_model_fraction']:.1%}); "
              f"zero sync: paper-mix wire {z['paper_mix_wire_fraction']:.1%}, "
              f"uniform wire {z['uniform_wire_fraction']:.1%}, "
              f"opt memory {z['opt_memory_fraction']:.1%}; "
              f"zero3: wire {z3['paper_mix_wire_fraction']:.1%}, "
              f"param residency {z3['residency_fraction']:.1%}, "
              f"{z3['n_gather_elided']} gathers elided "
              f"-> {path}")
        return

    pairs = list(live_pairs()) if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    lever_kw = {}
    if args.pad_heads:
        lever_kw["pad_heads"] = True
        lever_kw["max_pad_overhead"] = args.max_pad_overhead
    if args.attn_q_chunk:
        lever_kw["attn_q_chunk"] = args.attn_q_chunk
    if args.capacity_factor:
        lever_kw["capacity_factor"] = args.capacity_factor
    failures = []
    for arch, shape in pairs:
        if (arch, shape) in SKIPS:
            print(f"[{arch} × {shape}] SKIP: {SKIPS[(arch, shape)]}")
            continue
        for mp in meshes:
            tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
            if args.variant != "baseline":
                tag += f"__{args.variant}"
            if args.skip_existing and os.path.exists(
                    os.path.join(args.out, tag + ".json")):
                print(f"[{tag}] exists, skipping")
                continue
            try:
                # the roofline table is single-pod; the multi-pod pass only
                # needs to prove lowering, so skip its extra compiles
                rec = run_pair(arch, shape, multi_pod=mp,
                               seq_parallel=not args.no_seq_parallel,
                               fsdp=not args.no_fsdp,
                               extrapolate=(not args.no_extrapolate)
                               and not mp,
                               variant=args.variant, **lever_kw)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures.append((tag, repr(e)))
                print(f"[{tag}] FAILED: {e}")
    if failures:
        print(f"\n{len(failures)} failures:")
        for tag, err in failures:
            print(" ", tag, err[:200])
        raise SystemExit(1)
    print("\nAll requested dry-runs compiled successfully.")


if __name__ == "__main__":
    main()
