"""Roofline analysis from dry-run artifacts (``launch/dryrun.py`` JSON).

Per (arch × shape × mesh) JSON produced by repro.launch.dryrun:
  compute term    = HLO_FLOPs_per_device / peak_FLOP/s      (197e12 bf16)
  memory term     = HLO_bytes_per_device / HBM_bw           (819e9 B/s)
  collective term = collective_bytes_per_device / link_bw   (50e9 B/s)

plus MODEL_FLOPS = 6·N·D (train) / 2·N·D (prefill/decode) with N = active
params, and the usefulness ratio MODEL_FLOPS / (HLO_FLOPs × chips) that
catches remat/redundancy waste. FLOPs/bytes are scan-depth-extrapolated by
the dry-run (XLA counts while bodies once — calibrated in tests).

  python -m benchmarks.roofline --dir experiments/dryrun --md experiments/roofline.md
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict

import jax

from repro.configs import get_config
from repro.configs.base import INPUT_SHAPES, ModelConfig
from repro.launch.mesh import peaks

# the dry-run's production mesh is v5e (launch/mesh.py)
V5E = peaks("TPU v5 lite")


def param_counts(cfg: ModelConfig):
    """(total, active) parameter counts via eval_shape (no allocation)."""
    import functools
    from repro.models.transformer import init_model
    shapes = jax.eval_shape(functools.partial(init_model, cfg=cfg),
                            jax.random.PRNGKey(0))
    total = active = 0

    def walk(tree, in_moe=False, name=""):
        nonlocal total, active
        if isinstance(tree, dict):
            for k, v in tree.items():
                walk(v, in_moe or k == "moe", k)
            return
        if isinstance(tree, (list, tuple)):
            for v in tree:
                walk(v, in_moe, name)
            return
        n = 1
        for d in tree.shape:
            n *= d
        total += n
        routed = in_moe and name in ("w_up", "w_gate", "w_down")
        if routed and cfg.moe is not None:
            active += n * cfg.moe.top_k / cfg.moe.n_experts
        else:
            active += n
    walk(shapes)
    return int(total), int(active)


def _attn_context_flops_per_token(cfg: ModelConfig, ctx: int) -> float:
    """QK^T + PV flops for one new token attending over a ctx-long cache,
    summed over layers (window-limited for local layers)."""
    if cfg.n_heads == 0:
        return 0.0
    hd = cfg.resolved_head_dim
    total = 0.0
    for kind in cfg.layer_kinds:
        if kind == "attn_global":
            span = ctx
        elif kind == "attn_local":
            span = min(ctx, cfg.window or ctx)
        else:
            continue
        total += 2 * 2 * span * cfg.n_heads * hd
    return total


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    """Analytic useful FLOPs for the whole step (global, all chips)."""
    shape = INPUT_SHAPES[shape_name]
    total, active = param_counts(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        # + average causal attention context S/2
        attn = tokens * _attn_context_flops_per_token(cfg, shape.seq_len // 2)
        return 2.0 * active * tokens + attn
    # decode: one token per sequence attending over the full cache
    attn = shape.global_batch * _attn_context_flops_per_token(
        cfg, shape.seq_len)
    return 2.0 * active * shape.global_batch + attn


def analyze(rec: Dict) -> Dict:
    chips = 512 if rec["mesh"] == "2x16x16" else 256
    flops_dev = rec.get("flops", rec.get("flops_raw", 0.0))
    bytes_dev = max(rec.get("bytes", 0.0), rec.get("bytes_raw", 0.0))
    coll_dev = sum(rec.get("collectives", {}).values())
    t_comp = flops_dev / V5E["flops_bf16"]
    t_mem = bytes_dev / V5E["hbm_bw"]
    t_coll = coll_dev / V5E["ici_bw"]
    cfg = get_config(rec["arch"])
    mf = model_flops(cfg, rec["shape"])
    useful = mf / max(flops_dev * chips, 1.0)
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    lever = {
        "compute": "cut redundant/remat FLOPs (gated D2FT kernels, fused "
                   "attention) or add chips",
        "memory": "fuse elementwise chains + flash/chunked attention to cut "
                  "HBM traffic; bf16 activations",
        "collective": "reshard to reduce all-gather volume (kv-only gathers,"
                      " 2-axis vocab shard) or overlap collectives with "
                      "compute",
    }[dominant]
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "dominant": dominant, "model_flops": mf,
        "hlo_flops_global": flops_dev * chips, "useful_ratio": useful,
        "temp_gib": rec["memory"]["temp_bytes"] / 2 ** 30,
        "collectives": rec.get("collectives", {}),
        "lever": lever,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--md", default=None)
    ap.add_argument("--csv", default=None)
    ap.add_argument("--variants", action="store_true",
                    help="include hillclimb variant artifacts (tag __*)")
    ap.add_argument("--mesh", default=None, help="filter, e.g. 16x16")
    args = ap.parse_args()
    rows = []
    for path in sorted(glob.glob(os.path.join(args.dir, "*.json"))):
        if "__" in os.path.basename(path) and not args.variants:
            continue
        with open(path) as f:
            rec = json.load(f)
        if args.mesh and rec["mesh"] != args.mesh:
            continue
        rows.append(analyze(rec))

    hdr = ("arch,shape,mesh,t_compute_s,t_memory_s,t_collective_s,dominant,"
           "useful_ratio,temp_gib")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"{r['arch']},{r['shape']},{r['mesh']},{r['t_compute_s']:.4e},"
            f"{r['t_memory_s']:.4e},{r['t_collective_s']:.4e},"
            f"{r['dominant']},{r['useful_ratio']:.3f},{r['temp_gib']:.2f}")
    print("\n".join(lines))
    if args.csv:
        with open(args.csv, "w") as f:
            f.write("\n".join(lines) + "\n")
    if args.md:
        md = ["| arch | shape | mesh | compute (s) | memory (s) | "
              "collective (s) | dominant | useful ratio | lever |",
              "|---|---|---|---|---|---|---|---|---|"]
        for r in rows:
            md.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                f"{r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} | "
                f"{r['t_collective_s']:.3e} | **{r['dominant']}** | "
                f"{r['useful_ratio']:.3f} | {r['lever']} |")
        with open(args.md, "w") as f:
            f.write("\n".join(md) + "\n")
        print(f"\nwrote {args.md}")


if __name__ == "__main__":
    main()
