"""Standalone distributed-step benchmark driver (8 host CPU devices).

Must be its own process: ``--xla_force_host_platform_device_count`` is read
once, when jax initializes, so the flag is set here before any jax import.
Run directly::

  PYTHONPATH=src python -m benchmarks.dist_step [--n-devices 8] [--kernel]

or through ``python -m benchmarks.run --only distributed_step``, which
subprocesses this module so the forced device count never leaks into the
parent's jax runtime. Writes ``BENCH_distributed_step.json`` and prints
``name,us_per_call,derived`` CSV rows (no header) on stdout.
"""
import os

# append rather than setdefault: a pre-existing XLA_FLAGS value must not
# swallow the device-count flag (make_data_mesh refuses short meshes, but
# failing to even create 8 devices here should never happen silently)
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _FLAG + "=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import json
import sys

BENCH_DISTRIBUTED_STEP_JSON = "BENCH_distributed_step.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-devices", type=int, default=8)
    ap.add_argument("--time-steps", type=int, default=3,
                    help="executed steps per variant for wall time "
                         "(0 = lower/compile only)")
    ap.add_argument("--kernel", action="store_true",
                    help="route the local shards through the compacted "
                         "Pallas kernel path (interpret mode on CPU)")
    ap.add_argument("--out", default=BENCH_DISTRIBUTED_STEP_JSON)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.launch.diststep import measure_distributed_step
    rec = measure_distributed_step(args.n_devices, use_kernel=args.kernel,
                                   time_steps=args.time_steps)
    for name, var in rec["variants"].items():
        reb = var["rebalance"]
        print(f"distributed_step_{name},"
              f"{var.get('wall_us_per_step', 0.0):.1f},"
              f"wire_bytes={var['wire_bytes']:.3e};"
              f"all_reduce_bytes={var['all_reduce_bytes']:.3e};"
              f"sync_fraction={var['sync_plan']['fraction']:.3f};"
              f"load_spread={reb['spread']};imbalance={reb['imbalance']}")
    print(f"distributed_step_comm_saving,0.0,"
          f"all_reduce_fraction={rec['all_reduce_fraction']:.3f};"
          f"sync_model_fraction={rec['sync_model_fraction']:.3f};"
          f"paper_target<=0.60")
    z = rec["zero_sync"]
    print(f"zero_sync,0.0,"
          f"paper_mix_wire_fraction={z['paper_mix_wire_fraction']:.3f};"
          f"masked_wire_fraction={z['paper_mix_masked_wire_fraction']:.3f};"
          f"uniform_wire_fraction={z['uniform_wire_fraction']:.3f};"
          f"uniform_masked_n_skipped={z['uniform_masked_n_skipped']};"
          f"opt_memory_fraction={z['opt_memory_fraction']:.4f}")
    z3 = rec["zero3"]
    print(f"zero3,0.0,"
          f"paper_mix_wire_fraction={z3['paper_mix_wire_fraction']:.3f};"
          f"residency_fraction={z3['residency_fraction']:.3f};"
          f"n_gather_elided={z3['n_gather_elided']};"
          f"n_all_gather_ops={z3['n_all_gather_ops']};"
          f"opt_memory_fraction={z3['opt_memory_fraction']:.4f};"
          f"residency_target<=0.50")
    ov = rec["overlap"]
    print(f"overlap,0.0,"
          f"exposed_collective_fraction="
          f"{ov['exposed_collective_fraction']:.3f};"
          f"streamed_residency_fraction="
          f"{ov['streamed_residency_fraction']:.4f};"
          f"peak_agreement={ov['peak_agreement']:.4f};"
          f"double_buffer_fraction={ov['double_buffer_fraction']:.3f};"
          f"wire_ratio_vs_unstreamed={ov['wire_ratio_vs_unstreamed']:.4f};"
          f"exposed_target<1.0")
    pp = rec["pipeline"]
    print(f"pipeline,{pp.get('wall_us_per_step', 0.0):.1f},"
          f"mesh=data{pp['mesh']['data']}xstage{pp['mesh']['stage']};"
          f"boundaries={pp['boundaries']};"
          f"makespan_ratio={pp['makespan_ratio']:.3f};"
          f"bubble_fraction={pp['bubble_fraction']:.3f};"
          f"layer_count_bubble_fraction="
          f"{pp['layer_count_bubble_fraction']:.3f};"
          f"trace_ok={pp['trace']['trace_ok']};"
          f"ratio_target<0.95")
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    print(f"# wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
