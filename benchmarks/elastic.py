"""Standalone elastic fault-tolerance benchmark driver (8 host CPU
devices).

Must be its own process: ``--xla_force_host_platform_device_count`` is
read once, when jax initializes, so the flag is set here before any jax
import. Run directly::

  PYTHONPATH=src python -m benchmarks.elastic [--n-devices 8]

or through ``python -m benchmarks.run --only elastic``, which subprocesses
this module so the forced device count never leaks into the parent's jax
runtime. Runs the four fault scenarios of
``repro.launch.diststep.measure_elastic`` (straggler replanning, dropout
recovery, NaN-burst guard, lo-fi fallback), writes ``BENCH_elastic.json``
— gated by ``tools/check_bench.py`` — and prints
``name,us_per_call,derived`` CSV rows (no header) on stdout.
"""
import os

# append rather than setdefault: a pre-existing XLA_FLAGS value must not
# swallow the device-count flag
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _FLAG + "=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import json
import sys

BENCH_ELASTIC_JSON = "BENCH_elastic.json"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-devices", type=int, default=8)
    ap.add_argument("--out", default=BENCH_ELASTIC_JSON)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.launch.diststep import measure_elastic
    rec = measure_elastic(args.n_devices)
    s = rec["straggler"]
    print(f"elastic_straggler,{s['wall_s'] * 1e6:.1f},"
          f"mitigation_ratio={s['mitigation_ratio']:.4f};"
          f"makespan={s['makespan']:.3f};"
          f"unmitigated={s['unmitigated_makespan']:.3f};"
          f"straggler_unit_time={s['straggler_unit_time']:.3f};"
          f"capacity_refreshes={s['n_capacity_refreshes']}")
    d = rec["dropout"]
    print(f"elastic_dropout,{d['wall_s'] * 1e6:.1f},"
          f"recovery_steps={d['recovery_steps']};"
          f"ckpt_step={d['ckpt_step']};"
          f"n_devices_after={d['n_devices_after']};"
          f"resume_parity_diff={d['resume_parity_diff']:.3e};"
          f"resume_opt_diff={d['resume_opt_diff']:.3e}")
    g = rec["nan_guard"]
    print(f"elastic_nan_guard,{g['wall_s'] * 1e6:.1f},"
          f"steps_skipped={g['steps_skipped']};"
          f"skip_steps={g['skip_steps']};"
          f"loss_gap={g['loss_gap']:.4f};"
          f"gap_fraction={g['gap_fraction']:.4f}")
    lo = rec["lofi"]
    print(f"elastic_lofi,{lo['wall_s'] * 1e6:.1f},"
          f"fallback_step={lo['fallback_step']};"
          f"sync_drops={lo['sync_drops']};"
          f"n_merges={lo['n_merges']};"
          f"final_mode_local={lo['final_mode_local']};"
          f"loss_drop={lo['loss_drop']:.4f}")
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    print(f"# wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
