"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Needs a TPU with as many chips as the cell asks for; it exits non-zero and
prints no result otherwise (it never falls back to the CPU). The last line
of standard output is the result object; the numbers compared to decide
``correct`` come last on standard error too. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def require_chips(n: int):
    """Refuse anything but a TPU holding at least ``n`` chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise SystemExit(f"needs {n} TPU chips; JAX found {len(devices)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    layout = harness.Layout()
    cell = layout.cell(args.workload)
    require_chips(cell["chips"])
    harness.enable_cache()
    result = harness.run_cell(layout, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
