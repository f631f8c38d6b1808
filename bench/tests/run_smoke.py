"""One smoke-size run of a cell on the CPU, optionally with a planted fault.

    python -m bench.tests.run_smoke <cell> <seed> \
        [frozen|half_batch|no_exchange] [--benchmark BENCHMARK.json]

Skips only the harness's look for a chip: the rest of a run (feed, entry
point, window, metrics, the check against the reference) is the real one.
The faults break the timed path underneath the harness:

* ``frozen``: the optimizer returns the state it was given;
* ``half_batch``: the loss covers the first half of each batch, its mean
  taken over that half;
* ``no_exchange``: the reduce-scatter between chips keeps each chip's own
  gradient shard (scaled as if it were the sum) instead of summing.

``--benchmark`` names the layout to shrink (``smoke.smoke_layout``), the
repository's by default. Prints the result object as its last line.
"""
import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def plant(fault: str):
    import jax
    from repro.optim import optimizers
    from repro.train import loop

    if fault == "frozen":
        for name in ("sgd", "adamw"):
            make = getattr(optimizers, name)

            def frozen(*a, _make=make, **k):
                opt = _make(*a, **k)
                return opt._replace(update=lambda g, s, p: (p, s))
            setattr(optimizers, name, frozen)
    elif fault == "half_batch":
        def halve(fn, rows_of):
            def half(params, *args, **kw):
                n = rows_of(args) // 2
                args = tuple(a[:n] if hasattr(a, "shape") and a.ndim else a
                             for a in args)
                if kw.get("gates") is not None:
                    kw["gates"] = tuple(g[:, :n] for g in kw["gates"])
                return fn(params, *args, **kw)
            return half
        loop.lm_loss = halve(loop.lm_loss, lambda a: a[1].shape[0])
        loop.vit_loss = halve(loop.vit_loss, lambda a: a[0].shape[0])
    elif fault == "no_exchange":
        def local_only(x, axis_name, *, scatter_dimension=0, tiled=False):
            k = jax.lax.psum(1, axis_name)
            n = x.shape[scatter_dimension] // k
            idx = jax.lax.axis_index(axis_name)
            return jax.lax.dynamic_slice_in_dim(
                x, idx * n, n, axis=scatter_dimension) * k
        jax.lax.psum_scatter = local_only
    elif fault:
        raise ValueError(fault)


def main(argv):
    from bench import harness
    from bench.tests.smoke import BENCHMARK, smoke_layout
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seed", type=int)
    ap.add_argument("fault", nargs="?", default="")
    ap.add_argument("--benchmark", type=Path, default=BENCHMARK)
    args = ap.parse_args(argv)
    plant(args.fault)
    with tempfile.TemporaryDirectory() as tmp:
        layout = smoke_layout(Path(tmp), args.benchmark)
        result = harness.run_cell(layout, args.cell, args.seed, 1.0, False,
                                  T_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
