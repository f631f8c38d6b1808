"""Readings that the limits of ``correct`` are set from (on the chip).

    python bench/calibrate.py --workload <cell> --seeds 101,102,... \
        [--control-seeds 3] [--matmul default] [--out calib.jsonl]

In one process, for each seed: a run of the cell with a short window and
its compared numbers (the program against the reference: the lower
readings). Then, on the first ``--control-seeds`` seeds, the same numbers
for the control (the reference one precision step below the
configuration's, put in the program's place), for the half-batch fault
(the reference over the first half of each batch, the mean taken over
that half) and, in a D2FT cell, ``plan_gap`` of the planning fault
``correct.misplan`` (the run's schedule with p_o and a p_s swapped in
every subnet). A step that leaves the state unchanged reads 1 on
``update_gap`` by definition and needs no run. ``--matmul`` runs the
program and the control at another matmul precision than the
configuration states (the reference stays at ``highest``). The
benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
WINDOW_S = 2.0      # a short window: the readings come from the warm-up
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def reading(layout, run, **variant) -> dict:
    """The compared numbers of a reference variant against the reference,
    on a finished run's inputs and schedule."""
    from bench import correct
    _, ref = layout.config(run.cell["config"])
    batches, gate_list = correct.reference_inputs(run)
    rows = run.traffic["ref_rows"]
    base = run.reference or correct.reference_steps(
        ref, run.config, run.seed, batches, gate_list, row_block=rows)
    if variant.get("half"):
        half = run.traffic["batch"] // 2
        other = correct.reference_steps(ref, run.config, run.seed, batches,
                                        gate_list, row_block=min(rows, half),
                                        rows=half)
    else:
        other = correct.reference_steps(ref, run.config, run.seed, batches,
                                        gate_list, row_block=rows,
                                        lowered=True)
    return correct.gaps(other, base)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--matmul", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import correct, harness
    from bench.run import require_chips
    layout = harness.Layout()
    if args.matmul:
        config = layout.config

        def at_matmul(name):
            c, ref = config(name)
            return dict(c, precision=dict(c["precision"],
                                          matmul=args.matmul)), ref
        layout.config = at_matmul
    require_chips(layout.cell(args.workload)["chips"])
    harness.enable_cache()
    counter = harness.CompileCounter()
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = [int(s) for s in args.seeds.split(",")]
    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        run = harness.drive(layout, args.workload, seed, WINDOW_S, None, t0,
                            counter)
        checks = correct.check(layout, run)
        emit({"cell": args.workload, "seed": seed, "kind": "program",
              "setup_s": run.feed.t_open - t0,
              "step_ms": (run.t_close - run.feed.t_open)
              / run.feed.n_window * 1e3,
              "window_compiles": run.window_compiles,
              "runtime_peak_bytes": run.runtime_peak_bytes,
              "step_bytes": run.step_bytes, "losses": run.losses[:3],
              **{n: ch["value"] for n, ch in checks.items()}})
        if k < args.control_seeds:
            emit({"cell": args.workload, "seed": seed, "kind": "control",
                  **reading(layout, run)})
            emit({"cell": args.workload, "seed": seed, "kind": "half_batch",
                  **reading(layout, run, half=True)})
            if run.reference["scores"] is not None:
                emit({"cell": args.workload, "seed": seed, "kind": "misplan",
                      "plan_gap": correct.plan_gap(
                          correct.misplan(run.table),
                          run.reference["scores"], run.traffic)})
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
