"""The program's own record of a benchmark run: host phases, compilations
and device time by program scope.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \
        [--trace 1]

Runs the cell as ``bench/run.py`` does (the same harness, warm-up and
window; with ``--trace 1`` the profiler records the window's first
``harness.TRACE_SECONDS``) and prints one JSON object read from what the
entry point recorded in its ``TrainLog`` (``steps``: host phases and
compile events per step; ``counters``) and, with a trace, from the trace's
op metadata (``xplane.scopes``). The benchmark's own runs never run this.

The functions below take plain step records (``records``), so that a
per-layer reader can call them once the harness hands it the records.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))

PHASES = ("batch", "plan", "prepare", "h2d", "dispatch", "wait", "readback")
OPTIMIZER_SCOPES = ("clip", "optimizer")


# ------------------------------------------------------------- records
def records(log, lo: int, hi: int):
    """Steps ``lo`` to ``hi`` of a ``TrainLog`` as plain records
    ``{"spans": [(phase, t0_ns, t1_ns)], "compiles": [(kind, function,
    t0_ns, t1_ns)]}``; None where the program keeps no step records."""
    steps = getattr(log, "steps", None)
    if steps is None:
        return None
    return [{"spans": list(r.spans), "compiles": list(r.compiles)}
            for r in steps[lo:hi]]


def _end(rec, phase):
    return next((t1 for n, _, t1 in reversed(rec["spans"]) if n == phase),
                None)


def _union_s(intervals) -> float:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total, end = total + e - s, e
        elif e > end:
            total, end = total + e - end, e
    return total * 1e-9


def host_gap_ms(recs):
    """Mean over consecutive steps of the end of step i's ``dispatch``
    less the end of step i-1's ``wait``: the host's serial time in which
    no step is queued. Nothing with fewer than two steps."""
    if not recs or len(recs) < 2:
        return None
    gaps = [(_end(b, "dispatch") - _end(a, "wait")) * 1e-6
            for a, b in zip(recs, recs[1:])
            if _end(b, "dispatch") is not None and _end(a, "wait") is not None]
    return sum(gaps) / len(gaps) if gaps else None


def phase_ms(recs):
    """Milliseconds per step in each phase, over the steps given."""
    if not recs:
        return None
    out = {}
    for r in recs:
        for n, t0, t1 in r["spans"]:
            out[n] = out.get(n, 0.0) + (t1 - t0) * 1e-6 / len(recs)
    return {p: out[p] for p in PHASES if p in out}


def coverage(recs, t0_ns: int, t1_ns: int):
    """Share of the wall time from ``t0_ns`` to ``t1_ns`` inside a span."""
    if not recs or t1_ns <= t0_ns:
        return None
    cut = [(max(s, t0_ns), min(e, t1_ns)) for r in recs
           for _, s, e in r["spans"] if min(e, t1_ns) > max(s, t0_ns)]
    return _union_s(cut) / ((t1_ns - t0_ns) * 1e-9)


def plan_s(recs):
    """Seconds in ``plan`` spans (scoring, knapsack, assignment)."""
    if not recs:
        return None
    return sum((t1 - t0) * 1e-9 for r in recs for n, t0, t1 in r["spans"]
               if n == "plan")


def compile_s(recs):
    """Seconds compiling: the union of the compile events' intervals."""
    if not recs:
        return None
    return _union_s([(t0, t1) for r in recs for *_, t0, t1 in r["compiles"]])


def compiles_per_step(recs):
    """[step, executables built or loaded, seconds compiling, the
    functions compiled] of each step given."""
    out = []
    for i, r in enumerate(recs or []):
        built = [f for kind, f, *_ in r["compiles"] if kind == "compile"]
        out.append([i, len(built), compile_s([r]), sorted(set(built))])
    return out


# ----------------------------------------------------------- the trace
def backward_ms(scopes, steps: int):
    """Device ms per step, averaged over chips, of ops whose name stack
    holds ``transpose(``; nothing without a trace or with none there."""
    if not scopes or steps <= 0:
        return None
    s = sum(v for (_, way), v in scopes.items() if way == "backward")
    return s / steps * 1e3 if s > 0 else None


def optimizer_ms(scopes, steps: int):
    """Device ms per step under the ``clip`` and ``optimizer`` scopes;
    nothing where the program names no such scope."""
    if not scopes or steps <= 0:
        return None
    s = sum(v for (scope, _), v in scopes.items()
            if scope in OPTIMIZER_SCOPES)
    return s / steps * 1e3 if s > 0 else None


# ---------------------------------------------------------------- main
def report(run, log, scopes=None, reduced=None) -> dict:
    """Everything above, for a finished run of the harness."""
    from bench import xplane
    f = run.feed
    traced = f.n_traced is not None
    lo, hi = f.i_open, f.i_open + f.n_window
    warm, window = records(log, 0, lo), records(log, lo, hi)
    t_rest = f.t_resume if traced else f.t_open
    untraced = None if window is None else [
        r for r in window if r["spans"] and r["spans"][0][1] >= t_rest * 1e9]
    n_untraced = f.n_window - (f.n_traced if traced else 0)
    step_s = (run.t_close - t_rest) / n_untraced if n_untraced else None
    out = {
        "window_steps": f.n_window, "untraced_steps": n_untraced,
        "untraced_step_ms": step_s and step_s * 1e3,
        "warmup_s": f.t_open - f.requests[0],
        "counters": getattr(log, "counters", None),
        "phase_ms": phase_ms(untraced),
        "host_gap_ms": host_gap_ms(untraced),
        "span_coverage": coverage(window, int(t_rest * 1e9),
                                  int(run.t_close * 1e9)),
        "plan_s": plan_s(warm), "compile_s": compile_s(warm),
        "warmup_compiles": compiles_per_step(warm),
        "window_compiles": [n for _, n, *_ in compiles_per_step(window)],
    }
    if traced:
        out["traced_step_ms"] = (f.t_trace_end - f.t_open) / f.n_traced * 1e3
    if scopes is not None:
        total = sum(scopes.values())
        out["scopes"] = sorted(
            ([s, way, v / f.n_traced * 1e3] for (s, way), v in scopes.items()),
            key=lambda row: -row[2])
        out["scoped_share"] = 1 - sum(
            v for (s, _), v in scopes.items() if s == xplane.NONE) / total
        out["op_ms"] = total / f.n_traced * 1e3
        out["backward_ms"] = backward_ms(scopes, f.n_traced)
        out["optimizer_ms"] = optimizer_ms(scopes, f.n_traced)
    if reduced is not None:
        devs = reduced["devices"].values()
        busy = sum(d["busy_s"] for d in devs) / len(devs) / f.n_traced
        out["busy_ms"] = busy * 1e3
        if step_s:
            out["idle_ms"] = (step_s - busy) * 1e3
        out["breakdown"] = reduced["breakdown"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, trace, xplane
    from bench.run import require_chips
    layout = harness.Layout()
    cell = layout.cell(args.workload)
    require_chips(cell["chips"])
    harness.enable_cache()
    entry = layout.traffic(args.workload)["entry"]
    logs, drive = [], harness.ENTRIES[entry]

    def keep_log(c, t, held, feed, log):
        logs.append(log)
        return drive(c, t, held, feed, log)

    harness.ENTRIES[entry] = keep_log
    trace_dir = tempfile.mkdtemp(prefix="phases_") if args.trace else None
    try:
        run = harness.drive(layout, args.workload, args.seed, args.seconds,
                            trace_dir, T_START, harness.CompileCounter())
        scopes = reduced = None
        if trace_dir:
            path = trace.find_xplane(trace_dir)
            scopes, reduced = xplane.scopes(path), trace.reduce_trace(path)
            print(xplane.table(scopes, run.feed.n_traced), file=sys.stderr)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(report(run, logs[0], scopes, reduced)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
