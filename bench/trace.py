"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

The trace holds one plane per TPU (``/device:TPU:<n>``) whose ``XLA Ops``
line lists every operation the chip ran, with start and duration in
nanoseconds, and host planes whose lines carry the Python tracer's
function spans and the benchmark's own ``TraceAnnotation`` spans. The
benchmark wraps its measured window in the span ``WINDOW_SPAN``; every
number here is taken inside that span.

* busy: the union of the op intervals of a device;
* kernel time: the summed durations of the Pallas kernels (custom calls),
  in all and by kernel name: the custom call's instruction name, which XLA
  takes from the kernel's own ``name`` (``d2ft_attn_bwd_short``); a kernel
  without one goes under the name JAX gave the call
  (``jvp_jit__gated_attention_impl__``);
* collective time: the summed durations of all-gather, reduce-scatter,
  all-reduce and collective-permute ops; the exposed part is what of
  their intervals no other op covers on that device;
* idle gaps: the device's gaps between ops, each named by the innermost
  host span (Python function or benchmark span) that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"(all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all)",
    re.IGNORECASE)
# Pallas kernels reach the chip as custom calls to tpu_custom_call; the op's
# name is its HLO text, whose operands may name other kernels' outputs
KERNEL_CATEGORY = re.compile(r"custom[-_ ]?call", re.IGNORECASE)
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str):
    from jax._src.profiler import ProfileData
    return ProfileData.from_file(path)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:       # a stat of a type the reader cannot convert
        return {}


def is_kernel(name: str, stats: dict) -> bool:
    """A Pallas kernel: its HLO category says custom call, or, where the
    trace gives none, its HLO text calls ``tpu_custom_call``."""
    cat = str(stats.get("hlo_category", ""))
    if cat:
        return bool(KERNEL_CATEGORY.search(cat))
    return KERNEL_TARGET in name


def instruction(name: str) -> str:
    """An op's instruction name without the ``%`` and the numeric suffix,
    so that the copies of one op in every layer add up (the trace names an
    op by its whole HLO text)."""
    return re.sub(r"(\.\d+)+$", "", name.split(" = ", 1)[0].lstrip("%"))


def op_kind(name: str, stats: dict) -> str:
    """A short name for an op: its HLO category and its instruction name."""
    inst = instruction(name)
    cat = stats.get("hlo_category")
    return f"{cat}: {inst}" if cat else inst


def is_collective(name: str, stats: dict) -> bool:
    text = " ".join((name, str(stats.get("hlo_category", "")),
                     str(stats.get("long_name", ""))))
    return bool(COLLECTIVE.search(text))


def union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Parts of the merged intervals ``a`` that the merged ``b`` leave
    uncovered."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def host_spans(pd):
    """(name, start_ns, end_ns) of every host event with a duration."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def device_ops(pd):
    """{device index: [(name, start_ns, end_ns, stats)]} from XLA Ops."""
    out = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                evs.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            _stats(ev)))
        out[int(m.group(1))] = evs
    return out


def window_of(spans, ops) -> tuple:
    """The benchmark's window span; without one, the span of the ops."""
    marks = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if marks:
        return max(marks, key=lambda se: se[1] - se[0])
    starts = [s for evs in ops.values() for _, s, _, _ in evs]
    ends = [e for evs in ops.values() for _, _, e, _ in evs]
    return min(starts), max(ends)


def gap_owners(spans, mids):
    """For each time in ``mids``, the innermost host span covering it (the
    latest-started one still open), or "no host span"; one sweep."""
    import heapq
    spans = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
    order = sorted(range(len(mids)), key=lambda i: mids[i])
    out = ["no host span"] * len(mids)
    open_, j = [], 0                 # max-heap on start: (-start, end, name)
    for i in order:
        t = mids[i]
        while j < len(spans) and spans[j][0] <= t:
            heapq.heappush(open_, (-spans[j][0], spans[j][1], spans[j][2]))
            j += 1
        # drop ended spans from the top; ended ones below it are harmless
        while open_ and open_[0][1] <= t:
            heapq.heappop(open_)
        if open_:
            out[i] = open_[0][2]
    return out


def reduce_trace(path: str, top: int = 10) -> dict:
    """Numbers of the traced window; times in seconds.

    Returns window_s, and per device busy_s, kernel_s, ``kernels``
    ({kernel name: seconds}, which sum to kernel_s), collective_s and
    exposed_collective_s, and the ``breakdown`` lists (``device_ops``: the
    kinds of op (``op_kind``) that took most device time, summed over
    devices and divided by their number; ``idle_gaps``: idle time of
    device 0 summed by the host span that covers each gap).
    """
    pd = load(path)
    ops = device_ops(pd)
    if not ops or not any(ops.values()):
        raise ValueError(f"{path}: no TPU op events")
    spans = host_spans(pd)
    lo, hi = window_of(spans, ops)
    per_dev = {}
    op_time = defaultdict(float)
    for dev, evs in sorted(ops.items()):
        evs = [(n, max(s, lo), min(e, hi), st) for n, s, e, st in evs
               if min(e, hi) > max(s, lo)]
        busy = union([(s, e) for _, s, e, _ in evs])
        kern = [(instruction(n), s, e) for n, s, e, st in evs
                if is_kernel(n, st)]
        by_kernel = defaultdict(float)
        for n, s, e in kern:
            by_kernel[n] += (e - s) * 1e-9
        coll = [(s, e) for n, s, e, st in evs if is_collective(n, st)]
        other = union([(s, e) for n, s, e, st in evs
                       if not is_collective(n, st)])
        exposed = subtract(union(coll), other)
        for n, s, e, st in evs:
            op_time[op_kind(n, st)] += (e - s) / len(ops)
        per_dev[dev] = {"busy_s": total(busy) * 1e-9,
                        "kernel_s": total((s, e) for _, s, e in kern) * 1e-9,
                        "kernels": dict(by_kernel),
                        "collective_s": total(coll) * 1e-9,
                        "exposed_collective_s": total(exposed) * 1e-9,
                        "busy": busy}
    first = per_dev[min(per_dev)]
    gaps = subtract([(lo, hi)], first.pop("busy"))
    for d in per_dev.values():
        d.pop("busy", None)
    gap_time = defaultdict(float)
    owners = gap_owners(spans, [(s + e) / 2 for s, e in gaps])
    for (s, e), owner in zip(gaps, owners):
        gap_time[owner] += (e - s) * 1e-9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gap_time.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) * 1e-9, "devices": per_dev,
            "breakdown": {
                "device_ops": [[n, t * 1e-9] for n, t in top_ops],
                "idle_gaps": [[n, t] for n, t in top_gaps]}}
