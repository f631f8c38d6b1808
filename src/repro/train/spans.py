"""Host spans and compile counts of the fine-tuning loops.

``finetune_vit``, ``finetune`` and ``finetune_distributed`` keep one
``StepRecord`` per step in ``TrainLog.steps``: the host phases the step went
through, as ``(name, start_ns, end_ns)`` on ``time.perf_counter_ns``, and the
compilations that ran during it. Each phase is also a
``jax.profiler.TraceAnnotation`` named ``d2ft.<phase>``, so a profile of a
fine-tuning job shows the phases on the same clock as the device's ops. The
phases, in loop order:

  batch      waiting on the caller's batch iterator
  plan       scoring and knapsack (on the distributed path also device
             assignment, sync plan and reshard); only on steps that plan
  prepare    gates, compaction bounds, step pick, sample permutation
  h2d        the loop's explicit host-to-device transfers
  dispatch   the call of the jitted step
  wait       until the step's loss is ready
  readback   the ``float()`` of the step's metrics

Compilations are JAX's compile events (tracing to a jaxpr, lowering to an
MLIR module, and the backend compile, which JAX also reports when it loads
an executable from the persistent cache). One process-wide listener hands
them to the step record of the loop running at the time; a compile outside
any step (before a loop's first batch) is not recorded.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax

PHASES = ("batch", "plan", "prepare", "h2d", "dispatch", "wait", "readback")
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}

_running = []          # TrainLogs of the loops running now, innermost last
_listening = False


@dataclass
class StepRecord:
    """One step: its host phases and the compile events during it, both as
    (…, start_ns, end_ns) on ``time.perf_counter_ns``."""
    spans: list = field(default_factory=list)      # (phase, t0, t1)
    compiles: list = field(default_factory=list)   # (kind, fun_name, t0, t1)

    @property
    def n_compiles(self) -> int:
        """Executables built or loaded during the step."""
        return sum(kind == "compile" for kind, *_ in self.compiles)

    @property
    def compile_s(self) -> float:
        """Seconds spent compiling: the union of the compile events'
        intervals (a jit traced inside another's trace is not counted
        twice)."""
        return _union_ns([(t0, t1) for *_, t0, t1 in self.compiles]) * 1e-9

    def seconds(self, first: str, last: str) -> float:
        """From the start of phase ``first`` to the end of phase ``last``."""
        t0 = next(t for n, t, _ in self.spans if n == first)
        t1 = next(t for n, _, t in reversed(self.spans) if n == last)
        return (t1 - t0) * 1e-9


def _union_ns(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


class span:
    """``with span(log, name):`` times a phase of the current step into
    ``log.steps[-1]`` and marks it ``d2ft.<name>`` for the profiler. Setting
    ``keep = False`` inside the block leaves the phase out of the record
    (the profiler still shows it)."""

    __slots__ = ("log", "name", "keep", "t0", "_ann")

    def __init__(self, log, name: str):
        self.log, self.name, self.keep = log, name, True

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(f"d2ft.{self.name}")
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        if self.keep:
            self.log.steps[-1].spans.append((self.name, self.t0, t1))
        return False


def _on_duration(event: str, duration: float, fun_name: str = "", **_):
    kind = COMPILE_EVENTS.get(event)
    if kind is None or not _running or not _running[-1].steps:
        return
    t1 = time.perf_counter_ns()
    _running[-1].steps[-1].compiles.append(
        (kind, fun_name, t1 - int(duration * 1e9), t1))


def each_step(log, batches, steps: int):
    """The loop's batches as ``(i, batch)``, at most ``steps`` of them. Each
    batch is taken from ``batches`` inside a ``batch`` span that opens step
    i's record in ``log.steps``; while the loop runs, compile events go to
    the open record. A batch taken past ``steps`` is dropped, as is its
    record."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    it = iter(batches)
    end = object()
    _running.append(log)
    try:
        i = 0
        while True:
            log.steps.append(StepRecord())
            with span(log, "batch"):
                batch = next(it, end)
            if batch is end or i >= steps:
                log.steps.pop()
                return
            yield i, batch
            i += 1
    finally:
        _running.remove(log)
