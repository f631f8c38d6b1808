"""The numbers ``bench/phases.py`` reads from a run's step records and
scope table, on hand-made records (times in nanoseconds)."""
import pytest

from bench import phases

MS = 1_000_000


def step(t0, plan=0, compiles=()):
    """A step from ``t0`` ms: batch 1, [plan], prepare 1, h2d 2,
    dispatch 1, wait 10, readback 1 (ms)."""
    spans, t = [], t0 * MS
    for name, ms in (("batch", 1), ("plan", plan), ("prepare", 1),
                     ("h2d", 2), ("dispatch", 1), ("wait", 10),
                     ("readback", 1)):
        if ms:
            spans.append((name, t, t + ms * MS))
            t += ms * MS
    return {"spans": spans, "compiles": list(compiles)}


def test_host_gap_and_phases():
    recs = [step(0), step(16), step(32)]
    # readback 1 + batch 1 + prepare 1 + h2d 2 + dispatch 1 between waits
    assert phases.host_gap_ms(recs) == pytest.approx(6.0)
    assert phases.phase_ms(recs) == pytest.approx(
        {"batch": 1, "prepare": 1, "h2d": 2, "dispatch": 1, "wait": 10,
         "readback": 1})
    assert list(phases.phase_ms(recs)) == [
        "batch", "prepare", "h2d", "dispatch", "wait", "readback"]
    # 48 ms of spans over a 50 ms window
    assert phases.coverage(recs, 0, 50 * MS) == pytest.approx(48 / 50)


def test_nothing_without_an_untraced_step():
    assert phases.host_gap_ms([]) is None
    assert phases.host_gap_ms([step(0)]) is None
    assert phases.host_gap_ms(None) is None
    assert phases.phase_ms(None) is None
    assert phases.coverage([], 0, MS) is None


def test_plan_and_compile_seconds_of_the_warm_up():
    warm = [step(0, plan=100, compiles=[("trace", "step", 5 * MS, 9 * MS),
                                        ("trace", "inner", 6 * MS, 7 * MS),
                                        ("compile", "step", 9 * MS,
                                         20 * MS)]),
            step(200, compiles=[("compile", "norms", 201 * MS, 203 * MS)]),
            step(300)]
    assert phases.plan_s(warm) == pytest.approx(0.1)
    # the inner trace lies inside the outer one: 4 + 11 + 2 ms
    assert phases.compile_s(warm) == pytest.approx(0.017)
    assert phases.compiles_per_step(warm) == [
        [0, 1, pytest.approx(0.015), ["step"]],
        [1, 1, pytest.approx(0.002), ["norms"]],
        [2, 0, 0.0, []]]
    assert phases.plan_s([]) is None and phases.compile_s(None) is None


def test_backward_and_optimizer_from_the_scope_table():
    scopes = {("attn", "forward"): 0.2, ("attn", "backward"): 0.3,
              ("mlp", "backward"): 0.1, ("clip", "forward"): 0.01,
              ("optimizer", "forward"): 0.03, ("(none)", "forward"): 0.05}
    assert phases.backward_ms(scopes, 10) == pytest.approx(40.0)
    assert phases.optimizer_ms(scopes, 10) == pytest.approx(4.0)


def test_nothing_without_a_trace_or_a_scope():
    assert phases.backward_ms(None, 10) is None
    assert phases.optimizer_ms(None, 10) is None
    unscoped = {("(none)", "forward"): 0.5, ("(none)", "backward"): 0.4}
    assert phases.optimizer_ms(unscoped, 10) is None
    assert phases.backward_ms(unscoped, 10) == pytest.approx(40.0)
