"""Each per-layer reader on a hand-made context."""
import pytest

from bench import harness

LAYOUT = harness.Layout()
PEAKS = {"flops": 200e12, "hbm_bw": 800e9}


def ctx(**over):
    base = {"n_chips": 2, "peaks": PEAKS, "steps": 10,
            "untraced_s": 5.0, "untraced_steps": 20,
            "warmup_s": 7.5, "step_flops": 4e12, "attn_flops": 1e12,
            "attn_bytes": 4e9,
            "trace": {"window_s": 2.0, "devices": {
                0: {"busy_s": 1.5, "kernel_s": 0.2, "collective_s": 0.4,
                    "exposed_collective_s": 0.1},
                1: {"busy_s": 1.0, "kernel_s": 0.1, "collective_s": 0.6,
                    "exposed_collective_s": 0.3}}}}
    return dict(base, **over)


def read(name, **over):
    return LAYOUT.metric_reader(name)(ctx(**over))


def test_warmup_and_mfu():
    assert read("warmup_s") == 7.5
    # 4e12 x 20 untraced steps over 5 s x 2 chips x 200e12
    assert read("mfu") == pytest.approx(100 * 8e13 / (5 * 2 * 200e12))


def test_kernel_time_and_roofline():
    # mean kernel time per chip 0.15 s over 10 steps
    assert read("attn_kernel_ms") == pytest.approx(15.0)
    # per chip and step: max(0.5e12 / 200e12, 2e9 / 800e9) = 2.5 ms of 15 ms
    assert read("attn_roofline") == pytest.approx(100 * 2.5 / 15)


def test_idle_share():
    # 1.25 s busy a chip over 10 traced steps, 0.25 s a step untraced
    assert read("device_idle_share") == pytest.approx(100 * (1 - 0.125 / 0.25))


def test_host_clock_metrics_need_an_untraced_step():
    assert read("mfu", untraced_steps=0) is None
    assert read("device_idle_share", untraced_steps=0) is None
    assert read("device_idle_share", trace=None) is None


def test_collectives_on_the_busiest_chip():
    assert read("collective_ms") == pytest.approx(60.0)
    assert read("exposed_collective_ms") == pytest.approx(30.0)


def test_nothing_to_read_gives_nothing():
    one_chip = {"window_s": 2.0, "devices": {0: {
        "busy_s": 1.0, "kernel_s": 0.0, "collective_s": 0.0,
        "exposed_collective_s": 0.0}}}
    for name in ("attn_kernel_ms", "attn_roofline", "collective_ms",
                 "exposed_collective_ms"):
        assert read(name, trace=one_chip) is None
        assert read(name, trace=None) is None
