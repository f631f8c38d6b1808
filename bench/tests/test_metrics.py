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
                    "exposed_collective_s": 0.1,
                    "kernels": {"d2ft_attn_fwd_short": 0.05,
                                "d2ft_attn_bwd_short": 0.15}},
                1: {"busy_s": 1.0, "kernel_s": 0.1, "collective_s": 0.6,
                    "exposed_collective_s": 0.3,
                    "kernels": {"d2ft_attn_fwd_short": 0.04,
                                "d2ft_attn_bwd_short": 0.06}}}}}
    out = dict(base, **over)
    out.setdefault("kernel_ms", harness.kernel_ms(out["trace"], out["steps"]))
    return out


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
        "exposed_collective_s": 0.0, "kernels": {}}}}
    for name in ("attn_kernel_ms", "attn_roofline", "collective_ms",
                 "exposed_collective_ms"):
        assert read(name, trace=one_chip) is None
        assert read(name, trace=None) is None


def test_one_file_reads_a_named_kernel_and_the_ssd_counts(monkeypatch):
    """The context a real run hands its readers, for a hybrid of one
    Mamba-2 and one attention layer: a reader found by name in a directory
    of its own reads one kernel's time and the scan's required work."""
    from pathlib import Path
    from types import SimpleNamespace

    import numpy as np

    from bench import flops, trace
    fixture = Path(__file__).parent / "fixtures" / "attention.xplane.pb"
    c = {"name": "hybrid", "family": "lm", "hidden_size": 8,
         "num_hidden_layers": 2, "layer_types": ["mamba", "attention"],
         "num_attention_heads": 2, "num_key_value_heads": 2,
         "intermediate_size": 16, "vocab_size": 10, "mamba_n_heads": 4,
         "mamba_d_head": 4, "mamba_d_state": 4, "mamba_n_groups": 1,
         "mamba_chunk_size": 4, "mamba_d_conv": 4, "mamba_expand": 2}
    t = {"batch": 4, "n_microbatches": 2, "seq": 16}
    table = np.array([[[1, 2], [1, 3]], [[1, 1], [2, 3]]], np.int8)
    feed = SimpleNamespace(n_traced=3, n_window=5, t_open=1.0, t_resume=2.0,
                           requests=[0.0])
    run = harness.Run({"name": "hybrid.cell"}, c, t, 7, 1, 0.0, feed=feed,
                      t_close=3.0, table=table,
                      trace=trace.reduce_trace(str(fixture)))
    monkeypatch.setattr("bench.peaks.peaks", lambda kind: PEAKS)
    ctx = harness.metric_context(run)
    layout = harness.Layout(metric_dirs=[harness.BENCH / "tests" / "readers"])

    m = harness.model_dims(c, t)
    assert m["head_dim"] == 4 and m["layer_kinds"] == ["mamba", "attention"]
    f, b = flops.required_ssd(m, table, harness.microbatch_of(4, 2))
    assert (ctx["ssd_flops"], ctx["ssd_bytes"]) == (f, b) and f > 0
    assert set(ctx["kind_flops"]) == {"mamba", "attention"}
    ms = 45606e-9 / 3 * 1e3         # the kernel's time over 3 traced steps
    assert ctx["kernel_ms"]["transpose_jvp_jit__gated_attention_impl___"] \
        == pytest.approx(ms)
    least = max(f / PEAKS["flops"], b / PEAKS["hbm_bw"])
    assert layout.metric_reader("ssd_probe")(ctx) == \
        pytest.approx(100 * least / (ms * 1e-3))

