"""The trace reduction: interval arithmetic by hand, and a recorded trace."""
import pytest

from bench import trace


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.total(trace.union([(0, 2), (1, 3)])) == 3


def test_subtract_leaves_uncovered_parts():
    a = trace.union([(0, 10), (20, 30)])
    b = trace.union([(2, 4), (8, 22), (25, 26)])
    assert trace.subtract(a, b) == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert trace.subtract(a, []) == a
    assert trace.subtract(a, [(-5, 50)]) == []


def test_kinds_of_ops():
    assert trace.is_collective("all-gather-start.3", {})
    assert trace.is_collective("fusion.1", {"hlo_category": "reduce-scatter"})
    assert not trace.is_collective("fusion.2", {"hlo_category": "loop fusion"})
    assert trace.is_kernel("custom-call.7", {"hlo_category": "custom-call"})
    assert not trace.is_kernel("fusion.2", {"hlo_category": "loop fusion"})
    kernel = ('%k.1 = (f32[8,256,64]) custom-call(s32[8] %c), '
              'custom_call_target="tpu_custom_call"')
    consumer = "%fusion.3 = f32[8,256,64] fusion(f32[8,256,64] %pallas_call.2)"
    assert trace.is_kernel(kernel, {})
    assert not trace.is_kernel(consumer, {})


def test_gap_owner_is_innermost_open_span():
    spans = [("outer", 0, 100), ("inner", 10, 20), ("late", 50, 60),
             (trace.WINDOW_SPAN, 0, 1000)]
    assert trace.gap_owners(spans, [5, 15, 25, 55, 150]) == [
        "outer", "inner", "outer", "late", "no host span"]


def test_op_kind_adds_up_the_layers():
    full = ("%jvp_jit__gated_attention_impl__.4 = (f32[960,256,64]) "
            "custom-call(s32[960] %get-tuple-element.419)")
    assert trace.op_kind(full, {"hlo_category": "custom-call"}) == \
        "custom-call: jvp_jit__gated_attention_impl__"
    assert trace.op_kind("fusion.12", {}) == "fusion"


def test_recorded_trace():
    """A trace recorded on a TPU v5e: three calls of a jitted forward and
    backward through the gated attention kernel, inside the window span.
    The numbers are the ones the reduction gave on the chip."""
    from pathlib import Path
    out = trace.reduce_trace(str(Path(__file__).parent / "fixtures"
                                 / "attention.xplane.pb"))
    assert out["window_s"] == pytest.approx(3.50124e-3, rel=1e-9)
    (dev,) = out["devices"].values()
    # the forward and the backward kernel, 43,595 and 45,606 ns
    assert dev["kernel_s"] == pytest.approx(89201e-9, rel=1e-9)
    assert dev["busy_s"] == pytest.approx(132944e-9, rel=1e-9)
    assert dev["kernel_s"] < dev["busy_s"] < out["window_s"]
    assert dev["collective_s"] == dev["exposed_collective_s"] == 0
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["transpose_jvp_jit__gated_attention_impl___"] == \
        pytest.approx(45606e-9, rel=1e-9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - dev["busy_s"], rel=1e-6)


def test_recorded_trace_by_kernel():
    """Kernel time by kernel name; the fixture's kernels predate their
    names, so they go under the instruction names JAX gave the calls."""
    from pathlib import Path
    out = trace.reduce_trace(str(Path(__file__).parent / "fixtures"
                                 / "attention.xplane.pb"))
    (dev,) = out["devices"].values()
    assert dev["kernels"] == pytest.approx({
        "jvp_jit__gated_attention_impl__": 43595e-9,
        "transpose_jvp_jit__gated_attention_impl___": 45606e-9}, rel=1e-9)
    assert sum(dev["kernels"].values()) == pytest.approx(dev["kernel_s"],
                                                         rel=1e-12)


# the fixture's attention kernels predate the kernels' own names: the
# names they carry now
NAMED = {"jvp_jit__gated_attention_impl__": "d2ft_attn_fwd_flash",
         "transpose_jvp_jit__gated_attention_impl___": "d2ft_attn_bwd_flash"}


def test_attention_readers_on_the_recorded_trace():
    """The attention readers read what they read from ``kernel_s`` before
    kernels had names: the fixture holds only attention kernels, here under
    the names they carry now."""
    from pathlib import Path
    from bench import harness
    tr = trace.reduce_trace(str(Path(__file__).parent / "fixtures"
                                / "attention.xplane.pb"))
    steps, layout = 3, harness.Layout()
    kernel_ms = {NAMED[n]: v for n, v in harness.kernel_ms(tr, steps).items()}
    assert len(kernel_ms) == 2
    ctx = {"trace": tr, "steps": steps, "n_chips": 1,
           "kernel_ms": kernel_ms,
           "peaks": {"flops": 197e12, "hbm_bw": 819e9},
           "attn_flops": 4.0e8, "attn_bytes": 2.0e7}
    kernel_s = tr["devices"][0]["kernel_s"] / steps        # before
    assert layout.metric_reader("attn_kernel_ms")(ctx) == \
        pytest.approx(kernel_s * 1e3, rel=1e-12)
    least = max(4.0e8 / 197e12, 2.0e7 / 819e9)
    assert layout.metric_reader("attn_roofline")(ctx) == \
        pytest.approx(100.0 * least / kernel_s, rel=1e-12)


def test_kernel_names():
    named = ('%d2ft_attn_bwd_short.3 = (f32[1200,197,64]) custom-call('
             's32[180] %c), custom_call_target="tpu_custom_call"')
    assert trace.instruction(named) == "d2ft_attn_bwd_short"
    assert trace.instruction("%transpose_jvp_jit__gated_attention_impl___.2"
                             " = f32[8] custom-call()") == \
        "transpose_jvp_jit__gated_attention_impl___"
