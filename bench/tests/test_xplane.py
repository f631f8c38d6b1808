"""The trace's op metadata read from the wire format, and device time by
program scope, on the trace recorded on the chip (``attention.xplane.pb``:
three calls of a jitted forward and backward through the gated attention
kernel, from a program with no named scopes)."""
from pathlib import Path

import pytest

from bench import trace, xplane

FIXTURE = str(Path(__file__).parent / "fixtures" / "attention.xplane.pb")


def window_ops():
    pd = trace.load(FIXTURE)
    ops = trace.device_ops(pd)
    lo, hi = trace.window_of(trace.host_spans(pd), ops)
    return [(n, max(s, lo), min(e, hi)) for n, s, e, _ in ops[0]
            if min(e, hi) > max(s, lo)]


def test_every_op_has_its_metadata():
    meta = xplane.event_metadata(FIXTURE)["/device:TPU:0"]
    ops = window_ops()
    assert len(ops) == 51
    for name, _, _ in ops:
        assert "hlo_category" in meta[name], name
    # XLA's own ops carry the name stack; the copies and prefetches XLA
    # inserted around them carry none
    for name, _, _ in ops:
        stats = meta[name]
        if stats["hlo_category"] in ("custom-call", "convolution fusion"):
            assert stats["tf_op"].startswith("jit(loss)/"), name
    kernels = {meta[n]["tf_op"] for n, _, _ in ops
               if meta[n]["hlo_category"] == "custom-call"}
    assert kernels == {
        "jit(loss)/jvp(jit(_gated_attention_impl))/pallas_call:",
        "jit(loss)/transpose(jvp(jit(_gated_attention_impl)))/pallas_call:"}
    assert sum("tf_op" in meta[n] for n, _, _ in ops) == 36
    fusion = next(meta[n] for n, _, _ in ops if n.startswith("%fusion.11 "))
    assert fusion["flops"] == 135266304 and fusion["bytes_accessed"] > 0


def test_forward_backward_split_matches_a_hand_sum():
    times = xplane.scopes(FIXTURE)
    assert set(times) == {(xplane.NONE, "forward"),
                          (xplane.NONE, "backward")}
    # per call: fusion.6 1402, multiply_reduce_fusion 1024, broadcast_in_dim
    # 1441, copy.12 618 and the backward kernel 15208 ns (first call)
    backward = [n for n, _, _ in window_ops()
                if n.split(" = ")[0] in (
                    "%fusion.6", "%multiply_reduce_fusion",
                    "%broadcast_in_dim.2", "%copy.12",
                    "%transpose_jvp_jit__gated_attention_impl___.2")]
    assert len(backward) == 15
    assert times[(xplane.NONE, "backward")] == pytest.approx(
        (4206 + 3074 + 4322 + 1854 + 45606) * 1e-9, rel=1e-9)
    total = sum(e - s for _, s, e in window_ops()) * 1e-9
    assert sum(times.values()) == pytest.approx(total, rel=1e-12)


def test_the_existing_reduction_reads_the_same_trace_as_before():
    out = trace.reduce_trace(FIXTURE)
    (dev,) = out["devices"].values()
    # no op overlaps another here, so busy time is the summed op time
    assert dev["busy_s"] == pytest.approx(
        sum(xplane.scopes(FIXTURE).values()), rel=1e-9)


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(step)/jvp(attn)/dot_general", "attn"),
    ("jit(step)/transpose(jvp(attn))/dot_general:", "attn"),
    ("jit(step)/transpose(jvp(layers))/while/body/mlp/add", "mlp"),
    ("jit(step)/jvp(layers)/while/body/dynamic_update_slice", "layers"),
    ("jit(step)/optimizer/sub", "optimizer"),
    ("jit(step)/clip/reduce_sum", "clip"),
    ("jit(step)/jvp(jit(clip))/clamp", xplane.NONE),
    ("jit(loss)/transpose(jvp(jit(_gated_attention_impl)))/pallas_call:",
     xplane.NONE),
    ("", xplane.NONE),
])
def test_scope_of_a_name_stack(tf_op, scope):
    assert xplane.scope_of(tf_op) == scope


def test_direction():
    assert xplane.direction("jit(step)/transpose(jvp(mlp))/dot") == \
        "backward"
    assert xplane.direction("jit(step)/jvp(mlp)/dot") == "forward"
    assert xplane.direction("jit(step)/optimizer/sub") == "forward"
