"""Device time by program scope, from a profiler trace's op metadata.

``jax._src.profiler.ProfileData`` (what ``trace.py`` reads) gives each event
its own stats but not its plane's ``event_metadata``, where a TPU trace keeps
what XLA knows of every op: ``hlo_category``, ``tf_op`` (the JAX name stack,
e.g. ``jit(step)/transpose(jvp(attn))/dot_general``), ``flops``,
``bytes_accessed`` and ``source``. This module reads that metadata from the
``.xplane.pb`` wire format itself, with no generated protobuf module, and
splits each chip's op time by the program's named scope and by direction:
an op whose name stack holds ``transpose(`` belongs to the backward pass.

    python3 bench/xplane.py <trace.xplane.pb>

prints the table for the benchmark's window span, or for the whole trace
where it has none.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace  # noqa: E402

# the program's named scopes (models/transformer.py, models/vit.py and the
# step builders in train/loop.py); an op takes the innermost one it is under
SCOPES = ("embed", "layers", "attn", "ssd", "rglru", "mlp", "head", "clip",
          "optimizer")
NONE = "(none)"
_WRAPPED = re.compile(r"(\w+)\((.*)\)")

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_EVENT_MD_NAME, _EVENT_MD_STATS = 2, 5
_STAT_MD_ID, _STAT_MD_NAME = 1, 2
_STAT_MD, _STAT_DOUBLE, _STAT_UINT, _STAT_INT = 1, 2, 3, 4
_STAT_STR, _STAT_BYTES, _STAT_REF = 5, 6, 7


# ---------------------------------------------------------- wire format
def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field, bytes for a fixed
    64- or 32-bit one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            value, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield number, value


def _map_entry(buf) -> tuple:
    key = value = None
    for number, v in _fields(buf):
        if number == _MAP_KEY:
            key = v
        elif number == _MAP_VALUE:
            value = v
    return key, value


def _stat(buf, stat_names: dict) -> tuple:
    """(name, value) of one ``XStat``; a reference value is the name of
    the stat metadata it points to."""
    name, value = None, None
    for number, v in _fields(buf):
        if number == _STAT_MD:
            name = stat_names.get(v)
        elif number == _STAT_DOUBLE:
            value = float(memoryview(v).cast("d")[0])
        elif number == _STAT_INT:
            value = v - (1 << 64) if v >= 1 << 63 else v
        elif number == _STAT_UINT:
            value = v
        elif number == _STAT_STR:
            value = bytes(v).decode("utf-8", "replace")
        elif number == _STAT_BYTES:
            value = bytes(v)
        elif number == _STAT_REF:
            value = stat_names.get(v)
    return name, value


def event_metadata(path: str) -> dict:
    """{plane name: {event name: {stat name: value}}} of every plane's
    ``event_metadata``. A TPU plane names each op by its HLO text, as the
    events that ``trace.device_ops`` lists do."""
    data = memoryview(Path(path).read_bytes())
    out = {}
    for number, plane in _fields(data):
        if number != _SPACE_PLANES:
            continue
        name, stat_names, raw = "", {}, []
        for f, v in _fields(plane):
            if f == _PLANE_NAME:
                name = bytes(v).decode("utf-8", "replace")
            elif f == _PLANE_STAT_MD:
                md = dict(_fields(_map_entry(v)[1] or b""))
                stat_names[md.get(_STAT_MD_ID, 0)] = bytes(
                    md.get(_STAT_MD_NAME, b"")).decode("utf-8", "replace")
            elif f == _PLANE_EVENT_MD:
                raw.append(_map_entry(v)[1] or b"")
        events = {}
        for md in raw:
            ev_name, stats = "", {}
            for f, v in _fields(md):
                if f == _EVENT_MD_NAME:
                    ev_name = bytes(v).decode("utf-8", "replace")
                elif f == _EVENT_MD_STATS:
                    k, val = _stat(v, stat_names)
                    if k is not None:
                        stats[k] = val
            events.setdefault(ev_name, stats)
        out[name] = events
    return out


# ---------------------------------------------------------------- scopes
def scope_of(tf_op: str) -> str:
    """The innermost program scope in a JAX name stack, or ``NONE``.
    Autodiff wraps a scope's name (``jvp(attn)``, ``transpose(jvp(attn))``);
    ``jit(f)`` names a function, not a scope."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        m = _WRAPPED.fullmatch(part)
        while m and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
            m = _WRAPPED.fullmatch(part)
        if part in SCOPES:
            return part
    return NONE


def direction(tf_op: str) -> str:
    return "backward" if "transpose(" in tf_op else "forward"


def scopes(path: str) -> dict:
    """{(scope or ``NONE``, "forward" | "backward"): device seconds per
    chip} of the ops inside the window span (``trace.window_of``), each
    op's time cut to the window as ``trace.reduce_trace`` cuts it."""
    pd = trace.load(path)
    ops = trace.device_ops(pd)
    if not ops or not any(ops.values()):
        raise ValueError(f"{path}: no TPU op events")
    lo, hi = trace.window_of(trace.host_spans(pd), ops)
    meta = event_metadata(path)
    out = defaultdict(float)
    for dev, evs in ops.items():
        names = meta.get(f"/device:TPU:{dev}", {})
        for name, s, e, _ in evs:
            if min(e, hi) <= max(s, lo):
                continue
            tf_op = str(names.get(name, {}).get("tf_op", ""))
            key = (scope_of(tf_op), direction(tf_op))
            out[key] += (min(e, hi) - max(s, lo)) * 1e-9 / len(ops)
    return dict(out)


def table(times: dict, steps: int = 1) -> str:
    """The scope table, milliseconds per step, largest first."""
    total = sum(times.values())
    rows = [f"{'scope':<10} {'direction':<9} {'ms/step':>10} {'share':>7}"]
    for (scope, way), s in sorted(times.items(), key=lambda kv: -kv[1]):
        rows.append(f"{scope:<10} {way:<9} {s / steps * 1e3:>10.3f} "
                    f"{100 * s / total:>6.2f}%")
    rows.append(f"{'total':<20} {total / steps * 1e3:>10.3f}")
    return "\n".join(rows)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(f"usage: {sys.argv[0]} <trace.xplane.pb>")
    print(table(scopes(sys.argv[1])))
