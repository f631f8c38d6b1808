"""Compiled-HLO collective-traffic accounting.

Shared by the dry-run (``launch/dryrun.py``) and the distributed-step
measurement (``launch/diststep.py`` / ``benchmarks/dist_step.py``). Lives
in its own module because ``dryrun.py`` must set ``XLA_FLAGS`` for 512
host devices at import time — anything that wants the parser without that
side effect imports it from here.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Dict

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "c64": 8,
                "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

# One collective instruction: `name = <result type> <op>(operands...)`. The
# result type is one array (`f32[4,8]{1,0}`, TPU layouts may carry
# `{1,0:T(8,128)}`) or a tuple of them when XLA combines several
# collectives into one (`(f32[4,8]{1,0}, f32[16]{0}) all-reduce(...)`,
# long tuples interleaved with `/*index=5*/` comments); every array of the
# tuple is priced. Async pairs (`-start`/`-done`) count
# once, at `-done`, whose result is the plain (or combined) output —
# `-start` results also carry the operands.
_COLL_RE = re.compile(
    r"=\s*(\((?:[^()\n]|\([^()\n]*\))*\)|\w+\[[\d,]*\](?:\{[^}\n]*\})?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(([^\n]*)")
_ARRAY_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _result_bytes(result_type: str) -> int:
    """Bytes of an instruction's result: one array or a tuple of arrays."""
    total = 0
    for dt, dims in _ARRAY_RE.findall(result_type):
        n = _DTYPE_BYTES.get(dt, 4)
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


# replica_groups comes in two prints: explicit lists `{{0,1,2},{3,4,5}}`
# (group size = members of the first group) and the iota form
# `[n_groups,group_size]<=[...]`. An empty `replica_groups={}` means one
# group of every participant — only the caller knows that count.
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")

# `-start` halves of async pairs carry the replica_groups attribute (their
# tuple-shaped results don't parse as array instructions), so group sizes
# are collected from them by channel_id and looked up when the matching
# `-done` is priced.
_START_RE = re.compile(
    r"(?:all-gather|all-reduce|reduce-scatter|all-to-all"
    r"|collective-permute)-start\(([^\n]*)")


def _group_size(rest_of_line: str, default: int) -> int:
    m = _GROUPS_LIST_RE.search(rest_of_line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(rest_of_line)
    if m:
        return int(m.group(2))
    return default


def collective_bytes(hlo_text: str,
                     default_group_size: int = 2) -> Dict[str, float]:
    """Per-device ICI traffic (bytes) by collective type.

    Formulas (ring algorithms, k = group size, n = result bytes/device):
      all-gather: (k-1)/k * n_out ; all-reduce: 2*(k-1)/k * n ;
      reduce-scatter: (k-1)/k * n_in ~ (k-1)*n_out ; all-to-all: (k-1)/k * n;
      collective-permute: n.
    default_group_size: group size assumed when the instruction does not
    print one (`replica_groups={}` = all participants) — pass the mesh
    size when it is known.
    """
    start_groups = {}
    for m in _START_RE.finditer(hlo_text):
        ch = _CHANNEL_RE.search(m.group(1))
        k = _group_size(m.group(1), 0)
        if ch and k:
            start_groups[ch.group(1)] = k
    out: Dict[str, float] = Counter()
    for m in _COLL_RE.finditer(hlo_text):
        result_type, op, phase, rest = m.groups()
        if phase == "-start":
            continue                 # counted once, at the matching -done
        nbytes = _result_bytes(result_type)
        k = _group_size(rest, 0)
        if not k and phase == "-done":
            ch = _CHANNEL_RE.search(rest)
            k = start_groups.get(ch.group(1), 0) if ch else 0
        if not k:
            k = default_group_size
        if op == "all-gather":
            traffic = (k - 1) / k * nbytes
        elif op == "all-reduce":
            traffic = 2 * (k - 1) / k * nbytes
        elif op == "reduce-scatter":
            traffic = (k - 1) * nbytes
        elif op == "all-to-all":
            traffic = (k - 1) / k * nbytes
        else:
            traffic = float(nbytes)
        out[op] += traffic
    return dict(out)


def compare_collective_bytes(hlo_a: str, hlo_b: str, *,
                             default_group_size: int = 2) -> Dict[str, float]:
    """Total per-device collective bytes of two lowerings and their ratio.

    The wire-invariance check for streamed ZeRO-3: splitting one big param
    all-gather into per-unit (or per-cycle) gathers must not change the
    totals — every ring formula above is linear in the payload bytes — so
    the streamed/unstreamed ratio must be ~1.0 regardless of how the
    collectives are scheduled against compute."""
    a = collective_bytes(hlo_a, default_group_size)
    b = collective_bytes(hlo_b, default_group_size)
    ta, tb = float(sum(a.values())), float(sum(b.values()))
    return {"a_bytes": ta, "b_bytes": tb,
            "ratio": ta / tb if tb else (1.0 if not ta else float("inf"))}


def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Instruction counts by collective type (async pairs count once, at
    ``-done``). Lets a test assert a lowering *contains* the expected ops
    — e.g. the ZeRO-3 variants must carry param all-gathers where the
    masked psum carries none — instead of inferring presence from the
    byte totals alone."""
    out: Dict[str, int] = Counter()
    for m in _COLL_RE.finditer(hlo_text):
        _, op, phase, _ = m.groups()
        if phase == "-start":
            continue
        out[op] += 1
    return dict(out)
