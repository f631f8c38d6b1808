"""A copy of a benchmark's layout at smoke sizes, for runs on the CPU.

Every configuration keeps its reference and its keys; only the sizes
shrink (widths, depth, vocabulary, batch, sequence), as the ``smoke``
object of each configuration file and each workload file says. The
cells, traffic parameters, limits and metric readers are the real ones.
The rehearsals (``rehearsals.json``) add configurations and cells that
the benchmark does not hold yet. So a configuration and its cells enter
the rig by their own files and entries, with no edit here.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench import harness

BENCHMARK = harness.REPO / "BENCHMARK.json"
REHEARSALS = Path(__file__).with_name("rehearsals.json")
# what a workload's ``smoke`` object may shrink: sizes only, never a limit,
# so that a smoke check is held to the cell's own limits
SMOKE_SIZE_KEYS = {"batch", "seq", "n_microbatches", "ref_rows", "pool"}


def layout_of(benchmark: Path) -> harness.Layout:
    """The layout of a ``BENCHMARK.json``: its files under its ``paths``,
    relative to its own directory."""
    benchmark = Path(benchmark)
    root = benchmark.parent
    paths = [root / p for p in json.loads(benchmark.read_text())["paths"]]
    return harness.Layout(benchmark=benchmark, root=root,
                          workload_dirs=[p / "workloads" for p in paths],
                          metric_dirs=[p / "metrics" for p in paths])


def rehearsals(spec: dict) -> tuple[list, list]:
    """The rehearsed configurations and cells whose names ``spec`` does not
    hold: ([{name, file}], [{cell, traffic}])."""
    r = json.loads(REHEARSALS.read_text())
    configs = {c["name"] for c in spec["configs"]}
    cells = {w["name"] for w in spec["workloads"]}
    return ([c for c in r["configs"] if c["name"] not in configs],
            [x for x in r["cells"] if x["cell"]["name"] not in cells])


def smoke_cells(benchmark: Path = BENCHMARK) -> list:
    """Every cell the smoke layout of ``benchmark`` holds, rehearsals last."""
    spec = layout_of(benchmark).spec()
    return spec["workloads"] + [x["cell"] for x in rehearsals(spec)[1]]


def _smoke(obj: dict, path: Path) -> dict:
    if "smoke" not in obj:
        raise ValueError(f"{path} has no smoke object")
    return obj["smoke"]


def smoke_config(real: dict, path: Path) -> dict:
    """A configuration at its smoke sizes: published keys and the program
    object shrunk alike."""
    small = dict(_smoke(real, path))
    program = dict(real["program"], **small.pop("program", {}))
    return dict(real, **small, program=program)


def smoke_traffic(real: dict, path: Path) -> dict:
    """A traffic mix at its smoke sizes; a ``smoke`` key that is not a size
    (``SMOKE_SIZE_KEYS``) raises, naming the file."""
    small = _smoke(real, path)
    other = sorted(set(small) - SMOKE_SIZE_KEYS)
    if other:
        raise ValueError(f"{path}: smoke key {other[0]!r} is not one of "
                         f"{sorted(SMOKE_SIZE_KEYS)}")
    return dict(real, **small)


def smoke_layout(tmp: Path, benchmark: Path = BENCHMARK) -> harness.Layout:
    """Write the smoke copy of ``benchmark`` under ``tmp``; return a Layout
    reading it."""
    real = layout_of(benchmark)
    spec = real.spec()
    configs, cells = rehearsals(spec)
    (tmp / "configs").mkdir(parents=True)
    (tmp / "workloads").mkdir()
    sources = [real.root / c["file"] for c in spec["configs"]] + \
        [harness.REPO / c["file"] for c in configs]
    spec["configs"] += configs
    for c, src in zip(spec["configs"], sources):
        cfg = smoke_config(json.loads(src.read_text()), src)
        (tmp / "configs" / src.name).write_text(json.dumps(cfg))
        ref = src.with_name(src.name[:-len(".json")] + ".reference.py")
        shutil.copy(ref, tmp / "configs" / ref.name)
        c["file"] = f"configs/{src.name}"
    for w in spec["workloads"]:
        name = f"{w['name']}.json"
        src = harness._find(real.workload_dirs, name)
        t = smoke_traffic(json.loads(src.read_text()), src)
        (tmp / "workloads" / name).write_text(json.dumps(t))
    for x in cells:
        spec["workloads"].append(x["cell"])
        (tmp / "workloads" / f"{x['cell']['name']}.json").write_text(
            json.dumps(x["traffic"]))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Layout(benchmark=tmp / "BENCHMARK.json", root=tmp,
                          workload_dirs=[tmp / "workloads"],
                          metric_dirs=real.metric_dirs)


def run_smoke(cell: str, seed: int, fault: str = "",
              benchmark: Path = BENCHMARK) -> tuple[dict, str]:
    """``run_smoke.py`` in a subprocess with as many virtual CPU devices as
    the cell asks for chips: (its result object, its standard error)."""
    chips = {w["name"]: w["chips"] for w in smoke_cells(benchmark)}[cell]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.run_smoke", cell, str(seed),
         fault, "--benchmark", str(benchmark)], cwd=harness.REPO, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr
