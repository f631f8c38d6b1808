"""The harness finds cells, traffic files and metric readers by name."""
import json
from pathlib import Path

import pytest

from bench import harness


def test_every_cell_has_its_files():
    layout = harness.Layout()
    spec = layout.spec()
    for w in spec["workloads"]:
        cfg, ref = layout.config(w["config"])
        assert cfg["name"] == w["config"]
        assert callable(ref.loss) and callable(ref.init)
        t = layout.traffic(w["name"])
        assert t["entry"] in harness.ENTRIES
        assert set(t["limits"]) == {"loss_gap", "grad_gap", "update_gap"} | (
            {"plan_gap"} if t.get("d2ft", True) else set())
    for m in spec["per_layer"]:
        assert callable(layout.metric_reader(m["name"]))


def test_added_files_are_found_without_editing(tmp_path):
    """A later change adds a cell and a metric by adding files only."""
    real = harness.Layout()
    spec = real.spec()
    cell = dict(spec["workloads"][0], name="vit-s16.added-mix")
    spec["workloads"].append(cell)
    spec["per_layer"].append({"name": "added_metric", "unit": "s",
                              "better": "lower", "source": "host_clock",
                              "layer": "entry loop (train/loop.py)",
                              "moves": "setup_s",
                              "workloads": ["vit-s16.added-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "workloads").mkdir()
    (tmp_path / "metrics").mkdir()
    mix = dict(real.traffic(spec["workloads"][0]["name"]), batch=100)
    (tmp_path / "workloads" / "vit-s16.added-mix.json").write_text(
        json.dumps(mix))
    (tmp_path / "metrics" / "added_metric.py").write_text(
        "def read(ctx):\n    return ctx['warmup_s'] * 2\n")
    layout = harness.Layout(
        benchmark=tmp_path / "BENCHMARK.json", root=harness.REPO,
        workload_dirs=[tmp_path / "workloads", harness.BENCH / "workloads"],
        metric_dirs=[tmp_path / "metrics", harness.BENCH / "metrics"])
    assert layout.traffic("vit-s16.added-mix")["batch"] == 100
    assert layout.cell("vit-s16.added-mix")["config"] == "vit-s16"
    names = [m["name"] for m in layout.metrics_of("vit-s16.added-mix",
                                                  "per_layer")]
    assert "added_metric" in names and "collective_ms" not in names
    assert layout.metric_reader("added_metric")({"warmup_s": 1.5}) == 3.0
    # the real cells are untouched by the addition
    assert "added_metric" not in [
        m["name"] for m in layout.metrics_of(spec["workloads"][0]["name"],
                                             "per_layer")]


def test_unknown_names_raise():
    layout = harness.Layout()
    with pytest.raises(KeyError):
        layout.cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        layout.metric_reader("no_such_metric")


def test_paths_hold_only_the_benchmark():
    spec = harness.Layout().spec()
    assert spec["paths"] == ["bench"]
    for c in spec["configs"]:
        assert Path(c["file"]).parts[0] == "bench"
