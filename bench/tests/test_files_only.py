"""A configuration, a cell of it and a per-layer metric enter the benchmark
by new files and appended ``BENCHMARK.json`` entries alone: no byte of a
file the layout holds changes, and the smoke rig runs the new cell as it
stands. The layout is a copy of the repository's, in a temporary
directory; the code that reads it is the repository's."""
import hashlib
import json
import shutil

import pytest

from bench import harness
from bench.tests.smoke import (REHEARSALS, layout_of, run_smoke, smoke_cells,
                               smoke_layout)

NEW = "stablelm-added"
CELL = f"{NEW}.d2ft-paper"
METRIC = "added_steps"
LM = "bench/configs/stablelm-3b-l4.json"
TIE = {"tie_embeddings": "tie_word_embeddings"}


def copy_layout(root):
    """``BENCHMARK.json`` and the benchmark's files, copied under ``root``;
    returns the copy's ``BENCHMARK.json``."""
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.REPO / "BENCHMARK.json", root)
    return root / "BENCHMARK.json"


def digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def rehearsed_traffic(cell):
    return next(x["traffic"] for x in json.loads(REHEARSALS.read_text())
                ["cells"] if x["cell"]["name"] == cell)


def add_model(benchmark):
    """Add the LM configuration ``NEW`` (the rehearsal's under a new name,
    with a tie of its own), its cell ``CELL`` and a metric that only that
    cell reports: new files, and entries appended to ``BENCHMARK.json``."""
    root = benchmark.parent
    src = root / LM
    cfg = dict(json.loads(src.read_text()), name=NEW, ties=TIE)
    (root / "bench/configs" / f"{NEW}.json").write_text(json.dumps(cfg))
    shutil.copy(src.with_name("stablelm-3b-l4.reference.py"),
                root / "bench/configs" / f"{NEW}.reference.py")
    traffic = dict(rehearsed_traffic("stablelm-3b-l4.d2ft-paper"), seq=512,
                   smoke={"seq": 16})
    (root / "bench/workloads" / f"{CELL}.json").write_text(
        json.dumps(traffic))
    (root / "bench/metrics" / f"{METRIC}.py").write_text(
        "def read(ctx):\n    return ctx['steps']\n")
    spec = json.loads(benchmark.read_text())
    spec["configs"].append({"name": NEW, "source": cfg["source"],
                            "file": f"bench/configs/{NEW}.json",
                            "reduced": cfg["reduced"], "why": "added"})
    spec["workloads"].append({"name": CELL, "config": NEW,
                              "traffic": "d2ft-paper", "chips": 1,
                              "why": "added"})
    spec["per_layer"].append({"name": METRIC, "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "entry loop (train/loop.py)",
                              "moves": "step_ms", "workloads": [CELL]})
    benchmark.write_text(json.dumps(spec, indent=2))


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """(the copy's BENCHMARK.json, its files' digests before and after the
    addition, its spec before)."""
    benchmark = copy_layout(tmp_path_factory.mktemp("layout"))
    before, spec = digest(benchmark.parent), json.loads(benchmark.read_text())
    add_model(benchmark)
    return benchmark, before, digest(benchmark.parent), spec


def test_no_existing_byte_changes(added):
    benchmark, before, after, spec = added
    new = {f"bench/configs/{NEW}.json", f"bench/configs/{NEW}.reference.py",
           f"bench/workloads/{CELL}.json", f"bench/metrics/{METRIC}.py"}
    assert set(after) == set(before) | new
    assert {p: after[p] for p in before if p != "BENCHMARK.json"} == \
        {p: before[p] for p in before if p != "BENCHMARK.json"}
    grown = json.loads(benchmark.read_text())
    assert set(grown) == set(spec)
    for k, v in spec.items():
        assert grown[k][:len(v)] == v if isinstance(v, list) else \
            grown[k] == v, k


def test_the_new_cell_and_metric_are_found(added, tmp_path):
    benchmark = added[0]
    layout = layout_of(benchmark)
    cfg, ref = layout.config(NEW)        # check_config holds the tie
    assert cfg["ties"] and callable(ref.loss)
    assert [m["name"] for m in layout.metrics_of(CELL, "per_layer")][-1] \
        == METRIC
    for w in harness.Layout().spec()["workloads"]:
        assert METRIC not in [m["name"] for m in
                              layout.metrics_of(w["name"], "per_layer")]
    assert layout.metric_reader(METRIC)({"steps": 7}) == 7
    assert CELL in [w["name"] for w in smoke_cells(benchmark)]
    small = smoke_layout(tmp_path / "smoke", benchmark)
    assert small.traffic(CELL)["seq"] == 16
    assert small.config(NEW)[0]["hidden_size"] == 128


@pytest.mark.parametrize("fault", ["", "frozen", "half_batch"])
def test_the_new_cell_runs_and_its_faults_fail(added, fault):
    res, _ = run_smoke(CELL, 2 ** 33 + 11, fault, benchmark=added[0])
    assert res["correct"] is (fault == ""), res["checks"]


@pytest.mark.parametrize("change,match", [
    ({"ties": TIE, "tie_word_embeddings": True}, "tie_embeddings is False"),
    ({"ties": {"no_such_field": "tie_word_embeddings"}},
     "no attribute 'no_such_field'"),
    ({"ties": {"tie_embeddings": "no_such_key"}}, "states no 'no_such_key'"),
    ({"ties": {"d_model": "intermediate_size"}}, "PUBLISHED_KEY ties it"),
    ({"ties": {"d_model": None}}, "PUBLISHED_KEY ties it"),
], ids=["mismatch", "missing-attribute", "missing-key", "override", "drop"])
def test_a_bad_tie_is_refused(added, change, match):
    path = added[0].parent / "bench/configs" / f"{NEW}.json"
    cfg = dict(json.loads(path.read_text()), **change)
    with pytest.raises(ValueError, match=match) as e:
        harness.check_config(cfg, path)
    assert str(path) in str(e.value)


@pytest.mark.parametrize("rel,smoke", [
    ("bench/configs/vit-s16.json", None),
    ("bench/workloads/vit-s16.d2ft-paper.json", None),
    ("bench/workloads/vit-s16.d2ft-paper.json", {"limits": {}}),
], ids=["config-without", "workload-without", "workload-limits"])
def test_smoke_layout_names_a_file_without_smoke_sizes(tmp_path, rel, smoke):
    benchmark = copy_layout(tmp_path)
    path = tmp_path / rel
    data = json.loads(path.read_text())
    if smoke is None:
        del data["smoke"]
    else:
        data["smoke"] = smoke
    path.write_text(json.dumps(data))
    with pytest.raises(ValueError, match=str(path)):
        smoke_layout(tmp_path / "smoke", benchmark)


def test_a_promoted_rehearsal_is_no_longer_rehearsed(tmp_path):
    """Once ``BENCHMARK.json`` holds a rehearsed configuration and cell by
    name, the smoke layout takes them from the benchmark's own files."""
    benchmark = copy_layout(tmp_path)
    cell = "stablelm-3b-l4.d2ft-paper"
    spec = json.loads(benchmark.read_text())
    spec["configs"].append({"name": "stablelm-3b-l4", "file": LM})
    spec["workloads"].append({"name": cell, "config": "stablelm-3b-l4",
                              "traffic": "d2ft-paper", "chips": 1})
    benchmark.write_text(json.dumps(spec))
    traffic = rehearsed_traffic(cell)
    traffic = dict(traffic, seq=512, smoke={"seq": 16},
                   limits=dict(traffic["limits"], loss_gap=2e-5))
    (tmp_path / "bench/workloads" / f"{cell}.json").write_text(
        json.dumps(traffic))
    names = [w["name"] for w in smoke_cells(benchmark)]
    assert names.count(cell) == 1
    small = smoke_layout(tmp_path / "smoke", benchmark)
    assert [c["name"] for c in small.spec()["configs"]].count(
        "stablelm-3b-l4") == 1
    assert small.traffic(cell)["limits"]["loss_gap"] == 2e-5
    assert small.traffic(cell)["seq"] == 16


def test_every_cell_of_the_repository_has_smoke_sizes(tmp_path):
    layout = smoke_layout(tmp_path)
    assert [w["name"] for w in layout.spec()["workloads"]] == \
        [w["name"] for w in smoke_cells()]
    for c in layout.spec()["configs"]:
        layout.config(c["name"])     # check_config at smoke sizes
    for w in harness.Layout().spec()["workloads"]:
        assert layout.traffic(w["name"])["limits"] == \
            harness.Layout().traffic(w["name"])["limits"]
