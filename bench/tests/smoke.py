"""A copy of the benchmark's layout at smoke sizes, for runs on the CPU.

Every configuration keeps its reference and its keys; only the sizes
shrink (widths, depth, vocabulary, batch, sequence). The cells, traffic
parameters, limits and metric readers are the real ones.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench import harness

SMOKE_CONFIG = {
    "vit-s16": dict(hidden_size=96, num_hidden_layers=2, head_dim=16,
                    intermediate_size=192, patch_size=8, image_size=32,
                    program=dict(d_model=96, n_layers=2, d_ff=192, patch=8,
                                 image_size=32)),
    "stablelm-3b-l4": dict(hidden_size=128, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=4,
                           head_dim=32, intermediate_size=256,
                           vocab_size=512,
                           program=dict(d_model=128, n_layers=2, n_heads=4,
                                        n_kv_heads=4, head_dim=32, d_ff=256,
                                        vocab_size=512)),
}
SMOKE_TRAFFIC = {
    "vit-s16.d2ft-paper": dict(batch=10, ref_rows=5, pool=4),
    "vit-s16.full-ft": dict(batch=10, ref_rows=5, pool=4),
}
# Cells of the issue not yet in BENCHMARK.json (PERF.md, Open questions):
# rehearsed here, the four-chip one on four virtual CPU devices, so that
# their configuration, reference and entry drivers keep working until a
# later change adds the cells. Their limits are the CPU's (float32 is
# exact there), not the chip's.
SMOKE_ONLY_CONFIGS = [{"name": "stablelm-3b-l4",
                       "file": "bench/configs/stablelm-3b-l4.json"}]
SMOKE_ONLY = [
    ({"name": "stablelm-3b-l4.d2ft-paper", "config": "stablelm-3b-l4",
      "traffic": "d2ft-paper", "chips": 1},
     {"entry": "finetune", "batch": 5, "seq": 16, "n_microbatches": 5,
      "n_pf": 3, "n_po": 1, "pool": 4, "data": {"order_bias": 6.0},
      "ref_rows": 5,
      "limits": {"loss_gap": 1e-5, "grad_gap": 1e-4, "update_gap": 1e-4,
                 "plan_gap": 1e-3}}),
    ({"name": "stablelm-3b-l4.full-ft", "config": "stablelm-3b-l4",
      "traffic": "full-ft", "chips": 1},
     {"entry": "finetune", "d2ft": False, "batch": 5, "seq": 16,
      "n_microbatches": 5, "pool": 4, "data": {"order_bias": 6.0},
      "ref_rows": 5,
      "limits": {"loss_gap": 1e-5, "grad_gap": 1e-4, "update_gap": 1e-4}}),
    ({"name": "stablelm-3b-l4.d2ft-zero3-4chip", "config": "stablelm-3b-l4",
      "traffic": "d2ft-zero3-4chip", "chips": 4},
     {"entry": "finetune_distributed", "batch": 20, "seq": 16,
      "n_microbatches": 20, "n_pf": 12, "n_po": 4, "data_parallel": 4,
      "sync_mode": "zero3", "pool": 4, "data": {"order_bias": 6.0},
      "ref_rows": 5,
      "limits": {"loss_gap": 1e-5, "grad_gap": 1e-4, "update_gap": 1e-4,
                 "plan_gap": 1e-3}}),
]


def smoke_config(real: dict) -> dict:
    """A configuration at its smoke sizes: published keys and the program
    object shrunk alike."""
    small = dict(SMOKE_CONFIG[real["name"]])
    program = dict(real["program"], **small.pop("program"))
    return dict(real, **small, program=program)


def smoke_layout(tmp: Path) -> harness.Layout:
    """Write the smoke copy under ``tmp``; return a Layout reading it."""
    real = harness.Layout()
    spec = real.spec()
    (tmp / "configs").mkdir(parents=True)
    (tmp / "workloads").mkdir()
    spec["configs"] += SMOKE_ONLY_CONFIGS
    for c in spec["configs"]:
        src = harness.REPO / c["file"]
        cfg = smoke_config(json.loads(src.read_text()))
        (tmp / "configs" / src.name).write_text(json.dumps(cfg))
        ref = src.with_name(src.name[:-len(".json")] + ".reference.py")
        shutil.copy(ref, tmp / "configs" / ref.name)
        c["file"] = f"configs/{src.name}"
    for w in spec["workloads"]:
        t = dict(real.traffic(w["name"]), **SMOKE_TRAFFIC[w["name"]])
        (tmp / "workloads" / f"{w['name']}.json").write_text(json.dumps(t))
    for cell, traffic in SMOKE_ONLY:
        spec["workloads"].append(cell)
        (tmp / "workloads" / f"{cell['name']}.json").write_text(
            json.dumps(traffic))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Layout(benchmark=tmp / "BENCHMARK.json", root=tmp,
                          workload_dirs=[tmp / "workloads"])
