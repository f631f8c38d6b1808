"""The program's model configuration is built from each configuration
file's ``program`` object, and a file the harness cannot take fails at
load, naming what it cannot take."""
import json

import pytest

from bench import harness
from repro.configs.base import (ATTN_GLOBAL, SSD, ModelConfig, MoEConfig,
                                SSMConfig)
from repro.models.vit import ViTConfig

# the configurations the harness built by hand before the ``program``
# objects existed
BEFORE = {
    "vit-s16": ViTConfig(n_layers=12, d_model=384, n_heads=6, d_ff=1536,
                         patch=16, image_size=224, n_classes=10),
    "stablelm-3b-l4": ModelConfig(
        name="stablelm-3b-l4", arch_type="dense", n_layers=4, d_model=2560,
        n_heads=32, n_kv_heads=32, head_dim=80, d_ff=6912,
        vocab_size=50304, block_pattern=(ATTN_GLOBAL,), mlp_act="silu",
        mlp_gated=True, norm="layer", rope_theta=10000.0,
        tie_embeddings=False),
}


# the configuration files, also those no cell of BENCHMARK.json uses yet
FILES = {"vit-s16": "bench/configs/vit-s16.json",
         "stablelm-3b-l4": "bench/configs/stablelm-3b-l4.json"}


def config(name):
    return json.loads((harness.REPO / FILES[name]).read_text())


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_program_rebuilds_the_configuration(name):
    c = config(name)
    harness.check_config(c, FILES[name])
    built = harness.program_config(c)
    assert built == BEFORE[name]


def test_hybrid_program_builds_nested_dataclasses():
    c = {"family": "lm", "program": {
        "name": "hybrid", "arch_type": "hybrid", "n_layers": 4,
        "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_ff": 128,
        "vocab_size": 100, "block_pattern": ["ssd", "ssd", "attn_global"],
        "ssm": {"state_dim": 16, "head_dim": 16, "expand": 2,
                "conv_width": 4, "chunk": 8},
        "moe": {"n_experts": 4, "top_k": 2, "d_ff": 32}}}
    cfg = harness.program_config(c)
    assert cfg.block_pattern == (SSD, SSD, ATTN_GLOBAL)
    assert cfg.ssm == SSMConfig(state_dim=16, head_dim=16, expand=2,
                                conv_width=4, chunk=8)
    assert cfg.moe == MoEConfig(n_experts=4, top_k=2, d_ff=32)
    assert cfg.rglru is None
    assert cfg.layer_kinds == (SSD, SSD, ATTN_GLOBAL, SSD)


def test_unknown_nested_key_names_it():
    c = {"family": "lm", "program": {
        "name": "x", "arch_type": "ssm", "n_layers": 1, "d_model": 8,
        "n_heads": 0, "n_kv_heads": 0, "d_ff": 0, "vocab_size": 8,
        "ssm": {"state_dim": 16, "d_state": 16}}}
    with pytest.raises(ValueError, match="SSMConfig has no field 'd_state'"):
        harness.program_config(c)


def layout_with(tmp_path, name, **changes):
    """The real layout, holding every configuration file, with
    configuration ``name``'s file changed."""
    spec = harness.Layout().spec()
    spec["configs"] = []
    for n, f in FILES.items():
        src = harness.REPO / f
        cfg = dict(config(n), **(changes if n == name else {}))
        (tmp_path / src.name).write_text(json.dumps(cfg))
        ref = src.with_name(src.name[:-len(".json")] + ".reference.py")
        (tmp_path / ref.name).write_text(ref.read_text())
        spec["configs"].append({"name": n, "file": src.name})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Layout(benchmark=tmp_path / "BENCHMARK.json",
                          root=tmp_path)


def test_unknown_program_key_fails_at_load(tmp_path):
    program = dict(config("stablelm-3b-l4")["program"],
                   embedding_multiplier=12.0)
    layout = layout_with(tmp_path, "stablelm-3b-l4", program=program)
    with pytest.raises(ValueError, match="'embedding_multiplier'"):
        layout.config("stablelm-3b-l4")
    assert layout.config("vit-s16")[0]["name"] == "vit-s16"


def test_uncountable_layer_kind_fails_at_load(tmp_path):
    kinds = ["attention", "mamba", "rwkv", "attention"]
    layout = layout_with(tmp_path, "stablelm-3b-l4", layer_types=kinds)
    with pytest.raises(ValueError, match="layer kind 'rwkv'"):
        layout.config("stablelm-3b-l4")


@pytest.mark.parametrize("name,program,key", [
    ("stablelm-3b-l4", {"d_ff": 6000}, "intermediate_size"),
    ("stablelm-3b-l4", {"head_dim": 64}, "head_dim"),
    ("stablelm-3b-l4", {"mlp_act": "gelu"}, "hidden_act"),
    ("vit-s16", {"n_layers": 6}, "num_hidden_layers"),
    ("vit-s16", {"patch": 32}, "patch_size")])
def test_program_size_other_than_published_fails_at_load(tmp_path, name,
                                                         program, key):
    layout = layout_with(tmp_path, name, program=dict(config(name)["program"],
                                                       **program))
    with pytest.raises(ValueError, match=f"published {key} is"):
        layout.config(name)


def test_nested_program_size_is_held_to_the_published_key():
    c = {"family": "lm", "hidden_size": 64, "num_hidden_layers": 4,
         "num_attention_heads": 4, "mamba_d_state": 16,
         "mamba_chunk_size": 8, "program": {
             "name": "hybrid", "arch_type": "hybrid", "n_layers": 4,
             "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_ff": 128,
             "vocab_size": 100, "block_pattern": ["ssd", "attn_global"],
             "ssm": {"state_dim": 16, "head_dim": 16, "chunk": 8}}}
    harness.check_config(c, "hybrid.json")
    c["program"]["ssm"]["chunk"] = 16
    with pytest.raises(ValueError, match="hybrid.json: program ssm.chunk "
                       "is 16 where the published mamba_chunk_size is 8"):
        harness.check_config(c, "hybrid.json")
