"""chip_smoke.py at smoke size on the CPU (kernels in interpret mode).

The phases run the same entry points and parity checks as on the chip;
only the HLO check for a compiled kernel is the chip's. ``main()`` must
refuse to run anywhere but on a TPU, and the script alone, without the
repository beside it, must fail without printing a result.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import stablelm_3b, vit_small_paper

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check(rec, chip_smoke, steps):
    assert len(rec["losses"]) == steps
    assert rec["loss_rel_diff"] <= chip_smoke.LOSS_RTOL
    assert rec["update_rel_diff"] <= chip_smoke.UPDATE_RTOL


def test_main_refuses_without_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_script_alone_fails_without_result(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""


def test_vit_phase_smoke(chip_smoke):
    rec = chip_smoke.vit_phase(vit_small_paper.smoke_config(), batch=10,
                               steps=3, on_chip=False)
    _check(rec, chip_smoke, 3)


def test_llm_phase_smoke(chip_smoke):
    rec = chip_smoke.llm_phase(stablelm_3b.smoke_config(), batch=5, seq=32,
                               steps=3, on_chip=False)
    _check(rec, chip_smoke, 3)


FOUR_CHIP = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import chip_smoke
from repro.configs import stablelm_3b
rec = chip_smoke.four_chip_phase(stablelm_3b.smoke_config(), batch=8,
                                 seq=16, steps=2, on_chip=False)
for mode in ("masked", "zero3"):
    assert len(rec[mode]["losses"]) == 2, rec
print("FOUR_CHIP_OK", rec["masked"]["collectives"],
      rec["zero3"]["collectives"])
"""


def test_four_chip_phase_smoke_subprocess():
    """The --four-chips phase on four host CPU devices (a fresh process:
    this one is pinned to one device)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", FOUR_CHIP, str(ROOT)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_CHIP_OK" in out.stdout


def test_compile_cache_placed_from_outside_or_fixed(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; else the
    cache goes to the fixed .jax_cache/ at the checkout's root."""
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(compile_cache.CACHE_ENV)
    try:
        assert compile_cache.enable_compile_cache() == \
            str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_peaks_keyed_by_device_kind():
    from repro.launch.mesh import peaks

    assert peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
