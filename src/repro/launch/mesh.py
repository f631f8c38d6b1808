"""Production mesh construction.

Target: TPU v5e, 256 chips per pod. Single pod = (data=16, model=16);
two pods = (pod=2, data=16, model=16) with the ``pod`` axis carrying
data parallelism across the DCN/ICI boundary (gradient all-reduce only).

All constructors are thin wrappers over the one mesh entry point,
``launch.parallel.MeshSpec.build`` — multi-axis (data, stage, tensor)
meshes come straight from ``MeshSpec(...).build()``; the functions here
keep the legacy axis layouts (1-D ``("data",)``, GSPMD
``("data", "model")``) alive.

Defined as FUNCTIONS so importing this module never touches jax device
state (required: smoke tests must see 1 CPU device; only dryrun.py sets
XLA_FLAGS for 512 host devices before any jax import).
"""
from __future__ import annotations

import jax

def make_production_mesh(*, multi_pod: bool = False):
    from repro.launch.parallel import MeshSpec
    if multi_pod:
        # the pod axis rides the spec's data slot; data/model fill stage/
        # tensor — build() is a pure reshape+naming, the semantics live in
        # the axis names
        return MeshSpec(data=2, stage=16, tensor=16).build(
            axis_names=("pod", "data", "model"), auto_axes=True)
    return MeshSpec(data=16, stage=16).build(
        axis_names=("data", "model"), auto_axes=True)


def make_host_mesh(model: int = 1):
    """Tiny ("data", "model") mesh over ALL local devices (tests/examples)."""
    from repro.launch.parallel import MeshSpec
    n = len(jax.devices())
    if n % model:
        # never drop remainder devices silently (same contract as
        # make_data_mesh's short-mesh refusal)
        raise ValueError(
            f"make_host_mesh(model={model}) cannot tile {n} local devices: "
            f"{n} % {model} != 0 would silently drop "
            f"{n % model} device(s)")
    return MeshSpec(data=n // model, stage=model).build(
        axis_names=("data", "model"), auto_axes=True)


def make_data_mesh(n_devices=None):
    """1-D ("data",) mesh over the first n local devices.

    The distributed D2FT train step (train.loop.make_distributed_train_step)
    is pure data parallelism, so it runs on this or on make_host_mesh's
    ("data", "model") mesh alike; the explicit device count lets the
    dry-run carve an 8-device data mesh out of its 512 host devices."""
    from repro.launch.parallel import MeshSpec
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    return MeshSpec(data=n).build(axis_names=("data",))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect (4 links: 50 GB/s each way per link).
TPU_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind raises
    (a roofline against a guessed peak is not a measurement)."""
    try:
        return TPU_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(TPU_PEAKS)}") \
            from None
