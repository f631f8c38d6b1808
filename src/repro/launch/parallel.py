"""Unified multi-axis parallelism config: ``MeshSpec`` + ``ParallelConfig``.

``MeshSpec(data, stage, tensor)`` names the three parallel axes every
distributed D2FT path speaks:

* ``data``   — batch sharding; gradient sync (masked / ZeRO-1 / ZeRO-3
  / streamed) always runs over this axis.
* ``stage``  — GPipe-style pipeline stages over contiguous layer ranges,
  balanced by *live* schedule cost (``core.assignment.assign_stages``).
* ``tensor`` — Megatron-style sharding of attention heads / FFN columns
  at the same (layer, head-group) granularity the schedule gates.

``MeshSpec.build()`` is the single mesh constructor (the legacy
``make_data_mesh`` / ``make_host_mesh`` / ``make_production_mesh`` in
``launch.mesh`` are thin wrappers over it).

``ParallelConfig`` is the frozen bundle of mesh spec + execution options
that ``train.loop.make_distributed_train_step`` / ``finetune_distributed``
and ``launch/train.py`` take as their one way to set the distributed
step's options. All cross-option validation lives in
``ParallelConfig.validate()`` — run at construction — instead of being
scattered across call sites.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

SYNC_MODES = ("masked", "zero", "zero3", "local")

# canonical axis names, in mesh order
DATA_AXIS, STAGE_AXIS, TENSOR_AXIS = "data", "stage", "tensor"


@dataclass(frozen=True)
class MeshSpec:
    """Logical (data, stage, tensor) mesh shape. Frozen and hashable so a
    ParallelConfig can key jit caches."""
    data: int = 1
    stage: int = 1
    tensor: int = 1

    @classmethod
    def parse(cls, text: str) -> "MeshSpec":
        """Parse ``"data=4,stage=2,tensor=1"`` (unlisted axes default 1)."""
        sizes = {}
        for part in filter(None, (p.strip() for p in text.split(","))):
            if "=" not in part:
                raise ValueError(
                    f"bad --mesh entry {part!r}: expected axis=size "
                    "(e.g. data=4,stage=2,tensor=1)")
            k, v = part.split("=", 1)
            k = k.strip()
            if k not in (DATA_AXIS, STAGE_AXIS, TENSOR_AXIS):
                raise ValueError(
                    f"unknown mesh axis {k!r}: valid axes are "
                    f"{DATA_AXIS}/{STAGE_AXIS}/{TENSOR_AXIS}")
            if k in sizes:
                raise ValueError(f"mesh axis {k!r} given twice")
            sizes[k] = int(v)
        return cls(**sizes)

    @property
    def shape(self) -> Tuple[int, int, int]:
        return (self.data, self.stage, self.tensor)

    @property
    def axis_names(self) -> Tuple[str, str, str]:
        return (DATA_AXIS, STAGE_AXIS, TENSOR_AXIS)

    @property
    def size(self) -> int:
        return self.data * self.stage * self.tensor

    def validate(self):
        for name, n in zip(self.axis_names, self.shape):
            if not isinstance(n, int) or n < 1:
                raise ValueError(
                    f"mesh axis {name!r} must be a positive int, got {n!r}")

    def __post_init__(self):
        self.validate()

    def describe(self) -> str:
        return ",".join(f"{k}={v}"
                        for k, v in zip(self.axis_names, self.shape))

    def build(self, devices=None, *, axis_names=None, auto_axes=False):
        """Construct the ``jax.sharding.Mesh`` — the one mesh entry point.

        Default layout keeps all three ``("data", "stage", "tensor")``
        axes (singletons included) so step code can address any axis
        unconditionally.

        axis_names: legacy override — a tuple of 1..3 names renaming the
        (data, stage, tensor) positions in order; axes beyond its length
        must be singleton and are dropped. ``make_data_mesh`` passes
        ``("data",)``, ``make_host_mesh`` passes ``("data", "model")``.
        auto_axes: ``jax.make_mesh`` with Auto axis types over ALL local
        devices — what the GSPMD policy
        paths expect; the default builds an explicit
        ``Mesh(devices[:size])`` so a sub-mesh can be carved out of a
        larger host pool (bench/dry-run idiom).
        """
        import jax
        import numpy as np

        names = tuple(axis_names) if axis_names is not None \
            else self.axis_names
        if not 1 <= len(names) <= 3:
            raise ValueError(f"axis_names must name 1..3 axes, got {names!r}")
        for dropped_name, n in zip(self.axis_names[len(names):],
                                   self.shape[len(names):]):
            if n != 1:
                raise ValueError(
                    f"axis_names {names!r} drops the {dropped_name!r} axis "
                    f"but its size is {n} (must be 1)")
        shape = self.shape[:len(names)]
        if auto_axes:
            return jax.make_mesh(
                shape, names,
                axis_types=(jax.sharding.AxisType.Auto,) * len(names))
        devs = list(jax.devices()) if devices is None else list(devices)
        n = int(np.prod(shape))
        if n > len(devs):
            # never truncate silently: a bench/dry-run asking for 8 devices
            # on a 1-device backend would otherwise record a bogus
            # measurement
            raise ValueError(
                f"requested a {self.describe()} mesh ({n} devices) but only "
                f"{len(devs)} local devices exist "
                "(--xla_force_host_platform_device_count must be in "
                "XLA_FLAGS before jax initializes)")
        return jax.sharding.Mesh(
            np.asarray(devs[:n]).reshape(shape), names)


@dataclass(frozen=True)
class ParallelConfig:
    """Frozen execution config for the distributed D2FT train step.

    The only way to set ``make_distributed_train_step`` /
    ``finetune_distributed``'s options. All cross-option validation
    happens at construction.

    microbatches: pipeline microbatches per data shard (required > 0 when
    ``mesh.stage > 1``, must be 0 otherwise).
    """
    mesh: MeshSpec = field(default_factory=MeshSpec)
    sync_mode: str = "masked"
    streamed: bool = False
    opt_chunk: Optional[int] = None
    guard: bool = False
    use_kernel: bool = False
    microbatches: int = 0

    # -- axis name helpers (None when the axis would be degenerate) -----
    @property
    def data_axis(self) -> str:
        return DATA_AXIS

    @property
    def stage_axis(self) -> Optional[str]:
        return STAGE_AXIS if self.mesh.stage > 1 else None

    @property
    def tensor_axis(self) -> Optional[str]:
        return TENSOR_AXIS if self.mesh.tensor > 1 else None

    def __post_init__(self):
        self.validate()

    def validate(self):
        """Consolidated cross-option checks (formerly scattered across
        ``make_distributed_train_step`` and its call sites)."""
        self.mesh.validate()
        if self.sync_mode not in SYNC_MODES:
            raise ValueError(f"unknown sync_mode {self.sync_mode!r}: "
                             f"valid modes are {SYNC_MODES}")
        # streamed/opt_chunk ride the ZeRO-3 shard layout (AssertionError
        # kept for back-compat with the pre-ParallelConfig step API)
        if self.streamed or self.opt_chunk:
            assert self.sync_mode == "zero3", \
                "streamed/opt_chunk require sync_mode='zero3'"
        if self.streamed and self.guard:
            raise ValueError(
                "streamed ZeRO-3 cannot guard: the guard zeroes anomalous "
                "local grads before any collective, but the streamed "
                "reduce-scatters live inside the vjp")
        S, T = self.mesh.stage, self.mesh.tensor
        if self.sync_mode == "local" and (S > 1 or T > 1):
            raise ValueError("sync_mode='local' is communication-free and "
                             "incompatible with stage/tensor axes")
        if self.streamed and (S > 1 or T > 1):
            raise ValueError(
                "streamed ZeRO-3 fuses reduce-scatters into the vjp and "
                "does not compose with stage/tensor axes yet — use "
                "streamed=False")
        if self.guard and (S > 1 or T > 1):
            raise ValueError(
                "guard zeroes whole-device local grads, which are partial "
                "contributions under stage/tensor parallelism — guard "
                "requires a pure data mesh")
        if self.use_kernel and (S > 1 or T > 1):
            raise ValueError(
                "use_kernel has no stage/tensor route yet (the pipeline "
                "and tensor-parallel paths run the masked reference)")
        if S > 1:
            if self.microbatches < 1:
                raise ValueError(
                    f"stage={S} pipeline needs microbatches >= 1, got "
                    f"{self.microbatches}")
        elif self.microbatches:
            raise ValueError(
                "microbatches is a pipeline option: set mesh.stage > 1")

    def validate_model(self, cfg):
        """Model-dependent divisibility checks (tensor axis tiling)."""
        T = self.mesh.tensor
        if T > 1:
            if cfg.n_heads % T or cfg.n_kv_heads % T:
                raise ValueError(
                    f"tensor={T} must divide n_heads={cfg.n_heads} and "
                    f"n_kv_heads={cfg.n_kv_heads}")
        if self.mesh.stage > 1 and cfg.n_layers < self.mesh.stage:
            raise ValueError(
                f"stage={self.mesh.stage} needs at least that many layers "
                f"(n_layers={cfg.n_layers})")

    def validate_mesh(self, mesh):
        """Check a built jax Mesh carries the axes this config needs."""
        shape = dict(mesh.shape)
        if shape.get(DATA_AXIS, 1) != self.mesh.data:
            raise ValueError(
                f"mesh data axis is {shape.get(DATA_AXIS, 1)}, "
                f"ParallelConfig says {self.mesh.data}")
        for name, want in ((STAGE_AXIS, self.mesh.stage),
                           (TENSOR_AXIS, self.mesh.tensor)):
            if want > 1 and shape.get(name, 1) != want:
                raise ValueError(
                    f"ParallelConfig wants {name}={want} but the mesh has "
                    f"{name}={shape.get(name, 1)} "
                    f"(mesh axes: {dict(mesh.shape)})")
