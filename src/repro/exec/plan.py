"""First-class execution plans (LR-CNN Secs. III-C/IV as *policy objects*).

LR-CNN's contribution is a planner (Eqs. 7-16 pick a granularity N and a
strategy under a memory budget M) driving an executor (2PS / OverL / hybrid
rows).  :class:`ExecutionPlan` is the serializable hand-off between the two:
it records *what* to run (engine name, granularity, segmentation) together
with *why* (estimated peak bytes, the budget it was solved against,
feasibility), and nothing about *how* — mechanism lives in the engine
registry (:mod:`repro.exec.registry`).

Plans are plain data: JSON round-trippable, hashable, and diffable, so they
can be logged next to training metrics, shipped to remote workers, or
replayed for reproducibility.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Serializable device-mesh description — the sharding dimension of a
    plan, kept as plain data (axis names/sizes + which axis carries data
    parallelism and which carries model parallelism) so a plan solved on a
    pod replays identically on any host.

    The spec never touches jax device state; :func:`repro.launch.mesh.
    build_mesh` turns it into a live ``jax.sharding.Mesh`` over the local
    devices at execution time.
    """

    axes: Tuple[Tuple[str, int], ...]   # ordered (name, size) pairs
    data_axis: str = "data"
    model_axis: str = "model"

    def __post_init__(self):
        axes = tuple((str(n), int(s)) for n, s in self.axes)
        if not axes:
            raise ValueError("MeshSpec needs at least one axis")
        names = [n for n, _ in axes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate mesh axis names in {names}")
        for n, s in axes:
            if s < 1:
                raise ValueError(f"mesh axis {n!r} has size {s} < 1")
        object.__setattr__(self, "axes", axes)

    # ------------------------------------------------------------------
    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.axes)

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(s for _, s in self.axes)

    @property
    def n_devices(self) -> int:
        n = 1
        for _, s in self.axes:
            n *= s
        return n

    def extent(self, name: str) -> int:
        """Size of axis ``name`` (1 when the axis is absent)."""
        for n, s in self.axes:
            if n == name:
                return s
        return 1

    @property
    def data(self) -> int:
        return self.extent(self.data_axis)

    @property
    def model(self) -> int:
        return self.extent(self.model_axis)

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        """Axes the batch divides over: a "pod" axis when present plus the
        data axis — mirroring the logical-name vocabulary in
        launch/sharding.py (batch -> ("pod", "data")), so planner
        accounting and executed sharding can never disagree."""
        return tuple(n for n, _ in self.axes
                     if n == "pod" or n == self.data_axis)

    @property
    def batch_extent(self) -> int:
        """Data-parallel extent — what batch and budget divide by."""
        n = 1
        for name in self.batch_axes:
            n *= self.extent(name)
        return n

    # ------------------------------------------------------------------
    #: axis names the CLI vocabulary knows (the constructor stays general —
    #: a programmatic MeshSpec may rename data/model axes — but the string
    #: form maps onto the logical-name table in launch/sharding.py, so an
    #: unknown name there could never shard anything and is a typo).
    KNOWN_AXES = ("pod", "data", "model")

    @classmethod
    def parse(cls, s: str) -> "MeshSpec":
        """Parse the CLI form ``"data=8"`` / ``"data=4,model=2"`` (axis
        order is preserved; it becomes the mesh's major-to-minor order)."""
        axes = []
        for part in s.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(f"bad mesh axis {part!r}; expected name=N")
            n, v = part.split("=", 1)
            name = n.strip()
            if name not in cls.KNOWN_AXES:
                raise ValueError(f"unknown mesh axis {name!r}; expected one "
                                 f"of {cls.KNOWN_AXES}")
            axes.append((name, int(v)))
        return cls(axes=tuple(axes))

    def describe(self) -> str:
        return ",".join(f"{n}={s}" for n, s in self.axes)

    def to_dict(self) -> dict:
        return {"axes": [list(a) for a in self.axes],
                "data_axis": self.data_axis, "model_axis": self.model_axis}

    @classmethod
    def from_dict(cls, d: dict) -> "MeshSpec":
        return cls(axes=tuple(tuple(a) for a in d["axes"]),
                   data_axis=d.get("data_axis", "data"),
                   model_axis=d.get("model_axis", "model"))


def batch_shards(mesh: Optional[MeshSpec], batch: int) -> int:
    """THE per-device shard-count rule, shared by the Planner and
    :attr:`ExecutionPlan.data_shards`: the mesh's batch extent when it
    divides the batch evenly, else 1 (graceful replication — the
    ``filter_spec`` divisibility fallback applied at the plan level)."""
    if mesh is None:
        return 1
    k = mesh.batch_extent
    return k if k > 0 and batch % k == 0 else 1


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Serializable kernel-execution policy — the accelerator half of a
    plan.  ``backend`` picks which mechanism realises the row dataflow:
    ``"lax"`` (the reference engines; rows are framework-level slices) or
    ``"pallas"`` (rows become Pallas grid steps reusing a fixed VMEM
    working set — :mod:`repro.exec.pallas_engines`).  The tile fields are
    the per-kernel row granularities (``block_h`` for ``conv2d_rows``,
    ``bq``/``bk`` for ``swa_attention``, ``chunk`` for ``ssd_chunk``).

    ``interpret`` is tri-state: ``None`` defers to the platform
    (interpret everywhere but on a TPU — see
    :func:`repro.kernels.resolve_interpret`), so the same logged plan runs
    the Pallas interpreter on CPU CI and the compiled lowering on TPU.
    """

    backend: str = "lax"              # "lax" | "pallas"
    block_h: int = 8                  # conv2d_rows output-row block height
    bq: int = 128                     # swa_attention query block
    bk: int = 128                     # swa_attention kv block
    chunk: int = 128                  # ssd_chunk sequence chunk
    interpret: Optional[bool] = None  # None = platform default

    def __post_init__(self):
        if self.backend not in ("lax", "pallas"):
            raise ValueError(f"unknown kernel backend {self.backend!r}; "
                             f"expected 'lax' or 'pallas'")
        for f in ("block_h", "bq", "bk", "chunk"):
            if getattr(self, f) < 1:
                raise ValueError(f"KernelSpec.{f} must be >= 1, got "
                                 f"{getattr(self, f)}")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "KernelSpec":
        return cls(**d)


#: legal boundary-cache placements (ResidencySpec values)
RESIDENCY_POLICIES = ("device", "host", "recompute")


@dataclasses.dataclass(frozen=True)
class ResidencySpec:
    """Serializable boundary-cache residency policy — *where a row
    program's inter-row carries live* between the moment a row exports
    them and the moment they are consumed (next row in FP, the same row's
    recomputation in BP).

    LR-CNN's 2PS rows pin their bottom-boundary caches ("SD") from FP to
    BP, which skews the per-row memory profile; the paper offers "two
    solutions with different favorite scenarios" for that skew, and this
    spec is their policy surface:

    * ``"device"``    — caches stay in accelerator memory (the default;
      today's behaviour, fastest).
    * ``"host"``      — caches are offloaded to host memory after FP and
      double-buffered back during BP (``prefetch_depth`` rows ahead, so
      the ``jax.device_put`` round-trip overlaps the previous row's
      backward compute — the weak inter-row dependency makes the copy
      latency hideable).
    * ``"recompute"`` — caches are not saved at all; BP regenerates them
      by re-running the forward row chain (Chen et al.'s recompute end of
      the retain-vs-recompute tradeoff: cheapest memory, extra FLOPs).

    ``default`` applies to every named boundary cache; ``placements``
    overrides individual caches by name (the names a row program declares
    via ``carry_names`` — e.g. 2PS's per-level ``"sd_l3"``), so a plan can
    e.g. keep the small shallow-level caches on device and offload only
    the deep ones.  The spec is mechanism-agnostic plain data: the row-
    program executor (:mod:`repro.exec.rowprog`) applies it uniformly to
    every engine expressed as a row program.
    """

    default: str = "device"
    placements: Tuple[Tuple[str, str], ...] = ()  # (cache name, policy)
    prefetch_depth: int = 1

    def __post_init__(self):
        if self.default not in RESIDENCY_POLICIES:
            raise ValueError(f"unknown residency policy {self.default!r}; "
                             f"expected one of {RESIDENCY_POLICIES}")
        placements = tuple(sorted((str(n), str(p))
                                  for n, p in self.placements))
        for n, p in placements:
            if p not in RESIDENCY_POLICIES:
                raise ValueError(f"unknown residency policy {p!r} for "
                                 f"cache {n!r}; expected one of "
                                 f"{RESIDENCY_POLICIES}")
        names = [n for n, _ in placements]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate cache names in placements: "
                             f"{names}")
        object.__setattr__(self, "placements", placements)
        if self.prefetch_depth < 0:
            raise ValueError(f"prefetch_depth must be >= 0, got "
                             f"{self.prefetch_depth}")

    # ------------------------------------------------------------------
    @classmethod
    def parse(cls, s: str) -> Optional["ResidencySpec"]:
        """Parse the CLI/request form: a bare policy name ("host" /
        "recompute" / "device") becomes the uniform spec; "" means no
        policy (None).  The one place the string vocabulary lives — every
        CLI flag and PlanRequest funnels through here (the
        :meth:`MeshSpec.parse` pattern)."""
        s = s.strip()
        if not s:
            return None
        return cls(default=s)

    def placement(self, name: str) -> str:
        """Policy for the boundary cache called ``name``."""
        for n, p in self.placements:
            if n == name:
                return p
        return self.default

    @property
    def offloads(self) -> bool:
        """True when any cache leaves device memory (host or recompute)."""
        return self.default != "device" \
            or any(p != "device" for _, p in self.placements)

    def describe(self) -> str:
        bits = [self.default]
        if self.placements:
            bits += [f"{n}:{p}" for n, p in self.placements]
        if self.default == "host" \
                or any(p == "host" for _, p in self.placements):
            bits.append(f"prefetch={self.prefetch_depth}")
        return ",".join(bits)

    def to_dict(self) -> dict:
        return {"default": self.default,
                "placements": [list(p) for p in self.placements],
                "prefetch_depth": self.prefetch_depth}

    @classmethod
    def from_dict(cls, d: dict) -> "ResidencySpec":
        return cls(default=d.get("default", "device"),
                   placements=tuple(tuple(p)
                                    for p in d.get("placements", ())),
                   prefetch_depth=d.get("prefetch_depth", 1))


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """Serializable stage partition — *how the module trunk splits into S
    contiguous pipeline stages* (DESIGN.md §6).

    LR-CNN's rows are weakly dependent across every conv layer, which makes
    a row partition exactly the microbatch a GPipe-style schedule streams
    through layer stages: ``stages`` records the split as ``(start, end)``
    half-open module ranges that must tile the trunk contiguously, and the
    ``pipeline_rows`` engine (:mod:`repro.exec.pipeline`) runs the N row
    partitions through them with the stage-boundary activations carried as
    named row-program caches (``"stage_b{s}"``), so PR 5's residency
    placements apply to the pipeline stash unchanged.

    Under a mesh with a model axis, stage s's parameters live on model-axis
    coordinate ``s % model_extent`` conceptually; the spec itself is plain
    data and never touches device state (the :class:`MeshSpec` pattern).
    """

    stages: Tuple[Tuple[int, int], ...]   # per-stage (start, end) ranges

    def __post_init__(self):
        stages = tuple((int(a), int(b)) for a, b in self.stages)
        if not stages:
            raise ValueError("StageSpec needs at least one stage")
        if stages[0][0] != 0:
            raise ValueError(f"first stage must start at module 0, got "
                             f"{stages[0]}")
        for i, (a, b) in enumerate(stages):
            if b <= a:
                raise ValueError(f"stage {i} range ({a}, {b}) is empty")
            if i and a != stages[i - 1][1]:
                raise ValueError(f"stages must be contiguous: stage {i} "
                                 f"starts at {a} but stage {i - 1} ends at "
                                 f"{stages[i - 1][1]}")
        object.__setattr__(self, "stages", stages)

    # ------------------------------------------------------------------
    @property
    def n_stages(self) -> int:
        return len(self.stages)

    @property
    def n_modules(self) -> int:
        return self.stages[-1][1]

    @classmethod
    def even(cls, n_modules: int, n_stages: int) -> "StageSpec":
        """Split ``n_modules`` into ``n_stages`` contiguous near-even
        ranges (the remainder spreads over the leading stages)."""
        if not 1 <= n_stages <= n_modules:
            raise ValueError(f"cannot split {n_modules} modules into "
                             f"{n_stages} stages")
        base, rem = divmod(n_modules, n_stages)
        stages, start = [], 0
        for s in range(n_stages):
            end = start + base + (1 if s < rem else 0)
            stages.append((start, end))
            start = end
        return cls(stages=tuple(stages))

    def describe(self) -> str:
        return "|".join(f"{a}:{b}" for a, b in self.stages)

    def to_dict(self) -> dict:
        return {"stages": [list(s) for s in self.stages]}

    @classmethod
    def from_dict(cls, d: dict) -> "StageSpec":
        return cls(stages=tuple(tuple(s) for s in d["stages"]))


@dataclasses.dataclass(frozen=True)
class PlanRequest:
    """What a config *asks for* — resolved to an :class:`ExecutionPlan` by
    the :class:`~repro.exec.planner.Planner` at launch time.

    Either pin an engine/granularity explicitly, or leave ``n_rows`` at 0
    and set ``budget_gb`` to let the solver pick both (Eqs. 9/10/12/16).
    """

    engine: str = ""                  # "" = auto-select under budget
    n_rows: int = 0                   # 0 = solve min N under budget
    budget_gb: float = 0.0            # activation budget M (0 = none)
    n_segments: Optional[int] = None  # hybrid/ckp segment count (None = sqrt L)
    mesh: str = ""                    # "data=8[,model=2]"; "" = single-device
    kernel: str = ""                  # "pallas" = kernel-backed engines;
    #                                   "lax"/"" = reference engines
    residency: str = ""               # "host"/"recompute" = boundary-cache
    #                                   residency policy; ""/"device" = HBM


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """A resolved, serializable execution policy.

    ``segments`` (when non-empty) pins the hybrid segmentation as
    ``(start, end, n_rows)`` triples over the module list; engines honour it
    verbatim so a logged plan replays bit-for-bit.  ``extras`` carries
    engine-specific knobs (sequence axis, SWA window, ...) as a flat tuple
    of pairs to keep the plan hashable and JSON-clean.

    ``mesh`` (when set) makes sharding part of the policy: ``batch``,
    ``est_bytes`` and ``budget`` are *global*, ``est_bytes_per_device`` /
    ``budget // mesh.data`` are what one accelerator sees, and
    :meth:`per_device` projects the plan onto a single device (the sub-plan
    a one-device host replays).

    ``residency`` (when set) makes boundary-cache placement part of the
    policy: the row-program executor honours it uniformly for every
    carry-based engine (:mod:`repro.exec.rowprog`), and the Planner prices
    it (host-offload / recompute terms next to the Eqs. 7-16 accounting).
    It composes orthogonally with ``mesh`` and ``kernel``.

    ``stage`` (when set) makes pipeline-stage partitioning part of the
    policy: a :class:`StageSpec` splitting the trunk into S contiguous
    stages the ``pipeline_rows`` engine streams the N row microbatches
    through (:mod:`repro.exec.pipeline`), with ξ divided over the model
    axis per stage in the Planner's accounting.
    """

    engine: str
    n_rows: int = 1
    in_shape: Optional[Tuple[int, int, int]] = None  # (H, W, C); None for seq
    batch: int = 1
    dtype_bytes: int = 4
    n_segments: Optional[int] = None
    segments: Tuple[Tuple[int, int, int], ...] = ()
    est_bytes: int = 0       # global (sum over devices)
    est_bytes_per_device: int = 0
    budget: int = 0          # bytes, global; 0 = unconstrained
    feasible: bool = True
    mesh: Optional[MeshSpec] = None
    kernel: Optional[KernelSpec] = None
    residency: Optional[ResidencySpec] = None
    stage: Optional[StageSpec] = None
    extras: Tuple[Tuple[str, Any], ...] = ()

    def __post_init__(self):
        # normalize containers so equality survives a JSON round-trip
        object.__setattr__(self, "extras", tuple(sorted(self.extras)))
        object.__setattr__(self, "segments",
                           tuple(tuple(s) for s in self.segments))
        if self.in_shape is not None:
            object.__setattr__(self, "in_shape", tuple(self.in_shape))
        if isinstance(self.mesh, dict):
            object.__setattr__(self, "mesh", MeshSpec.from_dict(self.mesh))
        if isinstance(self.kernel, dict):
            object.__setattr__(self, "kernel",
                               KernelSpec.from_dict(self.kernel))
        if isinstance(self.residency, dict):
            object.__setattr__(self, "residency",
                               ResidencySpec.from_dict(self.residency))
        if isinstance(self.stage, dict):
            object.__setattr__(self, "stage",
                               StageSpec.from_dict(self.stage))
        if not self.est_bytes_per_device and self.est_bytes:
            object.__setattr__(self, "est_bytes_per_device",
                               self.est_bytes // self.data_shards)

    # ------------------------------------------------------------------
    @property
    def h0(self) -> int:
        """Input height the CNN engines partition over."""
        if self.in_shape is None:
            raise ValueError(f"plan for engine {self.engine!r} has no in_shape")
        return self.in_shape[0]

    @property
    def data_shards(self) -> int:
        """Effective data-parallel shard count (pod x data axes when they
        divide the batch evenly, else 1 — see :func:`batch_shards`)."""
        return batch_shards(self.mesh, self.batch)

    def per_device(self) -> "ExecutionPlan":
        """Project this plan onto ONE device: the sub-plan a single-device
        host replays (batch and budget divided by the data extent, estimates
        per-device, mesh dropped).  Identity for unsharded plans."""
        if self.mesh is None:
            return self
        k = self.data_shards
        repl = dataclasses.replace(
            self, mesh=None, batch=self.batch // k,
            est_bytes=self.est_bytes_per_device,
            est_bytes_per_device=self.est_bytes_per_device,
            budget=self.budget // k)
        if self.engine == "serve_pool":
            # decode slots ARE the batch: shard the slot count too
            repl = dataclasses.replace(repl, n_rows=max(1, self.n_rows // k))
        return repl

    def get(self, key: str, default: Any = None) -> Any:
        for k, v in self.extras:
            if k == key:
                return v
        return default

    def with_extras(self, **kv) -> "ExecutionPlan":
        extras = tuple((k, v) for k, v in self.extras if k not in kv) \
            + tuple(kv.items())
        return dataclasses.replace(self, extras=extras)

    # ------------------------------------------------------------------
    @classmethod
    def explicit(cls, engine: str, n_rows: int = 1,
                 in_shape: Optional[Tuple[int, int, int]] = None,
                 n_segments: Optional[int] = None,
                 mesh: Optional[MeshSpec] = None,
                 kernel: Optional[KernelSpec] = None,
                 residency: Optional[ResidencySpec] = None,
                 stage: Optional[StageSpec] = None,
                 **extras) -> "ExecutionPlan":
        """An unestimated plan pinning (engine, N) — the escape hatch for
        callers that already know what they want (benchmarks, tests)."""
        return cls(engine=engine, n_rows=n_rows, in_shape=in_shape,
                   n_segments=n_segments, mesh=mesh, kernel=kernel,
                   residency=residency, stage=stage,
                   extras=tuple(extras.items()))

    # ------------------------------------------------------------------
    def describe(self) -> str:
        bits = [f"engine={self.engine}", f"N={self.n_rows}"]
        if self.mesh is not None:
            bits.append(f"mesh={self.mesh.describe()}")
        if self.segments:
            bits.append(f"segments={len(self.segments)}")
        if self.est_bytes:
            bits.append(f"est={self.est_bytes / 2**20:.1f}MiB")
            if self.mesh is not None:
                bits.append(
                    f"est/dev={self.est_bytes_per_device / 2**20:.1f}MiB")
        if self.budget:
            bits.append(f"budget={self.budget / 2**20:.1f}MiB")
            bits.append(f"feasible={self.feasible}")
        if self.kernel is not None:
            bits.append(f"kernel={self.kernel.backend}")
        if self.residency is not None:
            bits.append(f"residency={self.residency.describe()}")
        if self.stage is not None:
            bits.append(f"stages={self.stage.describe()}")
        for k, v in self.extras:
            bits.append(f"{k}={v}")
        return "ExecutionPlan(" + " ".join(bits) + ")"

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["in_shape"] = list(self.in_shape) if self.in_shape else None
        d["segments"] = [list(s) for s in self.segments]
        d["extras"] = {k: v for k, v in self.extras}
        d["mesh"] = self.mesh.to_dict() if self.mesh is not None else None
        d["kernel"] = self.kernel.to_dict() if self.kernel is not None \
            else None
        d["residency"] = self.residency.to_dict() \
            if self.residency is not None else None
        d["stage"] = self.stage.to_dict() if self.stage is not None else None
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ExecutionPlan":
        d = dict(d)
        if d.get("in_shape") is not None:
            d["in_shape"] = tuple(d["in_shape"])
        d["segments"] = tuple(tuple(s) for s in d.get("segments", ()))
        d["extras"] = tuple(sorted(d.get("extras", {}).items()))
        if d.get("mesh") is not None:
            d["mesh"] = MeshSpec.from_dict(d["mesh"])
        if d.get("kernel") is not None:
            d["kernel"] = KernelSpec.from_dict(d["kernel"])
        if d.get("residency") is not None:
            d["residency"] = ResidencySpec.from_dict(d["residency"])
        if d.get("stage") is not None:
            d["stage"] = StageSpec.from_dict(d["stage"])
        return cls(**d)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "ExecutionPlan":
        return cls.from_dict(json.loads(s))
