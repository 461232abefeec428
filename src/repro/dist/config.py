"""DistConfig: the one distribution-surface dataclass.

Every mesh/sharding/worker knob that used to be threaded through ``SWAP``,
``SGDRun``, ``EpochRunner`` and the serving engines as loose kwargs lives
here as a first-class field (the alpa ``global_env.py`` config object is
the exemplar: mesh options, resharding mode, donation policy as named
knobs rather than call-site arguments). One frozen dataclass describes

  * mesh geometry        — ``mesh_shape`` / ``mesh_axes`` (pure data, so the
    config is hashable and JSON round-trippable; ``make_mesh()`` builds the
    runtime ``jax.sharding.Mesh`` from whatever devices exist),
  * the phase-2 engine   — ``phase2_engine``: "sharded" lowers the ensemble
    epoch as ONE program with the worker axis manual (``shard_map`` over
    ``worker`` with pinned in/out shardings — each worker block runs its
    own workers, which keeps the lowering free of cross-worker collectives
    and lets Pallas kernels lower per block); "vmap" is the plain vmap that
    stays as the bitwise equivalence oracle; "auto" picks "sharded" iff the
    mesh has a worker axis,
  * donation policy      — ``donate_state``: whether epoch chunks donate the
    input TrainState buffers (off for debugging / keeping references),
  * elastic averaging    — ``elastic_deadline_s`` (> 0 turns the strict
    phase-3 barrier into a deadline: the average folds whichever workers
    report in time), ``elastic_backoff`` / ``elastic_max_extensions``
    (straggler timeout growth while fewer than ``elastic_min_workers``
    reported) — see ``repro.core.averaging.ElasticAverage``,
  * multi-host layout    — ``coordinator`` / ``num_processes`` /
    ``process_id`` feed ``jax.distributed.initialize``; ``initialize()``
    is the launcher entry point.

The CLI flag surface (``add_dist_args`` / ``DistConfig.from_args``) and the
programmatic API expose identical knobs, and ``from_json``/``to_json``
round-trip a config through a file so a launch can be replayed exactly.

Back-compat: callers that still pass ``mesh=`` get a ``DeprecationWarning``
shim (``resolve_dist``) for one release — the mesh object keeps working and
a DistConfig is derived from its geometry.
"""
from __future__ import annotations

import dataclasses
import json
import os
import warnings
from dataclasses import dataclass
from typing import Optional, Tuple

_ENGINES = ("auto", "sharded", "vmap")

# rank -> default axis names for bare "2x2x2"-style mesh specs
_DEFAULT_AXES = {
    1: ("data",),
    2: ("data", "model"),
    3: ("worker", "data", "model"),
    4: ("pod", "worker", "data", "model"),
}


def parse_mesh(spec: str) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """Parse a ``--mesh`` spec into (shape, axes).

    Two syntaxes:
      * named:  ``worker:2,data:2,model:2``
      * bare:   ``2x2x2`` — axes default by rank (1d: data; 2d: data,model;
        3d: worker,data,model; 4d: pod,worker,data,model)
    """
    spec = spec.strip()
    if not spec:
        return (), ()
    if ":" in spec:
        shape, axes = [], []
        for part in spec.split(","):
            name, _, size = part.partition(":")
            if not name.strip() or not size.strip():
                raise ValueError(f"bad mesh axis {part!r} in {spec!r} "
                                 f"(want name:size)")
            axes.append(name.strip())
            shape.append(int(size))
        return tuple(shape), tuple(axes)
    sizes = tuple(int(t) for t in spec.lower().split("x"))
    if len(sizes) not in _DEFAULT_AXES:
        raise ValueError(
            f"bare mesh spec {spec!r} has rank {len(sizes)}; use the named "
            f"form (e.g. 'worker:2,data:4') for ranks outside "
            f"{sorted(_DEFAULT_AXES)}")
    return sizes, _DEFAULT_AXES[len(sizes)]


@dataclass(frozen=True)
class DistConfig:
    """The unified distribution config (see module docstring)."""

    # mesh geometry — () means "no mesh": single-device / plain-vmap paths
    mesh_shape: Tuple[int, ...] = ()
    mesh_axes: Tuple[str, ...] = ()
    n_workers: int = 1

    # phase-2 engine + donation policy
    phase2_engine: str = "auto"        # "auto" | "sharded" | "vmap"
    donate_state: bool = True

    # elastic averaging (0 = strict: phase 3 waits for every worker)
    elastic_deadline_s: float = 0.0
    elastic_backoff: float = 2.0
    elastic_max_extensions: int = 2
    elastic_min_workers: int = 1

    # multi-host (jax.distributed)
    coordinator: str = ""              # "host:port"; "" = single process
    num_processes: int = 1
    process_id: int = 0

    # heartbeat liveness ("" = disabled: elastic arrivals stay
    # caller-supplied / simulated). See repro.dist.heartbeat.
    heartbeat_dir: str = ""
    heartbeat_interval_s: float = 0.0  # min spacing between beats
    heartbeat_timeout_s: float = 0.0   # staleness = dead; 0 derives below

    def __post_init__(self):
        if len(self.mesh_shape) != len(self.mesh_axes):
            raise ValueError(
                f"mesh_shape {self.mesh_shape} and mesh_axes "
                f"{self.mesh_axes} must have equal rank")
        if self.phase2_engine not in _ENGINES:
            raise ValueError(f"phase2_engine must be one of {_ENGINES}, "
                             f"got {self.phase2_engine!r}")
        if self.n_workers < 1:
            raise ValueError(f"n_workers must be >= 1, got {self.n_workers}")
        if self.elastic_deadline_s < 0:
            raise ValueError("elastic_deadline_s must be >= 0")
        if self.elastic_backoff < 1.0:
            raise ValueError("elastic_backoff must be >= 1 (the deadline "
                             "never shrinks)")
        if self.elastic_max_extensions < 0:
            raise ValueError("elastic_max_extensions must be >= 0")
        if not (1 <= self.elastic_min_workers <= self.n_workers):
            raise ValueError(
                f"elastic_min_workers must be in [1, n_workers="
                f"{self.n_workers}], got {self.elastic_min_workers}")
        if self.num_processes < 1:
            raise ValueError("num_processes must be >= 1")
        if not (0 <= self.process_id < self.num_processes):
            raise ValueError(
                f"process_id {self.process_id} out of range for "
                f"num_processes {self.num_processes}")
        if self.num_processes > 1 and not self.coordinator:
            raise ValueError("multi-host (num_processes > 1) needs a "
                             "coordinator address ('host:port')")
        if self.heartbeat_interval_s < 0:
            raise ValueError("heartbeat_interval_s must be >= 0")
        if self.heartbeat_timeout_s < 0:
            raise ValueError("heartbeat_timeout_s must be >= 0")
        if (self.heartbeat_timeout_s > 0 and self.heartbeat_interval_s > 0
                and self.heartbeat_timeout_s < self.heartbeat_interval_s):
            raise ValueError(
                f"heartbeat_timeout_s ({self.heartbeat_timeout_s}) must be "
                f">= heartbeat_interval_s ({self.heartbeat_interval_s}): a "
                f"timeout shorter than the beat spacing declares every "
                f"worker dead between beats")

    # ------------------------------------------------------------------
    # derived properties
    # ------------------------------------------------------------------

    @property
    def elastic(self) -> bool:
        return self.elastic_deadline_s > 0

    @property
    def multihost(self) -> bool:
        return self.num_processes > 1

    @property
    def heartbeats(self) -> bool:
        return bool(self.heartbeat_dir)

    @property
    def resolved_heartbeat_timeout(self) -> float:
        """Liveness timeout in seconds: the explicit knob, else 3 beat
        intervals (one missed beat is a hiccup, three is a death), else a
        30s default for interval-less (beat-every-boundary) setups."""
        if self.heartbeat_timeout_s > 0:
            return self.heartbeat_timeout_s
        if self.heartbeat_interval_s > 0:
            return 3.0 * self.heartbeat_interval_s
        return 30.0

    @property
    def has_worker_axis(self) -> bool:
        return "worker" in self.mesh_axes

    @property
    def data_shard(self) -> Optional[Tuple[int, int]]:
        """Per-host data shard for ``repro.data.pipeline.Loader``:
        ``(process_id, num_processes)`` so each host materializes only its
        slice of every global batch; None for single-process runs."""
        return (self.process_id, self.num_processes) if self.multihost \
            else None

    def resolved_engine(self, mesh=None) -> str:
        """'sharded' or 'vmap'. 'auto' resolves to 'sharded' exactly when a
        mesh with a worker axis is in play."""
        if self.phase2_engine != "auto":
            return self.phase2_engine
        has_worker = ("worker" in mesh.axis_names) if mesh is not None \
            else self.has_worker_axis
        return "sharded" if has_worker else "vmap"

    # ------------------------------------------------------------------
    # runtime construction
    # ------------------------------------------------------------------

    def make_mesh(self):
        """Build the runtime Mesh from ``mesh_shape``/``mesh_axes`` over the
        devices that exist, or None when no mesh is configured. The worker
        axis (when present) must be outermost in ``mesh_axes`` so worker w
        owns a contiguous device-id block (the collective-audit contract,
        see ``dist.sharding.assert_no_cross_worker_collectives``)."""
        if not self.mesh_shape:
            return None
        if "worker" in self.mesh_axes and self.mesh_axes[0] != "worker" \
                and self.mesh_axes[0] != "pod":
            raise ValueError(
                f"the worker axis must be outermost (after an optional pod "
                f"axis) so each worker owns a contiguous device block; got "
                f"axes {self.mesh_axes}")
        import jax
        return jax.make_mesh(
            self.mesh_shape, self.mesh_axes,
            axis_types=(jax.sharding.AxisType.Auto,) * len(self.mesh_axes))

    def initialize(self) -> None:
        """``jax.distributed.initialize`` for multi-host runs; a no-op for
        single-process configs. Must run before the first jax device query
        in the process (the launchers call it first thing)."""
        if not self.multihost:
            return
        import jax
        jax.distributed.initialize(
            coordinator_address=self.coordinator,
            num_processes=self.num_processes,
            process_id=self.process_id)

    @classmethod
    def from_mesh(cls, mesh, **overrides) -> "DistConfig":
        """Derive a DistConfig from an existing Mesh's geometry (the
        ``mesh=`` deprecation shim path)."""
        axes = tuple(mesh.axis_names)
        shape = tuple(int(mesh.shape[a]) for a in axes)
        kw = dict(mesh_shape=shape, mesh_axes=axes)
        if "worker" in axes:
            kw["n_workers"] = int(mesh.shape["worker"])
        kw.update(overrides)
        return cls(**kw)

    # ------------------------------------------------------------------
    # JSON round trip
    # ------------------------------------------------------------------

    def to_json(self, path: Optional[str] = None) -> str:
        """Serialize to a JSON string; also write it to ``path`` if given."""
        text = json.dumps(dataclasses.asdict(self), indent=1)
        if path:
            with open(path, "w") as f:
                f.write(text + "\n")
        return text

    @classmethod
    def from_json(cls, src: str) -> "DistConfig":
        """Load from a JSON string or a path to a JSON file. Unknown keys
        are rejected (a typoed knob must not silently default)."""
        if os.path.exists(src):
            with open(src) as f:
                src = f.read()
        data = json.loads(src)
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(data) - fields
        if unknown:
            raise ValueError(f"unknown DistConfig keys {sorted(unknown)}; "
                             f"known: {sorted(fields)}")
        for key in ("mesh_shape", "mesh_axes"):
            if key in data:
                data[key] = tuple(data[key])
        return cls(**data)

    # ------------------------------------------------------------------
    # CLI flag surface (shared by launch.train / launch.serve / examples)
    # ------------------------------------------------------------------

    @classmethod
    def from_args(cls, args, n_workers_default: int = 1) -> "DistConfig":
        """Build from an argparse namespace produced by ``add_dist_args``.

        ``--dist-config FILE`` loads a base config; explicitly-passed flags
        override it (a flag left at its parser default defers to the file).
        """
        base = cls.from_json(args.dist_config) if args.dist_config else cls()
        kw = dict(
            (f.name, getattr(base, f.name)) for f in dataclasses.fields(cls))
        if args.mesh is not None:
            kw["mesh_shape"], kw["mesh_axes"] = parse_mesh(args.mesh)
        if args.workers is not None:
            kw["n_workers"] = args.workers
        elif not args.dist_config:
            kw["n_workers"] = n_workers_default
        if args.phase2_engine is not None:
            kw["phase2_engine"] = args.phase2_engine
        if args.elastic_deadline is not None:
            kw["elastic_deadline_s"] = args.elastic_deadline
        if args.elastic_backoff is not None:
            kw["elastic_backoff"] = args.elastic_backoff
        if args.elastic_min_workers is not None:
            kw["elastic_min_workers"] = args.elastic_min_workers
        if args.coordinator is not None:
            kw["coordinator"] = args.coordinator
        if args.num_processes is not None:
            kw["num_processes"] = args.num_processes
        if args.process_id is not None:
            kw["process_id"] = args.process_id
        if args.heartbeat_dir is not None:
            kw["heartbeat_dir"] = args.heartbeat_dir
        if args.heartbeat_interval is not None:
            kw["heartbeat_interval_s"] = args.heartbeat_interval
        if args.heartbeat_timeout is not None:
            kw["heartbeat_timeout_s"] = args.heartbeat_timeout
        return cls(**kw)


def add_dist_args(parser) -> None:
    """Install the unified DistConfig flag surface on an argparse parser.
    Defaults are all None so ``DistConfig.from_args`` can tell 'not passed'
    from 'passed the default value' (file-config overrides stay correct)."""
    g = parser.add_argument_group(
        "distribution (repro.dist.DistConfig; identical to the "
        "programmatic surface)")
    g.add_argument("--mesh", default=None, metavar="SPEC",
                   help="device mesh: 'worker:2,data:2,model:2' or '2x2x2' "
                        "(bare rank-3 means worker,data,model); omit for "
                        "single-device / plain-vmap execution")
    g.add_argument("--workers", type=int, default=None,
                   help="SWAP phase-2 worker count (DistConfig.n_workers)")
    g.add_argument("--phase2-engine", default=None,
                   choices=["auto", "sharded", "vmap"],
                   help="phase-2 lowering: one sharded-jit program over the "
                        "worker mesh axis, the plain-vmap oracle, or auto "
                        "(sharded iff the mesh has a worker axis)")
    g.add_argument("--elastic-deadline", type=float, default=None,
                   metavar="SECONDS",
                   help="elastic phase-3 averaging: fold whichever workers "
                        "report within this deadline (0 = strict barrier)")
    g.add_argument("--elastic-backoff", type=float, default=None,
                   help="deadline growth factor while fewer than "
                        "--elastic-min-workers reported (default 2.0)")
    g.add_argument("--elastic-min-workers", type=int, default=None,
                   help="fewest live workers an elastic average may fold "
                        "(all-late past the backed-off deadline is an error)")
    g.add_argument("--dist-config", default="", metavar="FILE",
                   help="load a DistConfig JSON file "
                        "(DistConfig.from_json); explicit flags override it")
    g.add_argument("--dump-dist-config", default="", metavar="FILE",
                   help="write the resolved DistConfig to FILE "
                        "(DistConfig.to_json) and continue")
    g.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="jax.distributed coordinator address (multi-host)")
    g.add_argument("--num-processes", type=int, default=None,
                   help="total jax.distributed processes (multi-host)")
    g.add_argument("--process-id", type=int, default=None,
                   help="this process's jax.distributed index (multi-host)")
    g.add_argument("--heartbeat-dir", default=None, metavar="DIR",
                   help="shared directory for per-worker heartbeat beacons "
                        "(repro.dist.heartbeat); enables real liveness in "
                        "place of simulated elastic arrivals")
    g.add_argument("--heartbeat-interval", type=float, default=None,
                   metavar="SECONDS",
                   help="minimum spacing between heartbeats (0 = beat at "
                        "every chunk boundary)")
    g.add_argument("--heartbeat-timeout", type=float, default=None,
                   metavar="SECONDS",
                   help="beacon staleness that declares a worker dead "
                        "(0 = 3x the interval, or 30s)")


def resolve_dist(dist: Optional[DistConfig] = None, mesh=None, *,
                 caller: str = "caller"):
    """Resolve the (dist=, mesh=) pair every surface accepts into
    ``(DistConfig, Optional[Mesh])``.

    ``mesh=`` is the deprecated spelling: it still works for one release
    (the passed Mesh object is used as-is and a DistConfig is derived from
    its geometry) but warns. Passing both is an error — a mesh that
    disagrees with the config would silently win."""
    if mesh is not None and dist is not None:
        raise ValueError(
            f"{caller}: pass dist= (DistConfig) or the deprecated mesh=, "
            f"not both")
    if mesh is not None:
        warnings.warn(
            f"{caller}(mesh=...) is deprecated; pass "
            f"dist=DistConfig.from_mesh(mesh) (or a hand-built DistConfig) "
            f"instead. The mesh= spelling will be removed next release.",
            DeprecationWarning, stacklevel=3)
        return DistConfig.from_mesh(mesh), mesh
    if dist is None:
        return DistConfig(), None
    return dist, dist.make_mesh()
