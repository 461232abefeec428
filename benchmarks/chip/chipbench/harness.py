"""What every cell shares: finding a cell's files by name, the device and
compile-cache set-up, the traced window, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Its pieces are
found by name, so a later cell adds files and edits none:

  configs/<config>.json    the configuration as run (published keys,
                           ``reduced``, ``assumed``, the program mapping)
  traffic/<traffic>.json   the job kind and its parameters
  jobs/<job>.py            one module per job kind: ``run(run) -> dict``
  limits/<workload>.json   the limit of each number ``correct`` compares
  metrics/<metric>.py      one reader per per-layer metric:
                           ``read(ctx) -> float | None``
  models/<model_type>.py   the model's operations per token and per
                           kernel call (the yardstick's counts)
  reference/<model_type>.py  the plain float32 reference
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import importlib.util
import json
import logging
import os
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]     # benchmarks/chip
CHECKOUT = BENCH.parents[1]


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file by path (names may hold dots: ``idle_share.train``)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list       # metric entries the cell reports
    per_layer: list
    bench: pathlib.Path

    def job(self):
        return load_module(self.bench / "jobs" / f"{self.traffic['job']}.py")

    def reference(self):
        return load_module(self.bench / "reference"
                           / f"{self.config['model_type']}.py")

    def model(self):
        return load_module(self.bench / "models"
                           / f"{self.config['model_type']}.py")

    def reader(self, metric: str):
        return load_module(self.bench / "metrics" / f"{metric}.py")


def find_cell(name: str, spec_path=None, bench=BENCH) -> Cell:
    spec = load_json(spec_path or CHECKOUT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{sorted(by_name)}")
    w = by_name[name]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(bench / "configs" / f"{w['config']}.json"),
                traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(bench / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, bench=bench)


# ---------------------------------------------------------------- device


def require_chips(chips: int):
    """The devices of a TPU with at least ``chips`` chips, or exit non-zero
    naming what JAX found. There is no fallback to another platform."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found platform "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: needs {chips} TPU chips, found "
                         f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache``, a fixed path, so the next run of the
    cell finds every program. Programs that compile in under a second are
    written too, so a warm run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CacheLog(logging.Handler):
    """Counts the programs JAX's persistent cache hit and missed, from the
    compiler's debug records (its warnings still reach the ``jax``
    logger)."""

    KINDS = {"Persistent compilation cache hit": "hits",
             "PERSISTENT COMPILATION CACHE MISS": "misses"}

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = {k: collections.Counter() for k in self.KINDS.values()}
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(self)

    def emit(self, record):
        if record.levelno >= logging.WARNING:
            logging.getLogger("jax").handle(record)
        for prefix, kind in self.KINDS.items():
            if str(record.msg).startswith(prefix):
                self.names[kind][str(record.args[0])] += 1

    def counts(self) -> dict:
        return {k: sum(c.values()) for k, c in self.names.items()}


class CompileWatch:
    """The host-clock time and name of every program JAX lowered, so a run
    can say whether anything was lowered or compiled inside its window."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seen = []
        jax.monitoring.register_event_duration_secs_listener(self._note)

    def _note(self, event, seconds, **kw):
        if event in self.EVENTS:
            self.seen.append((time.perf_counter(), event.rsplit("/", 1)[-1],
                              kw.get("fun_name", "?")))

    def inside(self, bounds) -> list:
        lo, hi = bounds
        return [(e, n) for t, e, n in self.seen if lo <= t <= hi]


# ---------------------------------------------------------------- a run


class Run:
    """What a job is handed: the cell, the run's arguments, the
    devices, the clock the set-up started on, and the traced window."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices, t_start: float, keep_trace: str = ""):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.devices, self.t_start = trace, devices, t_start
        self.keep_trace = keep_trace
        self.trace_dir = None
        self.window_bounds = None     # host-clock (start, end), set by a job

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @contextlib.contextmanager
    def traced(self):
        """The traced part of the window (``--trace 1`` only): the JAX
        profiler runs, and a host span ``chipbench.window`` marks its
        bounds on the profiler's own clock."""
        if not self.trace:
            yield
            return
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans, not every call
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("chipbench.window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def trace_file(self):
        if self.trace_dir is None:
            return None
        found = sorted(pathlib.Path(self.trace_dir).rglob("*.xplane.pb"))
        return found[-1] if found else None

    def drop_trace(self):
        if self.trace_dir is not None:
            if self.keep_trace:
                shutil.copytree(self.trace_dir, self.keep_trace,
                                dirs_exist_ok=True)
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry it names, with ``program.set`` (and ``program.ssm`` for its SSM
    group) applied, checked against the
    published keys by ``program.check`` (program field -> file key; dotted
    paths reach nested groups) so no width can drift."""
    from repro.configs import registry
    prog = cfg["program"]
    mc = registry.get_config(prog["registry"])
    mc = dataclasses.replace(mc, **prog.get("set", {}))
    if "ssm" in prog:
        mc = dataclasses.replace(mc, ssm=dataclasses.replace(mc.ssm,
                                                             **prog["ssm"]))

    def get(obj, dotted):
        for part in dotted.split("."):
            obj = obj[part] if isinstance(obj, dict) else getattr(obj, part)
        return obj

    for field, key in prog["check"].items():
        want = get(cfg, key) if isinstance(key, str) else key
        have = get(mc, field)
        if have != want:
            raise SystemExit(f"program config {field}={have!r} differs from "
                             f"the configuration's {key}={want!r}")
    return mc


def emit(result: dict, checks: dict) -> None:
    """The compared numbers, each beside its limit, as the last lines of
    standard error; then the result as the last line of standard output
    with the same numbers under ``checks``, last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    result = dict(result, checks=checks)
    print(json.dumps(result), flush=True)


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
