"""Compiled phase engine: scan-based epoch runner over device-resident data.

The SWAP controller used to dispatch one jitted step per Python iteration
and rebuild W worker batches on the host every step — the host loop, not
the hardware, set the step rate. This module replaces that with an
epoch-granular runner:

  * ``TrainState`` — the single pytree that flows through every phase:
    (bundle, opt_state, step, acc_ema, phase tag, rng, loss-scale state).
    Phase 2 carries the same structure with a leading W worker axis on
    every leaf. Train steps have the precision-pipeline signature
    ``(bundle, opt_state, batch, step, scale) -> (bundle, opt_state,
    scale, metrics)`` (see ``repro.train.precision``); plain-f32 phases
    thread the trivial scale state so the engine — and checkpoints — are
    uniform across precision configurations.
  * ``EpochRunner`` — compiles ``lax.scan(train_step)`` over an epoch-sized
    chunk inside ONE jit (vmapped over the worker axis for phase 2). Each
    scanned step gathers its batch in-trace via ``Loader.batch_in_trace``,
    so no per-step host work or host->device transfer remains. On a worker
    mesh the ensemble runner lowers as ONE program in which each worker
    block runs its own workers (``engine="sharded"``): ``shard_map`` over
    the ``worker`` axis with the in/out state shardings pinned to
    ``dist.sharding.ensemble_shardings``, so the compiled program contains
    no cross-worker collectives (checked by
    ``assert_no_cross_worker_collectives``) and Pallas kernels, which XLA
    cannot partition, lower inside each block. The plain-vmap form stays as
    the bitwise equivalence oracle.
  * ``run_phase`` — the thin host driver: one compiled call per epoch,
    early-exit on the accuracy EMA at *epoch boundaries* (the streaming
    equivalent of the paper's per-epoch train-accuracy check), metric-log
    extraction, periodic checkpointing, and an ``on_chunk`` hook (curve
    collection / eval) whose wall time is accounted separately from train
    time.
  * ``python_loop_reference`` — the replaced per-step host loop, kept as
    the equivalence oracle for tests and the baseline for
    ``benchmarks/bench_train_loop.py``.

Chunk lengths are static (steps_per_epoch, plus one shorter final chunk),
so a phase compiles at most two programs per runner.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import Loader
from repro.train.precision import (
    LossScaleState, default_scale_state, stack_scale_state,
)

# phase tags carried inside TrainState (checkpointable, trace-friendly)
PHASE_TAGS = {"sgd": 0, "phase1": 1, "phase2": 2}


class TrainState(NamedTuple):
    """Everything a phase needs to continue training from an exact point.

    A registered pytree (NamedTuple), so it vmaps over a leading worker
    axis, flows through ``lax.scan`` as the carry, and round-trips through
    ``repro.checkpoint`` byte-exactly.
    """

    bundle: Any        # {"params": ..., "state": ...}
    opt_state: Any
    step: Any          # int32 scalar (per-worker vector in phase 2)
    acc_ema: Any       # float32 scalar — streaming train-accuracy EMA
    phase: Any         # int32 PHASE_TAGS value
    rng: Any           # PRNGKey (reserved for stochastic steps)
    scale: Any         # LossScaleState (trivial for plain-f32 policies)


def init_train_state(bundle, opt_state, *, step: int = 0,
                     acc_ema: float = 0.0, phase: str = "phase1",
                     seed: int = 0,
                     scale: Optional[LossScaleState] = None) -> TrainState:
    return TrainState(
        bundle=bundle, opt_state=opt_state,
        step=jnp.asarray(step, jnp.int32),
        acc_ema=jnp.asarray(acc_ema, jnp.float32),
        phase=jnp.asarray(PHASE_TAGS.get(phase, 0), jnp.int32),
        rng=jax.random.PRNGKey(seed),
        scale=scale if scale is not None else default_scale_state())


def stack_train_state(stacked_bundle, stacked_opt_state, n_workers: int,
                      seed: int = 0,
                      scale: Optional[LossScaleState] = None) -> TrainState:
    """Assemble the phase-2 start state from an already-stacked bundle
    (every worker begins from the common phase-1 model) and freshly
    initialized per-worker optimizer state, both with a leading W axis."""
    return TrainState(
        bundle=stacked_bundle, opt_state=stacked_opt_state,
        step=jnp.zeros((n_workers,), jnp.int32),
        acc_ema=jnp.zeros((n_workers,), jnp.float32),
        phase=jnp.full((n_workers,), PHASE_TAGS["phase2"], jnp.int32),
        rng=jax.random.split(jax.random.PRNGKey(seed), n_workers),
        scale=stack_scale_state(
            scale if scale is not None else default_scale_state(), n_workers))


def _require_auto_axes(mesh) -> None:
    if jax.sharding.AxisType.Explicit in getattr(mesh, "axis_types", ()):
        raise ValueError(
            f"EpochRunner needs a mesh with Auto axes, got axis_types "
            f"{mesh.axis_types}: build it with DistConfig.make_mesh / "
            f"launch.mesh, or jax.make_mesh(..., axis_types=(AxisType.Auto,"
            f" ...))")


def _require_auto_inputs(*trees) -> None:
    for leaf in jax.tree_util.tree_leaves(trees):
        mesh = getattr(getattr(leaf, "sharding", None), "mesh", None)
        if mesh is not None:
            _require_auto_axes(mesh)


def _resize_rows(mesh, tree, n_rows: int):
    """Every leaf's leading (worker) axis cut or grown to ``n_rows`` (growth
    repeats the last row), in one program whose output is placed by
    ``ensemble_shardings``, so no device materialises more than its share."""
    from repro.dist.sharding import ensemble_shardings

    def resize(t):
        return jax.tree_util.tree_map(
            lambda a: a[:n_rows] if a.shape[0] >= n_rows else jnp.concatenate(
                [a, jnp.repeat(a[-1:], n_rows - a.shape[0], axis=0)]), t)

    out = ensemble_shardings(mesh, jax.eval_shape(resize, tree))
    return jax.jit(resize, out_shardings=out)(tree)


class EpochRunner:
    """jit(lax.scan(train_step)) over epoch-sized chunks, with the batch
    gathered in-trace.

    ``ensemble=True`` vmaps the whole scanned epoch over the leading worker
    axis of the state (SWAP phase 2): one compiled program advances all W
    workers a full epoch, and — with the state placed by
    ``dist.sharding.ensemble_shardings`` on a worker mesh — lowers to W
    independent per-worker sub-programs with no cross-worker collectives.

    ``engine`` picks the ensemble lowering (``repro.dist.DistConfig``
    resolves it; non-ensemble runners ignore it):

      * ``"vmap"`` (default) — plain ``jax.vmap``; single-device oracle.
      * ``"sharded"`` — ``jax.shard_map`` over the mesh's ``worker`` axis
        of a ``jax.vmap`` over the block's own workers, jitted with
        ``in_shardings``/``out_shardings`` pinned to
        ``ensemble_shardings(mesh, ...)``. The worker axis is manual, so
        per-worker content cannot be re-gathered across workers, and a
        Mosaic kernel in the step lowers per block (XLA refuses to
        partition one across a sharded operand). This is the lowering the
        no-cross-worker-collective audit runs against, and the form a real
        worker mesh executes. Other mesh axes stay automatic inside each
        block, where a Mosaic kernel would still need its own partitioning.
        Requires ``mesh`` with a ``worker`` axis. Bitwise-identical to the
        ``"vmap"`` engine on the same mesh (asserted in
        tests/test_sharded_engine.py).

    Meshes and placed inputs must have Auto axes (GSPMD placement), as
    ``DistConfig.make_mesh`` and ``launch.mesh`` build them. The in-trace
    batch gather and permutation draw do not trace or lower under
    Explicit axes, the default of a bare ``jax.make_mesh``, so the runner
    rejects those with a ValueError instead.

    Compiled programs are cached per chunk length; the input state is
    donated (``donate=False`` — DistConfig.donate_state — keeps the
    caller's buffers alive instead), so long runs do not accumulate
    buffers.

    ``unroll=True`` emits the chunk as straight-line code instead of an XLA
    ``while`` loop (capped at ``_UNROLL_CAP`` steps to bound compile time).
    XLA:CPU executes convolutions inside while-loop bodies on a slow
    non-vectorized path (~8x at smoke scale, independent of thread count),
    so conv models on CPU hosts should unroll; LM/transformer chunks are
    fastest in while form, and on TPU the while form is always right
    (compile-bounded, Pallas-compatible). The choice only affects scheduling
    — per-step math is identical either way.
    """

    _UNROLL_CAP = 32

    def __init__(self, step_fn: Callable, loader: Loader, ema_beta: float,
                 ensemble: bool = False, unroll: bool = False,
                 mesh=None, engine: str = "vmap", donate: bool = True):
        if engine not in ("vmap", "sharded"):
            raise ValueError(f"engine must be 'vmap' or 'sharded', "
                             f"got {engine!r}")
        if engine == "sharded":
            if not ensemble:
                raise ValueError("engine='sharded' is the ensemble lowering "
                                 "(worker axis); use ensemble=True")
            if mesh is None or "worker" not in mesh.axis_names:
                raise ValueError("engine='sharded' needs a mesh with a "
                                 "'worker' axis (see DistConfig.make_mesh / "
                                 "launch.mesh.make_worker_mesh)")
        if mesh is not None:
            _require_auto_axes(mesh)
        self.step_fn = step_fn
        self.loader = loader
        self.ema_beta = ema_beta
        self.ensemble = ensemble
        self.unroll = unroll
        self.mesh = mesh
        self.engine = engine
        self.donate = donate
        self._compiled: Dict[int, Callable] = {}

    def _chunk_fn(self, n_steps: int, state=None, worker=None) -> Callable:
        fn = self._compiled.get(n_steps)
        if fn is not None:
            return fn
        _require_auto_inputs(state, worker)
        step_fn, loader, beta = self.step_fn, self.loader, self.ema_beta

        def run_chunk(state: TrainState, worker):
            def body(st, _):
                batch = loader.batch_in_trace(st.step, worker)
                bundle, opt, scale, metrics = step_fn(
                    st.bundle, st.opt_state, batch, st.step, st.scale)
                ema = (beta * st.acc_ema
                       + (1.0 - beta) * metrics["accuracy"]
                       .astype(jnp.float32))
                if "skipped" in metrics:
                    # dynamic-loss-scale policies flag overflow steps; the
                    # stopping EMA must not absorb their (unapplied) batch
                    ema = jnp.where(metrics["skipped"] > 0, st.acc_ema, ema)
                st = TrainState(bundle, opt, st.step + 1, ema,
                                st.phase, st.rng, scale)
                return st, dict(metrics, ema=ema)

            return jax.lax.scan(body, state, xs=None, length=n_steps,
                                unroll=(self.unroll
                                        and n_steps <= self._UNROLL_CAP))

        donate = (0,) if self.donate else ()
        if self.ensemble and self.engine == "sharded":
            # ONE program, each worker block running its own workers: the
            # worker axis is manual inside shard_map, and the explicit
            # in/out shardings pin the carried state, so nothing can be
            # re-gathered across worker blocks. Shardings are
            # derived from the example state/worker (ShapeDtypeStructs
            # suffice — only shapes matter), whose structure is fixed for
            # the runner's lifetime.
            if state is None or worker is None:
                raise ValueError("sharded engine needs the example state/"
                                 "worker to derive shardings")
            if self._worker_pad(worker):
                raise ValueError(
                    f"sharded engine: {worker.shape[0]} workers do not "
                    f"divide over the mesh's worker axis of "
                    f"{self.mesh.shape['worker']} (run_chunk pads them)")
            from repro.dist.sharding import ensemble_shardings
            st_sh = ensemble_shardings(self.mesh, state)
            wk_sh = ensemble_shardings(self.mesh, worker)

            def specs(tree):
                return jax.tree_util.tree_map(lambda s: s.spec, tree)

            # metrics are (W, n_steps) per leaf: they follow the worker ids
            local = jax.shard_map(
                jax.vmap(run_chunk), mesh=self.mesh,
                in_specs=(specs(st_sh), specs(wk_sh)),
                out_specs=(specs(st_sh), wk_sh.spec),
                axis_names={"worker"}, check_vma=False)
            fn = jax.jit(local, in_shardings=(st_sh, wk_sh),
                         out_shardings=(st_sh, None),
                         donate_argnums=donate)
        else:
            if self.ensemble:
                run_chunk = jax.vmap(run_chunk)
            fn = jax.jit(run_chunk, donate_argnums=donate)
        self._compiled[n_steps] = fn
        return fn

    def _worker_pad(self, worker) -> int:
        """Rows the sharded engine adds so W divides the worker axis."""
        if not (self.ensemble and self.engine == "sharded"):
            return 0
        return -worker.shape[0] % self.mesh.shape["worker"]

    def run_chunk(self, state: TrainState, worker, n_steps: int):
        """Advance ``n_steps`` inside one compiled call. Returns
        (new_state, metrics) with every metric stacked over the step axis
        (``(n_steps,)`` leaves; ``(W, n_steps)`` for ensembles).

        The sharded engine needs W to be a multiple of the mesh's worker
        axis. Where it is not (an ensemble that lost workers), the last
        worker is repeated up to the next multiple, so each worker block
        still trains only its own share; the copies are dropped afterwards
        and the state goes back to ``ensemble_shardings`` of the W workers
        (replicated on the worker axis, since W does not divide it)."""
        pad = self._worker_pad(worker)
        if not pad:
            return self._chunk_fn(n_steps, state, worker)(state, worker)
        n = worker.shape[0]
        padded = _resize_rows(self.mesh, (state, worker), n + pad)
        if self.donate:  # free the caller's copy before the chunk runs
            jax.tree_util.tree_map(lambda a: a.delete(), state)
        state, metrics = self._chunk_fn(n_steps, *padded)(*padded)
        return (_resize_rows(self.mesh, state, n),
                jax.tree_util.tree_map(lambda a: a[:n], metrics))

    def lower_chunk(self, state, worker, n_steps: int):
        """AOT-lower one chunk without executing it (``state``/``worker``
        may be ShapeDtypeStructs). The dry-run collective audit lowers the
        sharded phase-2 engine this way on a 256-fake-device mesh."""
        return self._chunk_fn(n_steps, state, worker).lower(state, worker)


class PhaseResult(NamedTuple):
    state: TrainState
    steps: int          # steps executed by THIS driver invocation
    train_time: float   # wall time inside compiled train chunks only
    hook_time: float    # wall time in on_chunk / checkpoint / logging


def _ema_value(state: TrainState) -> float:
    ema = np.asarray(state.acc_ema)
    return float(ema if ema.ndim == 0 else ema.min())


def as_hooks(on_chunk) -> tuple:
    """Normalize ``run_phase``'s ``on_chunk`` argument — None, a single
    callable, or a sequence of callables — into a tuple. The epoch-boundary
    hook surface: every hook is called as ``hook(state, steps_done)`` after
    each compiled chunk, in order (curve eval, live weight publishing via
    ``repro.serve.publish.WeightPublisher.on_epoch``, ...)."""
    if on_chunk is None:
        return ()
    if callable(on_chunk):
        return (on_chunk,)
    return tuple(on_chunk)


def _append_log(log: List[dict], metrics: Dict, first_step: int) -> None:
    host = {k: np.asarray(v) for k, v in metrics.items()
            if k in ("accuracy", "ema", "loss", "lr")}
    n = host["accuracy"].shape[-1]
    for i in range(n):
        log.append({"step": first_step + i,
                    "accuracy": float(host["accuracy"][..., i]),
                    "ema": float(host["ema"][..., i]),
                    "loss": float(host["loss"][..., i]),
                    "lr": float(host["lr"][..., i])})


def run_phase(runner: EpochRunner, state: TrainState, worker, *,
              max_steps: int, stop_accuracy: Optional[float] = None,
              chunk_steps: Optional[int] = None, log: Optional[list] = None,
              checkpointer=None, tag: str = "phase1",
              checkpoint_meta: Optional[Callable] = None,
              on_chunk: Optional[Callable] = None) -> PhaseResult:
    """Drive a phase to completion: epoch-sized compiled chunks with
    early-exit on the accuracy EMA at epoch boundaries.

    ``max_steps`` counts from the CURRENT ``state.step`` (so a resumed state
    runs only the remainder). ``on_chunk`` — one callable or a sequence of
    them, each ``hook(state, steps_done)`` — and checkpointing run between
    chunks; their time is returned separately in ``hook_time`` so
    eval/publishing never pollutes the train-rate measurement.
    ``checkpoint_meta(train_time_so_far) -> dict`` attaches caller metadata
    (e.g. cumulative phase wall/train time, so a later resume can report
    totals instead of remainder-only figures) to each snapshot.

    Mid-chunk entry realigns to epoch boundaries: when ``state.step`` is
    not a chunk multiple (a phase resumed from a snapshot cut mid-epoch,
    e.g. by a max_steps cap), the FIRST chunk is truncated to the next
    boundary. Without this, every post-resume chunk ended mid-epoch, so
    the stopping check consulted an EMA whose latest fold predates the
    true epoch boundary — the documented epoch-boundary semantics
    (docs/training.md) silently shifted by the resume offset.
    """
    if log is not None and runner.ensemble:
        raise ValueError(
            "per-step logs are single-model only: ensemble metrics carry a "
            "leading worker axis — consume them via on_chunk instead")
    chunk = chunk_steps or runner.loader.steps_per_epoch
    hooks = as_hooks(on_chunk)
    done, train_time, hook_time = 0, 0.0, 0.0
    # entry check, not just post-chunk: a restored state that already meets
    # the threshold (killed between its last snapshot and the phase-final
    # save) must not train an extra epoch — resume stays bit-exact
    if stop_accuracy is not None and _ema_value(state) >= stop_accuracy:
        return PhaseResult(state, 0, 0.0, 0.0)
    offset = int(np.asarray(state.step).reshape(-1)[0]) % chunk
    first = chunk - offset if offset else chunk
    while done < max_steps:
        n = min(first if done == 0 else chunk, max_steps - done)
        t0 = time.perf_counter()
        state, metrics = runner.run_chunk(state, worker, n)
        jax.block_until_ready(state.bundle)
        train_time += time.perf_counter() - t0
        done += n

        t1 = time.perf_counter()
        if log is not None:
            start = int(np.asarray(state.step).reshape(-1)[0]) - n
            _append_log(log, metrics, start)
        for hook in hooks:
            hook(state, done)
        if checkpointer is not None:
            checkpointer.maybe_save(
                tag, state,
                checkpoint_meta(train_time) if checkpoint_meta else None)
        hook_time += time.perf_counter() - t1

        if stop_accuracy is not None and _ema_value(state) >= stop_accuracy:
            break
    return PhaseResult(state, done, train_time, hook_time)


def stack_host_batches(loader: Loader, step: int, n_workers: int):
    """The replaced phase-2 host path: build every worker's batch on the
    host and stack along a leading W axis. Baseline/oracle only — the
    engine gathers batches in-trace instead (``Loader.batch_in_trace``)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[loader.batch(step, worker=w) for w in range(n_workers)])


def python_loop_reference(step_fn: Callable, loader: Loader,
                          state: TrainState, worker: int = 0, *,
                          n_steps: int, ema_beta: float):
    """The per-step host-driven loop the scan engine replaced: one jitted
    step dispatch per Python iteration, batch built on the host each step.

    Kept as the equivalence oracle (tests assert the scan engine reproduces
    it exactly) and as the baseline side of
    ``benchmarks/bench_train_loop.py``. Returns (state, per-step log dicts).
    """
    fn = jax.jit(step_fn, donate_argnums=(0, 1))
    bundle, opt, scale = state.bundle, state.opt_state, state.scale
    start = int(np.asarray(state.step))
    ema = jnp.asarray(state.acc_ema)
    logs = []
    for s in range(start, start + n_steps):
        batch = loader.batch(s, worker=worker)
        bundle, opt, scale, metrics = fn(bundle, opt, batch, s, scale)
        new_ema = (ema_beta * ema
                   + (1.0 - ema_beta) * metrics["accuracy"]
                   .astype(jnp.float32))
        if "skipped" in metrics:
            new_ema = jnp.where(metrics["skipped"] > 0, ema, new_ema)
        ema = new_ema
        logs.append({"step": s, "accuracy": float(metrics["accuracy"]),
                     "ema": float(ema), "loss": float(metrics["loss"]),
                     "lr": float(metrics["lr"])})
    jax.block_until_ready(bundle)
    return state._replace(
        bundle=bundle, opt_state=opt, scale=scale,
        step=jnp.asarray(start + n_steps, jnp.int32),
        acc_ema=ema.astype(jnp.float32)), logs
