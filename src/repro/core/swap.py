"""SWAP (Algorithm 1 of the paper) — the three-phase controller.

Phase 1: synchronous large-batch SGD until train accuracy >= τ (EMA over
         batch accuracy, checked at epoch boundaries — the paper uses epoch
         train accuracy; the streaming EMA surfaced once per compiled epoch
         chunk is its engine-native equivalent) or max_steps.
Phase 2: W independent small-batch workers from the common phase-1 model,
         each with its own data ordering — executed as a *worker-axis
         ensemble*: parameters stacked on a leading W axis and the whole
         scanned epoch advanced in one program. On a worker mesh the
         engine lowers SHARDED (``EpochRunner(engine="sharded")``:
         ``shard_map`` over ``worker`` with in/out shardings pinned to
         ``ensemble_shardings``) so the compiled program has no
         cross-worker collectives and deploys with the worker axis across
         hosts; without a mesh the same chunk runs as the plain-vmap
         oracle. ``repro.dist.DistConfig`` selects mesh + engine.
Phase 3: average the W models; recompute BN statistics (adapter hook).
         With ``DistConfig.elastic_deadline_s > 0`` the average is ELASTIC:
         it folds whichever workers report within the deadline
         (``repro.core.averaging.ElasticAverage`` — online partial folds,
         per-worker liveness mask, straggler backoff), so a lost worker
         shrinks the ensemble instead of stalling the run.

Execution runs on the compiled phase engine (``repro.train.loop``): a
``TrainState`` (bundle, opt_state, step, accuracy EMA, phase tag, rng)
flows through each phase as epoch-sized ``lax.scan`` chunks inside one jit,
with every worker batch gathered in-trace from device-resident data — the
host never builds or stacks batches in the hot loop. Curve collection,
eval, and checkpointing happen between chunks and are timed separately
from training. With ``SWAPConfig.checkpoint_dir``/``checkpoint_every`` set,
periodic snapshots allow ``run(resume=True)`` to restart bit-exactly
mid-phase-1 or mid-phase-2 (see ``repro.checkpoint.state``).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint.state import (
    Checkpointer, checkpoint_workers, find_resume_point, list_checkpoints,
    load_train_state, shrink_worker_axis, state_step,
)
from repro.configs.base import PhaseConfig, SWAPConfig
from repro.core.averaging import average_stacked, elastic_average_stacked
from repro.core.schedules import schedule_fn as make_schedule
from repro.data.pipeline import Loader
from repro.dist.config import DistConfig, resolve_dist
from repro.dist.sharding import ensemble_shardings
from repro.train.loop import (
    EpochRunner, TrainState, init_train_state, run_phase, stack_train_state,
)
from repro.train.precision import resolve_policy

_PHASE1_SUMMARY_KEYS = ("phase1_steps", "phase1_train_acc", "phase1_time",
                        "phase1_test_acc", "phase1_skipped_steps",
                        "phase1_loss_scale")


def _stack_bundles(bundle, n: int):
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), bundle)


def _engine_unroll(adapter) -> bool:
    """Unroll epoch chunks for conv models on CPU hosts: XLA:CPU runs
    convolutions inside while-loop bodies on a slow non-vectorized path
    (see EpochRunner); everywhere else the while-form scan is right."""
    return getattr(adapter, "kind", "") == "cnn" \
        and jax.default_backend() == "cpu"


class SGDRun:
    """Plain single-model training (phase 1, and the small/large-batch
    baselines of Tables 1-3) on the compiled phase engine: epoch-sized scan
    chunks, EMA early-exit at epoch boundaries."""

    def __init__(self, adapter, phase: PhaseConfig, train_arrays: Dict,
                 seed: int = 0, dist: Optional[DistConfig] = None):
        self.adapter = adapter
        self.phase = phase
        self.dist = dist if dist is not None else DistConfig()
        self.loader = Loader(train_arrays, phase.batch_size, seed=seed,
                             shard=self.dist.data_shard)
        sched = make_schedule(phase.schedule)
        self.policy = resolve_policy(phase.precision, adapter.opt_cfg)
        self.runner = EpochRunner(
            adapter.make_train_step(sched, policy=self.policy,
                                    grad_accum_steps=phase.grad_accum_steps),
            self.loader, phase.accuracy_ema,
            unroll=_engine_unroll(adapter), donate=self.dist.donate_state)

    def init_state(self, bundle, opt_state=None, start_step: int = 0,
                   phase_tag: str = "phase1") -> TrainState:
        opt_state = opt_state if opt_state is not None \
            else self.adapter.init_opt(bundle)
        return init_train_state(bundle, opt_state, step=start_step,
                                phase=phase_tag,
                                scale=self.policy.init_scale_state())

    def run(self, bundle, opt_state=None, start_step: int = 0,
            log: Optional[list] = None, worker: int = 0,
            checkpointer: Optional[Checkpointer] = None,
            tag: str = "phase1"):
        """Returns (bundle, opt_state, steps_taken, acc_ema)."""
        state = self.init_state(bundle, opt_state, start_step)
        res = run_phase(self.runner, state, worker,
                        max_steps=self.phase.max_steps,
                        stop_accuracy=self.phase.stop_accuracy, log=log,
                        checkpointer=checkpointer, tag=tag)
        st = res.state
        return (st.bundle, st.opt_state, res.steps,
                float(np.asarray(st.acc_ema)))


class SWAP:
    """The full three-phase algorithm over an adapter + dataset."""

    def __init__(self, adapter, cfg: SWAPConfig, train_arrays: Dict,
                 test_loader: Loader, mesh=None,
                 dist: Optional[DistConfig] = None, supervisor=None):
        """``dist``: the unified distribution surface
        (``repro.dist.DistConfig``) — mesh geometry, phase-2 engine choice,
        donation policy, elastic-averaging knobs, multi-host layout. With a
        worker mesh, the phase-2 stacked TrainState is placed with its
        leading W axis sharded over ``worker``
        (``dist.sharding.ensemble_shardings``) and the ensemble epoch
        lowers as ONE sharded-jit program that executes as W independent
        per-worker sub-programs — the paper's no-synchronization property,
        checked in HLO by ``assert_no_cross_worker_collectives``. Without a
        mesh the same code runs as a plain single-device vmap.

        ``mesh=`` is the deprecated pre-DistConfig spelling: it still works
        for one release (a DistConfig is derived from the mesh geometry)
        but emits a DeprecationWarning — see ``repro.dist.resolve_dist``.

        ``supervisor``: an optional ``repro.resilience.PhaseSupervisor``.
        With one attached, both phases run under its retry/rollback/
        dead-worker-recovery state machine — a diverging chunk rolls back
        to the last verified checkpoint, and (with a heartbeat monitor on
        the supervisor) a worker that stops beating mid-phase-2 is dropped
        and the phase resumes with the survivors via the elastic shrink
        path. Recovery actions are surfaced in
        ``results["recovery_events"]``."""
        self.adapter = adapter
        self.cfg = cfg
        self.train_arrays = train_arrays
        self.test_loader = test_loader
        self.dist, self.mesh = resolve_dist(dist, mesh, caller="SWAP")
        self.supervisor = supervisor
        if self.dist.n_workers not in (1, cfg.n_workers) \
                and self.dist.mesh_shape:
            raise ValueError(
                f"DistConfig.n_workers={self.dist.n_workers} disagrees with "
                f"SWAPConfig.n_workers={cfg.n_workers}")

    def _place_ensemble(self, tree):
        if self.mesh is None or "worker" not in self.mesh.axis_names:
            return tree
        return jax.device_put(tree, ensemble_shardings(self.mesh, tree))

    # ------------------------------------------------------------------
    # phase 2 state assembly / restore
    # ------------------------------------------------------------------

    def _phase2_init_state(self, bundle, policy,
                           n_workers: Optional[int] = None) -> TrainState:
        """Fresh stacked phase-2 start state. ``n_workers`` overrides the
        configured W when building a TEMPLATE matching a checkpoint written
        by a different-sized run (worker-count-aware resume)."""
        W = n_workers if n_workers is not None else self.cfg.n_workers
        stacked = _stack_bundles(bundle, W)
        opt_stacked = jax.vmap(self.adapter.init_opt)(stacked)
        return stack_train_state(stacked, opt_stacked, W,
                                 seed=self.cfg.seed + 2,
                                 scale=policy.init_scale_state())

    def run(self, key, collect_curves: bool = False,
            resume: bool = False, phase2_hooks: Sequence = (),
            worker_arrivals: Optional[Sequence[float]] = None,
            heartbeats=None, phase2_chunk_filter=None) -> Dict:
        """``phase2_hooks``: extra epoch-boundary hooks for phase 2, each
        called as ``hook(state, steps_done)`` after every compiled chunk
        (the ``run_phase`` hook surface) — e.g.
        ``repro.serve.publish.WeightPublisher.on_epoch``, which folds the
        across-worker mean into a running average and hot-swaps it into
        live serving engines. Hooks run before curve collection.

        ``worker_arrivals``: per-worker phase-2 report times in seconds for
        ELASTIC phase 3 (``DistConfig.elastic_deadline_s > 0``) —
        ``float('inf')`` marks a lost worker, None means everyone reports
        instantly. The in-process engine finishes workers in lockstep, so
        this is the simulation surface (the ``--lost-workers`` launcher
        flag, tests); multi-host drivers feed real timestamps to
        ``ElasticAverage.collect`` directly.

        ``heartbeats``: an optional ``repro.dist.heartbeat.
        HeartbeatMonitor``. With elastic averaging on, phase-3 arrivals
        come from REAL beacon staleness at averaging time (overriding any
        simulated ``worker_arrivals``) — a stale worker arrives late or
        inf and is backed off / dropped exactly like a simulated one.

        ``phase2_chunk_filter``: a ``(state, metrics) -> (state, metrics)``
        transform applied to what each compiled phase-2 chunk surfaces,
        BEFORE the supervisor's health guard — the fault-injection seam
        (``repro.testing.faults.FaultPlan.chunk_filter``). Requires a
        supervisor: unsupervised runs have no guard to observe the fault,
        so accepting the filter there would silently train on it."""
        cfg = self.cfg
        adapter = self.adapter
        results: Dict = {"phase1_log": [], "phase2_curves": [],
                         "recovery_events": []}

        def _supervised(runner, state, worker, **kw):
            res = self.supervisor.run_phase(runner, state, worker, **kw)
            results["recovery_events"].extend(
                {"kind": e.kind, "attempt": e.attempt, "tag": e.tag,
                 "error": e.error, "restored_step": e.restored_step,
                 "restored_from": e.restored_from,
                 "lost_workers": list(e.lost_workers)} for e in res.events)
            return res

        ckpt = Checkpointer(cfg.checkpoint_dir, cfg.checkpoint_every) \
            if cfg.checkpoint_dir else None
        resume_pt = find_resume_point(cfg.checkpoint_dir) \
            if (resume and cfg.checkpoint_dir) else None

        # ---------------- phase 1: large batch, synchronous --------------
        with obs.span("SWAP.phase1") as phase1:
            bundle = adapter.init(key)
            p1 = SGDRun(adapter, cfg.phase1, self.train_arrays, seed=cfg.seed,
                        dist=self.dist)
            if resume_pt is not None and resume_pt["tag"] in ("phase1_final",
                                                              "phase2"):
                # phase 1 finished in a previous process: restore its final
                # state + summary metrics from the phase1_final snapshot
                finals = [c for c in list_checkpoints(cfg.checkpoint_dir)
                          if c["tag"] == "phase1_final"]
                if not finals:
                    raise ValueError(
                        f"cannot resume {resume_pt['tag']} from "
                        f"{cfg.checkpoint_dir!r}: no phase1_final snapshot")
                state1 = load_train_state(finals[-1]["path"],
                                          p1.init_state(bundle))
                bundle = state1.bundle
                for k in _PHASE1_SUMMARY_KEYS:
                    if k in finals[-1]["meta"]:
                        results[k] = finals[-1]["meta"][k]
            else:
                state1 = p1.init_state(bundle)
                prior_t1 = 0.0
                if resume_pt is not None:      # tag == "phase1": mid-phase-1
                    state1 = load_train_state(resume_pt["path"], state1)
                    # pre-interrupt wall time, so reported phase1_time stays
                    # consistent with the cumulative phase1_steps
                    prior_t1 = resume_pt["meta"].get("phase1_time", 0.0)
                phase1_kw = dict(
                    max_steps=(cfg.phase1.max_steps
                               - int(np.asarray(state1.step))),
                    stop_accuracy=cfg.phase1.stop_accuracy,
                    log=results["phase1_log"], checkpointer=ckpt, tag="phase1",
                    checkpoint_meta=lambda tt: {
                        "phase1_time": prior_t1 + phase1.elapsed()})
                res1 = _supervised(p1.runner, state1, 0, **phase1_kw) \
                    if self.supervisor is not None \
                    else run_phase(p1.runner, state1, 0, **phase1_kw)
                state1 = res1.state
                bundle = state1.bundle
                results["phase1_steps"] = int(np.asarray(state1.step))
                results["phase1_train_acc"] = float(np.asarray(state1.acc_ema))
                # loss-scale diagnostics (trivially 0 skips, scale 1, in f32)
                results["phase1_skipped_steps"] = int(
                    np.asarray(state1.scale.skipped))
                results["phase1_loss_scale"] = float(
                    np.asarray(state1.scale.scale))
                results["phase1_time"] = prior_t1 + phase1.elapsed()
                results["phase1_test_acc"] = adapter.eval_accuracy(
                    bundle, self.test_loader)
                if ckpt is not None:
                    ckpt.save("phase1_final", state1,
                              meta={k: results[k]
                                    for k in _PHASE1_SUMMARY_KEYS})

        # ---------------- phase 2: independent small-batch workers -------
        W = cfg.n_workers
        loader2 = Loader(self.train_arrays, cfg.phase2.batch_size,
                         seed=cfg.seed + 1)
        # phase 2 defaults to f32 (PhaseConfig.precision): small batches
        # don't need the memory/compute levers, and keeping the refinement
        # trajectories full-precision leaves the paper's averaging /
        # generalization claims untouched
        policy2 = resolve_policy(cfg.phase2.precision, adapter.opt_cfg)
        runner2 = EpochRunner(
            adapter.make_train_step(
                make_schedule(cfg.phase2.schedule), policy=policy2,
                grad_accum_steps=cfg.phase2.grad_accum_steps),
            loader2, cfg.phase2.accuracy_ema, ensemble=True,
            unroll=_engine_unroll(adapter), mesh=self.mesh,
            engine=self.dist.resolved_engine(self.mesh),
            donate=self.dist.donate_state)

        state2 = self._phase2_init_state(bundle, policy2)
        prior_t2 = 0.0
        if resume_pt is not None and resume_pt["tag"] == "phase2":
            # worker-count-aware resume: the snapshot records its W in the
            # sidecar meta; load into a template of THAT size, then shrink
            # the worker axis to this run's W (growing is refused — see
            # repro.checkpoint.state.shrink_worker_axis)
            ckpt_w = checkpoint_workers(resume_pt["meta"])
            template = state2 if ckpt_w in (None, W) \
                else self._phase2_init_state(bundle, policy2, n_workers=ckpt_w)
            state2 = shrink_worker_axis(
                load_train_state(resume_pt["path"], template), W)
            prior_t2 = resume_pt["meta"].get("phase2_train_time", 0.0)
        state2 = self._place_ensemble(state2)
        workers = self._place_ensemble(jnp.arange(W, dtype=jnp.int32))

        # hoisted out of the loop: ONE BN-recompute loader serves every
        # curve point and the final phase-3 finalize
        bn_loader = Loader(self.train_arrays, cfg.bn_recompute_batch_size,
                           seed=cfg.seed)
        hooks = list(phase2_hooks)
        if collect_curves:
            def curve_hook(state: TrainState, done: int):
                avg_now = adapter.finalize(
                    average_stacked(state.bundle["params"]), bn_loader,
                    cfg.bn_recompute_batches)
                # worker count read off the state: a supervised run may
                # have shrunk the ensemble mid-phase
                n_live = int(np.asarray(state.step).reshape(-1).shape[0])
                accs: List[float] = [
                    adapter.eval_accuracy(
                        jax.tree_util.tree_map(lambda a: a[w], state.bundle),
                        self.test_loader, max_batches=2)
                    for w in range(n_live)]
                results["phase2_curves"].append({
                    "step": state_step(state) - 1,
                    "worker_test_accs": accs,
                    "avg_test_acc": adapter.eval_accuracy(
                        avg_now, self.test_loader, max_batches=2)})

            hooks.append(curve_hook)

        phase2_kw = dict(
            max_steps=cfg.phase2.max_steps - state_step(state2),
            chunk_steps=1 if collect_curves else None,
            checkpointer=ckpt, tag="phase2",
            checkpoint_meta=lambda tt: {
                "phase2_train_time": prior_t2 + tt,
                "n_workers": W},
            on_chunk=hooks)
        if self.supervisor is None and phase2_chunk_filter is not None:
            raise ValueError(
                "phase2_chunk_filter needs a supervisor attached "
                "(SWAP(..., supervisor=...)): without one, no guard "
                "observes the injected fault")
        with obs.span("SWAP.phase2"):
            if self.supervisor is not None:
                res2 = _supervised(runner2, state2, workers,
                                   place=self._place_ensemble,
                                   chunk_filter=phase2_chunk_filter,
                                   **phase2_kw)
                workers = res2.worker
            else:
                res2 = run_phase(runner2, state2, workers, **phase2_kw)
        state2 = res2.state
        # surviving ensemble: the stacked leading axis after any mid-phase
        # recovery shrink, with original worker identities preserved
        W_live = int(np.asarray(state2.step).reshape(-1).shape[0])
        worker_ids = [int(x) for x in np.asarray(workers).reshape(-1)]
        results["phase2_worker_ids"] = worker_ids
        results["phase2_steps"] = state_step(state2)
        # train time only (cumulative across resumes) — curve eval /
        # checkpoint time is reported separately so the paper's speed claim
        # is measured on the hot path
        results["phase2_time"] = prior_t2 + res2.train_time
        results["phase2_eval_time"] = res2.hook_time

        # per-worker test accuracy BEFORE averaging (paper's row 3),
        # indexed by stacked position (worker_ids maps position → identity)
        worker_accs = []
        for w in range(W_live):
            b_w = jax.tree_util.tree_map(lambda a: a[w], state2.bundle)
            worker_accs.append(adapter.eval_accuracy(b_w, self.test_loader))
        results["worker_test_accs"] = worker_accs

        # ---------------- phase 3: average + BN recompute ----------------
        if self.dist.elastic:
            # deadline-gated: fold whichever workers reported in time; a
            # lost worker (arrival inf) shrinks the ensemble instead of
            # stalling the run. The liveness mask scopes every averaged-
            # model comparison to the workers that actually contributed.
            # With a heartbeat monitor, arrivals are real beacon staleness
            # at averaging time (staleness-as-lateness) — the simulated
            # worker_arrivals surface only drives heartbeat-less runs.
            if heartbeats is not None:
                worker_arrivals = heartbeats.arrivals(worker_ids)
            elif worker_arrivals is not None and W_live != W \
                    and len(worker_arrivals) == W:
                # simulated arrivals are per ORIGINAL worker id; realign to
                # the survivors' stacked positions
                worker_arrivals = [worker_arrivals[wid] for wid in worker_ids]
        with obs.span("SWAP.phase3") as phase3:
            with obs.span("SWAP.average"):
                if self.dist.elastic:
                    avg_params, live_mask = elastic_average_stacked(
                        state2.bundle["params"], self.dist,
                        worker_arrivals=worker_arrivals)
                else:
                    avg_params = average_stacked(state2.bundle["params"])
                    live_mask = np.ones(W_live, dtype=bool)
                jax.block_until_ready(avg_params)
            with obs.span("SWAP.bn_recompute"):
                final = adapter.finalize(avg_params, bn_loader,
                                         cfg.bn_recompute_batches)
        results["phase3_time"] = phase3.seconds
        # report liveness over the ORIGINAL configured ensemble: a worker
        # dropped by mid-phase recovery is dead, a surviving position maps
        # back to its identity
        full_mask = [False] * W
        for pos, wid in enumerate(worker_ids):
            full_mask[wid] = bool(live_mask[pos])
        results["worker_live_mask"] = full_mask
        results["phase2_live_workers"] = int(sum(full_mask))
        live_accs = [a for a, live in zip(worker_accs, live_mask) if live]
        results["before_avg_test_acc"] = sum(live_accs) / len(live_accs)
        results["after_avg_test_acc"] = adapter.eval_accuracy(
            final, self.test_loader)
        results["final_bundle"] = final
        results["stacked_params"] = state2.bundle["params"]
        results["phase1_bundle"] = bundle
        return results
