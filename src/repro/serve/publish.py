"""Live weight publishing: the continuous train→serve loop.

SWAP's product is the *averaged* model (Algorithm 1, lines 27-28), and the
paper's production story is a model that keeps improving while it serves.
This module closes that loop:

  * ``WeightPublisher`` — an epoch-boundary hook for the phase engine
    (``repro.train.loop.run_phase``'s ``on_chunk`` surface): at each chunk
    boundary it folds the current across-worker parameter mean into a
    ``StreamingAverage`` over epochs (the online-averaging schedule of
    Izmailov et al. SWA, applied to SWAP's phase-2 ensemble), then pushes
    the new running average — a new weight *generation* — into live
    ``CompiledServingEngine`` replicas via ``engine.publish`` and/or an
    atomic publish snapshot (``repro.checkpoint.state.save_publish``).

  * ``PublishFollower`` — the consumer side for engines in OTHER
    processes: tail a checkpoint directory for new publish generations
    (``launch.serve --follow``). Atomic write-then-rename means a poll can
    never observe a torn generation; a publisher killed mid-write is
    simply invisible until it completes.

The swap itself is the engine's job (double-buffered device params,
per-slot generation pinning — see ``repro.serve.compiled``); the publisher
only decides WHAT to publish and WHEN. In-process publishing moves one
host->device params transfer per generation and zero extra device->host
syncs, so the engine's single-transfer-per-decode-call invariant holds
across swaps.
"""
from __future__ import annotations

import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro import obs
from repro.checkpoint.state import (
    find_latest_publish, load_publish, save_publish, state_step,
)
from repro.core.averaging import StreamingAverage, average_stacked

# the transient failure modes a publish retry can actually fix: I/O
# hiccups on the snapshot dir, an engine's delivery raising. Programming
# errors (TypeError, bad trees) are not retried.
_RETRYABLE = (OSError, RuntimeError, ValueError)


class WeightPublisher:
    """Epoch-boundary snapshot + atomic hot-swap of the running average.

    ``engines``: live ``CompiledServingEngine`` instances to swap in
    process. ``directory``: optional checkpoint dir for atomic publish
    snapshots (cross-process consumers follow it with ``PublishFollower``).
    ``ensemble``: the hooked phase carries a leading worker axis (SWAP
    phase 2) that is averaged across before folding; set False when
    publishing from a single-model phase. ``every``: publish each
    ``every``-th epoch boundary (1 = every chunk).

    Use ``publisher.on_epoch`` as a ``run_phase``/``SWAP.run`` hook, or
    call ``publish(params)`` directly with an already-averaged tree.

    Delivery resilience: ``max_retries`` re-attempts a failed publish
    (snapshot write or engine delivery raising) with exponential backoff
    (``retry_backoff_s * 2**k``, via an injectable ``sleep``). After the
    budget, ``on_failure`` decides: ``"raise"`` (default — the failure
    propagates exactly as without retries, and the generation counter
    never advanced) or ``"skip"`` (record in ``self.failures``, warn, and
    return the current generation — training proceeds and the NEXT epoch
    boundary publishes a fresher average anyway, so one lost delivery
    costs staleness, not the run).
    """

    def __init__(self, engines=(), *, directory: Optional[str] = None,
                 ensemble: bool = True, every: int = 1, impl: str = "auto",
                 max_retries: int = 0, retry_backoff_s: float = 0.05,
                 on_failure: str = "raise",
                 sleep: Callable[[float], None] = time.sleep):
        if not engines and not directory:
            raise ValueError(
                "WeightPublisher needs somewhere to publish: pass live "
                "engines, a snapshot directory, or both")
        if on_failure not in ("raise", "skip"):
            raise ValueError(f"on_failure must be 'raise' or 'skip', "
                             f"got {on_failure!r}")
        if max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if retry_backoff_s < 0:
            raise ValueError("retry_backoff_s must be >= 0")
        self.engines: List[Any] = list(engines)
        self.directory = directory
        self.ensemble = ensemble
        self.every = max(1, every)
        self.average = StreamingAverage(impl=impl)
        self.generation = 0
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.on_failure = on_failure
        self._sleep = sleep
        self._boundaries = 0
        self.log: List[Dict[str, int]] = []   # [{generation, step, folds}]
        self.failures: List[Dict[str, Any]] = []   # skipped publishes

    def attach(self, engine) -> None:
        """Add a live engine; it receives generations published later."""
        self.engines.append(engine)

    # -- run_phase hook surface (state, steps_done) ---------------------

    def on_epoch(self, state, done: int) -> Optional[int]:
        """Fold this epoch boundary's model into the running average and
        publish it. Signature matches ``run_phase(on_chunk=...)`` hooks;
        attach via ``SWAP.run(phase2_hooks=[publisher.on_epoch])``."""
        self._boundaries += 1
        if self._boundaries % self.every:
            return None
        params = state.bundle["params"]
        if self.ensemble:
            # across-worker mean first (phase 3's average_stacked), then
            # the across-epoch streaming fold — online SWA over SWAP
            params = average_stacked(params)
        avg = self.average.add(params)
        return self.publish(avg, step=state_step(state))

    # -- direct publishing ----------------------------------------------

    def publish(self, params, step: int = 0) -> int:
        """Publish ``params`` as the next generation: atomic snapshot
        first (so a crash mid-publish never leaves an engine ahead of the
        durable record), then hot-swap into every attached engine.

        The generation counter and log only advance once the publish
        actually lands somewhere: a ``save_publish`` failure propagates
        (after the retry budget) without consuming a generation number,
        and if every attached engine rejects the generation as stale
        (``publish`` -> None) the counter rolls back too — otherwise a
        flaky snapshot dir or a restarted publisher racing a fresher one
        would burn generations and log publishes that never happened.

        Retries re-run the whole attempt (snapshot + delivery) under the
        SAME generation number — ``save_publish`` is an atomic overwrite,
        so a half-delivered retry can never fork generation history."""
        with obs.span("WeightPublisher.publish"):
            attempt = 0
            while True:
                try:
                    return self._publish_once(params, step)
                except _RETRYABLE as err:
                    attempt += 1
                    if attempt <= self.max_retries:
                        self._sleep(self.retry_backoff_s * 2 ** (attempt - 1))
                        continue
                    if self.on_failure == "raise":
                        raise
                    self.failures.append(
                        {"step": step, "attempts": attempt,
                         "error": f"{type(err).__name__}: {err}"})
                    warnings.warn(
                        f"publish at step {step} failed after {attempt} "
                        f"attempt(s) ({err}); skipping — the next epoch "
                        f"boundary publishes a fresher average",
                        RuntimeWarning)
                    return self.generation

    def _publish_once(self, params, step: int) -> int:
        gen = self.generation + 1
        if self.directory:
            save_publish(self.directory, gen, step, params,
                         meta={"folds": self.average.n})
        delivered = not self.engines
        for engine in self.engines:
            # engines: True = swapped now, False = deferred (will apply),
            # None = rejected as stale — only non-None counts as delivery
            if engine.publish(params, generation=gen) is not None:
                delivered = True
        if not delivered:
            return self.generation                # all engines rejected
        self.generation = gen
        self.log.append({"generation": gen, "step": step,
                         "folds": self.average.n})
        return gen


class PublishFollower:
    """Tail a checkpoint directory for new publish generations.

    ``poll()`` returns ``(generation, params)`` when a generation newer
    than the last seen one is fully visible, else None. Because publishes
    are write-then-rename with the sidecar written before the snapshot, a
    torn write is never returned — the follower just sees the previous
    generation until the new one completes.
    """

    def __init__(self, directory: str, template):
        self.directory = directory
        self.template = template
        self.generation = 0        # newest generation already consumed

    def poll(self) -> Optional[Tuple[int, Any]]:
        latest = find_latest_publish(self.directory)
        if latest is None or latest["generation"] <= self.generation:
            return None
        params = load_publish(latest["path"], self.template)
        self.generation = latest["generation"]
        return latest["generation"], params
