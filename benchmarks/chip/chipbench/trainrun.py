"""The flow both training jobs share.

Set-up makes the weights (the benchmark's, from the seed) and the Markov
rows on the device, builds the program's runner and drives it through the
first ``REF_STEPS`` steps with the window's own call, one step per call,
keeping the readings ``chipbench.traincheck`` compares. The same runner and
state then run the window: calls are dispatched back to back, about
``AHEAD_S`` seconds of steps ahead of the one waited for, so that the chip
stays fed while the host stands still, until the steps in flight would
end at ``--seconds`` (at the set-up's step time); then nothing more is
sent, and the window ends when the last dispatched step is done, so every
step counted is whole. After the window the
program's state is freed and the plain reference follows the same first
steps of every worker.

The rows are one corpus for every seed, drawn from the traffic's
``corpus_seed``: the program's loader holds its rows and its seed as
constants of the compiled step, so rows drawn from ``--seed`` would make
every seed compile anew. ``--seed`` draws the weights and picks the order
of the rows: it is the loader's ``worker`` argument (``stream``), which
selects a permutation stream and is an argument of the compiled step.
"""
from __future__ import annotations

import dataclasses
import math
import sys
import time
from typing import Any, Callable

REF_STEPS = 3
AHEAD_S = 6.0       # seconds of steps dispatched ahead of the one waited for


@dataclasses.dataclass
class Program:
    runner: Any                 # repro.train.loop.EpochRunner
    state: Any                  # its TrainState
    worker: Any                 # run_chunk's worker argument
    workers: int                # W (1 for phase 1)
    shapes: Any                 # param shapes of one model
    batches: list               # per worker, the rows of steps 0..2
    step_tokens: int            # tokens per step, over all workers
    params0: Callable           # () -> the start weights, again


def log(job: str, msg: str) -> None:
    print(f"[{job}] {msg}", file=sys.stderr, flush=True)


def stream(seed: int, workers: int = 1) -> int:
    """The loader's ``worker`` argument for ``--seed``: the permutation
    stream that orders the rows, with room for ``workers`` streams below
    2**31."""
    return seed % (2**31 - workers)


def window(run, prog: Program, step_s: float):
    """Returns (state, steps, seconds, losses)."""
    import jax
    ahead = max(2, math.ceil(AHEAD_S / step_s))
    losses, state = [], prog.state
    with run.traced():
        t0 = time.perf_counter()
        while True:
            state, metrics = prog.runner.run_chunk(state, prog.worker, 1)
            losses.append(metrics["loss"])
            if len(losses) > ahead:
                losses[-ahead - 1].block_until_ready()
            in_flight = min(len(losses), ahead)
            if time.perf_counter() - t0 + in_flight * step_s >= run.seconds:
                break
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0
    run.window_bounds = (t0, t0 + elapsed)
    return state, len(losses), elapsed, losses


def run(run, prog: Program, job: str) -> dict:
    import numpy as np
    from chipbench import harness, traincheck

    lead = 1 if prog.workers > 1 else 0
    state = prog.state
    losses, took = [], []
    for step in range(REF_STEPS):
        t = time.perf_counter()
        state, metrics = prog.runner.run_chunk(state, prog.worker, 1)
        losses.append(np.asarray(metrics["loss"]).reshape(prog.workers))
        took.append(time.perf_counter() - t)
        if step == 0:
            first = traincheck.leaf_norms(state.opt_state["mu"], lead)
    delta = traincheck.delta_norms(state.bundle["params"], prog.params0(),
                                   lead)
    mine = {"losses": [float(x) for x in np.stack(losses, 1).reshape(-1)],
            "first": first, "delta": delta}
    prog.state = state
    setup_s = time.perf_counter() - run.t_start
    log(job, f"set-up {setup_s:.2f} s; first losses {mine['losses']}")

    state, steps, elapsed, window_losses = window(run, prog, min(took[1:]))
    window_losses = np.concatenate(
        [np.asarray(x).reshape(-1) for x in window_losses])
    failed = int(np.sum(~np.isfinite(window_losses)))
    peak = harness.memory_peak(run.devices)
    tokens_per_s = steps * prog.step_tokens / elapsed
    log(job, f"window {elapsed:.3f} s, {steps} steps, {tokens_per_s:.1f} "
        f"tokens/s, last losses {window_losses[-prog.workers:]}")
    del state
    prog.state = prog.runner = None

    t_ref = time.perf_counter()
    ref = run.cell.reference()
    theirs = {"losses": [], "first": {}, "delta": {}, "grad": {}}
    for w, batches in enumerate(prog.batches):
        r = traincheck.reference_steps(ref, run.config, prog.shapes,
                                       run.seed, batches, run.traffic)
        tag = f"<w{w}>" if lead else ""
        theirs["losses"] += r["losses"]
        for k in ("first", "delta", "grad"):
            theirs[k].update({tag + n: v for n, v in r[k].items()})
    log(job, f"reference {time.perf_counter() - t_ref:.1f} s; losses "
        f"{theirs['losses']}")
    checks = traincheck.compare(mine, theirs, run.cell.limits)

    model = run.cell.model()
    t = run.traffic
    per_step = model.train_kernels(run.config, t["batch"], t["seq"])
    calls = steps * prog.workers
    return {
        "metrics": {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        "attempted": steps * prog.workers, "failed": failed,
        "memory_peak_bytes": peak, "checks": checks,
        "facts": {"tokens_per_s": tokens_per_s, "steps": steps,
                  "window_s": elapsed,
                  "flops_per_token": model.train_flops_per_token(
                      run.config, t["seq"]),
                  "kernels": {k: {"ops": calls * o, "bytes": calls * b}
                              for k, (o, b) in per_step.items()}},
    }


def adapter_and_data(run):
    """The program's LM adapter for the configuration, the traffic's nesterov
    SGD and the Markov rows of the traffic's corpus."""
    from chipbench import harness, lmdata
    from repro.configs.base import OptimizerConfig
    from repro.core.adapters import LMAdapter

    t = run.traffic
    mc = harness.program_config(run.config)
    adapter = LMAdapter(mc, OptimizerConfig(
        kind="sgd", momentum=t["momentum"], nesterov=True,
        weight_decay=t["weight_decay"]))
    data = lmdata.markov_rows(t["corpus_seed"], t["rows"], t["seq"],
                              mc.vocab_size, t["markov_states"])
    return adapter, data


def schedule(traffic: dict):
    """Linear decay from ``lr`` to 0 over ``lr_decay_steps``, no warm-up."""
    from repro.configs.base import ScheduleConfig
    return ScheduleConfig(kind="warmup_linear", peak_lr=traffic["lr"],
                          warmup_steps=0,
                          total_steps=traffic["lr_decay_steps"], end_lr=0.0)
