"""SWAP phase 1 on one chip: synchronous large-batch SGD through the
program's own entry points, ``SGDRun`` -> ``EpochRunner.run_chunk``, one
step per call (see ``chipbench/trainrun.py`` for the flow and the
comparison).

Traffic keys: ``seq``, ``batch`` (sequences per step), ``rows`` (distinct
sequences in the data set), ``markov_states``, ``corpus_seed`` (draws the
rows), ``lr``, ``lr_decay_steps``, ``momentum``, ``weight_decay``.
"""
from __future__ import annotations


def run(run) -> dict:
    import jax
    import jax.numpy as jnp
    from chipbench import trainrun, weights
    from repro.configs.base import PhaseConfig
    from repro.core.swap import SGDRun

    t = run.traffic
    adapter, data = trainrun.adapter_and_data(run)
    sgd = SGDRun(adapter, PhaseConfig(batch_size=t["batch"],
                                      schedule=trainrun.schedule(t)),
                 data, seed=t["corpus_seed"])
    worker = trainrun.stream(run.seed)
    shapes = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))["params"]

    def params0():
        return weights.make(shapes, run.seed, jnp.float32)

    prog = trainrun.Program(
        runner=sgd.runner,
        state=sgd.init_state({"params": params0(), "state": {}}),
        worker=worker, workers=1, shapes=shapes,
        batches=[[sgd.loader.batch(s, worker)
                  for s in range(trainrun.REF_STEPS)]],
        step_tokens=t["batch"] * t["seq"], params0=params0)
    del sgd
    return trainrun.run(run, prog, "train_phase1")
