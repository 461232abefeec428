"""SWAP phase 2: W independent small-batch workers, one per chip, on the
program's sharded engine: ``DistConfig`` on a ``worker:W`` mesh resolves
``EpochRunner(ensemble=True, engine="sharded")``, the ensemble state is
placed by ``ensemble_shardings``, and each worker walks its own
permutation of the rows. Every worker starts from the same weights (the
common phase-1 model). One step per call; the flow and the comparison,
which covers every worker, are ``chipbench/trainrun.py``'s.

Traffic keys: those of ``train_phase1`` (``batch`` is per worker), and
``workers``.
"""
from __future__ import annotations


def run(run) -> dict:
    import jax
    import jax.numpy as jnp
    from chipbench import trainrun, weights
    from repro.core.schedules import schedule_fn
    from repro.data.pipeline import Loader
    from repro.dist.config import DistConfig
    from repro.dist.sharding import ensemble_shardings
    from repro.train.loop import EpochRunner, stack_train_state

    t = run.traffic
    W = t["workers"]
    dist = DistConfig(mesh_shape=(W,), mesh_axes=("worker",), n_workers=W)
    mesh = dist.make_mesh()
    engine = dist.resolved_engine(mesh)
    if engine != "sharded":
        raise SystemExit(f"train_phase2: a worker:{W} mesh resolved the "
                         f"{engine!r} engine, not 'sharded'")
    adapter, data = trainrun.adapter_and_data(run)
    loader = Loader(data, t["batch"], seed=t["corpus_seed"])
    first = trainrun.stream(run.seed, W)
    runner = EpochRunner(adapter.make_train_step(schedule_fn(
        trainrun.schedule(t))), loader, 0.9, ensemble=True, mesh=mesh,
        engine=engine, donate=dist.donate_state)
    shapes = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))["params"]

    def params0():
        return weights.make(shapes, run.seed, jnp.float32)

    def stack(params):
        bundle = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (W,) + a.shape),
            {"params": params, "state": {}})
        return stack_train_state(bundle, jax.vmap(adapter.init_opt)(bundle),
                                 W, seed=run.seed)

    p0 = params0()
    state = jax.jit(stack, out_shardings=ensemble_shardings(
        mesh, jax.eval_shape(stack, p0)))(p0)
    del p0
    worker = jax.device_put(first + jnp.arange(W, dtype=jnp.int32),
                            ensemble_shardings(mesh, jnp.arange(W)))
    prog = trainrun.Program(
        runner=runner, state=state, worker=worker, workers=W, shapes=shapes,
        batches=[[loader.batch(s, worker=first + w)
                  for s in range(trainrun.REF_STEPS)] for w in range(W)],
        step_tokens=W * t["batch"] * t["seq"], params0=params0)
    return trainrun.run(run, prog, "train_phase2")
