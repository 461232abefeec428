"""Sharded-jit phase-2 engine (EpochRunner engine="sharded"): must be
bitwise-identical to the plain-vmap oracle on the same worker mesh, lower
with zero cross-worker collectives, and reject invalid configurations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs.base import (ModelConfig, MoEConfig, OptimizerConfig,
                                ScheduleConfig)
from repro.core.adapters import LMAdapter
from repro.core.schedules import schedule_fn
from repro.core.swap import _stack_bundles
from repro.data.pipeline import Loader, make_markov_lm
from repro.dist.sharding import (assert_no_cross_worker_collectives,
                                 ensemble_shardings)
from repro.train.loop import EpochRunner, stack_train_state

W = 2
PER_WORKER = 4  # data=2 x model=2 inside each worker block


def tiny_lm() -> ModelConfig:
    return ModelConfig(
        name="tiny-lm", family="dense", n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=32, attention="gqa",
        dtype="float32", remat=False, scan_layers=False)


def tiny_moe() -> ModelConfig:
    """tiny_lm with expert FFNs: top-k routing and capacity scatter."""
    return ModelConfig(
        name="tiny-moe", family="moe", n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=32, attention="gqa",
        moe=MoEConfig(n_experts=4, top_k=2, d_ff=32, capacity_factor=1.5),
        dtype="float32", remat=False, scan_layers=False)


def _pieces(cfg=None):
    cfg = cfg or tiny_lm()
    adapter = LMAdapter(cfg, OptimizerConfig(kind="sgd"))
    data = make_markov_lm(0, vocab=cfg.vocab_size, n_train=128, n_test=32,
                          seq_len=16)
    train = {"tokens": data["train_tokens"], "labels": data["train_labels"]}
    loader = Loader(train, 16, seed=3)
    step_fn = adapter.make_train_step(schedule_fn(
        ScheduleConfig(kind="warmup_linear", peak_lr=0.1, warmup_steps=3,
                       total_steps=12)))
    return adapter, loader, step_fn


def _worker_mesh():
    if len(jax.devices()) < W * PER_WORKER:
        pytest.skip(f"needs {W * PER_WORKER} devices "
                    f"(conftest forces 8 on CPU hosts)")
    # Auto axes, as DistConfig.make_mesh builds it (a bare jax.make_mesh
    # defaults to Explicit axes, which the engine rejects)
    return jax.make_mesh((W, 2, 2), ("worker", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)


def _placed_inputs(adapter, mesh, n_workers=W):
    """Ensemble TrainState + worker ids, placed by ensemble_shardings —
    the same physical placement for both engines under test."""
    bundle = adapter.init(jax.random.PRNGKey(0))
    stacked = _stack_bundles(bundle, n_workers)
    state = stack_train_state(stacked, jax.vmap(adapter.init_opt)(stacked),
                              n_workers)
    state = jax.device_put(state, ensemble_shardings(mesh, state))
    workers = jnp.arange(n_workers, dtype=jnp.int32)
    workers = jax.device_put(workers, ensemble_shardings(mesh, workers))
    return state, workers


def test_sharded_engine_bitwise_matches_vmap_oracle():
    """One full epoch chunk through the sharded-jit lowering and through
    plain vmap, from identical placed inputs on the same mesh: every state
    leaf and every stacked metric must match bitwise. This is the oracle
    relationship docs/sharding.md promises — the manual worker axis plus
    pinned shardings change the partitioning, never the math."""
    mesh = _worker_mesh()
    adapter, loader, step_fn = _pieces()
    n = loader.steps_per_epoch

    state_v, workers_v = _placed_inputs(adapter, mesh)
    oracle = EpochRunner(step_fn, loader, 0.9, ensemble=True, donate=False)
    ref_state, ref_metrics = oracle.run_chunk(state_v, workers_v, n)

    state_s, workers_s = _placed_inputs(adapter, mesh)
    sharded = EpochRunner(step_fn, loader, 0.9, ensemble=True, mesh=mesh,
                          engine="sharded", donate=False)
    out_state, out_metrics = sharded.run_chunk(state_s, workers_s, n)

    for a, b in zip(jax.tree_util.tree_leaves(ref_state),
                    jax.tree_util.tree_leaves(out_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ref_metrics:
        np.testing.assert_array_equal(np.asarray(ref_metrics[k]),
                                      np.asarray(out_metrics[k]), err_msg=k)


@pytest.mark.parametrize("n_workers", [1, 3])
def test_sharded_engine_pads_workers_the_axis_does_not_divide(n_workers):
    """An ensemble that lost workers (W not a multiple of the worker axis,
    as after a supervised worker loss) still matches the vmap oracle
    bitwise, returns (W, n) metrics, and keeps its state placed by
    ensemble_shardings of the W workers. A donating runner frees the
    caller's state, as it does when nothing is padded."""
    mesh = _worker_mesh()
    adapter, loader, step_fn = _pieces()
    n = loader.steps_per_epoch

    state_v, workers_v = _placed_inputs(adapter, mesh, n_workers)
    oracle = EpochRunner(step_fn, loader, 0.9, ensemble=True, donate=False)
    ref_state, ref_metrics = oracle.run_chunk(state_v, workers_v, n)

    state_s, workers_s = _placed_inputs(adapter, mesh, n_workers)
    shapes = jax.eval_shape(lambda t: t, state_s)
    sharded = EpochRunner(step_fn, loader, 0.9, ensemble=True, mesh=mesh,
                          engine="sharded", donate=True)
    out_state, out_metrics = sharded.run_chunk(state_s, workers_s, n)
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(state_s))

    for a, b in zip(jax.tree_util.tree_leaves(ref_state),
                    jax.tree_util.tree_leaves(out_state)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ref_metrics:
        assert out_metrics[k].shape == (n_workers, n), k
        np.testing.assert_array_equal(np.asarray(ref_metrics[k]),
                                      np.asarray(out_metrics[k]), err_msg=k)
    want = ensemble_shardings(mesh, out_state)
    for leaf, sh in zip(jax.tree_util.tree_leaves(out_state),
                        jax.tree_util.tree_leaves(
                            want, is_leaf=lambda x: hasattr(x, "spec"))):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
    with pytest.raises(ValueError, match="do not divide"):
        sharded.lower_chunk(shapes, workers_s, n - 1)


@pytest.mark.parametrize("make_cfg", [tiny_lm, tiny_moe],
                         ids=["dense", "moe"])
def test_sharded_lowering_has_no_cross_worker_collectives(make_cfg):
    """The compiled sharded-jit chunk on the worker mesh must contain no
    collective whose replica group spans two worker blocks — phase 2 is
    zero-communication by construction. The MoE case covers the expert
    top-k and capacity scatter, which the partitioner may split over the
    block's own data/model axes but never over the manual worker axis."""
    mesh = _worker_mesh()
    adapter, loader, step_fn = _pieces(make_cfg())
    state, workers = _placed_inputs(adapter, mesh)
    runner = EpochRunner(step_fn, loader, 0.9, ensemble=True, mesh=mesh,
                         engine="sharded", donate=False)
    hlo = runner.lower_chunk(
        state, workers, loader.steps_per_epoch).compile().as_text()
    assert_no_cross_worker_collectives(hlo, n_workers=W,
                                       devices_per_worker=PER_WORKER)


def test_sharded_engine_output_keeps_ensemble_sharding():
    """out_shardings pins the advanced state to the same placement as the
    input, so chained chunks never bounce through a replicated layout."""
    mesh = _worker_mesh()
    adapter, loader, step_fn = _pieces()
    state, workers = _placed_inputs(adapter, mesh)
    runner = EpochRunner(step_fn, loader, 0.9, ensemble=True, mesh=mesh,
                         engine="sharded", donate=False)
    out, _ = runner.run_chunk(state, workers, 2)
    want = ensemble_shardings(mesh, out)
    for leaf, sh in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(
                            want, is_leaf=lambda x: hasattr(x, "spec"))):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)


def test_engine_validation_errors():
    adapter, loader, step_fn = _pieces()
    with pytest.raises(ValueError, match="engine must be"):
        EpochRunner(step_fn, loader, 0.9, engine="pmap")
    with pytest.raises(ValueError, match="ensemble"):
        EpochRunner(step_fn, loader, 0.9, engine="sharded")
    with pytest.raises(ValueError, match="worker"):
        EpochRunner(step_fn, loader, 0.9, ensemble=True, engine="sharded")
    no_worker = jax.make_mesh((2, 2), ("data", "model"))
    with pytest.raises(ValueError, match="worker"):
        EpochRunner(step_fn, loader, 0.9, ensemble=True, engine="sharded",
                    mesh=no_worker)


def test_engine_rejects_explicit_axes():
    """The in-trace gather and permutation draw need Auto axes: an
    Explicit-axis mesh, or inputs placed on one, is refused up front with a
    ValueError that names the fix, not a ShardingTypeError from the trace."""
    if len(jax.devices()) < W * PER_WORKER:
        pytest.skip(f"needs {W * PER_WORKER} devices")
    adapter, loader, step_fn = _pieces()
    explicit = jax.make_mesh((W, 2, 2), ("worker", "data", "model"),
                             axis_types=(AxisType.Explicit,) * 3)
    with pytest.raises(ValueError, match="Auto axes"):
        EpochRunner(step_fn, loader, 0.9, ensemble=True, mesh=explicit,
                    engine="sharded")
    state, workers = _placed_inputs(adapter, explicit)
    oracle = EpochRunner(step_fn, loader, 0.9, ensemble=True, donate=False)
    with pytest.raises(ValueError, match="Auto axes"):
        oracle.run_chunk(state, workers, 1)
