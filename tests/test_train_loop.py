"""Phase engine (repro.train.loop): the scan-based epoch runner must
reproduce the per-step Python loop exactly, stop at epoch boundaries, and —
vmapped with the in-trace batch gather on a worker mesh — lower with no
cross-worker collectives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ModelConfig, OptimizerConfig, ScheduleConfig
from repro.core.adapters import CNNAdapter, LMAdapter
from repro.core.schedules import schedule_fn
from repro.core.swap import _stack_bundles
from repro.data.pipeline import Loader, make_gmm_images, make_markov_lm
from repro.dist.sharding import (assert_no_cross_worker_collectives,
                                 ensemble_shardings)
from repro.train.loop import (EpochRunner, init_train_state,
                              python_loop_reference, run_phase,
                              stack_train_state)


def tiny_lm() -> ModelConfig:
    return ModelConfig(
        name="tiny-lm", family="dense", n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=32, attention="gqa",
        dtype="float32", remat=False, scan_layers=False)


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_logs_match(ref_log, log, exact=True):
    """Per-step trajectories across the two engines. The EMA is always
    compared to f32-ulp tolerance because XLA contracts ``b*ema +
    (1-b)*acc`` into an FMA inside the compiled chunk (one rounding) while
    the eager reference rounds twice; with ``exact=False`` the step outputs
    get the same treatment (conv/BN fusion differs between the scanned and
    standalone compilations of the CNN step)."""
    assert [e["step"] for e in ref_log] == [e["step"] for e in log]
    for k in ("accuracy", "loss", "lr"):
        if exact:
            assert [e[k] for e in ref_log] == [e[k] for e in log], k
        else:
            np.testing.assert_allclose([e[k] for e in ref_log],
                                       [e[k] for e in log],
                                       rtol=1e-5, atol=1e-7, err_msg=k)
    np.testing.assert_allclose([e["ema"] for e in ref_log],
                               [e["ema"] for e in log], rtol=1e-5, atol=1e-9)


def _lm_pieces(n_train=128, batch=16, seq_len=16, seed=0):
    cfg = tiny_lm()
    adapter = LMAdapter(cfg, OptimizerConfig(kind="sgd"))
    data = make_markov_lm(seed, vocab=cfg.vocab_size, n_train=n_train,
                          n_test=32, seq_len=seq_len)
    train = {"tokens": data["train_tokens"], "labels": data["train_labels"]}
    loader = Loader(train, batch, seed=3)
    step_fn = adapter.make_train_step(schedule_fn(
        ScheduleConfig(kind="warmup_linear", peak_lr=0.1, warmup_steps=3,
                       total_steps=12)))
    return adapter, loader, step_fn


def _fresh_state(adapter, key=1):
    bundle = adapter.init(jax.random.PRNGKey(key))
    return init_train_state(bundle, adapter.init_opt(bundle))


def test_scan_matches_python_loop_lm():
    """Same params AND same per-step metric/EMA trajectory, bitwise, on the
    Markov-LM task (12 steps across an epoch boundary: spe=8)."""
    adapter, loader, step_fn = _lm_pieces()
    n = 12
    assert loader.steps_per_epoch == 8  # crosses an epoch boundary

    ref_state, ref_log = python_loop_reference(
        step_fn, loader, _fresh_state(adapter), n_steps=n, ema_beta=0.9)

    runner = EpochRunner(step_fn, loader, 0.9)
    log = []
    res = run_phase(runner, _fresh_state(adapter), 0, max_steps=n, log=log)

    _assert_trees_equal(ref_state.bundle, res.state.bundle)
    _assert_trees_equal(ref_state.opt_state, res.state.opt_state)
    _assert_logs_match(ref_log, log)
    assert float(np.asarray(res.state.acc_ema)) == log[-1]["ema"]


def test_scan_matches_python_loop_cnn():
    """Same equivalence on the GMM-image task through the CNN+BN adapter —
    this also exercises the traced aug_seed path (augmentation consumes it)
    and the BN state flowing through the scan carry. Conv/BN ops compile
    with different fusion inside scan than standalone, so this task gets
    tight tolerances instead of the LM's bitwise equality."""
    cfg = registry.get_smoke_config("cifar-cnn")
    adapter = CNNAdapter(cfg, OptimizerConfig(kind="sgd"))
    data = make_gmm_images(0, n_classes=10, image_size=16, n_train=128,
                           n_test=32, noise=2.0)
    train = {"images": data["train_images"], "labels": data["train_labels"]}
    loader = Loader(train, 16, seed=5)
    step_fn = adapter.make_train_step(schedule_fn(
        ScheduleConfig(kind="const", peak_lr=0.1)))
    n = 10  # spe=8 -> crosses an epoch boundary

    ref_state, ref_log = python_loop_reference(
        step_fn, loader, _fresh_state(adapter), n_steps=n, ema_beta=0.9)

    runner = EpochRunner(step_fn, loader, 0.9)
    log = []
    res = run_phase(runner, _fresh_state(adapter), 0, max_steps=n, log=log)

    for a, b in zip(jax.tree_util.tree_leaves(ref_state.bundle),
                    jax.tree_util.tree_leaves(res.state.bundle)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-7)
    _assert_logs_match(ref_log, log, exact=False)


def test_early_exit_at_epoch_boundary():
    """EMA stopping is checked at chunk granularity: a threshold crossed
    during an epoch stops at that epoch's boundary, never mid-chunk; a
    threshold already met at entry (e.g. a restored state) runs nothing."""
    adapter, loader, step_fn = _lm_pieces()
    runner = EpochRunner(step_fn, loader, 0.9)
    res = run_phase(runner, _fresh_state(adapter), 0, max_steps=40,
                    stop_accuracy=1e-6)  # crossed within the first epoch
    assert res.steps == loader.steps_per_epoch
    assert int(np.asarray(res.state.step)) == loader.steps_per_epoch

    # entry check: resuming an already-converged state trains zero steps
    res2 = run_phase(runner, res.state, 0, max_steps=40, stop_accuracy=1e-6)
    assert res2.steps == 0
    assert int(np.asarray(res2.state.step)) == loader.steps_per_epoch


def test_mid_chunk_entry_realigns_to_epoch_boundaries():
    """Regression: a phase entered at a non-boundary step (a snapshot cut
    mid-epoch, e.g. by a max_steps cap) must truncate its FIRST chunk to
    the next epoch boundary. The old driver ran full-length chunks from
    the resume offset, so every subsequent 'epoch boundary' — where the
    EMA stopping check and the on_chunk hooks run — was shifted by the
    offset for the rest of the phase."""
    adapter, loader, step_fn = _lm_pieces()
    spe = loader.steps_per_epoch
    assert spe == 8

    # a state 3 steps into an epoch, as a mid-chunk snapshot would leave
    # it (rebuilt per consumer: both engines donate their input buffers)
    def entry():
        st, _ = python_loop_reference(step_fn, loader,
                                      _fresh_state(adapter), n_steps=3,
                                      ema_beta=0.9)
        return st

    boundaries = []
    runner = EpochRunner(step_fn, loader, 0.9)
    log = []
    res = run_phase(runner, entry(), 0, max_steps=10, log=log,
                    on_chunk=lambda st, done: boundaries.append(
                        int(np.asarray(st.step))))
    # chunks [5, 5]: the first is truncated to the boundary at step 8
    assert boundaries == [spe, 13]
    assert res.steps == 10

    # realignment only reschedules chunk cuts — the trajectory is still
    # bitwise the uninterrupted one
    full_state, full_log = python_loop_reference(
        step_fn, loader, entry(), n_steps=10, ema_beta=0.9)
    _assert_trees_equal(full_state.bundle, res.state.bundle)
    _assert_logs_match(full_log, log)


def test_worker_identity_changes_data_order():
    """The in-trace gather must honor the traced worker id: two workers
    stepping from identical state diverge (different permutations)."""
    adapter, loader, step_fn = _lm_pieces()
    runner = EpochRunner(step_fn, loader, 0.9, ensemble=True)
    bundle = adapter.init(jax.random.PRNGKey(0))
    stacked = _stack_bundles(bundle, 2)
    state = stack_train_state(stacked, jax.vmap(adapter.init_opt)(stacked), 2)
    out, _ = runner.run_chunk(state, jnp.arange(2, dtype=jnp.int32), 4)
    diffs = jax.tree_util.tree_map(
        lambda a: float(jnp.abs(a[0] - a[1]).max()), out.bundle["params"])
    assert max(jax.tree_util.tree_leaves(diffs)) > 1e-6


# ---------------------------------------------------------------------------
# phase-2 no-synchronization property on the scanned + vmapped engine
# ---------------------------------------------------------------------------

W = 2
PER_WORKER = 4  # data=2 x model=2 inside each worker block


def test_phase2_scan_epoch_has_no_cross_worker_collectives():
    """The whole scanned epoch — in-trace permutation, batch gather, W
    vmapped train steps per iteration — must lower onto the worker mesh
    with every collective contained inside one worker block."""
    if len(jax.devices()) < W * PER_WORKER:
        pytest.skip(f"needs {W * PER_WORKER} devices "
                    f"(conftest forces 8 on CPU hosts)")
    mesh = jax.make_mesh((W, 2, 2), ("worker", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)

    cfg = registry.get_smoke_config("internlm2-1.8b")
    adapter = LMAdapter(cfg, OptimizerConfig(kind="sgd"))
    data = make_markov_lm(0, vocab=cfg.vocab_size, n_train=64, n_test=32,
                          seq_len=16)
    train = {"tokens": data["train_tokens"], "labels": data["train_labels"]}
    loader = Loader(train, 8, seed=1)
    step_fn = adapter.make_train_step(schedule_fn(
        ScheduleConfig(kind="const", peak_lr=0.05)))

    bundle = adapter.init(jax.random.PRNGKey(0))
    stacked = _stack_bundles(bundle, W)
    state = stack_train_state(stacked, jax.vmap(adapter.init_opt)(stacked), W)
    state = jax.device_put(state, ensemble_shardings(mesh, state))
    workers = jax.device_put(
        jnp.arange(W, dtype=jnp.int32),
        ensemble_shardings(mesh, jnp.arange(W, dtype=jnp.int32)))

    runner = EpochRunner(step_fn, loader, 0.9, ensemble=True)
    fn = runner._chunk_fn(loader.steps_per_epoch)
    hlo = fn.lower(state, workers).compile().as_text()
    assert_no_cross_worker_collectives(hlo, n_workers=W,
                                       devices_per_worker=PER_WORKER)
