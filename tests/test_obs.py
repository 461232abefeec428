"""The program's host spans and counters (``repro.obs``): what they keep in
memory, that they land on the profiler's clock, and that the phase engine
and the SWAP controller report their times from them."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


def test_span_counts_totals_and_nesting():
    with obs.span("outer") as outer:
        for _ in range(3):
            with obs.span("inner") as inner:
                pass
        assert outer.elapsed() >= 0.0
    assert obs.stats("inner")["count"] == 3
    assert obs.stats("outer")["count"] == 1
    # the outer span covers the three inner ones
    assert outer.seconds >= obs.stats("inner")["total_s"] > 0.0
    assert inner.seconds == obs.recent("inner")[-1]
    assert obs.stats("never") == {"count": 0, "total_s": 0.0}
    assert obs.recent("never") == []


def test_span_records_when_the_body_raises():
    with pytest.raises(ValueError):
        with obs.span("fails"):
            raise ValueError("boom")
    assert obs.stats("fails")["count"] == 1


def test_recent_durations_are_bounded():
    n = obs.RECENT + 10
    for _ in range(n):
        with obs.span("many"):
            pass
    assert obs.stats("many")["count"] == n
    assert len(obs.recent("many")) == obs.RECENT


def test_counters_add_and_reset():
    obs.count("things")
    obs.count("things", 2.5)
    assert obs.counter("things") == 3.5
    assert obs.counter("other") == 0
    obs.reset()
    assert obs.counter("things") == 0


def test_spans_are_on_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("test.outer"):
            with obs.span("test.inner"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    events = {e.name: e for p in ProfileData.from_file(str(path)).planes
              for line in p.lines for e in line.events}
    outer, inner = events["test.outer"], events["test.inner"]
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)


def _tiny_runner():
    from repro.configs import registry
    from repro.configs.base import OptimizerConfig, ScheduleConfig
    from repro.core.adapters import LMAdapter
    from repro.core.schedules import schedule_fn
    from repro.data.pipeline import Loader
    from repro.train.loop import EpochRunner, init_train_state

    adapter = LMAdapter(registry.get_smoke_config("internlm2-1.8b"),
                        OptimizerConfig(kind="sgd"))
    tokens = np.zeros((8, 16), np.int32)
    runner = EpochRunner(
        adapter.make_train_step(schedule_fn(ScheduleConfig(peak_lr=0.1))),
        Loader({"tokens": tokens, "labels": tokens}, 4), 0.9)
    bundle = adapter.init(jax.random.PRNGKey(0))
    return runner, init_train_state(bundle, adapter.init_opt(bundle))


def test_run_phase_times_come_from_its_spans():
    from repro.train.loop import run_phase
    runner, state = _tiny_runner()
    res = run_phase(runner, state, 0, max_steps=4, chunk_steps=2,
                    on_chunk=lambda st, done: None)
    chunks, hooks = obs.stats("run_phase.chunk"), obs.stats("run_phase.hooks")
    assert chunks["count"] == hooks["count"] == 2
    assert res.train_time == pytest.approx(chunks["total_s"])
    assert res.hook_time == pytest.approx(hooks["total_s"])
    # one host span per compiled call, inside the chunk's span
    assert obs.stats("EpochRunner.run_chunk")["count"] == 2
    assert obs.stats("EpochRunner.run_chunk")["total_s"] <= chunks["total_s"]
    # one chunk length, so one program
    assert obs.counter("EpochRunner.programs") == 1


def _tiny_lm_data():
    from repro.configs import registry
    from repro.configs.base import OptimizerConfig
    from repro.core.adapters import LMAdapter
    from repro.data.pipeline import Loader, make_markov_lm

    cfg = registry.get_smoke_config("internlm2-1.8b")
    data = make_markov_lm(0, vocab=cfg.vocab_size, n_train=64, n_test=32,
                          seq_len=8)
    train = {"tokens": data["train_tokens"], "labels": data["train_labels"]}
    test_loader = Loader({"tokens": data["test_tokens"],
                          "labels": data["test_labels"]}, 32)
    return LMAdapter(cfg, OptimizerConfig(kind="sgd")), train, test_loader


def test_swap_phase_times_come_from_its_spans():
    from repro.configs.base import PhaseConfig, ScheduleConfig, SWAPConfig
    from repro.core.swap import SWAP

    adapter, train, test_loader = _tiny_lm_data()
    sched = ScheduleConfig(kind="const", peak_lr=0.02)
    cfg = SWAPConfig(n_workers=2,
                     phase1=PhaseConfig(batch_size=32, max_steps=2,
                                        schedule=sched),
                     phase2=PhaseConfig(batch_size=16, max_steps=2,
                                        schedule=sched),
                     bn_recompute_batches=1, bn_recompute_batch_size=32)
    res = SWAP(adapter, cfg, train, test_loader).run(jax.random.PRNGKey(0))
    for name in ("SWAP.phase1", "SWAP.phase2", "SWAP.phase3",
                 "SWAP.average", "SWAP.bn_recompute"):
        assert obs.stats(name)["count"] == 1, name
    assert res["phase3_time"] == obs.recent("SWAP.phase3")[-1]
    assert 0 < res["phase1_time"] <= obs.recent("SWAP.phase1")[-1]
    assert 0 < res["phase2_time"] <= obs.recent("SWAP.phase2")[-1]


def test_swa_time_comes_from_its_span():
    from repro.configs.base import ScheduleConfig, SWAConfig
    from repro.core.swa import SWA

    adapter, train, test_loader = _tiny_lm_data()
    cfg = SWAConfig(n_samples=2, cycle_steps=1, batch_size=32,
                    schedule=ScheduleConfig(kind="cyclic", peak_lr=0.1,
                                            min_lr=0.01, cycle_steps=1))
    res = SWA(adapter, cfg, train, test_loader).run(
        adapter.init(jax.random.PRNGKey(0)))
    assert res["time"] == obs.recent("SWA.run")[-1] > 0
    assert obs.stats("SWA.bn_recompute")["count"] == 1


def test_weight_publisher_publish_is_a_span(tmp_path):
    from repro.serve.publish import WeightPublisher
    pub = WeightPublisher(directory=str(tmp_path), ensemble=False)
    assert pub.publish({"w": jnp.ones(4)}, step=3) == 1
    assert obs.stats("WeightPublisher.publish")["count"] == 1
