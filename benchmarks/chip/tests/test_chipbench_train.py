"""The training cell's comparison on the CPU: a sound run passes, and a run
with the timed step broken underneath it comes out not correct, once for
each fault a one-chip training cell can have. The control (the reference
computed in float8) fails at least one number."""
from __future__ import annotations

import pytest

from chipbench_testing import (INTERNLM2_SMALL, MAMBA2_SMALL, TRAIN_SMALL,
                               TRAIN_SMALL_LIMITS as LIMITS, cell, run_job)
from chipbench import harness, traincheck


def test_sound_run_is_correct():
    out = run_job(cell(INTERNLM2_SMALL, TRAIN_SMALL, LIMITS))
    assert harness.correct(out["checks"]), out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"]["train_tokens_per_s"] > 0


def test_sound_mamba2_run_is_correct():
    """The SSD model's cell, whose reference takes the gradient one
    sequence at a time (``GRAD_ROWS``)."""
    out = run_job(cell(MAMBA2_SMALL, TRAIN_SMALL, LIMITS))
    assert harness.correct(out["checks"]), out["checks"]
    assert out["failed"] == 0


def _unchanged(step):
    def broken(bundle, opt_state, batch, step_no, scale):
        _, _, _, metrics = step(bundle, opt_state, batch, step_no, scale)
        return bundle, opt_state, scale, metrics
    return broken


def _half_batch(step):
    def broken(bundle, opt_state, batch, step_no, scale):
        half = {k: (v[:v.shape[0] // 2] if getattr(v, "ndim", 0) else v)
                for k, v in batch.items()}
        return step(bundle, opt_state, half, step_no, scale)
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    from repro.core.adapters import LMAdapter
    real = LMAdapter.make_train_step

    def make(self, *a, **kw):
        return fault(real(self, *a, **kw))

    monkeypatch.setattr(LMAdapter, "make_train_step", make)
    out = run_job(cell(INTERNLM2_SMALL, TRAIN_SMALL, LIMITS))
    assert not harness.correct(out["checks"]), out["checks"]


def test_float8_control_is_not_correct():
    """The reference in float8 put in the program's place."""
    import jax
    import jax.numpy as jnp
    from chipbench import lmdata

    c = cell(INTERNLM2_SMALL, TRAIN_SMALL, LIMITS)
    ref = c.reference()
    t = TRAIN_SMALL
    rows = lmdata.markov_rows(7, t["rows"], t["seq"], 256, 64)
    batches = [{k: v[i * t["batch"]:(i + 1) * t["batch"]]
                for k, v in rows.items()} for i in range(3)]
    from repro.models.model import Model
    shapes = jax.eval_shape(Model(harness.program_config(c.config)).init,
                            jax.random.PRNGKey(0))
    got = {m: traincheck.reference_steps(ref, c.config, shapes, 7, batches,
                                         t, mode=m)
           for m in ("f32", "fp8")}
    checks = traincheck.compare(got["fp8"], got["f32"], LIMITS)
    assert not harness.correct(checks), checks
    assert jnp.isfinite(jnp.asarray(got["fp8"]["losses"])).all()
