"""The serving cell's open-loop generator and comparison on the CPU."""
from __future__ import annotations

import math
import time

from chipbench_testing import (INTERNLM2_SMALL, SERVE_SMALL,
                               SERVE_SMALL_LIMITS as LIMITS, cell, run_job)
from chipbench import harness, openloop


def test_schedule_offers_the_same_work_in_another_order():
    a = openloop.schedule(SERVE_SMALL, 10.0, 1)
    b = openloop.schedule(SERVE_SMALL, 10.0, 2)
    assert len(a) == len(b) == 120
    assert [d.at for d in a] == [d.at for d in b]
    assert all(0 <= d.at < 10.0 for d in a)
    assert sorted((d.prompt_len, d.output_len) for d in a) == \
        sorted((d.prompt_len, d.output_len) for d in b)
    assert [d.prompt_len for d in a] != [d.prompt_len for d in b]
    assert all(d.prompt_len + d.output_len <= SERVE_SMALL["max_total"]
               for d in a)


def test_latencies_are_timed_from_due():
    ts = [openloop.Timing(due=1.0, submitted=1.5, first=2.0, last=4.0,
                          n_out=5),
          openloop.Timing(due=2.0, submitted=2.0, first=2.1, last=2.1,
                          n_out=1),
          openloop.Timing(due=3.0, failed=True)]
    assert openloop.ttft(ts[0]) == 1.0
    assert openloop.tpot(ts[0]) == 0.5
    assert math.isinf(openloop.ttft(ts[2]))
    s = openloop.summary(ts)
    assert s["ttft_p95_ms"] == math.inf and s["failed"] == 1
    assert s["late_max_ms"] == 500.0
    assert openloop.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0


def test_sound_run_serves_every_request_correctly():
    out = run_job(cell(INTERNLM2_SMALL, SERVE_SMALL, LIMITS), seconds=2.0)
    assert out["attempted"] == 24 and out["failed"] == 0
    assert harness.correct(out["checks"]), out["checks"]
    assert out["metrics"]["ttft_p95_ms"] > 0
    assert out["metrics"]["tpot_p95_ms"] > 0


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve.compiled import CompiledServingEngine
    real = CompiledServingEngine._sample

    def altered(self, logits, key):
        tok = real(self, logits, key)
        return (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(CompiledServingEngine, "_sample", altered)
    out = run_job(cell(INTERNLM2_SMALL, SERVE_SMALL, LIMITS), seconds=2.0)
    assert not harness.correct(out["checks"]), out["checks"]


def test_float8_control_is_not_correct():
    """The float8 reference's first choice at each served position reads
    a gap over the limit; the program's served tokens do not."""
    import jax
    c = cell(INTERNLM2_SMALL, SERVE_SMALL, LIMITS)
    run = harness.Run(c, 99, 2.0, False, jax.devices()[:1],
                      time.perf_counter())
    out = c.job().run(run, control=True)
    assert out["gaps"]["control_gap"] > LIMITS["served_logit_gap"]
    assert harness.correct(out["checks"]), out["checks"]
