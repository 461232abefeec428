"""``run_chunk_host_ms.train``: the phase engine's host time per
``run_chunk`` call, read from the program's own span record on the small
training cell; nothing where the program recorded no span, or keeps no
record at all."""
from __future__ import annotations

import sys

from chipbench_testing import (INTERNLM2_SMALL, TRAIN_SMALL,
                               TRAIN_SMALL_LIMITS, cell, run_job)

METRIC = "run_chunk_host_ms.train"


def test_reads_the_windows_run_chunk_spans():
    from repro import obs
    c = cell(INTERNLM2_SMALL, TRAIN_SMALL, TRAIN_SMALL_LIMITS)
    obs.reset()
    out = run_job(c)
    steps = out["facts"]["steps"]
    calls = obs.recent("EpochRunner.run_chunk")
    # the checked steps before the window, then one call per window step
    assert steps >= 1 and len(calls) > steps
    value = c.reader(METRIC).read({"facts": out["facts"]})
    assert value > 0
    assert min(calls[-steps:]) * 1e3 <= value <= max(calls[-steps:]) * 1e3
    obs.reset()
    assert c.reader(METRIC).read({"facts": out["facts"]}) is None


def test_nothing_to_read_without_the_programs_record(monkeypatch):
    """A program without ``repro.obs`` (the commit before it) reads as
    nothing, not as an error."""
    import repro
    from repro import obs
    c = cell(INTERNLM2_SMALL, TRAIN_SMALL, TRAIN_SMALL_LIMITS)
    ctx = {"facts": {"steps": 3}}
    obs.reset()
    for _ in range(3):
        with obs.span("EpochRunner.run_chunk"):
            pass
    assert c.reader(METRIC).read(ctx) > 0
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)   # import fails
    assert c.reader(METRIC).read(ctx) is None
    obs.reset()
