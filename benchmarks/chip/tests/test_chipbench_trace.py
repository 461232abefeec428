"""The trace reduction on the CPU: busy time, kernel time and idle gaps of
a hand-built profile with the planes, lines and events of
``jax.profiler.ProfileData``, and of traces recorded on a TPU v5e
(``fixtures/<workload>.json.gz``, made by ``make_trace_fixture.py`` from
``run.py --workload <workload> --seconds 5 --trace 1 --keep-trace <dir>``)."""
from __future__ import annotations

import dataclasses
import gzip
import json
import pathlib

import pytest

import chipbench_testing  # noqa: F401  (puts the harness on the path)
from chipbench import flops, harness, peaks, trace

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
# steps in each recorded window, and the kernel its cell's roofline reads
RECORDED = {"internlm2-1.8b.train.phase1": (7, "flash_attention"),
            "mamba2-2.7b.train.phase1": (7, "ssd")}


@dataclasses.dataclass
class Event:
    name: str
    start_ns: int
    duration_ns: int
    stats: tuple = ()


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Profile:
    planes: list


def _profile():
    host = Plane("/host:CPU", [Line("python", [
        Event(trace.WINDOW, 1000, 9000),
        Event("dispatch", 4000, 3000)])])
    ops = Line("XLA Ops", [
        Event("%fusion.1 = f32[8] fusion(f32[8] %p)", 500, 1000),  # half out
        Event("%flash_attention_pallas_fwd.3 = bf16[8] custom-call()",
              2000, 1000),
        Event("%flash_attention_pallas_bwd.7 = bf16[8] custom-call()",
              2500, 1000),                                 # overlaps by 500
        Event("%convolution.4 = bf16[8] convolution(bf16[8] "
              "%flash_attention_pallas_fwd.3)", 8000, 500)])  # an operand
    mods = Line("XLA Modules", [Event("jit_step(123)", 1500, 2500)])
    return Profile([host, Plane("/device:TPU:0", [ops, mods])])


def test_union_and_gaps():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace._gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    # a while over two operations keeps only the time between them
    own = trace._own_times([(0, 10, "while"), (1, 4, "a"), (6, 9, "b")])
    assert sorted(own) == [(3, "a"), (3, "b"), (4, "while")]


def test_reduction_of_a_hand_built_profile():
    r = trace.reduce_profile(_profile(), 1)
    # window 1000..10000; busy 1000..1500, 2000..3500, 8000..8500
    assert r["window_s"] == 9000e-9
    assert r["busy_s"] == pytest.approx(2500e-9)
    assert r["kernel_s"] == {"flash_attention": pytest.approx(2000e-9)}
    assert r["module_s"] == {"jit_step": pytest.approx(2500e-9)}
    ops = dict(r["breakdown"]["device_ops"])
    # the second flash call starts inside the first: 500 ns of the first
    # are counted as the second's
    assert ops["%flash_attention_pallas_fwd.3 = bf16[8] custom-call()"] == \
        pytest.approx(500e-9)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 3500..8000 is covered by the host span "dispatch" at its middle
    assert gaps["dispatch"] == pytest.approx(4500e-9)
    assert gaps["no host span"] == pytest.approx(2000e-9)
    assert len(r["breakdown"]["device_ops"]) == 4


def test_readers_of_the_reduction():
    r = trace.reduce_profile(_profile(), 1)
    assert trace.idle_share(r) == pytest.approx(100 * (1 - 2500 / 9000))
    ctx = {"trace": r, "peaks": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9},
           "facts": {"kernels": {"flash_attention": {"ops": 1e3,
                                                     "bytes": 500}}}}
    # 1e3 ops at 1e12/s: 1 ns; 500 B at 1e9 B/s: 500 ns, over 2000 ns
    assert flops.kernel_roofline(ctx, "flash_attention") == pytest.approx(25)
    assert flops.kernel_roofline(ctx, "ssd") is None
    assert trace.idle_share(None) is None


def test_a_profile_without_the_window_span_is_refused():
    p = _profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match=trace.WINDOW):
        trace.reduce_profile(p, 1)


def _recorded(workload):
    with gzip.open(FIXTURES / f"{workload}.json.gz", "rt") as f:
        data = json.load(f)
    return Profile([Plane(p["name"], [
        Line(ln["name"], [Event(n, s, d, tuple(map(tuple, st)))
                          for n, s, d, st in ln["events"]])
        for ln in p["lines"]]) for p in data["planes"]])


@pytest.mark.parametrize("workload", sorted(RECORDED))
def test_reduction_of_a_recorded_chip_trace(workload):
    steps, kernel = RECORDED[workload]
    profile = _recorded(workload)
    r = trace.reduce_profile(profile, 1)
    # one step program, device-bound: busy all but a few milliseconds
    assert r["devices"] == 1 and r["module_s"].keys() == {"jit_run_chunk"}
    assert 0.99 * r["window_s"] < r["busy_s"] <= r["window_s"]
    # the kernel's time is that of the operations it names, not of those
    # that take its results as operands (the internlm2 trace has such
    # operations, the mamba2 trace none)
    ops = next(ln for ln in profile.planes[0].lines if ln.name == "XLA Ops")
    named = sum(e.duration_ns for e in ops.events
                if trace.kernel_of(e.name) == kernel) / 1e9
    assert r["kernel_s"] == {kernel: pytest.approx(named)}
    assert any(kernel_name in e.name and trace.kernel_of(e.name) is None
               for e in ops.events
               for kernel_name in trace.KERNELS[kernel]) == (
        kernel == "flash_attention")
    # own times: the breakdown's operations fit in the window together
    assert sum(v for _, v in r["breakdown"]["device_ops"]) <= r["window_s"]
    # the cell's roofline share over the recorded steps lies in (0, 100)
    cell = harness.find_cell(workload)
    t = cell.traffic
    ops_step, bytes_step = cell.model().train_kernels(
        cell.config, t["batch"], t["seq"])[kernel]
    ctx = {"trace": r, "peaks": peaks.peaks("TPU v5 lite"),
           "facts": {"kernels": {kernel: {"ops": steps * ops_step,
                                          "bytes": steps * bytes_step}}}}
    assert 1 < flops.kernel_roofline(ctx, kernel) < 100
