"""The phase-2 job on the CPU, in a process of its own that holds four
virtual devices: the sharded engine on a worker:2 mesh trains each worker
on its own rows, and the comparison with the reference covers every
worker."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from chipbench_testing import BENCH

SCRIPT = r"""
import json, sys
sys.path[:0] = [sys.argv[1]]
from chipbench_testing import (INTERNLM2_SMALL, TRAIN_SMALL,
                               TRAIN_SMALL_LIMITS as limits, cell, run_job)
from chipbench import harness
traffic = dict(TRAIN_SMALL, job="train_phase2", workers=2, batch=2)
out = run_job(cell(INTERNLM2_SMALL, traffic, limits, chips=2))
print(json.dumps({"correct": harness.correct(out["checks"]),
                  "checks": out["checks"], "attempted": out["attempted"]}))
"""


def test_phase2_on_a_worker_mesh_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT,
                        str(BENCH / "tests")], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["attempted"] % 2 == 0 and out["attempted"] >= 2
