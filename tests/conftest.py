import os
import sys

# Multi-device CPU harness: mesh/sharding tests exercise 8 fake host devices
# (worker x data x model splits) instead of a degenerate 1-device mesh. Must
# be set BEFORE jax is first imported. Importing repro.launch.dryrun during
# collection must NOT flip the process to 512 devices (dryrun uses
# setdefault, so the explicit assignment here wins).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

import repro.dist  # noqa: E402,F401  (sharding subsystem, imported once up front)

# The CI image has no hypothesis; install the deterministic stub only when
# the real library is absent (see repro/testing/hypothesis_stub.py).
try:
    import hypothesis  # noqa: F401
except ImportError:
    from repro.testing import hypothesis_stub

    sys.modules["hypothesis"] = hypothesis_stub
    sys.modules["hypothesis.strategies"] = hypothesis_stub.strategies

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: needs a real TPU (Pallas compiled mode, ICI-bandwidth asserts)"
        " — skipped on CPU hosts")
    config.addinivalue_line(
        "markers",
        "gpu: needs a real GPU (compiled Triton lowering; the interpret-"
        "mode equivalence tests run everywhere) — skipped on CPU hosts")
    config.addinivalue_line(
        "markers",
        "multihost: spawns a 2-process jax.distributed cluster (local TCP "
        "coordinator) — opt in with REPRO_MULTIHOST=1 (the CI smoke step "
        "sets it); skipped by default so plain tier-1 runs stay hermetic")


def pytest_collection_modifyitems(config, items):
    backend = jax.default_backend()
    skips = {marker: pytest.mark.skip(
        reason=f"requires a real {marker.upper()}; this host runs the XLA "
               f"{backend.upper()} backend")
        for marker in ("tpu", "gpu") if marker != backend}
    if os.environ.get("REPRO_MULTIHOST") != "1":
        skips["multihost"] = pytest.mark.skip(
            reason="2-process jax.distributed smoke; set REPRO_MULTIHOST=1 "
                   "to run")
    for item in items:
        for marker, skip in skips.items():
            if marker in item.keywords:
                item.add_marker(skip)
