"""Production meshes.

Functions, not module-level constants — importing this module never touches
jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE importing
jax; everything here just asks for whatever devices exist.
"""
from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Phase-1 (synchronous large-batch) mesh: one TPU v5e pod is (16, 16)
    = 256 chips; two pods stack a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_worker_mesh(n_workers: int = 8, *, multi_pod: bool = False):
    """Phase-2 mesh: the data axis is split into `n_workers` independent
    blocks; each worker keeps FSDP/tensor parallelism inside its block.
    512 = 8 workers x 4 data x 16 model (workers never straddle pods for
    n_workers >= n_pods since the worker axis is outermost in device order).
    """
    total = 512 if multi_pod else 256
    model = 16
    data = total // (n_workers * model)
    if data < 1:
        raise ValueError(f"{n_workers} workers don't fit {total} chips")
    return _mk((n_workers, data, model), ("worker", "data", "model"))


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host has (CPU tests / examples)."""
    n = len(jax.devices())
    model = min(model_parallel, n)
    return _mk((n // model, model), ("data", "model"))
