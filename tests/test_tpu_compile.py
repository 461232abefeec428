"""Compile the main path's Mosaic kernels for a TPU v5e that is described,
not attached: the TPU compiler refuses block shapes off the (8, 128) tiling,
VMEM overruns and kernels that cannot be partitioned, none of which
interpret mode sees. Each compile asserts the kernel survived as a
``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers each
import every test file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import (flash_attention_pallas_bwd,
                                                  flash_attention_pallas_fwd)
from repro.kernels.ssd.kernel import ssd_chunk_pallas, ssd_chunk_pallas_bwd
from repro.kernels.swa_avg.kernel import running_average_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_fwd_bwd_internlm2_widths(one_chip):
    B, S, H, KVH, D = 1, 4096, 16, 8, 128
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, KVH, D), jnp.bfloat16, one_chip)
    lse = _sds((B, S, H), jnp.float32, one_chip)
    _compile(lambda q, k, v: flash_attention_pallas_fwd(
        q, k, v, interpret=False), q, kv, kv)
    _compile(lambda q, k, v, o, lse, do: flash_attention_pallas_bwd(
        q, k, v, o, lse, do, interpret=False), q, kv, kv, q, lse, q)


def test_flash_attention_at_the_cells_shape_and_resolved_tile(one_chip,
                                                             monkeypatch):
    """The training cell's attention call (B=2, S=4096, 16/8 heads x 128)
    forward and backward through the public op, with the tile the TPU path
    resolves for it, so a VMEM overrun at that tile fails here."""
    from repro import obs
    from repro.kernels import dispatch
    from repro.kernels.flash_attention.ops import (_mosaic_blocks,
                                                   flash_attention)

    monkeypatch.setattr(dispatch, "current_backend", lambda: "tpu")
    B, S, H, KVH, D = 2, 4096, 16, 8, 128
    bq, bk = _mosaic_blocks(dispatch.resolve(
        "auto", kernel="flash_attention", shape=(S, D)), False, S, S, D)
    assert (bq, bk) != (128, 128)
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, KVH, D), jnp.bfloat16, one_chip)
    tiles = obs.counter("flash_attention.tiles")
    _compile(lambda q, k, v: flash_attention(q, k, v), q, kv, kv)
    assert obs.counter("flash_attention.tiles") - tiles == \
        (S // bq) * (S // bk)
    _compile(lambda q, k, v, do: jax.vjp(flash_attention, q, k, v)[1](do),
             q, kv, kv, q)


def test_flash_attention_at_granite_widths(one_chip, monkeypatch):
    """granite-4.0-h-micro's attention layer in its training cell: B=2,
    S=4096, 32/8 heads x 64 (a head half the lane width), the softmax
    scale 1/64 and no rotary positions, forward and backward through the
    public op at the TPU tile; the tile counters record the call."""
    from repro import obs
    from repro.kernels import dispatch
    from repro.kernels.flash_attention.ops import flash_attention

    monkeypatch.setattr(dispatch, "current_backend", lambda: "tpu")
    B, S, H, KVH, D = 2, 4096, 32, 8, 64
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, KVH, D), jnp.bfloat16, one_chip)

    def attend(q, k, v):
        return flash_attention(q, k, v, scale=1 / 64)

    tiles = obs.counter("flash_attention.tiles")
    live = obs.counter("flash_attention.tiles_live")
    _compile(attend, q, kv, kv)
    assert obs.counter("flash_attention.tiles") - tiles == 16
    assert obs.counter("flash_attention.tiles_live") - live == 10
    _compile(lambda q, k, v, do: jax.vjp(attend, q, k, v)[1](do),
             q, kv, kv, q)


def test_ssd_fwd_bwd_mamba2_widths(one_chip):
    B, S, H, P_, G, N, chunk = 1, 4096, 80, 64, 1, 128, 256
    x = _sds((B, S, H, P_), jnp.bfloat16, one_chip)
    dt = _sds((B, S, H), jnp.float32, one_chip)
    A = _sds((H,), jnp.float32, one_chip)
    bc = _sds((B, S, G, N), jnp.bfloat16, one_chip)
    _compile(lambda x, dt, A, b, c: ssd_chunk_pallas(
        x, dt, A, b, c, chunk=chunk, interpret=False), x, dt, A, bc, bc)
    nc = S // chunk
    _compile(lambda x, dt, A, b, c, dy, ds, dc: ssd_chunk_pallas_bwd(
        x, dt, A, b, c, dy, ds, dc, chunk=chunk, interpret=False),
        x, dt, A, bc, bc, _sds((B, S, H, P_), jnp.float32, one_chip),
        _sds((B, nc, H, P_, N), jnp.float32, one_chip), dt)


def test_ssd_fwd_bwd_granite_widths(one_chip, monkeypatch):
    """granite-4.0-h-micro's Mamba-2 mixer in its training cell (B=2,
    S=4096, 64 heads x 64 on one group of d_state 128, chunk 256), forward
    and backward through the public op at the TPU lowering: both kernels
    compile under their declared names, and the counters record 64 heads
    sharing each of the two sequences' group."""
    from repro import obs
    from repro.kernels import dispatch
    from repro.kernels.ssd.ops import ssd_scan

    monkeypatch.setattr(dispatch, "current_backend", lambda: "tpu")
    B, S, H, P_, G, N, chunk = 2, 4096, 64, 64, 1, 128, 256
    x = _sds((B, S, H, P_), jnp.bfloat16, one_chip)
    dt = _sds((B, S, H), jnp.float32, one_chip)
    A = _sds((H,), jnp.float32, one_chip)
    bc = _sds((B, S, G, N), jnp.bfloat16, one_chip)
    y = _sds((B, S, H, P_), jnp.float32, one_chip)
    final = _sds((B, H, P_, N), jnp.float32, one_chip)

    def scan(x, dt, A, b, c):
        return ssd_scan(x, dt, A, b, c, chunk=chunk)

    before = obs.counter("ssd.heads"), obs.counter("ssd.groups")
    compiled = _compile(lambda x, dt, A, b, c, dy, ds: jax.vjp(
        scan, x, dt, A, b, c)[1]((dy, ds)), x, dt, A, bc, bc, y, final)
    assert (obs.counter("ssd.heads") - before[0],
            obs.counter("ssd.groups") - before[1]) == (128, 2)
    assert {_declared(n) for n in _kernel_calls(compiled.as_text())} == {
        "ssd_chunk_pallas", "ssd_chunk_pallas_bwd"}


def test_swa_avg_fold(one_chip):
    a = _sds((1 << 20,), jnp.float32, one_chip)
    _compile(lambda a, w, n: running_average_pallas(a, w, n,
                                                    interpret=False),
             a, a, _sds((), jnp.float32, one_chip))


def test_sharded_phase2_with_kernels_on_four_chips(topo, monkeypatch):
    """The sharded phase-2 engine on a ``worker:4`` mesh, one worker per
    chip, with the Mosaic flash kernel in the step: XLA cannot partition a
    Mosaic kernel, so the engine must hand each worker block its own
    program — and that program must still hold no cross-worker
    collective."""
    from repro.configs import registry
    from repro.configs.base import (OptimizerConfig, ScheduleConfig)
    from repro.core.adapters import LMAdapter
    from repro.core.schedules import schedule_fn
    from repro.data.pipeline import Loader
    from repro.dist.sharding import (assert_no_cross_worker_collectives,
                                     ensemble_shardings)
    from repro.kernels import dispatch
    from repro.train.loop import EpochRunner, stack_train_state

    # "auto" asks the live backend (the CPU here); steer it to the chip's
    monkeypatch.setattr(dispatch, "current_backend", lambda: "tpu")
    W = 4
    mesh = Mesh(np.array(topo.devices), ("worker",),
                axis_types=(AxisType.Auto,))
    cfg = dataclasses.replace(registry.get_smoke_config("internlm2-1.8b"),
                              dtype="bfloat16")
    adapter = LMAdapter(cfg, OptimizerConfig(kind="sgd"))
    tokens = np.zeros((16, 128), np.int32)
    runner = EpochRunner(
        adapter.make_train_step(schedule_fn(ScheduleConfig(peak_lr=0.1))),
        Loader({"tokens": tokens, "labels": tokens}, 4), 0.9,
        ensemble=True, mesh=mesh, engine="sharded")

    def init(key):
        bundle = adapter.init(key)
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), bundle)
        return stack_train_state(stacked, jax.vmap(adapter.init_opt)(stacked),
                                 W)

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    state = jax.tree_util.tree_map(
        lambda a, s: _sds(a.shape, a.dtype, s), shapes,
        ensemble_shardings(mesh, shapes))
    worker = _sds((W,), jnp.int32, NamedSharding(mesh, P("worker")))
    hlo = runner.lower_chunk(state, worker, 2).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert_no_cross_worker_collectives(hlo, n_workers=W, devices_per_worker=1)


# ------------------------------------------------------- declared names
#
# A v5e trace names each device operation after its HLO instruction, and
# the benchmark's trace reduction finds the kernels by those names
# (``benchmarks/chip/chipbench/trace.py`` ``KERNELS``). The names are
# declared on each ``pallas_call``; these compiles pin that they reach the
# instructions.

def _trace_module():
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
            / "chip" / "chipbench" / "trace.py")
    spec = importlib.util.spec_from_file_location("chipbench_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _kernel_calls(hlo: str) -> dict:
    """instruction name -> op_name of every Mosaic kernel call."""
    import re
    out = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT )?%(\S+) = ", line).group(1)
            op = re.search(r'op_name="([^"]*)"', line)
            out[name] = op.group(1) if op else ""
    return out


def _declared(name: str) -> str:
    """``flash_attention_pallas_bwd_dq.12`` -> its declared name."""
    return name.split(".", 1)[0]


def _small_flash(one_chip):
    B, S, H, KVH, D = 1, 256, 4, 2, 128
    return (_sds((B, S, H, D), jnp.bfloat16, one_chip),
            _sds((B, S, KVH, D), jnp.bfloat16, one_chip),
            _sds((B, S, H), jnp.float32, one_chip))


def _small_ssd(one_chip):
    B, S, H, P_, G, N = 1, 256, 4, 64, 1, 128
    return (_sds((B, S, H, P_), jnp.bfloat16, one_chip),
            _sds((B, S, H), jnp.float32, one_chip),
            _sds((H,), jnp.float32, one_chip),
            _sds((B, S, G, N), jnp.bfloat16, one_chip),
            _sds((B, S, H, P_), jnp.float32, one_chip),
            _sds((B, S // 128, H, P_, N), jnp.float32, one_chip))


def _compile_small(which, one_chip):
    if which in ("flash_fwd", "flash_bwd"):
        q, kv, lse = _small_flash(one_chip)
        if which == "flash_fwd":
            return _compile(lambda q, k, v: flash_attention_pallas_fwd(
                q, k, v, interpret=False), q, kv, kv)
        return _compile(lambda q, k, v, o, lse, do: flash_attention_pallas_bwd(
            q, k, v, o, lse, do, interpret=False), q, kv, kv, q, lse, q)
    if which in ("ssd_fwd", "ssd_bwd"):
        x, dt, A, bc, dy, ds = _small_ssd(one_chip)
        if which == "ssd_fwd":
            return _compile(lambda x, dt, A, b, c: ssd_chunk_pallas(
                x, dt, A, b, c, chunk=128, interpret=False), x, dt, A, bc, bc)
        return _compile(
            lambda x, dt, A, b, c, dy, ds, dc: ssd_chunk_pallas_bwd(
                x, dt, A, b, c, dy, ds, dc, chunk=128, interpret=False),
            x, dt, A, bc, bc, dy, ds, dt)
    a = _sds((1 << 16,), jnp.float32, one_chip)
    return _compile(lambda a, w, n: running_average_pallas(
        a, w, n, interpret=False), a, a, _sds((), jnp.float32, one_chip))


@pytest.mark.parametrize("which,names,kernel", [
    ("flash_fwd", ["flash_attention_pallas_fwd"], "flash_attention"),
    ("flash_bwd", ["flash_attention_pallas_bwd_dkv",
                   "flash_attention_pallas_bwd_dq"], "flash_attention"),
    ("ssd_fwd", ["ssd_chunk_pallas"], "ssd"),
    ("ssd_bwd", ["ssd_chunk_pallas_bwd"], "ssd"),
    ("swa_avg", ["swa_avg_pallas"], None),
])
def test_kernel_instruction_names_are_declared(one_chip, which, names,
                                               kernel):
    """Each Mosaic call's instruction starts with its declared name, so
    dq and dk/dv are two names, and the trace reduction maps each to its
    kernel (it reads no ``swa_avg`` time yet)."""
    calls = _kernel_calls(_compile_small(which, one_chip).as_text())
    assert sorted(_declared(n) for n in calls) == names
    trace = _trace_module()
    for name in calls:
        assert trace.kernel_of(f"%{name} = custom-call()") == kernel, name


@pytest.mark.parametrize("arch,names", [
    ("internlm2-1.8b", {"flash_attention_pallas_fwd",
                        "flash_attention_pallas_bwd_dq",
                        "flash_attention_pallas_bwd_dkv"}),
    ("mamba2-2.7b", {"ssd_chunk_pallas", "ssd_chunk_pallas_bwd"}),
    ("granite-4.0-h-micro", {"flash_attention_pallas_fwd",
                             "flash_attention_pallas_bwd_dq",
                             "flash_attention_pallas_bwd_dkv",
                             "ssd_chunk_pallas", "ssd_chunk_pallas_bwd"}),
])
def test_train_step_scopes_and_kernel_names(one_chip, monkeypatch, arch,
                                            names):
    """The small LM train step compiled for the chip: every matmul and
    every kernel call sits under a layer's scope, and the kernels keep
    their declared names inside the whole step."""
    from repro.kernels import dispatch
    from test_step_scopes import check_scopes, lm_chunk_hlo, scope_parts

    monkeypatch.setattr(dispatch, "current_backend", lambda: "tpu")
    hlo = lm_chunk_hlo(arch, one_chip)
    check_scopes(hlo, ("convolution", "dot"))
    calls = _kernel_calls(hlo)
    assert {_declared(n) for n in calls} == names
    for name, op_name in calls.items():
        block = "attention" if name.startswith("flash") else "mixer"
        assert {"layers", block} <= scope_parts(op_name), (name, op_name)
