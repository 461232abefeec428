"""repro.dist beyond the seed spec: mesh context semantics, optimizer-state
mirror determinism, and the end-to-end phase-2 no-cross-worker-collectives
property (positive on the real vmapped ensemble step, negative on a
deliberate cross-worker psum)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.configs.base import OptimizerConfig, ScheduleConfig
from repro.core.adapters import LMAdapter
from repro.core.schedules import schedule_fn
from repro.core.swap import _stack_bundles
from repro.dist.sharding import (
    assert_no_cross_worker_collectives, ensemble_shardings, get_mesh,
    logical_constraint, param_spec, set_mesh,
)
from repro.train.precision import default_scale_state, stack_scale_state


# ---------------------------------------------------------------------------
# mesh context + logical_constraint
# ---------------------------------------------------------------------------


def test_logical_constraint_identity_without_mesh():
    """With no ambient mesh, logical_constraint returns its input object —
    not a copy, not a traced transform — so bare-CPU model code pays zero."""
    assert get_mesh() is None
    x = jnp.arange(12.0).reshape(3, 4)
    y = logical_constraint(x, ("batch", None))
    assert y is x
    # also the identity inside jit (traces to the traced value itself)
    out = jax.jit(lambda a: logical_constraint(a, ("batch",)))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_logical_constraint_applies_under_mesh():
    n = len(jax.devices())
    # Auto axes, as DistConfig.make_mesh builds it: constraints name Auto
    # axes only (a bare jax.make_mesh defaults to Explicit axes)
    mesh = jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    x = jnp.zeros((n * 2, 16))
    with set_mesh(mesh):
        out = jax.jit(lambda a: logical_constraint(a, ("batch",)))(x)
    assert out.sharding.spec == P("data")


def test_set_mesh_is_reentrant():
    n = len(jax.devices())
    m1 = jax.make_mesh((n,), ("data",))
    m2 = jax.make_mesh((n,), ("model",))
    assert get_mesh() is None
    with set_mesh(m1):
        assert get_mesh() is m1
        with set_mesh(m2):
            assert get_mesh() is m2
        assert get_mesh() is m1
    assert get_mesh() is None


# ---------------------------------------------------------------------------
# param_spec determinism across optimizer-state mirrors
# ---------------------------------------------------------------------------


class _FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 2}


_MIRROR_CASES = [
    ("embed/table", (512, 256)),
    ("head/w", (256, 512)),
    ("blocks/attn/wq", (4, 1, 256, 512)),
    ("blocks/mlp/wi", (4, 1, 256, 1024)),
    ("blocks/ln1/scale", (4, 1, 256)),
    ("blocks/moe/wi", (4, 1, 8, 256, 512)),
    ("tail/out/w", (3, 256, 256)),
]


@pytest.mark.parametrize("name,shape", _MIRROR_CASES)
def test_param_spec_deterministic_across_opt_mirrors(name, shape):
    """mu/ nu/ m/ v/ (and nested mu/nu) mirrors resolve to the parameter's
    own spec, and repeated calls are bit-identical (pure function)."""
    base = param_spec(name, shape, _FakeMesh)
    assert param_spec(name, shape, _FakeMesh) == base  # deterministic
    for prefix in ("mu/", "nu/", "m/", "v/", "mu/nu/"):
        assert param_spec(prefix + name, shape, _FakeMesh) == base, \
            f"{prefix + name} diverged from {name}"


def test_param_spec_divisibility_fallback_to_replication():
    # 2 core dims but neither divisible by its mesh axis -> fully replicated
    assert param_spec("blocks/attn/wq", (4, 1, 255, 3), _FakeMesh) == P()
    # embed table with indivisible vocab: model axis dropped
    assert param_spec("embed/table", (512, 3), _FakeMesh) == P()


# ---------------------------------------------------------------------------
# end-to-end: phase-2 ensemble step on a worker mesh
# ---------------------------------------------------------------------------

W = 2          # workers
PER_WORKER = 4  # data=2 x model=2 inside each worker block


def _worker_mesh():
    if len(jax.devices()) < W * PER_WORKER:
        pytest.skip(f"needs {W * PER_WORKER} devices "
                    f"(conftest forces 8 on CPU hosts)")
    return jax.make_mesh((W, 2, 2), ("worker", "data", "model"))


@pytest.fixture(scope="module")
def worker_mesh():
    return _worker_mesh()


def _phase2_compiled(mesh):
    """Compile the REAL phase-2 ensemble step (adapter train step, vmapped
    over the leading worker axis — exactly what SWAP.run jits) with the
    stacked trees placed by ensemble_shardings, and return its HLO."""
    cfg = registry.get_smoke_config("internlm2-1.8b")
    adapter = LMAdapter(cfg, OptimizerConfig(kind="sgd"))
    raw_step = adapter.make_train_step(schedule_fn(
        ScheduleConfig(kind="const")))
    ens_step = jax.vmap(raw_step, in_axes=(0, 0, 0, None, 0))

    bundle = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))
    stacked = jax.eval_shape(lambda b: _stack_bundles(b, W), bundle)
    opt = jax.eval_shape(jax.vmap(adapter.init_opt), stacked)
    batch = {
        "tokens": jax.ShapeDtypeStruct((W, 4, 16), jnp.int32),
        "labels": jax.ShapeDtypeStruct((W, 4, 16), jnp.int32),
    }
    scale = jax.eval_shape(
        lambda: stack_scale_state(default_scale_state(), W))

    s_sh = ensemble_shardings(mesh, stacked)
    o_sh = ensemble_shardings(mesh, opt)
    b_sh = ensemble_shardings(mesh, batch)
    sc_sh = ensemble_shardings(mesh, scale)
    fn = jax.jit(ens_step, in_shardings=(s_sh, o_sh, b_sh, None, sc_sh),
                 out_shardings=(s_sh, o_sh, sc_sh, None))
    step = jax.ShapeDtypeStruct((), jnp.int32)
    return fn.lower(stacked, opt, batch, step, scale).compile()


def test_phase2_ensemble_step_has_no_cross_worker_collectives(worker_mesh):
    compiled = _phase2_compiled(worker_mesh)
    assert_no_cross_worker_collectives(compiled.as_text(), n_workers=W,
                                       devices_per_worker=PER_WORKER)


def test_cross_worker_psum_is_rejected(worker_mesh):
    """Negative control: a step that psums over the worker axis must trip
    the assert — proves the check can actually see a violation."""
    from jax.experimental.shard_map import shard_map

    def bad_step(x):
        return jax.lax.psum(x, "worker")

    f = shard_map(bad_step, mesh=worker_mesh,
                  in_specs=P("worker"), out_specs=P())
    hlo = jax.jit(f).lower(
        jax.ShapeDtypeStruct((W * PER_WORKER, 1), jnp.float32)
    ).compile().as_text()
    with pytest.raises(AssertionError, match="spans workers"):
        assert_no_cross_worker_collectives(hlo, n_workers=W,
                                           devices_per_worker=PER_WORKER)


def test_cross_worker_collective_permute_is_rejected():
    """collective-permute carries source_target_pairs, not replica_groups —
    a cross-worker permute must still trip the assert."""
    hlo = ("%cp = f32[4]{0} collective-permute(%x), "
           "source_target_pairs={{0,1},{2,4},{3,6}}")
    with pytest.raises(AssertionError, match="spans workers"):
        assert_no_cross_worker_collectives(hlo, n_workers=2,
                                           devices_per_worker=4)
    ok = ("%cp = f32[4]{0} collective-permute(%x), "
          "source_target_pairs={{0,1},{1,2},{4,5}}")
    assert assert_no_cross_worker_collectives(
        ok, n_workers=2, devices_per_worker=4) == 3


def test_empty_replica_groups_means_all_devices():
    """replica_groups={} is XLA's 'one group of ALL replicas' — with more
    than one worker that is by definition a cross-worker sync."""
    hlo = "%ar = f32[4]{0} all-reduce(%x), replica_groups={}"
    with pytest.raises(AssertionError, match="spans workers"):
        assert_no_cross_worker_collectives(hlo, n_workers=2,
                                           devices_per_worker=2)
    # degenerate single-worker deployment: nothing to cross
    assert_no_cross_worker_collectives(hlo, n_workers=1,
                                       devices_per_worker=4)


def test_collective_bytes_async_start_counts_result_only():
    from repro.dist.sharding import collective_bytes

    hlo = ("%ars = (f32[128,256]{1,0}, f32[128,256]{1,0}) "
           "all-reduce-start(f32[128,256]{1,0} %x), "
           "replica_groups={{0,1}}\n"
           "%ard = f32[128,256]{1,0} all-reduce-done(%ars)\n"
           "%ags = (bf16[2,64]{1,0}, bf16[8,64]{1,0}) "
           "all-gather-start(bf16[2,64]{1,0} %y), replica_groups={{0,1,2,3}}")
    out = collective_bytes(hlo)
    # operand half of the -start tuple must not be double counted, and the
    # -done form must not count at all
    assert out["all-reduce"] == 128 * 256 * 4
    assert out["all-gather"] == 8 * 64 * 2


def test_ensemble_shardings_put_worker_axis_first(worker_mesh):
    tree = {"w": jax.ShapeDtypeStruct((W, 6, 8), jnp.float32),
            "scalar": jax.ShapeDtypeStruct((), jnp.float32),
            "odd": jax.ShapeDtypeStruct((3, 4), jnp.float32)}
    sh = ensemble_shardings(worker_mesh, tree)
    # _resolve pads to the leaf's full rank; only the leading dim is named
    assert sh["w"].spec == P("worker", None, None)
    assert sh["scalar"].spec == P()
    # leading dim not divisible by W -> replicated, never an error
    assert sh["odd"].spec == P()
