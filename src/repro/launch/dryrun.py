import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, with NO allocation (ShapeDtypeStruct inputs, AOT
compile only). Emits the roofline raw terms per combination:

  flops/bytes per device   from compiled.cost_analysis()
  collective bytes         parsed from post-SPMD HLO (per kind)
  memory_analysis          argument/output/temp bytes per device

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      [--arch qwen2.5-14b ...] [--shape train_4k ...] \
      [--mesh single|multi|both] [--phase2] [--out results/dryrun.json]
      [--skip-existing]

Phase-2 mode lowers the SWAP worker-ensemble step on the
('worker','data','model') mesh and ASSERTS no collective spans two workers
(the paper's "no synchronization between workers" property, checked in HLO).
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, registry, shape_applicable
from repro.configs.base import (
    ModelConfig, OptimizerConfig, ScheduleConfig, ShapeConfig,
)
from repro.core.schedules import schedule_fn
from repro.dist.sharding import (
    assert_no_cross_worker_collectives, batch_shardings, cache_shardings,
    collective_bytes, param_shardings, set_mesh,
)
from repro.launch.mesh import make_production_mesh, make_worker_mesh
from repro.models.model import Model
from repro.train.precision import resolve_policy
from repro.train.steps import make_lm_train_step

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    specs = {}
    if shape.kind in ("train",):
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    else:  # decode: one new token, cache of length S
        specs["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
    if cfg.family == "vlm" and shape.kind != "decode":
        specs["vision_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_vision_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
    if cfg.family == "audio" and shape.kind != "decode":
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.d_model), jnp.dtype(cfg.dtype))
    return specs


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N_active·D per train step (fwd+bwd), 2·N_active·D per inference
    token — the roofline's useful-compute numerator."""
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * tokens


def _jit_for_shape(model: Model, cfg: ModelConfig, shape: ShapeConfig, mesh,
                   precision: str = "float32", grad_accum_steps: int = 1):
    """Build (jitted_fn, example_args) for the step this shape exercises."""
    specs = input_specs(cfg, shape)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = param_shardings(mesh, params_shape)
    b_sh = batch_shardings(mesh, specs)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        opt_cfg = OptimizerConfig(kind="sgd")
        opt_init, train_step = make_lm_train_step(
            model, opt_cfg, schedule_fn(ScheduleConfig(kind="const")),
            policy=resolve_policy(precision, opt_cfg),
            grad_accum_steps=grad_accum_steps)
        opt_shape = jax.eval_shape(opt_init, params_shape)
        o_sh = param_shardings(mesh, opt_shape)
        fn = jax.jit(
            train_step,
            in_shardings=(p_sh, o_sh, b_sh, repl),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1))
        args = (params_shape, opt_shape, specs,
                jax.ShapeDtypeStruct((), jnp.int32))
        return fn, args

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            return model.prefill(
                params, batch["tokens"],
                vision_embeds=batch.get("vision_embeds"),
                frames=batch.get("frames"))
        fn = jax.jit(prefill_step, in_shardings=(p_sh, b_sh))
        return fn, (params_shape, specs)

    # decode
    cache_shape = jax.eval_shape(
        lambda: model.empty_cache(shape.global_batch, shape.seq_len))
    c_sh = cache_shardings(mesh, cache_shape, shape.global_batch)

    def decode_step(params, cache, token, pos):
        return model.decode(params, cache, token, pos)

    fn = jax.jit(decode_step,
                 in_shardings=(p_sh, c_sh, b_sh["tokens"], repl),
                 out_shardings=(None, c_sh),
                 donate_argnums=(1,))
    args = (params_shape, cache_shape, specs["tokens"],
            jax.ShapeDtypeStruct((), jnp.int32))
    return fn, args


def _terms_from_compiled(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.values())),
        "coll_by_kind": coll,
    }


def roofline_extrapolated(arch: str, shape: ShapeConfig, mesh,
                          cfg: ModelConfig, precision: str = "float32",
                          grad_accum_steps: int = 1) -> dict:
    """XLA's cost_analysis counts a scan body ONCE (trip count ignored), so
    the production scan-lowered program under-reports flops/bytes/collective
    bytes. We recover exact totals by lowering two small UNROLLED variants —
    tail + 1 unit and tail + 2 units — and extrapolating linearly:

        term(n_units) = t1 + (n_units - 1) * (t2 - t1)

    The delta (t2 - t1) is exactly one pattern-unit's contribution (incl.
    its per-unit gradient all-reduce share); t1 carries embed/head/tail.
    Validated against a full unroll in tests/test_dryrun.py."""
    import dataclasses as dc
    unit_len = len(Model(cfg).unit_kinds)
    tail = cfg.n_layers % unit_len
    n_units = cfg.n_layers // unit_len

    def probe(k_units: int) -> dict:
        vcfg = dc.replace(cfg, n_layers=k_units * unit_len + tail,
                          scan_layers=False)
        vmodel = Model(vcfg)
        # set_mesh here, not at the caller: logical_constraint() resolves
        # against the ambient mesh and silently no-ops without it — which
        # would probe an unconstrained (partial-sum-heavy) program.
        with set_mesh(mesh):
            fn, args = _jit_for_shape(vmodel, vcfg, shape, mesh,
                                      precision=precision,
                                      grad_accum_steps=grad_accum_steps)
            return _terms_from_compiled(fn.lower(*args).compile())

    if n_units <= 8:
        # cheap enough to lower the exact unrolled program
        t = probe(n_units)
        t["per_unit"] = {}
        return t

    # XLA's per-unit cost drifts linearly with depth (live-range growth),
    # so fit a + b·k + c·k² through k = 2, 4, 6 units (k=1 programs get
    # special-cased by XLA optimizations and poison the fit); validated to
    # <0.1% against full unrolls in tests/test_dryrun.py.
    t2, t4, t6 = probe(2), probe(4), probe(6)

    def fit(f2, f4, f6, n):
        c = ((f6 - f4) - (f4 - f2)) / 8.0
        b = (f4 - f2) / 2.0 - 6.0 * c
        a = f2 - 2.0 * b - 4.0 * c
        return a + b * n + c * n * n

    out = {key: fit(t2[key], t4[key], t6[key], n_units)
           for key in ("flops", "bytes", "coll")}
    kinds = set(t2["coll_by_kind"]) | set(t4["coll_by_kind"]) \
        | set(t6["coll_by_kind"])
    out["coll_by_kind"] = {
        k: fit(t2["coll_by_kind"].get(k, 0), t4["coll_by_kind"].get(k, 0),
               t6["coll_by_kind"].get(k, 0), n_units) for k in kinds}
    out["per_unit"] = {k: (t4[k] - t2[k]) / 2.0
                       for k in ("flops", "bytes", "coll")}
    return out


def run_one(arch: str, shape_name: str, mesh_kind: str,
            phase2: bool = False, n_workers: int = 8,
            precision: str = "float32", grad_accum_steps: int = 1,
            phase2_engine: str = "programs") -> dict:
    cfg = registry.get_config(arch)
    if precision not in ("float32", "", "f32", "fp32"):
        # thread the compute dtype through the model's per-matmul casts,
        # same as the LM adapter's training path
        import dataclasses as dc
        cfg = dc.replace(
            cfg, dtype=resolve_policy(precision).compute_dtype)
    shape = SHAPES[shape_name]
    if phase2_engine not in ("programs", "sharded"):
        raise ValueError(f"phase2_engine must be 'programs' or 'sharded', "
                         f"got {phase2_engine!r}")
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "phase2": phase2, "status": "ok",
           "precision": precision or "float32",
           "grad_accum_steps": grad_accum_steps}
    if phase2:
        rec["phase2_engine"] = phase2_engine
    if not shape_applicable(arch, cfg.family, shape):
        rec["status"] = "skipped"
        rec["reason"] = ("full-attention arch: long_500k requires "
                         "sub-quadratic attention (DESIGN.md §4)")
        return rec
    if phase2 and shape.kind != "train":
        rec["status"] = "skipped"
        rec["reason"] = "phase-2 ensemble applies to training only"
        return rec

    multi = mesh_kind == "multi"
    if phase2:
        mesh = make_worker_mesh(n_workers, multi_pod=multi)
    else:
        mesh = make_production_mesh(multi_pod=multi)
    rec["mesh_shape"] = dict(zip(mesh.axis_names,
                                 [int(mesh.shape[a]) for a in mesh.axis_names]))
    n_dev = mesh.devices.size
    model = Model(cfg)

    t0 = time.perf_counter()
    if phase2 and phase2_engine == "sharded":
        # one global sharded-jit program (the production engine lowering)
        with set_mesh(mesh):
            lowered, _ = _ensemble_sharded_lower(cfg, shape, mesh, n_workers)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
    else:
        if phase2:
            fn, args, block_mesh = _ensemble_jit(model, cfg, shape, mesh,
                                                 n_workers)
            ctx_mesh = block_mesh
        else:
            fn, args = _jit_for_shape(model, cfg, shape, mesh,
                                      precision=precision,
                                      grad_accum_steps=grad_accum_steps)
            ctx_mesh = mesh
        with set_mesh(ctx_mesh):
            lowered = fn.lower(*args)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()

    ma = compiled.memory_analysis()
    hlo = compiled.as_text()

    # Roofline terms: the production (scanned) compile above proves the
    # sharding + memory story; exact per-device flops/bytes/collectives come
    # from the unrolled 1-unit/2-unit extrapolation (scan bodies are counted
    # once by cost_analysis regardless of trip count).
    t3 = time.perf_counter()
    if phase2:
        extra = _terms_from_compiled(compiled)  # structure check only
    else:
        extra = roofline_extrapolated(arch, shape, mesh, cfg,
                                      precision=precision,
                                      grad_accum_steps=grad_accum_steps)
    t4 = time.perf_counter()

    flops_dev = extra["flops"]
    bytes_dev = extra["bytes"]
    coll_dev = extra["coll"]
    coll = {k: float(v) for k, v in extra["coll_by_kind"].items()}
    mf = model_flops(cfg, SHAPES[shape_name])

    rec.update({
        "n_devices": int(n_dev),
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "roofline_probe_s": round(t4 - t3, 2),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collectives": coll,
        "model_flops_total": mf,
        "useful_compute_ratio": (mf / (flops_dev * n_dev)
                                 if flops_dev else None),
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_dev / ICI_BW,
    })
    terms = {"compute": rec["compute_s"], "memory": rec["memory_s"],
             "collective": rec["collective_s"]}
    rec["bottleneck"] = max(terms, key=terms.get)
    if ma is not None:
        rec["memory_analysis"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
    if phase2:
        per_worker = n_dev // n_workers
        n_groups = assert_no_cross_worker_collectives(hlo, n_workers,
                                                      per_worker)
        rec["phase2_collective_groups_checked"] = n_groups
        rec["phase2_no_cross_worker_collectives"] = True
        rec["phase2_deployment"] = (
            f"one sharded-jit program, {n_workers} worker blocks x "
            f"{per_worker} chips"
            if phase2_engine == "sharded" else
            f"{n_workers} independent programs x {per_worker} chips")
    return rec


def _ensemble_sharded_lower(cfg: ModelConfig, shape: ShapeConfig, mesh,
                            n_workers: int, n_steps: int = 2):
    """Phase-2 lowered the way the PRODUCTION engine runs it: ONE
    program over the whole worker mesh — ``EpochRunner(engine="sharded")``,
    i.e. ``shard_map`` over ``worker`` of ``vmap(scan(step))`` with the
    carried TrainState pinned to ``ensemble_shardings``. The worker axis
    is manual, so the partitioner cannot place a collective across worker
    blocks; the downstream audit checks the compiled HLO all the same. The
    weekly CI audit runs it on internlm2-1.8b train_4k at 256 devices.

    Returns ``(lowered, n_steps)`` — a lowered (not compiled) chunk of
    ``n_steps`` scanned train steps over a tiny zero-token dataset (the
    audit is about program STRUCTURE; batch content never matters)."""
    from repro.core.adapters import LMAdapter
    from repro.data.pipeline import Loader
    from repro.train.loop import EpochRunner, TrainState
    from repro.train.precision import default_scale_state, stack_scale_state

    W = n_workers
    adapter = LMAdapter(cfg, OptimizerConfig(kind="sgd"))
    step_fn = adapter.make_train_step(
        schedule_fn(ScheduleConfig(kind="const")))
    # per-worker batch = global batch / W (paper: B2 = B1/W); dataset is
    # n_steps batches so the loader's epoch covers the lowered chunk
    B = max(shape.global_batch // W, 1)
    import numpy as np
    arrays = {"tokens": np.zeros((B * n_steps, shape.seq_len), np.int32),
              "labels": np.zeros((B * n_steps, shape.seq_len), np.int32)}
    loader = Loader(arrays, B)
    runner = EpochRunner(step_fn, loader, 0.9, ensemble=True,
                         mesh=mesh, engine="sharded")

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((W,) + s.shape, s.dtype), tree)

    bundle = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(adapter.init_opt, bundle)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    scale = jax.eval_shape(
        lambda: stack_scale_state(default_scale_state(), W))
    i32 = jnp.int32
    state = TrainState(
        bundle=stack(bundle), opt_state=stack(opt),
        step=jax.ShapeDtypeStruct((W,), i32),
        acc_ema=jax.ShapeDtypeStruct((W,), jnp.float32),
        phase=jax.ShapeDtypeStruct((W,), i32),
        rng=jax.ShapeDtypeStruct((W,) + key.shape, key.dtype),
        scale=scale)
    worker = jax.ShapeDtypeStruct((W,), i32)
    return runner.lower_chunk(state, worker, n_steps), n_steps


def _ensemble_jit(model: Model, cfg: ModelConfig, shape: ShapeConfig, mesh,
                  n_workers: int):
    """Phase-2 SWAP step, compiled the way it DEPLOYS: one independent
    program per worker block, exactly like the paper's Horovod phase 2 (W
    separate single-GPU processes). Cross-worker collectives are impossible
    by construction — each program only spans its own block's devices; the
    assert downstream re-verifies that every HLO replica group stays within
    one block. The one-program form is ``_ensemble_sharded_lower``."""
    opt_cfg = OptimizerConfig(kind="sgd")
    opt_init, train_step = make_lm_train_step(
        model, opt_cfg, schedule_fn(ScheduleConfig(kind="const")))
    specs = input_specs(cfg, shape)
    W = n_workers

    # worker block mesh: the first (data/W, model) block of the global mesh
    n_dev = mesh.devices.size
    block_size = n_dev // W
    model_par = mesh.shape["model"]
    block_devices = mesh.devices.reshape(-1)[:block_size].reshape(
        block_size // model_par, model_par)
    block_mesh = jax.sharding.Mesh(block_devices, ("data", "model"))

    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(opt_init, params_shape)
    # per-worker batch = shape's global batch / W (paper: B2 = B1/W)
    bs = {k: jax.ShapeDtypeStruct((v.shape[0] // W,) + v.shape[1:], v.dtype)
          for k, v in specs.items()}

    p_sh = param_shardings(block_mesh, params_shape)
    o_sh = param_shardings(block_mesh, opt_shape)
    b_sh = batch_shardings(block_mesh, bs)
    repl = NamedSharding(block_mesh, P())

    fn = jax.jit(train_step, in_shardings=(p_sh, o_sh, b_sh, repl),
                 out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
    return fn, (params_shape, opt_shape, bs,
                jax.ShapeDtypeStruct((), jnp.int32)), block_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=registry.ASSIGNED_ARCHS)
    ap.add_argument("--shape", nargs="*", default=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--phase2", action="store_true")
    ap.add_argument("--phase2-engine", default="programs",
                    choices=["programs", "sharded"],
                    help="phase-2 lowering to audit: per-worker-block "
                         "independent programs (deployment-shaped, safe "
                         "for every arch) or the production sharded-jit "
                         "engine (one global program, "
                         "shard_map over worker with pinned shardings)")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--precision", default="float32",
                    choices=["float32", "bfloat16"],
                    help="train-shape numerics: bf16 compute + f32 master "
                         "weights (f16's dynamic scaling is stateful — "
                         "engine-only, not AOT-lowerable here)")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="train-shape microbatch accumulation factor")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    results = {}
    if args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    for arch in args.arch:
        for shape in args.shape:
            for mesh_kind in meshes:
                key = f"{arch}|{shape}|{mesh_kind}" + \
                    ("|phase2" if args.phase2 else "") + \
                    ("|sharded" if args.phase2
                     and args.phase2_engine == "sharded" else "") + \
                    (f"|{args.precision}" if args.precision != "float32"
                     else "") + \
                    (f"|accum{args.grad_accum}" if args.grad_accum > 1
                     else "")
                if args.skip_existing and results.get(key, {}).get("status") == "ok":
                    print(f"[skip] {key}")
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    rec = run_one(arch, shape, mesh_kind, phase2=args.phase2,
                                  n_workers=args.workers,
                                  precision=args.precision,
                                  grad_accum_steps=args.grad_accum,
                                  phase2_engine=args.phase2_engine)
                except (ValueError, TypeError, KeyError,
                        NotImplementedError, RuntimeError) as e:
                    # the failure modes a sweep tolerates and records:
                    # config/shape validation (ValueError/TypeError/
                    # KeyError), arch paths a lowering doesn't implement
                    # (NotImplementedError), and XLA lowering/compile
                    # failures (XlaRuntimeError subclasses RuntimeError).
                    # Anything else — KeyboardInterrupt, MemoryError, a
                    # genuine bug — aborts the sweep instead of being
                    # silently filed as one more per-config error record.
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                results[key] = rec
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f" compile={rec['compile_s']}s "
                             f"bottleneck={rec['bottleneck']}")
                elif status == "error":
                    extra = f" {rec['error']}"
                print(f"[done] {key}: {status}{extra}", flush=True)

    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    print(f"summary: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
