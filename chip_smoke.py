#!/usr/bin/env python3
"""SWAP train -> average -> serve, once, on a TPU, through the normal entry
points: ``SGDRun`` and ``EpochRunner`` for phases 1 and 2,
``average_stacked`` and ``StreamingAverage`` for phase 3,
``CompiledServingEngine`` for serving.

  python chip_smoke.py              # one chip
  python chip_smoke.py --four-chip  # phase 2 only, one worker per chip

The model is internlm2-1.8b at its published widths (d_model 2048, 16
query / 8 KV heads of 128, d_ff 8192, vocab 92544) cut to ``N_LAYERS``
layers, with random weights and Markov-chain data made from ``--seed``.

One chip: kernel dispatch check, phase 1, phase 2 with W=2 workers on the
plain vmap engine, the phase-3 average with the swa_avg kernel checked
bitwise against the reference fold, a flash-vs-reference logits check, and
four requests served from the published average. ``--four-chip``: phase 2
with W=4 on the sharded engine over a ``worker:4`` mesh, compared with the
plain vmap engine run one worker at a time on the chip that holds that
worker, with the no-cross-worker-collective audit and per-device memory.
Every check raises, and there is no CPU fallback. The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import logging
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "internlm2-1.8b"
# Depth cut. Phase 2 holds W=2 stacked copies of params and momentum, their
# grads and the activations of 2 x 2 x 512 tokens; the 379M embedding and
# head parameters dominate. Each compile line prints the program's
# argument + temporary bytes as the compiler reckons them.
N_LAYERS = 2
SEQ = 512
PHASE1_BATCH = 4          # global batch, in sequences of SEQ tokens
PHASE2_BATCH = 2          # per worker
CHUNK = 4                 # steps per compiled chunk; two chunks per phase
N_TRAIN, N_TEST = 64, 16
# Flash (Pallas) against reference attention: relative L2 error of the
# full-sequence logits at bf16 compute. bf16 rounds at 2^-8 ~ 4e-3; a wrong
# mask, tile or normalisation is off by order 1.
LOGITS_REL_TOL = 2e-2
# The four-chip engines run the same per-worker math: final params differ
# by under this fraction of the distance each worker travelled. A worker
# fed another's batches, or a cross-worker reduction, is off by order 1.
ENGINE_REL_TOL = 1e-3
PROMPT_LENS = (128, 256, 384, 512)
NEW_TOKENS = 32


def require(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def check_device(n_chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devices[0].platform!r}")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips, found "
                 f"{len(devices)}")
    return devices


def check_kernels() -> None:
    from repro.kernels import dispatch
    for kernel in ("flash_attention", "ssd", "swa_avg"):
        d = dispatch.resolve("auto", kernel=kernel)
        require(d.impl == "pallas" and d.variant == "mosaic"
                and not d.interpret,
                f"{kernel}: 'auto' resolved to {d} on the TPU")
    print("[kernels] auto -> pallas (mosaic, compiled) for flash_attention, "
          "ssd, swa_avg")


def model_config():
    from repro.configs import registry
    full = registry.get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=N_LAYERS)
    print(f"[config] {ARCH}: d_model {cfg.d_model}, heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads} x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; depth cut {full.n_layers} -> {cfg.n_layers} "
          f"layers, {cfg.param_count() / 1e6:.1f}M params")
    return cfg


def make_data(seed: int):
    from repro.data.pipeline import make_markov_lm
    data = make_markov_lm(seed, vocab=512, n_train=N_TRAIN, n_test=N_TEST,
                          seq_len=SEQ)
    train = {"tokens": data["train_tokens"], "labels": data["train_labels"]}
    return train, data["test_tokens"]


def schedule(peak_lr: float):
    from repro.configs.base import ScheduleConfig
    return ScheduleConfig(kind="warmup_linear", peak_lr=peak_lr,
                          warmup_steps=CHUNK, total_steps=4 * CHUNK)


def stat(device, key: str) -> int:
    return (device.memory_stats() or {}).get(key, 0)


def memory(device) -> str:
    return (f"in use {gib(stat(device, 'bytes_in_use'))}, peak "
            f"{gib(stat(device, 'peak_bytes_in_use'))}")


def run_chunks(runner, state, worker, label: str):
    """AOT-compile one chunk (timed; its HLO is returned for audits), then
    run two chunks through ``EpochRunner.run_chunk``: the first loads the
    program, the second is the steady step time. Losses must be finite."""
    import jax
    import numpy as np

    t = time.perf_counter()
    compiled = runner.lower_chunk(state, worker, CHUNK).compile()
    mem = compiled.memory_analysis()
    limit = stat(jax.devices()[0], "bytes_limit")
    print(f"[{label}] compile {time.perf_counter() - t:.1f} s; program "
          f"args {gib(mem.argument_size_in_bytes)} + temp "
          f"{gib(mem.temp_size_in_bytes)} = "
          f"{gib(mem.argument_size_in_bytes + mem.temp_size_in_bytes)} "
          f"reckoned, of a device limit of {gib(limit)}")
    times, losses = [], []
    for _ in range(2):
        t = time.perf_counter()
        state, metrics = runner.run_chunk(state, worker, CHUNK)
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t)
        losses.append(np.asarray(metrics["loss"]))
    loss = np.concatenate(losses, axis=-1)
    require(np.isfinite(loss).all(), f"{label}: non-finite loss {loss}")
    print(f"[{label}] {2 * CHUNK} steps: first chunk {times[0]:.2f} s, "
          f"steady {times[1] / CHUNK * 1e3:.1f} ms/step; loss "
          f"{np.array2string(loss, precision=3)}")
    return state, compiled.as_text()


def phase1(adapter, train, key, seed: int):
    import jax
    from repro.configs.base import PhaseConfig
    from repro.core.swap import SGDRun

    p1 = SGDRun(adapter, PhaseConfig(batch_size=PHASE1_BATCH,
                                     max_steps=2 * CHUNK,
                                     schedule=schedule(0.05)),
                train, seed=seed)
    state, hlo = run_chunks(p1.runner, p1.init_state(adapter.init(key)), 0,
                            "phase1")
    require("tpu_custom_call" in hlo,
            "phase1: no tpu_custom_call in the train-step HLO, so the "
            "Pallas kernels did not run")
    print("[phase1] train-step HLO contains tpu_custom_call")
    print(f"[phase1] device memory {memory(jax.devices()[0])}")
    return state.bundle


def stacked_state(adapter, bundle, n_workers: int, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.train.loop import stack_train_state

    stacked = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n_workers,) + a.shape), bundle)
    return stack_train_state(stacked, jax.vmap(adapter.init_opt)(stacked),
                             n_workers, seed=seed)


def phase2_runner(adapter, train, seed: int, **engine):
    from repro.core.schedules import schedule_fn
    from repro.data.pipeline import Loader
    from repro.train.loop import EpochRunner

    return EpochRunner(adapter.make_train_step(schedule_fn(schedule(0.02))),
                       Loader(train, PHASE2_BATCH, seed=seed + 1), 0.9,
                       ensemble=True, **engine)


def phase2(adapter, train, state, seed: int):
    """``state`` is donated to the first chunk, so nothing else keeps its
    buffers alive while phase 2 runs."""
    import jax.numpy as jnp
    from repro.dist.config import DistConfig

    W = state.step.shape[0]
    engine = DistConfig(n_workers=W).resolved_engine()
    require(engine == "vmap", f"phase2: one chip resolved engine {engine}")
    runner = phase2_runner(adapter, train, seed, engine=engine)
    state, _ = run_chunks(runner, state, jnp.arange(W, dtype=jnp.int32),
                          f"phase2 W={W} {engine}")
    return state.bundle["params"]


def phase3(stacked):
    import jax
    import jax.numpy as jnp
    from repro.core.averaging import StreamingAverage, average_stacked

    t = time.perf_counter()
    avg = jax.block_until_ready(average_stacked(stacked))
    print(f"[phase3] average_stacked {time.perf_counter() - t:.2f} s")
    W = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    folds = {}
    for impl in ("auto", "reference"):
        acc = StreamingAverage(impl=impl)
        t = time.perf_counter()
        for w in range(W):
            acc.add(jax.tree_util.tree_map(lambda a: a[w], stacked))
        folds[impl] = jax.block_until_ready(acc.value())
        print(f"[phase3] StreamingAverage({impl!r}) fold of {W} workers "
              f"{time.perf_counter() - t:.2f} s (first call compiles)")
    same = jax.tree_util.tree_map(jnp.array_equal, folds["auto"],
                                  folds["reference"])
    require(all(bool(x) for x in jax.tree_util.tree_leaves(same)),
            "phase3: swa_avg Pallas fold differs from the reference fold")
    gap = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(folds["auto"]),
        jax.tree_util.tree_leaves(avg)))
    require(gap <= 1e-6, f"phase3: streaming fold vs mean gap {gap}")
    print(f"[phase3] swa_avg Pallas fold == reference fold bitwise; "
          f"|fold - mean| <= {gap:.2e}")
    return avg


def check_flash_logits(cfg, params, tokens) -> None:
    import jax
    import jax.numpy as jnp
    from repro.models.model import Model

    logits = {}
    for impl in ("auto", "reference"):
        model = Model(dataclasses.replace(cfg, attention_impl=impl))
        fwd = jax.jit(lambda p, t, m=model: m.apply(p, t)[0]
                      .astype(jnp.float32))
        logits[impl] = fwd(params, tokens)
    got, want = logits["auto"], logits["reference"]
    require(bool(jnp.all(jnp.isfinite(got))), "flash logits not finite")
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    require(rel <= LOGITS_REL_TOL,
            f"flash vs reference logits: relative L2 {rel:.3e} > "
            f"{LOGITS_REL_TOL}")
    print(f"[flash] logits {tuple(got.shape)}: Pallas flash vs reference "
          f"relative L2 {rel:.3e} (tolerance {LOGITS_REL_TOL})")


def serve(cfg, first_params, avg, test_tokens) -> None:
    import jax.numpy as jnp
    from repro.models.model import Model
    from repro.serve.compiled import CompiledServingEngine
    from repro.serve.engine import Request

    engine = CompiledServingEngine(
        Model(cfg), first_params, max_batch=len(PROMPT_LENS),
        max_seq=max(PROMPT_LENS) + 2 * NEW_TOKENS, decode_block=8,
        prefill_buckets=(128, 256, 512))
    t = time.perf_counter()
    engine.warmup()
    print(f"[serve] warmup compile {time.perf_counter() - t:.1f} s, "
          f"buckets {engine.buckets}, kv {engine.kv_layout}")
    require(engine.publish(avg) is True and engine.generation == 1,
            "serve: publishing the average did not swap it in")
    reqs = [Request(rid=i, prompt=jnp.asarray(test_tokens[i, :n]),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]
    t = time.perf_counter()
    engine.run(reqs)
    wall = time.perf_counter() - t
    for r in reqs:
        require(r.done and not r.rejected
                and len(r.generated) == NEW_TOKENS and r.generation == 1,
                f"serve: request {r.rid} done={r.done} rejected="
                f"{r.rejected} tokens={len(r.generated)} generation="
                f"{r.generation}")
    st = engine.stats
    require(st["decode_transfers"] == st["decode_calls"],
            f"serve: {st['decode_transfers']} transfers for "
            f"{st['decode_calls']} decode calls")
    print(f"[serve] {len(reqs)} requests (prompts {PROMPT_LENS}, "
          f"{NEW_TOKENS} new tokens each) on generation {engine.generation} "
          f"in {wall:.2f} s; decode_calls {st['decode_calls']} == "
          f"decode_transfers {st['decode_transfers']}")


def one_chip(devices, seed: int) -> None:
    import jax
    from repro.configs.base import OptimizerConfig
    from repro.core.adapters import LMAdapter

    check_kernels()
    cfg = model_config()
    adapter = LMAdapter(cfg, OptimizerConfig(kind="sgd"))
    train, test_tokens = make_data(seed)
    bundle = phase1(adapter, train, jax.random.PRNGKey(seed), seed)
    state = stacked_state(adapter, bundle, 2, seed + 2)
    del bundle
    stacked = phase2(adapter, train, state, seed)
    print(f"[phase2] device memory {memory(devices[0])}")
    avg = phase3(stacked)
    first = jax.tree_util.tree_map(lambda a: a[0], stacked)
    del stacked
    check_flash_logits(cfg, avg, test_tokens[:2])
    serve(cfg, first, avg, test_tokens)
    print(f"[serve] device memory {memory(devices[0])}")


def four_chip(devices, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs.base import OptimizerConfig
    from repro.core.adapters import LMAdapter
    from repro.dist.config import DistConfig
    from repro.dist.sharding import (assert_no_cross_worker_collectives,
                                     ensemble_shardings)

    W = 4
    dist = DistConfig(mesh_shape=(W,), mesh_axes=("worker",), n_workers=W)
    mesh = dist.make_mesh()
    engine = dist.resolved_engine(mesh)
    require(engine == "sharded", f"worker:{W} mesh resolved engine {engine}")
    cfg = model_config()
    adapter = LMAdapter(cfg, OptimizerConfig(kind="sgd"))
    train, _ = make_data(seed)
    key = jax.random.PRNGKey(seed)

    def init(key):
        return stacked_state(adapter, adapter.init(key), W, seed + 2)

    shardings = ensemble_shardings(mesh, jax.eval_shape(init, key))
    init = jax.jit(init, out_shardings=shardings)
    workers = jax.device_put(jnp.arange(W, dtype=jnp.int32),
                             ensemble_shardings(mesh, jnp.arange(W)))

    start = init(key)
    used = [stat(d, "bytes_in_use") for d in devices[:W]]
    total = sum(x.nbytes for x in jax.tree_util.tree_leaves(start))
    print(f"[4chip] ensemble state {gib(total)}; bytes in use per device "
          f"{[gib(u) for u in used]}")
    require(max(used) < 0.5 * total,
            "4chip: the ensemble is not spread over the devices")
    # ``start`` is kept (not donated): the comparison below starts from it
    sharded = phase2_runner(adapter, train, seed, mesh=mesh, engine=engine,
                            donate=False)
    out_s, hlo = run_chunks(sharded, start, workers, f"4chip {engine} W={W}")
    n = assert_no_cross_worker_collectives(hlo, n_workers=W,
                                           devices_per_worker=1)
    print(f"[4chip] no cross-worker collectives ({n} replica groups "
          f"checked)")
    print(f"[4chip] peak bytes per device "
          f"{[gib(stat(d, 'peak_bytes_in_use')) for d in devices]}")
    final = out_s.bundle["params"]
    del out_s

    # The plain vmap engine cannot take the mesh-placed ensemble: XLA does
    # not partition a Mosaic kernel. It runs each worker alone instead, on
    # the chip that holds that worker's block and on the same batches, and
    # must land where the sharded engine did.
    def block(tree, device):
        return jax.tree_util.tree_map(
            lambda a: next(s.data for s in a.addressable_shards
                           if s.device == device), tree)

    plain = phase2_runner(adapter, train, seed, donate=False)
    rels, bitwise = [], True
    for w, device in enumerate(mesh.devices.flat):
        out, _ = run_chunks(plain, block(start, device),
                            jax.device_put(jnp.asarray([w], jnp.int32),
                                           device),
                            f"4chip vmap worker {w} alone")
        gap = moved = 0.0
        for s_, v_, v0 in zip(*(jax.tree_util.tree_leaves(t) for t in (
                block(final, device), out.bundle["params"],
                block(start.bundle["params"], device)))):
            gap += float(jnp.sum(jnp.square(s_ - v_)))
            moved += float(jnp.sum(jnp.square(v_ - v0)))
            bitwise = bitwise and bool(jnp.array_equal(s_, v_))
        del out
        rels.append((gap / moved) ** 0.5)
    require(max(rels) <= ENGINE_REL_TOL,
            f"4chip: sharded vs vmap per-worker relative gap {rels}")
    print(f"[4chip] sharded vs vmap final params: per-worker |diff|/|moved| "
          f"{[f'{x:.2e}' for x in rels]} (tolerance {ENGINE_REL_TOL}), "
          f"bitwise equal: {bitwise}")


class CacheLog(logging.Handler):
    """Names the programs JAX's persistent cache hit, missed, and did not
    write back (JAX skips programs that compiled in under
    ``jax_persistent_cache_min_compile_time_secs``). These are debug records
    of ``jax._src.compiler``; its warnings still reach the ``jax`` logger."""

    KINDS = {"Persistent compilation cache hit": "hits",
             "PERSISTENT COMPILATION CACHE MISS": "misses",
             "Not writing persistent cache entry for": "not written"}

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = {k: collections.Counter() for k in self.KINDS.values()}
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(self)

    def emit(self, record):
        if record.levelno >= logging.WARNING:
            logging.getLogger("jax").handle(record)
        for prefix, kind in self.KINDS.items():
            if str(record.msg).startswith(prefix):
                self.names[kind][record.args[0]] += 1

    def report(self) -> str:
        return "; ".join(
            f"{sum(c.values())} {kind}"
            + (": " + ", ".join(f"{n} x{k}" if k > 1 else n
                                for n, k in sorted(c.items())) if c else "")
            for kind, c in self.names.items())


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only phase 2 with one worker on each of four "
                         "chips, against the plain vmap engine")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devices = check_device(4 if args.four_chip else 1)

    from repro.launch.compile_cache import enable_compile_cache

    cache = CacheLog()
    cache_dir = enable_compile_cache()
    t = time.perf_counter()
    (four_chip if args.four_chip else one_chip)(devices, args.seed)
    print(f"[cache] {cache_dir}: {cache.report()}; total "
          f"{time.perf_counter() - t:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
