"""SWAP training launcher.

Runs the full three-phase SWAP schedule on an LM architecture (smoke-sized
by default so it executes on this host; full configs are exercised via the
dry-run). The same controller drives the TPU path: phase 1 on the
('data','model') mesh, phase 2 on ('worker','data','model').

  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      [--full] [--workers 4] [--phase1-steps 150] [--phase2-steps 60] \
      [--stop-acc 0.6] [--optimizer sgd|lars|adamw] [--save out.ckpt] \
      [--phase1-precision bfloat16] [--grad-accum 4] \
      [--checkpoint-dir ckpts/ --checkpoint-every 50] [--resume] \
      [--mesh worker:4,data:2] [--elastic-deadline 30] [--lost-workers 3]

Large phase-1 batches: --phase1-precision bfloat16 computes the forward/
backward in bf16 with f32 master weights; --grad-accum k runs each global
batch as k sequential microbatches (same effective batch, ~k× less
activation memory). See docs/training.md §Precision & accumulation.

Long jobs: pass --checkpoint-dir/--checkpoint-every for periodic TrainState
snapshots (epoch-aligned), then relaunch with --resume to continue
bit-exactly from the newest snapshot — mid-phase-1 or mid-phase-2.

Distribution: the --mesh/--workers/--phase2-engine/--elastic-*/
--coordinator flag group is the unified ``repro.dist.DistConfig`` surface
(``--dist-config file.json`` loads one, ``--dump-dist-config`` records the
resolved config for exact replay); multi-host launches pass
--coordinator/--num-processes/--process-id per host and each host then
loads only its shard of every phase-1 batch. --lost-workers simulates
worker loss for the elastic phase-3 averaging drill (docs/training.md
§Elastic averaging).

Resilience (docs/resilience.md): --heartbeat-dir switches elastic
arrivals from the simulated --lost-workers surface to REAL per-worker
heartbeat beacons (this in-process launcher beats every live worker at
each phase-2 chunk boundary; --lost-workers now marks workers that never
beat, so the monitor — not a hand-fed timestamp — declares them dead).
--supervise N wraps both phases in a PhaseSupervisor with an N-retry
budget: divergence rolls back to the last verified checkpoint, and a
worker whose beacon goes stale mid-phase-2 is dropped and the phase
resumes with the survivors.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.checkpoint.io import save_pytree
from repro.configs import registry
from repro.configs.base import (OptimizerConfig, PhaseConfig, ScheduleConfig,
                                SWAPConfig)
from repro.core.adapters import LMAdapter
from repro.core.swap import SWAP
from repro.data.pipeline import Loader, make_markov_lm
from repro.dist.config import DistConfig, add_dist_args
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b",
                    choices=registry.list_archs())
    ap.add_argument("--full", action="store_true",
                    help="use the full (assigned) config instead of smoke")
    add_dist_args(ap)
    ap.add_argument("--lost-workers", default="",
                    help="comma-separated worker indices that never report "
                         "in phase 3 (elastic-averaging drill; needs "
                         "--elastic-deadline > 0)")
    ap.add_argument("--phase1-steps", type=int, default=150)
    ap.add_argument("--phase2-steps", type=int, default=60)
    ap.add_argument("--phase1-batch", type=int, default=256)
    ap.add_argument("--phase2-batch", type=int, default=32)
    ap.add_argument("--stop-acc", type=float, default=0.55)
    ap.add_argument("--peak-lr", type=float, default=0.5)
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "lars", "adamw"])
    ap.add_argument("--phase1-precision", default="float32",
                    choices=["float32", "bfloat16", "float16"],
                    help="phase-1 PrecisionPolicy preset (bf16 compute + "
                         "f32 master weights; f16 adds dynamic loss "
                         "scaling with inf/nan step skipping)")
    ap.add_argument("--phase2-precision", default="float32",
                    choices=["float32", "bfloat16", "float16"],
                    help="phase-2 preset; keep f32 (default) to leave the "
                         "averaging/generalization claims untouched")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="phase-1 microbatch accumulation: split each "
                         "global batch into this many sequential "
                         "microbatches (identical effective batch size)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default="")
    ap.add_argument("--json-out", default="")
    ap.add_argument("--checkpoint-dir", default="",
                    help="directory for periodic TrainState snapshots")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="snapshot cadence in steps (epoch-aligned); 0 = off")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest snapshot in "
                         "--checkpoint-dir (bit-exact, mid-phase)")
    ap.add_argument("--supervise", type=int, default=0, metavar="RETRIES",
                    help="wrap both phases in a resilience.PhaseSupervisor "
                         "with this retry budget (0 = unsupervised); "
                         "divergence rolls back to the last verified "
                         "checkpoint, stale-heartbeat workers are dropped")
    args = ap.parse_args()
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume requires --checkpoint-dir")
    dist = DistConfig.from_args(args, n_workers_default=4)
    # multi-host: join the jax.distributed cluster BEFORE any device query
    dist.initialize()
    enable_compile_cache()
    if args.dump_dist_config:
        dist.to_json(args.dump_dist_config)
        print(f"wrote resolved DistConfig to {args.dump_dist_config}")
    lost = [int(w) for w in args.lost_workers.split(",") if w.strip()]
    if lost and not dist.elastic:
        raise SystemExit("--lost-workers needs --elastic-deadline > 0 "
                         "(a strict phase-3 barrier cannot drop workers)")
    worker_arrivals = None
    monitor = None
    phase2_hooks = []
    if dist.heartbeats:
        # real liveness replaces the simulated-arrival path: every worker
        # this launcher drives beats at each phase-2 chunk boundary, a
        # --lost-workers worker simply never beats, and phase 3 reads
        # arrival lateness off beacon staleness via the monitor
        from repro.dist.heartbeat import (HeartbeatMonitor, HeartbeatWriter,
                                          beat_on_chunk)
        writers = [HeartbeatWriter(dist.heartbeat_dir, w,
                                   interval_s=dist.heartbeat_interval_s)
                   for w in range(dist.n_workers) if w not in lost]
        for wtr in writers:
            wtr.beat()                       # everyone alive at launch
        monitor = HeartbeatMonitor(dist.heartbeat_dir, dist.n_workers,
                                   timeout_s=dist.resolved_heartbeat_timeout)
        phase2_hooks.append(beat_on_chunk(writers))
    elif lost:
        worker_arrivals = [float("inf") if w in lost else 0.0
                           for w in range(dist.n_workers)]
    supervisor = None
    if args.supervise > 0:
        from repro.resilience import PhaseSupervisor, SupervisorConfig
        supervisor = PhaseSupervisor(
            SupervisorConfig(max_retries=args.supervise), monitor=monitor)

    cfg = (registry.get_config(args.arch) if args.full
           else registry.get_smoke_config(args.arch))
    if cfg.family == "cnn":
        raise SystemExit("use benchmarks/table1_cifar10.py for the CNN")

    data = make_markov_lm(args.seed, vocab=min(cfg.vocab_size, 512),
                          n_train=4096, n_test=1024, seq_len=args.seq_len)
    train = {"tokens": data["train_tokens"] % cfg.vocab_size,
             "labels": data["train_labels"] % cfg.vocab_size}
    test_loader = Loader({"tokens": data["test_tokens"] % cfg.vocab_size,
                          "labels": data["test_labels"] % cfg.vocab_size},
                         256)

    lr_small = args.peak_lr * args.phase2_batch / args.phase1_batch
    opt = OptimizerConfig(kind=args.optimizer,
                          weight_decay=5e-4 if args.optimizer != "adamw"
                          else 0.01)
    if args.optimizer == "adamw":
        args.peak_lr, lr_small = 3e-3, 1e-3
    adapter = LMAdapter(cfg, opt)
    swap_cfg = SWAPConfig(
        n_workers=dist.n_workers,
        phase1=PhaseConfig(
            batch_size=args.phase1_batch, max_steps=args.phase1_steps,
            stop_accuracy=args.stop_acc,
            precision=args.phase1_precision,
            grad_accum_steps=args.grad_accum,
            schedule=ScheduleConfig(kind="warmup_linear", peak_lr=args.peak_lr,
                                    warmup_steps=args.phase1_steps // 5,
                                    total_steps=args.phase1_steps)),
        phase2=PhaseConfig(
            batch_size=args.phase2_batch, max_steps=args.phase2_steps,
            precision=args.phase2_precision,
            schedule=ScheduleConfig(kind="warmup_linear", peak_lr=lr_small,
                                    warmup_steps=0,
                                    total_steps=args.phase2_steps)),
        seed=args.seed, checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every)

    n_params = cfg.param_count()
    swap = SWAP(adapter, swap_cfg, train, test_loader, dist=dist,
                supervisor=supervisor)
    print(f"arch={cfg.name} family={cfg.family} params={n_params/1e6:.1f}M "
          f"workers={dist.n_workers} "
          f"engine={dist.resolved_engine(swap.mesh)}"
          + (f" mesh={'x'.join(map(str, dist.mesh_shape))}"
             if dist.mesh_shape else ""))
    t0 = time.time()
    res = swap.run(jax.random.PRNGKey(args.seed), resume=args.resume,
                   worker_arrivals=worker_arrivals,
                   phase2_hooks=phase2_hooks, heartbeats=monitor)
    out = {k: v for k, v in res.items()
           if isinstance(v, (int, float, list)) and k != "phase1_log"}
    out["wall_s"] = time.time() - t0
    print(json.dumps({k: v for k, v in out.items()
                      if not isinstance(v, list)}, indent=1))
    print(f"worker accs: {['%.4f' % a for a in res['worker_test_accs']]}")
    if dist.elastic:
        print(f"elastic: {res['phase2_live_workers']}/{dist.n_workers} "
              f"workers in the average, live mask "
              f"{res['worker_live_mask']}")
    for ev in res.get("recovery_events", []):
        print(f"recovery: {ev['kind']} in {ev['tag']} (attempt "
              f"{ev['attempt']}) -> resumed from {ev['restored_from']} at "
              f"step {ev['restored_step']}")
    print(f"SWAP: before avg {res['before_avg_test_acc']:.4f} -> "
          f"after avg {res['after_avg_test_acc']:.4f}")
    if args.save:
        save_pytree(args.save, res["final_bundle"]["params"])
        print(f"saved averaged model to {args.save}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
