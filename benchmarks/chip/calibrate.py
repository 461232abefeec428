#!/usr/bin/env python3
"""Readings that set a cell's limits, made once on the chip, not in the
benchmark's runs. For each seed it prints the compared numbers of:

  training cells: the control (the reference computed in float8 in the
  program's place) and the planted fault "half of the batch left out, the
  mean taken over the rest" (the reference on half of each step's rows),
  both against the float32 reference;
  serving cells: a whole run of the cell whose reference pass also reads
  the control (the gap of the token the float8 reference puts first).

  python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
      [--seconds 20]
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))


def training(run) -> dict:
    import jax
    from chipbench import traincheck, trainrun
    from repro.data.pipeline import Loader

    t = run.traffic
    adapter, data = trainrun.adapter_and_data(run)
    loader = Loader(data, t["batch"], seed=t["corpus_seed"])
    shapes = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))["params"]
    batches = [loader.batch(s, trainrun.stream(run.seed))
               for s in range(trainrun.REF_STEPS)]
    half = [{k: v[:v.shape[0] // 2] for k, v in b.items()
             if k in ("tokens", "labels")} for b in batches]
    ref = run.cell.reference()
    f32 = traincheck.reference_steps(ref, run.config, shapes, run.seed,
                                     batches, t)
    out = {}
    for name, b, mode in (("control_fp8", batches, "fp8"),
                          ("fault_half_batch", half, "f32")):
        got = traincheck.reference_steps(ref, run.config, shapes, run.seed,
                                         b, t, mode=mode)
        every = dict.fromkeys(("loss_gap", "first_grad_gap", "delta_gap"))
        out[name] = {k: v["value"] for k, v in traincheck.compare(
            got, f32, every).items()}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()

    from chipbench import harness
    cell = harness.find_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        run = harness.Run(cell, seed, args.seconds, False, devices,
                          time.perf_counter())
        if cell.traffic["job"].startswith("serve"):
            out = cell.job().run(run, control=True)
            line = {"program": out["gaps"]["served_gap"],
                    "control_fp8": out["gaps"]["control_gap"],
                    "summary": out["summary"]}
        else:
            line = training(run)
        print(json.dumps({"seed": seed, **line}), flush=True)


if __name__ == "__main__":
    main()
