#!/usr/bin/env python3
"""Find the serving knee once, on the chip: run a serving cell's window at
each of several fixed rates in one process and print, per rate, the
completed requests per second, the TTFT and TPOT tails and whether the
queue grew through the window (TTFT of the last quarter of requests over
the first quarter).

  python3 benchmarks/chip/sweep.py --workload <serving cell> \
      --rates 2,4,6,8 --seconds 20 --seed 1
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
DRAIN_SECONDS = 20    # past a rate's window: a backlog shows as failures
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import numpy as np
    from chipbench import harness
    cell = harness.find_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate=rate,
                            drain_seconds=DRAIN_SECONDS)
        run = harness.Run(cell, args.seed, args.seconds, False, devices,
                          time.perf_counter())
        out = cell.job().run(run)
        s = out["summary"]
        q = max(len(out["ttft_s"]) // 4, 1)
        grow = (float(np.median(out["ttft_s"][-q:]))
                / max(float(np.median(out["ttft_s"][:q])), 1e-9))
        print(json.dumps({"rate": rate, "done_per_s": out["done_per_s"],
                          "ttft_p95_ms": s["ttft_p95_ms"],
                          "ttft_p50_ms": s["ttft_p50_ms"],
                          "tpot_p95_ms": s["tpot_p95_ms"],
                          "failed": s["failed"], "n": s["n"],
                          "ttft_last_over_first_quarter": grow,
                          "checks": out["checks"]}), flush=True)


if __name__ == "__main__":
    main()
