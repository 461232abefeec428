#!/usr/bin/env python3
"""Chip benchmark of the SWAP system: one cell, one process.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \
      --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and its files by name (see
``chipbench/harness.py``), checks that it is on a TPU with the chips the
cell needs (there is no fallback), runs the cell's job, and prints one JSON
object as the last line of standard output. With ``--trace 0`` its metrics
are the cell's end-to-end metrics; with ``--trace 1`` the window is traced
and the metrics are the cell's per-layer metrics, read from the trace and
the job's counters by ``metrics/<name>.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))


def per_layer(cell, out: dict, reduced) -> dict:
    from chipbench import peaks
    ctx = {"facts": out.get("facts", {}), "counters": out.get("counters", {}),
           "trace": reduced, "chips": cell.chips,
           "peaks": peaks.peaks(out["device_kind"])}
    metrics = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            print(f"[metrics] {m['name']}: nothing to read in this run, "
                  f"left out", file=sys.stderr, flush=True)
    return metrics


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="copy the raw profiler trace to this directory")
    args = ap.parse_args()

    from chipbench import harness
    cell = harness.find_cell(args.workload)
    import repro  # noqa: F401  (the system under test must be present)
    devices = harness.require_chips(cell.chips)
    cache_dir = harness.enable_compile_cache()
    cache = harness.CacheLog()
    compiles = harness.CompileWatch()

    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_START, keep_trace=args.keep_trace)
    out = cell.job().run(run)
    out["device_kind"] = devices[0].device_kind
    print(f"[cache] {cache_dir}: {cache.counts()}, missed "
          f"{sorted(cache.names['misses'])}; lowered or compiled in "
          f"the window: {compiles.inside(run.window_bounds)}",
          file=sys.stderr, flush=True)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": harness.correct(out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    if args.trace:
        from chipbench import trace
        path = run.trace_file()
        try:
            reduced = trace.reduce(path, len(devices))
        finally:
            run.drop_trace()
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["metrics"] = per_layer(cell, out, reduced)
        result["device"] = device
        result["breakdown"] = reduced["breakdown"]
    else:
        result["metrics"] = {
            m["name"]: {"value": out["metrics"][m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
        result["device"] = device
    harness.emit(result, out["checks"])


if __name__ == "__main__":
    main()
