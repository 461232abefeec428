#!/usr/bin/env python3
"""Write a recorded chip trace as a small test fixture.

  python3 benchmarks/chip/tests/make_trace_fixture.py <run.xplane.pb> \
      <out.json.gz>

Keeps what ``chipbench.trace.reduce_profile`` reads: each plane's name, its
lines' names and their events (name, start, duration and the stats the
reduction looks at), only events that overlap the ``chipbench.window``
span, and at most ``MAX_EVENTS`` per line (the longest, which keeps the
kernels and the window span); the device's ``Async XLA Ops`` line, which
the reduction does not read, is left out. ``test_chipbench_trace.py``
loads it back from ``tests/fixtures/<workload>.json.gz``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import trace  # noqa: E402

STATS = ("hlo_op", "long_name", "tf_op")
MAX_EVENTS = 4000
SKIP_LINES = ("Async XLA Ops",)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("out")
    args = ap.parse_args()

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(args.xplane)
    spans = [(e.start_ns, e.start_ns + e.duration_ns)
             for p in pd.planes if not p.name.startswith("/device:")
             for line in p.lines for e in line.events
             if e.name == trace.WINDOW]
    lo, hi = spans[0]
    planes = []
    for p in pd.planes:
        lines = []
        for line in p.lines:
            if line.name in SKIP_LINES:
                continue
            events = [e for e in line.events
                      if e.start_ns < hi and e.start_ns + e.duration_ns > lo]
            events.sort(key=lambda e: -e.duration_ns)
            kept = [[e.name, e.start_ns, e.duration_ns,
                     [[k, str(v)] for k, v in e.stats if k in STATS]]
                    for e in events[:MAX_EVENTS]]
            if kept:
                lines.append({"name": line.name, "events": kept})
        if lines:
            planes.append({"name": p.name, "lines": lines})
    with gzip.open(args.out, "wt") as f:
        json.dump({"planes": planes}, f)


if __name__ == "__main__":
    main()
