"""Open-loop serving traffic: a schedule of requests due at fixed times,
and the latencies of what the server did with them.

The schedule's shape is the same for every seed: ``n = rate * seconds``
arrival times drawn uniformly over the window (a Poisson process of that
rate, conditioned on its count) and ``n`` (prompt, output) lengths from
clipped lognormals, all drawn from the traffic file's ``shape_seed``. The
run's ``--seed`` only permutes which lengths go to which arrival and draws
the prompts' tokens. So two seeds offer the same work in another order.

Every request is timed from when it was due, so a stall delays every later
request's first token (open loop). First-token and last-token times are
taken when the engine call that produced them returns.
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np


@dataclasses.dataclass
class Due:
    rid: int
    at: float            # seconds after the window opens
    prompt_len: int
    output_len: int


def lognormal_lengths(rng, n: int, spec: dict) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def schedule(traffic: dict, seconds: float, seed: int) -> list:
    """The requests due in a window of ``seconds``, in order of arrival."""
    n = int(round(traffic["rate"] * seconds))
    shape = np.random.default_rng(traffic["shape_seed"])
    at = np.sort(shape.uniform(0.0, seconds, n))
    prompts = lognormal_lengths(shape, n, traffic["prompt"])
    outputs = lognormal_lengths(shape, n, traffic["output"])
    outputs = np.minimum(outputs, traffic["max_total"] - prompts)
    order = np.random.default_rng(seed).permutation(n)
    return [Due(i, float(at[i]), int(prompts[order[i]]),
                int(outputs[order[i]])) for i in range(n)]


@dataclasses.dataclass
class Timing:
    due: float                  # absolute host-clock time it was due
    submitted: float = math.nan
    first: float = math.nan     # return of the call that made token 1
    last: float = math.nan      # return of the call that made the last
    n_out: int = 0
    failed: bool = False


def percentile(values, q: float) -> float:
    """The q-th percentile (nearest rank, so it is a value that occurred);
    missing values (nan, inf) count as worse than any other."""
    v = np.asarray(values, dtype=np.float64)
    v = np.sort(np.where(np.isnan(v), np.inf, v))
    k = max(int(math.ceil(q / 100.0 * len(v))) - 1, 0)
    return float(v[k])


def ttft(t: Timing) -> float:
    return math.inf if t.failed or math.isnan(t.first) else t.first - t.due


def tpot(t: Timing) -> float:
    if t.failed or math.isnan(t.last) or t.n_out < 2:
        return math.inf
    return (t.last - t.first) / (t.n_out - 1)


def summary(timings: list) -> dict:
    """TTFT and TPOT p50/p95 in ms over every request due (a failed or
    unfinished request counts as missing every limit), and the generator's
    lateness."""
    late = [t.submitted - t.due for t in timings
            if not math.isnan(t.submitted)]
    return {
        "ttft_p95_ms": 1e3 * percentile([ttft(t) for t in timings], 95),
        "ttft_p50_ms": 1e3 * percentile([ttft(t) for t in timings], 50),
        "tpot_p95_ms": 1e3 * percentile([tpot(t) for t in timings], 95),
        "tpot_p50_ms": 1e3 * percentile([tpot(t) for t in timings], 50),
        "late_p50_ms": 1e3 * float(np.median(late)) if late else math.nan,
        "late_max_ms": 1e3 * float(np.max(late)) if late else math.nan,
        "n": len(timings),
        "failed": sum(t.failed for t in timings),
    }


def drive(engine, requests: list, timings: list, t0: float,
          stop_after: float, on_step=None) -> list:
    """Offer ``requests`` (engine ``Request`` objects, in order of
    ``timings[i].due``) to ``engine`` at their due times, and step it until
    every one is done or ``stop_after`` seconds past ``t0``. Records
    submission, first- and last-token times in ``timings``; a second call
    carries on where the first stopped. ``on_step(seconds, admitted)`` sees
    the duration of each ``step``. Times are ``time.perf_counter``'s."""
    clock = time.perf_counter
    pending = [i for i, t in enumerate(timings) if math.isnan(t.submitted)]
    live = [i for i, t in enumerate(timings)
            if not math.isnan(t.submitted) and not requests[i].done]

    def note(now):
        still = []
        for i in live:
            r, t = requests[i], timings[i]
            if math.isnan(t.first) and r.generated:
                t.first = now
            if r.done:
                t.n_out = len(r.generated)
                t.failed = bool(r.rejected)
                if not t.failed:
                    t.last = now
            else:
                still.append(i)
        live[:] = still

    while pending or live:
        now = clock()
        if now - t0 > stop_after:
            break
        while pending and timings[pending[0]].due <= now:
            i = pending.pop(0)
            timings[i].submitted = clock()
            live.append(i)
            engine.submit(requests[i])
            note(clock())
        if engine.active or engine.waiting:
            before = engine.stats["admissions"]
            s = clock()
            engine.step()
            e = clock()
            note(e)
            if on_step is not None:
                on_step(e - s, engine.stats["admissions"] != before)
        elif pending:
            time.sleep(max(0.0, timings[pending[0]].due - clock()))
        else:
            note(clock())

    return live


def close(timings: list, unfinished: list) -> None:
    """Requests still unfinished when driving stopped missed every limit."""
    for i in unfinished:
        timings[i].failed = True
