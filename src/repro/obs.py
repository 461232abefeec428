"""Host spans and counters of the running program, on the profiler's clock.

``span(name)`` times a stretch of host code. It opens
``jax.profiler.TraceAnnotation(name)``, so under a running
``jax.profiler`` trace the span shows on the host threads beside the
device's operations, and it keeps, per name, the number of spans, their
total seconds and the durations of the most recent ``RECENT`` of them.
``count(name, amount)`` adds to a plain counter. Everything is kept in
memory: there is no exporter and nothing to switch on. The benchmark and
tests read the record through ``stats``, ``recent`` and ``counter``.

Spans belong in host code only. Inside a traced (jitted) function a span
would time the trace, once; the device work of a step is named with
``jax.named_scope`` instead (``models/model.py``, ``train/precision.py``).

    with obs.span("EpochRunner.run_chunk") as s:
        ...
    s.seconds        # this span's duration
"""
from __future__ import annotations

import collections
import threading
import time

import jax

RECENT = 4096       # durations kept per span name

_lock = threading.Lock()
_spans: dict = {}
_counters: collections.Counter = collections.Counter()


class _Record:
    __slots__ = ("count", "total_s", "recent")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.recent = collections.deque(maxlen=RECENT)


class span:
    """Context manager: a host span called ``name``. ``seconds`` holds its
    duration once it has closed."""

    __slots__ = ("name", "seconds", "_t0", "_annotation")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def elapsed(self) -> float:
        """Seconds since the span opened; use while it is open."""
        return time.perf_counter() - self._t0

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        with _lock:
            rec = _spans.get(self.name)
            if rec is None:
                rec = _spans[self.name] = _Record()
            rec.count += 1
            rec.total_s += self.seconds
            rec.recent.append(self.seconds)


def count(name: str, amount: float = 1) -> None:
    """Add ``amount`` to the counter ``name``."""
    with _lock:
        _counters[name] += amount


def counter(name: str) -> float:
    """The counter ``name``'s total so far (0 if never counted)."""
    with _lock:
        return _counters[name]


def stats(name: str) -> dict:
    """``{"count", "total_s"}`` of the spans called ``name`` so far."""
    with _lock:
        rec = _spans.get(name)
        return ({"count": rec.count, "total_s": rec.total_s} if rec
                else {"count": 0, "total_s": 0.0})


def recent(name: str) -> list:
    """Durations (s) of the latest spans called ``name``, oldest first, at
    most ``RECENT`` of them."""
    with _lock:
        rec = _spans.get(name)
        return list(rec.recent) if rec else []


def reset() -> None:
    """Forget every span and counter."""
    with _lock:
        _spans.clear()
        _counters.clear()
