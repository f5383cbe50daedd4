"""Spans and counters of the serving path, on the profiler's clock.

A :class:`SpanLog` keeps, per span name, a fixed-size ring of the last
``window`` intervals (``time.perf_counter`` seconds) — O(window) state
however long the process serves, like the latency rings of
:mod:`repro.serve.metrics` — plus cumulative integer counters.  Each span
also opens a ``jax.profiler.TraceAnnotation`` of the same name, so under
a profiler trace the interval lands on the host plane of the same
``.xplane.pb`` as the device's operations; with no trace running the
annotation is a no-op.

There is no process-wide log: a :class:`~repro.core.QueryContext` owns
one (its engine steps, ingests and artifact rebuilds), a
:class:`~repro.serve.CoocServer` owns one for its lanes, and the
server's snapshot merges its own with its lanes' contexts'
(:func:`merge`).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Deque, Dict, Iterable, Tuple

from jax.profiler import TraceAnnotation


@dataclasses.dataclass(frozen=True)
class SpanSummary:
    """One span name over its ring: intervals held, their sum and max."""
    count: int
    total_ms: float
    max_ms: float


class Span:
    """One timed interval; ``start`` / ``end`` are set on entry / exit."""

    __slots__ = ("_log", "name", "start", "end", "_annotation")

    def __init__(self, log: "SpanLog", name: str):
        self._log = log
        self.name = name
        self.start = self.end = 0.0

    def __enter__(self) -> "Span":
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._log.ring(self.name).append((self.start, self.end))

    @property
    def ms(self) -> float:
        return (self.end - self.start) * 1e3


class SpanLog:
    """Per-name interval rings plus counters, safe to record from the
    event loop and executor threads at once."""

    def __init__(self, window: int = 4096):
        if int(window) < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.window = int(window)
        self._rings: Dict[str, Deque[Tuple[float, float]]] = {}
        self.counters: Dict[str, int] = {}
        self._lock = threading.Lock()

    def span(self, name: str) -> Span:
        """``with log.span(name) as s:`` times the block into the ring
        ``name``; ``s.ms`` is its duration once the block has left."""
        return Span(self, name)

    def ring(self, name: str) -> Deque[Tuple[float, float]]:
        """The ring of ``(start, end)`` intervals held for ``name``."""
        ring = self._rings.get(name)
        if ring is None:
            with self._lock:
                ring = self._rings.setdefault(
                    name, deque(maxlen=self.window))
        return ring

    def count(self, name: str, n: int = 1, **labels: str) -> None:
        """Add ``n`` to the counter ``name{label="value",...}``."""
        if labels:
            name += "{" + ",".join(f'{k}="{v}"'
                                   for k, v in sorted(labels.items())) + "}"
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + int(n)

    def names(self) -> Tuple[str, ...]:
        return tuple(sorted(self._rings))


def merge(logs: Iterable[SpanLog]
          ) -> Tuple[Dict[str, SpanSummary], Dict[str, int]]:
    """Per-name summaries and summed counters over ``logs`` (each log
    counted once, however often it is passed)."""
    seen = {id(log): log for log in logs}.values()
    spans: Dict[str, list] = {}
    counters: Dict[str, int] = {}
    for log in seen:
        for name in log.names():
            spans.setdefault(name, []).extend(log.ring(name))
        for name, n in list(log.counters.items()):
            counters[name] = counters.get(name, 0) + n
    summaries = {}
    for name in sorted(spans):
        ms = [(b - a) * 1e3 for a, b in spans[name]]
        summaries[name] = SpanSummary(len(ms), float(sum(ms)),
                                      float(max(ms, default=0.0)))
    return summaries, dict(sorted(counters.items()))
