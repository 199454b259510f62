"""Spans and counters at the layer boundaries of the served path.

``span(name, **meta)`` times a block twice over: it writes a
``jax.profiler.TraceAnnotation``, so the span lands on the profiler's
host plane on the same clock as the device ops, and it adds its
``perf_counter`` seconds and one call to the :class:`Sink` bound to the
current thread, if any. ``count(name, n)`` adds to the same sink. A lane
worker binds its lane's sink (``bound``) around each collect and step,
so spans opened deeper (``pipeline/share.py``, ``pipeline/backend.py``)
land in the lane that caused them without those modules knowing about
lanes. With no sink bound (client threads, ``MorphingSession.sql``) only
the annotation is written. ``MorphingServer.stats`` exports the lanes'
sinks as ``span_seconds``, ``span_calls`` and ``counts``.

Names are fixed strings, listed in ``SPAN_NAMES`` for readers of a
trace; children nest inside their parent on one thread.
"""
from __future__ import annotations

import sys
import threading
from time import perf_counter
from typing import Dict, Optional, Tuple

SPAN_NAMES: Tuple[str, ...] = (
    # front door, on the client's thread
    "engine.submit", "engine.parse", "engine.filter",
    # the lane worker: waiting for requests, the step, publishing results
    "lane.collect", "lane.step", "lane.publish",
    # children of lane.step; lane.dedup and lane.scatter open inside the
    # share step, CacheChain.step in pipeline/share.py
    "lane.stack", "lane.dedup", "lane.scatter", "lane.head",
    # the share cache
    "share.lookup", "share.fingerprint", "share.search", "share.resort",
    "share.insert", "share.grow", "share.evict",
    # the trunk call
    "backend.run_infer", "backend.pad", "backend.call", "backend.fetch",
    "backend.compile",
)

_local = threading.local()


class Sink:
    """Seconds and calls per span name, and counters, of one lane."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.calls: Dict[str, int] = {}
        self.counts: Dict[str, int] = {}
        self._lock = threading.Lock()

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self.seconds[name] = self.seconds.get(name, 0.0) + seconds
            self.calls[name] = self.calls.get(name, 0) + 1

    def count(self, name: str, n: int) -> None:
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + int(n)

    def merge_into(self, seconds: Dict[str, float], calls: Dict[str, int],
                   counts: Dict[str, int]) -> None:
        """Add this sink's totals to the given dicts (an aggregate)."""
        with self._lock:
            for src, dst in ((self.seconds, seconds), (self.calls, calls),
                             (self.counts, counts)):
                for k, v in src.items():
                    dst[k] = dst.get(k, 0) + v


class bound:
    """Bind ``sink`` to the current thread for the ``with`` block."""

    __slots__ = ("sink", "_prev")

    def __init__(self, sink: Optional[Sink]):
        self.sink = sink

    def __enter__(self) -> Optional[Sink]:
        self._prev = getattr(_local, "sink", None)
        _local.sink = self.sink
        return self.sink

    def __exit__(self, *exc) -> bool:
        _local.sink = self._prev
        return False


def _annotation(name: str, meta: dict):
    # A trace can only be running where jax is loaded: the host-only
    # paths never import jax for the sake of a span.
    prof = sys.modules.get("jax.profiler")
    return None if prof is None else prof.TraceAnnotation(name, **meta)


class span:
    """Time a block into the bound sink and the profiler's host plane."""

    __slots__ = ("name", "_ann", "_sink", "_t0")

    def __init__(self, name: str, **meta):
        self.name = name
        self._ann = _annotation(name, meta)

    def __enter__(self) -> "span":
        self._sink = getattr(_local, "sink", None)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._sink is not None:
            self._sink.add(self.name, dt)
        return False

    def set_meta(self, **meta) -> None:
        """Attach metadata known only after the span opened."""
        if self._ann is not None:
            self._ann.set_metadata(**meta)


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` of the sink bound to this thread."""
    sink = getattr(_local, "sink", None)
    if sink is not None:
        sink.count(name, n)
