"""Spans and counters of one ``simulate_batch`` call.

``simulate_batch`` opens one :class:`Recorder` per call with
:func:`record`; the functions it calls mark their phases with
:func:`span` and their sizes with :func:`count`, and the call hands the
records back on its ``BatchResult``.  Each span is also a
``jax.profiler.TraceAnnotation`` of the same name, so a profile shows it
on the host timeline beside the device's ops.  Spans are stamped with
``time.time_ns()``, the clock the profiler stamps its events with, so a
record lands at ``profile_start_time + start_ns`` of its annotation.

The call's recorder is found through a ``ContextVar`` bound for the
duration of the call (and reset after it), rather than passed as an
argument, so that ``step.run_bucket_jnp`` keeps its two-argument
signature: callers and fault tests replace that function with one of the
same signature.  Outside a ``record()`` block, ``span`` only annotates
and ``count`` does nothing.
"""
from __future__ import annotations

import sys
import time
from contextlib import contextmanager, nullcontext
from contextvars import ContextVar
from typing import Dict, Iterator, List, NamedTuple, Optional


class Span(NamedTuple):
    """One phase of a call: ``time.time_ns()`` at its start and end, and
    the name of the span it ran inside (``None`` for the root)."""

    name: str
    parent: Optional[str]
    start_ns: int
    end_ns: int

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6


class Recorder:
    """The spans (in start order) and counters of one call."""

    def __init__(self):
        self.spans: List[Span] = []
        self.counters: Dict[str, int] = {}
        self._open: List[str] = []

    @contextmanager
    def span(self, name: str) -> Iterator[None]:
        parent = self._open[-1] if self._open else None
        slot = len(self.spans)
        self.spans.append(None)              # keeps start order
        self._open.append(name)
        with _annotation(name):
            start = time.time_ns()
            try:
                yield
            finally:
                end = time.time_ns()
                self._open.pop()
                self.spans[slot] = Span(name, parent, start, end)

    def count(self, name: str, n: int) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)


_CURRENT: ContextVar[Optional[Recorder]] = ContextVar(
    "repro_sim_recorder", default=None)


def _annotation(name: str):
    # no profiler can be running in a process that never imported jax
    jax = sys.modules.get("jax")
    return (jax.profiler.TraceAnnotation(name) if jax is not None
            else nullcontext())


@contextmanager
def record() -> Iterator[Recorder]:
    """Bind a new recorder for the block and yield it."""
    rec = Recorder()
    token = _CURRENT.set(rec)
    try:
        yield rec
    finally:
        _CURRENT.reset(token)


def span(name: str):
    """A span of the bound recorder (a bare annotation outside one)."""
    rec = _CURRENT.get()
    return rec.span(name) if rec is not None else _annotation(name)


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` of the bound recorder."""
    rec = _CURRENT.get()
    if rec is not None:
        rec.count(name, n)
