"""Spans of the serving step loop.

`span(name, **counts)` times a block of host work on `time.perf_counter`
and does two things with it:

- it opens a `jax.profiler.TraceAnnotation`, so the block lands on the
  profiler's host timeline beside the device's operations whenever a
  trace is being taken (and costs next to nothing otherwise);
- on exit it appends itself to a process-wide ring of the last `MAXLEN`
  spans, which `spans(t0, t1)` reads back by start time.

The ring is always on: a microsecond or two a span, counters an operator
reads. ``parent`` is the index of the span open around this one on the
same thread (-1 at the top), so a reader can split a step into its
children. A span is never held across an ``await``: the step loop that
opens them is synchronous.

    with span("serve.step", step_num=n) as sp:
        ...
        sp.set(live=rows)       # counts known only at the end
    took = sp.elapsed
"""
from __future__ import annotations

import collections
import itertools
import math
import threading
import time

import jax

MAXLEN = 65536

_ring: collections.deque = collections.deque(maxlen=MAXLEN)
_index = itertools.count()
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """One span (see the module docstring); after exit it is the ring's
    record. With ``step_num`` the profiler half is a
    `jax.profiler.StepTraceAnnotation`: one step of a loop."""

    __slots__ = ("index", "name", "start", "end", "parent", "counts",
                 "_ann")

    def __init__(self, name: str, step_num: int | None = None, **counts):
        self.name, self.counts = name, counts
        self._ann = jax.profiler.TraceAnnotation(name, **counts) \
            if step_num is None else \
            jax.profiler.StepTraceAnnotation(name, step_num=step_num,
                                             **counts)
        self.start = self.end = None

    def __enter__(self) -> "span":
        stack = _stack()
        self.parent = stack[-1] if stack else -1
        self.index = next(_index)
        stack.append(self.index)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        self._ann = None
        _stack().pop()
        _ring.append(self)

    def set(self, **counts) -> None:
        """Add counts to the open span (and to its profiler event)."""
        self.counts.update(counts)
        self._ann.set_metadata(**counts)

    @property
    def elapsed(self) -> float:
        """Seconds from entry to exit (to now while still open)."""
        end = time.perf_counter() if self.end is None else self.end
        return end - self.start

    def __repr__(self) -> str:
        return (f"span({self.name!r}, index={self.index}, "
                f"parent={self.parent}, start={self.start}, "
                f"end={self.end}, counts={self.counts})")


def spans(t0: float = -math.inf, t1: float = math.inf,
          name: str | None = None) -> list[span]:
    """Recorded spans whose start lies in ``[t0, t1)`` (all of them, or
    those called ``name``), in the order they were opened."""
    out = [s for s in list(_ring) if t0 <= s.start < t1
           and (name is None or s.name == name)]
    out.sort(key=lambda s: s.index)
    return out
