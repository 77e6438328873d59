"""Host spans of the round engine, on two clocks at once.

``with span("repro.chunk", round=r, rounds=k):`` enters a
``jax.profiler.TraceAnnotation`` (so the span lands in any profiler trace,
on the device trace's clock) and, when the block ends, appends a
:class:`Span` to a bounded in-memory ring, even when an exception passes
through it.  Counts ride as attributes (``bytes=``); an attribute known
only inside the block is added with :meth:`_Open.set`.

``spans()`` reads the record of the newest ``FederatedJob.run`` in the
process: every run calls :func:`start_run`, which empties the ring.  The
ring keeps the newest :data:`RING` spans, so a run that closes more (the
stacked engines close four per chunk) keeps only its last ones and loses
the once-per-shape ``repro.chunk.lower`` / ``.compile`` spans;
``JobResult.compile_s`` keeps their sum.  The record is
process-wide so that a reader with no handle on the job (the on-chip
benchmark's per-layer metrics) finds it.  With the profiler stopped a
span costs one ring append.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

RING = 4096
_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count()
_local = threading.local()


class Span(NamedTuple):
    id: int
    name: str
    start_ns: int               # time.perf_counter_ns()
    end_ns: int
    parent: Optional[int]       # id of the enclosing span, None at the top
    attrs: Dict[str, Any]
    error: Optional[str]        # the exception's type that closed it

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class _Open:
    """An open span; after the block, ``seconds`` is its duration."""

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name, self.attrs, self.seconds = name, attrs, 0.0
        self._inner: Dict[str, float] = collections.defaultdict(float)

    def inner(self, *names: str) -> float:
        """Seconds spent so far in the closed spans directly inside this
        one that carry one of ``names``."""
        return sum(self._inner[n] for n in names)

    def set(self, **attrs) -> None:
        """Add attributes from inside the block."""
        self.attrs.update(attrs)
        self._trace.set_metadata(**attrs)

    def __enter__(self):
        stack = _local.__dict__.setdefault("stack", [])
        self._parent = stack[-1] if stack else None
        self._id = next(_ids)
        stack.append(self)
        self._trace = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._trace.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        self._trace.__exit__(exc_type, exc, tb)
        _local.stack.pop()
        self.seconds = (t1 - self._t0) / 1e9
        if self._parent is not None:
            self._parent._inner[self.name] += self.seconds
        _ring.append(Span(self._id, self.name, self._t0, t1,
                          None if self._parent is None else self._parent._id,
                          self.attrs, exc_type and exc_type.__name__))
        return False


def span(name: str, **attrs) -> _Open:
    return _Open(name, attrs)


def start_run() -> None:
    """Begin a new record: the spans of earlier runs are dropped."""
    _ring.clear()


def spans() -> List[Span]:
    """The newest run's spans, in the order they closed: its last
    :data:`RING` spans where it closed more."""
    return list(_ring)
