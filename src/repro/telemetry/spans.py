"""Host spans: named intervals of the program's own host work (DESIGN.md §10).

A :class:`SpanRecorder` holds the spans of one grid — one
``Experiment.run_simulation`` or one ``FleetRunner.run`` — in the order
they opened.  Opening a span

* opens a ``jax.profiler.TraceAnnotation`` of the same name, so that under
  the profiler the span lands on the host plane, on the device ops' clock;
* appends ``(name, start, end, parent, grid, attrs)`` to the recorder,
  ``parent`` being the index of the span open around it (-1 at the root).

It costs two clock reads and an append, so spans are always on; none is
opened per job or per event.  Span names are dotted by layer
(``experiment.run``, ``fleet.launch``, ``results.write``, ...).
"""
from __future__ import annotations

import contextlib
import itertools
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

# process-wide grid ids, so spans of several grids stay apart when merged
_GRID_IDS = itertools.count(1)


@dataclass
class Span:
    """One closed (or still open) span; times are ``time.perf_counter()``
    seconds."""

    name: str
    start: float
    end: float
    parent: int
    grid: int
    attrs: Dict[str, object] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


def _annotation(name: str, grid: int, attrs: Dict[str, object]):
    # no JAX profiler can be running before JAX is imported, and a grid
    # of host lanes alone should not pay JAX's import for its spans
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, grid=grid, **attrs)


class SpanRecorder:
    """The spans of one grid, in the order they opened."""

    def __init__(self, grid: Optional[int] = None) -> None:
        self.grid = next(_GRID_IDS) if grid is None else grid
        self.spans: List[Span] = []
        self._open: List[int] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Span]:
        """Open span ``name`` inside the innermost open one; ``attrs``
        (str or number values) go to the record and the annotation."""
        sp = Span(name, 0.0, 0.0, self._open[-1] if self._open else -1,
                  self.grid, attrs)
        self._open.append(len(self.spans))
        self.spans.append(sp)
        try:
            with _annotation(name, self.grid, attrs):
                sp.start = time.perf_counter()
                try:
                    yield sp
                finally:
                    sp.end = time.perf_counter()
        finally:
            self._open.pop()

    def children(self, i: int) -> List[Span]:
        """The spans opened directly inside span ``i``."""
        return [s for s in self.spans if s.parent == i]

    def totals(self) -> Dict[str, float]:
        """Seconds per span name, summed over the recorder's spans."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.seconds
        return out
