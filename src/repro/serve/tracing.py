"""Host spans and counts of the serving path, kept in memory.

One recorder per process, as there is one profiler.  ``span(name,
**counts)`` times a block on ``time.perf_counter_ns`` into a ring of
``CAPACITY`` spans and also opens ``jax.profiler.TraceAnnotation(name)``,
so a profiler trace shows the span on its host plane, on the clock the
device events are mapped to.  The block may add counts to the dict the
context manager yields (what it admitted, rows it computed).

``window(t0, t1)`` gives the spans that lie wholly inside a stretch of
``time.perf_counter`` seconds, or None once the ring has overwritten a
span that started in it, so a truncated stretch gives no number rather
than a wrong one.  ``totals()`` sums every span since the process started,
by name.  Spans nest in the order they open on one thread; the serving
engines tick on one thread.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import jax

CAPACITY = 1 << 16


@dataclasses.dataclass(frozen=True)
class Span:
    seq: int  # order of opening, unique in the recorder
    parent: int  # ``seq`` of the span it opened inside, -1 at the top
    name: str
    start: int  # ns, time.perf_counter_ns
    end: int
    counts: dict

    @property
    def dur(self) -> int:
        return self.end - self.start


class Recorder:
    def __init__(self, capacity: int = CAPACITY,
                 clock=time.perf_counter_ns):
        self._clock = clock
        self._ring: list[Span | None] = [None] * capacity
        self._written = 0
        self._opened = 0
        self._open: list[int] = []  # seqs of the spans now open
        self._lost_start = -1  # latest start of a span the ring overwrote
        self._totals: dict[str, dict] = {}

    @contextlib.contextmanager
    def span(self, name: str, **counts):
        seq, self._opened = self._opened, self._opened + 1
        parent = self._open[-1] if self._open else -1
        self._open.append(seq)
        start = self._clock()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield counts
        finally:
            end = self._clock()
            self._open.pop()
            self._write(Span(seq, parent, name, start, end, counts))

    def _write(self, span: Span) -> None:
        i = self._written % len(self._ring)
        old = self._ring[i]
        if old is not None:
            self._lost_start = max(self._lost_start, old.start)
        self._ring[i] = span
        self._written += 1
        tot = self._totals.setdefault(span.name, {"n": 0, "s": 0.0})
        tot["n"] += 1
        tot["s"] += span.dur * 1e-9
        for k, v in span.counts.items():
            tot[k] = tot.get(k, 0) + v

    def window(self, t0: float, t1: float) -> list[Span] | None:
        """Spans inside ``[t0, t1]`` (``time.perf_counter`` seconds), in
        the order they opened; None if the ring lost one of them."""
        lo, hi = t0 * 1e9, t1 * 1e9
        if self._lost_start >= lo:
            return None
        return sorted((s for s in self._ring
                       if s is not None and lo <= s.start and s.end <= hi),
                      key=lambda s: s.seq)

    def totals(self) -> dict:
        """{name: {"n": spans, "s": seconds, count: sum}} since the start."""
        return {k: dict(v) for k, v in self._totals.items()}


def within(spans: list[Span], outer: Span) -> list[Span]:
    """The spans of ``spans`` (in opening order) nested inside ``outer``."""
    inside, out = {outer.seq}, []
    for s in spans:
        if s.parent in inside:
            inside.add(s.seq)
            out.append(s)
    return out


RECORDER = Recorder()
span = RECORDER.span
window = RECORDER.window
totals = RECORDER.totals
