"""Span-based tracing of the query lifecycle.

One request's life through the serve stack is a handful of stages —

    admission -> window[coalesce -> launch[encode, dispatch]
                        -> finalize[escalate] -> scatter] [degrade]

(rerank is fused into the compiled plan program, so on the host it is
timed inside launch/finalize; on the device it carries the named scope
``quiver.rerank``, DESIGN.md §12).  A :class:`Tracer` hands out integer
trace ids at admission, every stage records a :class:`Span` carrying
that id, and finished spans land in

* a bounded ring of recent spans (inspection/debugging — ``spans()``;
  ``max_spans=0`` keeps none),
* per-stage duration histograms in a :class:`MetricsRegistry`
  (``quiver_stage_seconds{stage=...}``) — the operational signal,
* always-on per-stage totals and the stage split of the slowest
  ``window`` (``stage_report()``): where a slow admission window went,
  readable without a profiler or a registry.

A stage timed by :meth:`Tracer.span` is also a
``jax.profiler.TraceAnnotation`` named ``quiver.<stage>``, so a
profiler trace shows every stage as a host event on the device ops'
clock; with the profiler off an annotation costs about a microsecond.
Spans are plain dataclasses, ids are a counter behind a lock, and the
ring is a ``deque(maxlen=...)``.  No repro.* imports besides the
sibling metrics module.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time

from jax.profiler import TraceAnnotation

from repro.obs.metrics import MetricsRegistry

STAGES = (
    "admission", "coalesce", "launch", "encode", "dispatch", "finalize",
    "escalate", "scatter", "degrade", "request", "window",
)

# profiler-event name prefix of every stage timed by ``Tracer.span``
ANNOTATION_PREFIX = "quiver."
# the envelope stage whose slowest instance ``stage_report`` splits
WINDOW = "window"


@dataclasses.dataclass
class Span:
    name: str
    trace_id: int
    start: float
    end: float | None = None
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float | None:
        return None if self.end is None else self.end - self.start

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "start": self.start,
            "seconds": self.seconds,
            **({"attrs": self.attrs} if self.attrs else {}),
        }


class Tracer:
    """Hands out trace ids and records finished spans.

    ``registry`` (optional) receives per-stage duration histograms; the
    ring keeps the last ``max_spans`` finished spans for inspection
    (0: none).  Stage totals and the slowest window's split are kept
    either way.  ``clock`` is injectable for tests (same convention as
    QueryEngine).
    """

    def __init__(
        self,
        registry: MetricsRegistry | None = None,
        *,
        max_spans: int = 2048,
        clock=time.monotonic,
    ):
        self.registry = registry
        self.clock = clock
        self._spans = collections.deque(maxlen=max_spans)
        self._next = 0
        self._lock = threading.Lock()
        self._stage_hist = (
            registry.histogram(
                "quiver_stage_seconds",
                "query-lifecycle stage durations",
                labels=("stage",),
            )
            if registry is not None else None
        )
        self.reset()

    def new_trace(self) -> int:
        with self._lock:
            self._next += 1
            return self._next

    def record(self, span: Span) -> Span:
        """File a finished span (sets ``end`` if the caller didn't)."""
        if span.end is None:
            span.end = self.clock()
        self._spans.append(span)
        if self._stage_hist is not None:
            self._stage_hist.observe(span.seconds, stage=span.name)
        return span

    @contextlib.contextmanager
    def span(self, name: str, trace_id: int = 0, **attrs):
        """Context-managed stage span; records on exit (even on error,
        so a failing launch still shows up in the stage histogram).

        The stage is also the profiler event ``quiver.<name>`` and
        counts in the stage totals; a ``window`` span opens a fresh
        stage split, which the stages closed inside it fill."""
        with TraceAnnotation(ANNOTATION_PREFIX + name):
            if name == WINDOW:
                with self._lock:
                    self._split = {}
            s = Span(name=name, trace_id=trace_id, start=self.clock(),
                     attrs=dict(attrs))
            try:
                yield s
            finally:
                self.record(s)
                self._tally(s)

    def _tally(self, s: Span) -> None:
        secs = s.seconds
        with self._lock:
            slot = self._totals.setdefault(s.name, [0, 0.0])
            slot[0] += 1
            slot[1] += secs
            if s.name != WINDOW:
                if self._split is not None:
                    self._split[s.name] = self._split.get(s.name, 0.0) + secs
                return
            split, self._split = self._split or {}, None
            if self._slowest is None or secs > self._slowest["seconds"]:
                self._slowest = {"index": self._windows, "seconds": secs,
                                 "stages": split}
            self._windows += 1

    def reset(self) -> None:
        """Zero the stage totals and forget the slowest window (the ring
        and the histograms keep what they hold)."""
        with self._lock:
            self._totals: dict[str, list] = {}
            self._split: dict[str, float] | None = None
            self._slowest: dict | None = None
            self._windows = 0

    def stage_report(self) -> dict:
        """Always-on stage timings since construction or ``reset()``:
        per-stage ``{count, total_s}``, the number of windows, and the
        slowest window with its ``index``, ``seconds`` and ``stages``
        (seconds of each stage closed inside it; nested stages count in
        their parent too, so ``encode`` and ``dispatch`` are part of
        ``launch``)."""
        with self._lock:
            slowest = (None if self._slowest is None
                       else {**self._slowest,
                             "stages": dict(self._slowest["stages"])})
            return {
                "stages": {n: {"count": c, "total_s": t}
                           for n, (c, t) in sorted(self._totals.items())},
                "windows": self._windows,
                "slowest_window": slowest,
            }

    def spans(self, trace_id: int | None = None,
              name: str | None = None) -> list[Span]:
        out = list(self._spans)
        if trace_id is not None:
            out = [s for s in out if s.trace_id == trace_id]
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def report(self) -> dict:
        """Per-stage {count, total_s, mean_ms} over the span ring."""
        agg: dict[str, list] = {}
        for s in self._spans:
            if s.seconds is None:
                continue
            slot = agg.setdefault(s.name, [0, 0.0])
            slot[0] += 1
            slot[1] += s.seconds
        return {
            name: {
                "count": c,
                "total_s": round(tot, 6),
                "mean_ms": round(tot / c * 1e3, 4),
            }
            for name, (c, tot) in sorted(agg.items())
        }
