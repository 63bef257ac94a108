"""Host-side span tracing for the solve path (the flight recorder).

A :class:`Tracer` records a tree of :class:`Span`\\ s — one per stage
execution, attempt, checkpoint save/restore, capacity-estimation
pre-pass, or front-door pipeline — with wall timings bounded by the
driver's existing ``jax.block_until_ready`` device syncs. Spans carry
arbitrary JSON-safe annotations (recursion level, attempt number, the
active :class:`~repro.core.listrank.tuner.CapacityScales`, the stage's
statically counted collective footprint, and the §2.6 predicted time).

The cardinal rule (DESIGN.md §12): **instrumentation never perturbs a
traced program.** The tracer is pure host python; it is never part of a
jit cache key, never closes over device values, and adds zero
collectives — a solve with tracing on reproduces the tracer-off bytes,
counters, and jaxpr collective counts exactly (pinned in
``tests/test_obs.py``).

A recording tracer also writes every span into the JAX profiler as a
host annotation named ``<ANNOTATION_PREFIX><cat>/<name>``: ``begin``
enters a :class:`jax.profiler.TraceAnnotation` and ``end`` exits it
(and those of the forgotten children it closes, innermost first). In a
``jax.profiler`` trace the spans then sit on the host plane, on the
same clock as the device's ops. The tracer also counts the solve
path's blocking host<->device syncs (:meth:`Tracer.host_sync`).

When tracing is off, every instrumentation site goes through
:data:`NULL_TRACER`, whose ``span``/``begin`` return one shared
:data:`NULL_SPAN` singleton — no Span objects or annotations are
allocated, no clock is read (also pinned by test).
"""
from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Iterator

from jax.profiler import TraceAnnotation

#: prefix of every span's host annotation in the JAX profiler's trace
ANNOTATION_PREFIX = "repro:"


@dataclasses.dataclass
class Span:
    """One recorded interval. Times are ``perf_counter`` seconds
    relative to the tracer's epoch; ``t1 is None`` while open."""
    name: str
    cat: str
    index: int                 #: creation order (stable tie-break)
    parent: int                #: index of the enclosing span, -1 at root
    depth: int                 #: nesting depth at open time
    t0: float
    t1: float | None = None
    args: dict = dataclasses.field(default_factory=dict)

    @property
    def duration(self) -> float:
        return (self.t1 if self.t1 is not None else self.t0) - self.t0

    def annotate(self, **kw) -> "Span":
        self.args.update(kw)
        return self

    # context-manager protocol is provided by the tracer-bound handle;
    # a bare Span is just the record.


class _SpanHandle:
    """A live span bound to its tracer — usable as a context manager
    (``with tracer.span(...) as sp``) or via explicit
    ``tracer.end(handle)``. Holds the span's entered profiler
    annotation until the span ends."""

    __slots__ = ("_tracer", "span", "_annotation")

    def __init__(self, tracer: "Tracer", span: Span,
                 annotation: TraceAnnotation):
        self._tracer = tracer
        self.span = span
        self._annotation = annotation

    def annotate(self, **kw) -> "_SpanHandle":
        self.span.args.update(kw)
        return self

    def __enter__(self) -> "_SpanHandle":
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None and "outcome" not in self.span.args:
            self.span.args["outcome"] = exc_type.__name__
        self._tracer.end(self)
        return False


class _NullSpan:
    """The shared do-nothing span handle of :data:`NULL_TRACER`."""

    __slots__ = ()

    def annotate(self, **kw) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every operation is a no-op returning the shared
    :data:`NULL_SPAN`. ``enabled`` gates any instrumentation work with
    a measurable cost (jaxpr tracing for footprints, registry updates).
    """

    enabled = False
    spans: tuple = ()
    host_syncs = 0
    metrics = None

    def span(self, name: str, cat: str = "host", **args):
        return NULL_SPAN

    def begin(self, name: str, cat: str = "host", **args):
        return NULL_SPAN

    def end(self, handle, **args) -> None:
        pass

    def instant(self, name: str, cat: str = "host", **args) -> None:
        pass

    def host_sync(self) -> None:
        pass


NULL_TRACER = NullTracer()


def ensure(tracer) -> "Tracer | NullTracer":
    """Normalize an optional tracer argument: None -> the no-op
    singleton, anything else passed through."""
    return NULL_TRACER if tracer is None else tracer


class Tracer:
    """The recording tracer.

    ``metrics`` is an optional
    :class:`~repro.obs.metrics.MetricsRegistry` the instrumented
    drivers feed (one is created lazily on first use if not supplied).
    """

    enabled = True

    def __init__(self, metrics=None, clock=time.perf_counter):
        self._clock = clock
        self.epoch = clock()
        self.spans: list[Span] = []
        self.instants: list[Span] = []
        #: blocking host<->device syncs counted so far (``host_sync``)
        self.host_syncs = 0
        self._stack: list[_SpanHandle] = []
        self._metrics = metrics

    # ------------------------------------------------------------ metrics
    @property
    def metrics(self):
        if self._metrics is None:
            from repro.obs.metrics import MetricsRegistry
            self._metrics = MetricsRegistry()
        return self._metrics

    # -------------------------------------------------------------- spans
    def now(self) -> float:
        return self._clock() - self.epoch

    def begin(self, name: str, cat: str = "host", **args) -> _SpanHandle:
        parent = self._stack[-1].span.index if self._stack else -1
        span = Span(name=name, cat=cat, index=len(self.spans),
                    parent=parent, depth=len(self._stack), t0=self.now(),
                    args=dict(args))
        self.spans.append(span)
        annotation = TraceAnnotation(f"{ANNOTATION_PREFIX}{cat}/{name}")
        annotation.__enter__()
        handle = _SpanHandle(self, span, annotation)
        self._stack.append(handle)
        return handle

    def end(self, handle: _SpanHandle, **args) -> None:
        if isinstance(handle, _NullSpan):  # tolerate mixed call sites
            return
        handle.span.args.update(args)
        if handle.span.t1 is not None:
            return  # already ended
        # close any forgotten children, innermost first, so the span
        # tree and the profiler's annotations stay well nested
        while True:
            top = self._stack.pop()
            top._annotation.__exit__(None, None, None)
            top.span.t1 = self.now()
            if top is handle:
                return

    def span(self, name: str, cat: str = "host", **args) -> _SpanHandle:
        """``with tracer.span("base@2", cat="stage") as sp: ...``"""
        return self.begin(name, cat, **args)

    def instant(self, name: str, cat: str = "host", **args) -> None:
        """A zero-duration event (fault injections, preemptions, ...)."""
        parent = self._stack[-1].span.index if self._stack else -1
        t = self.now()
        self.instants.append(Span(name=name, cat=cat, index=-1,
                                  parent=parent, depth=len(self._stack),
                                  t0=t, t1=t, args=dict(args)))

    def host_sync(self) -> None:
        """Count one blocking host<->device sync (a ``device_get`` or
        ``block_until_ready``)."""
        self.host_syncs += 1

    # ------------------------------------------------------------ queries
    def find(self, cat: str | None = None,
             name: str | None = None) -> Iterator[Span]:
        for s in self.spans:
            if cat is not None and s.cat != cat:
                continue
            if name is not None and s.name != name:
                continue
            yield s

    def children(self, span: Span) -> list[Span]:
        return [s for s in self.spans if s.parent == span.index]

    def close_all(self) -> None:
        """Close every span still open (end-of-process safety)."""
        while self._stack:
            self.end(self._stack[-1])


def span_tree_lines(tracer: Tracer) -> list[str]:
    """Human-readable indented rendering of the span tree (debugging)."""
    out = []
    for s in tracer.spans:
        dur = f"{s.duration * 1e3:8.2f}ms" if s.t1 is not None else "    open"
        out.append(f"{'  ' * s.depth}{s.name} [{s.cat}] {dur}")
    return out


def maybe(tracer, cond: bool) -> "Tracer | NullTracer":
    """``tracer`` when ``cond`` else the no-op singleton — lets call
    sites gate nested instrumentation without branching."""
    return tracer if cond else NULL_TRACER


def profile_spans(logdir) -> list[tuple[str, float, float]]:
    """The spans a :class:`Tracer` wrote into the newest
    ``jax.profiler`` trace under ``logdir``: ``(cat/name, start s,
    end s)`` of each prefixed host annotation, by start. Times are on
    the profiler's clock, which the trace's device ops share."""
    from jax.profiler import ProfileData

    files = sorted(Path(logdir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda f: f.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(str(files[-1]))
    return sorted(((ev.name[len(ANNOTATION_PREFIX):], ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for plane in pd.planes if plane.name == "/host:CPU"
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith(ANNOTATION_PREFIX)),
                  key=lambda e: (e[1], -e[2]))


__all__ = ["Span", "Tracer", "NullTracer", "NULL_TRACER", "NULL_SPAN",
           "ANNOTATION_PREFIX", "ensure", "maybe", "profile_spans",
           "span_tree_lines"]
