"""Span tracing: deterministic request timelines and lexical spans that
share one clock with the ``jax.profiler`` trace.

A :class:`Tracer` records **spans** (named intervals with attributes and a
parent) and **events** (named instants) against an injectable clock — the
same clock the scheduler runs on, so a workload driven by
``scheduler.ManualClock`` produces *byte-identical* JSONL traces run to
run: span ids are sequence numbers, timestamps come from the manual
clock, and export order is record order.  The scheduler threads one span
tree per request through its lifecycle::

    request              (submit -> done/expired)
      queued             (submit -> admit | expiry)
      prefill            (admit -> first token; chunks= counts rounds)
      * first_token      (instant)
      decode             (first token -> done; tokens=)

:func:`profile_scope` is the one **lexical** span call.  It records into
the tracer installed by :func:`recording` (a traced ``Scheduler`` installs
its own for each round), nesting under the innermost open lexical span,
and, with :func:`enable_profiler_annotations`, also opens a
``jax.profiler.TraceAnnotation`` that carries the span's ``t0`` and ``id``
as metadata.  :meth:`Tracer.profiler_offset` fits the offset between the
two clocks from those anchors, and the exports take it, so request
timelines overlay the device trace.  With neither active a scope is a
free null context.

Two export formats:

  * :meth:`Tracer.export_jsonl` — one JSON object per line, schema
    ``{"type": "span"|"event", "name", "id", "parent", "rid", "t0",
    "t1", "attrs"}`` (events carry ``t0`` only).  The CI obs-smoke step
    schema-checks this file.
  * :meth:`Tracer.export_chrome` — Chrome ``chrome://tracing`` / Perfetto
    JSON (complete ``"X"`` events, microsecond timestamps, one row per
    request id), so a served workload can be read as a timeline.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import statistics
import time
from typing import NamedTuple

__all__ = [
    "ClockFit",
    "ProfileEvent",
    "Span",
    "Tracer",
    "anchored_events",
    "profile_scope",
    "recording",
    "enable_profiler_annotations",
    "disable_profiler_annotations",
    "profiler_annotations_enabled",
]


@dataclasses.dataclass
class Span:
    """One named interval; ``end()`` via the owning tracer."""

    name: str
    id: int
    parent: int | None
    rid: int | None
    t0: float
    t1: float | None = None
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def open(self) -> bool:
        return self.t1 is None


class ClockFit(NamedTuple):
    """Tracer time + ``offset_s`` = profiler time; ``spread_s`` is the
    largest distance of an anchored span from that fit."""

    offset_s: float
    spread_s: float
    n: int


class Tracer:
    """Append-only span/event recorder with deterministic ids and times.

    ``clock`` is any zero-arg callable returning seconds (the scheduler
    passes its own, so trace timestamps share the ``arrival_s`` timebase);
    default wall ``time.perf_counter`` rebased to 0 at construction.
    ``max_records`` bounds memory for long-lived servers: the oldest
    *closed* records are dropped once exceeded (export notes the drop).
    """

    def __init__(self, clock=None, max_records: int = 100_000):
        if clock is None:
            t0 = time.perf_counter()
            clock = lambda: time.perf_counter() - t0
        self._now = clock
        self.max_records = max_records
        self._records: list = []       # Span | event dicts, record order
        self._lexical: list = []       # open profile_scope spans, inner last
        self._open = 0
        self._next_id = 0
        self.dropped = 0

    # -- recording --------------------------------------------------------

    def begin(self, name: str, parent: Span | None = None,
              rid: int | None = None, **attrs) -> Span:
        """Open a span; close it with :meth:`end` (spans here are not
        lexically scoped — a request span stays open across many
        scheduling rounds)."""
        return self._start(name, parent, rid, self._now(), attrs)

    def _start(self, name, parent, rid, t0, attrs) -> Span:
        span = Span(
            name=name, id=self._next_id,
            parent=None if parent is None else parent.id,
            rid=rid if rid is not None else (
                None if parent is None else parent.rid),
            t0=t0, attrs=dict(attrs),
        )
        self._next_id += 1
        self._records.append(span)
        self._open += 1
        return span

    def end(self, span: Span, **attrs) -> Span:
        if span.t1 is not None:
            raise ValueError(f"span {span.name}#{span.id} already ended")
        span.t1 = self._now()
        span.attrs.update(attrs)
        self._open -= 1
        self._trim()
        return span

    def event(self, name: str, parent: Span | None = None,
              rid: int | None = None, **attrs) -> None:
        """A named instant (exported with ``t0`` only)."""
        self._records.append({
            "name": name, "id": self._next_id,
            "parent": None if parent is None else parent.id,
            "rid": rid if rid is not None else (
                None if parent is None else parent.rid),
            "t0": self._now(), "attrs": dict(attrs),
        })
        self._next_id += 1
        self._trim()

    def _trim(self) -> None:
        # drop oldest CLOSED records past the cap; open spans must survive
        # (their end() still mutates them in place)
        excess = len(self._records) - self.max_records
        if excess <= 0:
            return
        keep = []
        for r in self._records:
            if excess > 0 and not (isinstance(r, Span) and r.open):
                excess -= 1
                self.dropped += 1
            else:
                keep.append(r)
        self._records = keep

    # -- export -----------------------------------------------------------

    def records(self, offset_s: float = 0.0) -> list:
        """Every record as a JSON-ready dict, in record order; times are
        shifted by ``offset_s`` (a :meth:`profiler_offset` puts them on
        the profiler's clock)."""
        out = []
        for r in self._records:
            if isinstance(r, Span):
                out.append({
                    "type": "span", "name": r.name, "id": r.id,
                    "parent": r.parent, "rid": r.rid,
                    "t0": round(r.t0 + offset_s, 9),
                    "t1": None if r.t1 is None else round(r.t1 + offset_s, 9),
                    "attrs": r.attrs,
                })
            else:
                out.append({
                    "type": "event", "name": r["name"], "id": r["id"],
                    "parent": r["parent"], "rid": r["rid"],
                    "t0": round(r["t0"] + offset_s, 9), "attrs": r["attrs"],
                })
        return out

    def profiler_offset(self, events) -> ClockFit | None:
        """Fit the offset from this tracer's clock to a ``jax.profiler``
        trace's, from the annotations :func:`profile_scope` anchored with
        a span's ``t0`` and ``id`` (:func:`anchored_events`; anything with
        ``start_ns`` and ``stats`` will do).  The offset is the median of
        (profiler start - ``t0``) over the events whose anchor names a span
        of this tracer; None when there is none."""
        t0_of = {r.id: r.t0 for r in self._records if isinstance(r, Span)}
        diffs = []
        for e in events:
            st = dict(e.stats)
            sid, t0 = st.get("id"), st.get("t0")
            if sid is None or t0 is None or t0_of.get(int(sid)) != t0:
                continue
            diffs.append(e.start_ns * 1e-9 - t0)
        if not diffs:
            return None
        off = statistics.median(diffs)
        return ClockFit(off, max(abs(d - off) for d in diffs), len(diffs))

    def skeleton(self) -> list:
        """The payload-free span tree: (type, name, id, parent, rid, t0, t1)
        tuples.  The trace-determinism acceptance compares THIS across
        backends — attrs may legitimately differ (e.g. ``backend=``)."""
        return [
            (d["type"], d["name"], d["id"], d["parent"], d["rid"],
             d["t0"], d.get("t1"))
            for d in self.records()
        ]

    def export_jsonl(self, path) -> None:
        """One compact JSON object per line, record order; deterministic
        byte-for-byte for a deterministic-clock run."""
        with open(path, "w") as f:
            for d in self.records():
                f.write(json.dumps(d, sort_keys=True,
                                   separators=(",", ":")) + "\n")
            if self.dropped:
                f.write(json.dumps(
                    {"type": "meta", "dropped_records": self.dropped},
                    sort_keys=True, separators=(",", ":")) + "\n")

    def export_chrome(self, path, offset_s: float = 0.0) -> None:
        """Chrome trace-event JSON: ``ph:"X"`` complete events in
        microseconds, ``tid`` = request id (-1 for global spans) so each
        request reads as one timeline row.  With a :meth:`profiler_offset`
        the timestamps are those of the profiler's Perfetto view."""
        events = []
        for d in self.records(offset_s):
            tid = -1 if d["rid"] is None else d["rid"]
            base = {"name": d["name"], "pid": 0, "tid": tid,
                    "ts": d["t0"] * 1e6, "args": d["attrs"]}
            if d["type"] == "span":
                t1 = d["t1"] if d["t1"] is not None else d["t0"]
                events.append({**base, "ph": "X",
                               "dur": (t1 - d["t0"]) * 1e6})
            else:
                events.append({**base, "ph": "i", "s": "t"})
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)

    def clear(self) -> None:
        self._records = []
        self._lexical = []
        self._open = 0
        self._next_id = 0
        self.dropped = 0


# -- lexical spans: the active tracer and jax.profiler annotations ----------

_PROFILER_ANNOTATIONS = False
_RECORDING: Tracer | None = None
_NULL = contextlib.nullcontext()


def enable_profiler_annotations() -> None:
    """Turn ``jax.profiler.TraceAnnotation`` wrapping on for every
    :func:`profile_scope` (scheduler rounds, engine steps, executor
    dispatch)."""
    global _PROFILER_ANNOTATIONS
    _PROFILER_ANNOTATIONS = True


def disable_profiler_annotations() -> None:
    global _PROFILER_ANNOTATIONS
    _PROFILER_ANNOTATIONS = False


def profiler_annotations_enabled() -> bool:
    return _PROFILER_ANNOTATIONS


def scopes_on() -> bool:
    """Whether a :func:`profile_scope` would record or annotate now."""
    return _RECORDING is not None or _PROFILER_ANNOTATIONS


@contextlib.contextmanager
def recording(tracer: Tracer | None):
    """Make ``tracer`` the one :func:`profile_scope` records into for the
    body (None records nothing); the previous one is restored after."""
    global _RECORDING
    prev, _RECORDING = _RECORDING, tracer
    try:
        yield tracer
    finally:
        _RECORDING = prev


def profile_scope(name: str, *, record: bool = True, **attrs):
    """The lexical span: a span of the :func:`recording` tracer, child of
    its innermost open lexical span, and a ``TraceAnnotation(name)`` when
    annotations are enabled.  Entering yields the span (None when nothing
    records it).  ``record=False`` keeps a scope annotation-only (one that
    fires while JAX traces a program, whose record would depend on the
    compile cache).  With neither active, a free null context."""
    tracer = _RECORDING if record else None
    if tracer is None and not _PROFILER_ANNOTATIONS:
        return _NULL
    return _Scope(tracer, name, attrs)


class _Scope:
    __slots__ = ("tracer", "name", "attrs", "span", "ann")

    def __init__(self, tracer, name, attrs):
        self.tracer, self.name, self.attrs = tracer, name, attrs
        self.span = self.ann = None

    def __enter__(self):
        if _PROFILER_ANNOTATIONS:
            from jax.profiler import TraceAnnotation

            self.ann = TraceAnnotation(self.name, **self.attrs)
            self.ann.__enter__()
        tr = self.tracer
        if tr is not None:
            # the span's clock is read right after the annotation starts,
            # so the anchor that ties the two is as close as it can be
            t0 = tr._now()
            self.span = tr._start(self.name,
                                  tr._lexical[-1] if tr._lexical else None,
                                  None, t0, self.attrs)
            tr._lexical.append(self.span)
            if self.ann is not None:
                self.ann.set_metadata(t0=t0, id=self.span.id)
        return self.span

    def __exit__(self, *exc):
        if self.ann is not None:
            self.ann.__exit__(*exc)
        if self.span is not None:
            self.tracer._lexical.pop()
            self.tracer.end(self.span)
        return False


class ProfileEvent(NamedTuple):
    """A host event of a ``jax.profiler`` capture, times in ns on the
    profiler's clock (that of its Perfetto view)."""

    name: str
    start_ns: float
    duration_ns: float
    stats: dict


def anchored_events(path) -> list:
    """The host events of a ``jax.profiler`` capture (an ``.xplane.pb``)
    that carry a :func:`profile_scope` anchor (``t0`` and ``id``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "t0" in st and "id" in st:
                    out.append(ProfileEvent(e.name, e.start_ns,
                                            e.duration_ns, st))
    return out
