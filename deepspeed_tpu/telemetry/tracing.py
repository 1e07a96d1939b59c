"""Span tracing → Chrome/Perfetto trace-event JSON + profiler annotations.

Spans are HOST-side intervals: ``span()`` stamps ``time.perf_counter``
at enter/exit and appends one complete ("ph": "X") event — no device
sync anywhere in this module.  For compiled-step work that means a span
measures *dispatch* latency, which is exactly the point: the engine
emits a ``train/steps_interval`` span at its periodic ``steps_per_print``
materialization, and that synced interval is the ground truth the
per-step dispatch spans are read against (the same discipline as
``engine._report``; see docs/observability.md).  Unlike the
``wall_clock_breakdown`` timers, tracing never adds a
``block_until_ready`` to the step path.

Every span is ALSO a ``jax.profiler.TraceAnnotation`` for its life:
whenever an xplane session runs (the engine's ``profiler`` block, the
benchmark's ``--trace 1``, an operator's ``start_trace``) the span is an
event on ``/host:CPU``, on the thread that did the work and on the
device planes' clock; with no session the annotation is a flag check.
The module-level :func:`span` is the ONE door the engines' span helpers
go through: with a recorder it records and annotates, with ``None``
(telemetry off) it only annotates — so a traced run holds the program's
spans whether or not telemetry is on.

The exported file loads in ``chrome://tracing`` / Perfetto and in
``json.loads`` — every event carries ``ph``/``ts``/``name`` (the
acceptance contract tests assert).

Causal tracing (docs/observability.md): a :class:`TraceContext` is the
lightweight identity that rides an item across a stage boundary (a
prefetched batch through its channel, a checkpoint job into the writer,
a serve request through its queue), and the ``flow_start`` /
``flow_step`` / ``flow_end`` methods emit Chrome *flow events*
(``ph: s/t/f``) that draw causal arrows between the spans enclosing
them — producer thread to consumer thread.  Flow events are plain
host-side appends emitted INSIDE already-open spans, so the tested
zero-added-device-syncs contract is untouched.  Chrome binds a flow by
the (cat, id, name) triple; emit every phase of one flow with the same
name.  ``flush_flows`` (called by ``export``) terminates flows still
open at shutdown so an aborted run's arrows don't dangle.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

_ids = itertools.count(1)


def _next_id() -> int:
    # itertools.count.__next__ is atomic under the GIL
    return next(_ids)


class TraceContext:
    """Process-wide-unique identity for one unit of work crossing a
    stage boundary.  ``trace_id`` is the Chrome flow id; ``span_id`` /
    ``parent_id`` give nested hand-offs (``child()``) a lineage without
    any global registry.  Deliberately tiny: it is attached to every
    prefetched batch and serve request on hot paths."""

    __slots__ = ("trace_id", "span_id", "parent_id")

    def __init__(self, trace_id: int, span_id: int = 0,
                 parent_id: int = 0):
        self.trace_id = int(trace_id)
        self.span_id = int(span_id)
        self.parent_id = int(parent_id)

    @classmethod
    def new(cls) -> "TraceContext":
        return cls(trace_id=_next_id())

    def child(self) -> "TraceContext":
        """A hand-off one hop further down the same flow."""
        return TraceContext(self.trace_id, span_id=_next_id(),
                            parent_id=self.span_id)

    def __repr__(self):
        return (f"TraceContext(trace_id={self.trace_id}, "
                f"span_id={self.span_id}, parent_id={self.parent_id})")


class AsyncSpan:
    """An open Chrome *async* event pair (``ph: b``/``e``), for
    intervals that overlap other instances of themselves and cross
    threads — per-request serving lifetimes.  Complete (``X``) events
    assume a per-thread call stack and mis-render overlapping,
    non-nested slices; async events are matched by (cat, id, name) and
    render on their own track.  The ``b`` is emitted at construction on
    the opening thread; ``end()`` (idempotent) emits the ``e`` wherever
    the interval actually closes."""

    __slots__ = ("_tracer", "name", "cat", "id", "_done")

    def __init__(self, tracer: "TraceRecorder", name: str, cat: str,
                 span_id: int, args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.id = int(span_id)
        self._done = False
        tracer._emit_async("b", name, cat, self.id, args)

    def end(self, **extra_args):
        if self._done:
            return
        self._done = True
        self._tracer._emit_async("e", self.name, self.cat, self.id,
                                 extra_args or None)


class SpanHandle:
    """An open span; ``end()`` closes it (idempotent).  A context
    manager, and also usable where a ``with`` block cannot bracket the
    interval — e.g. a span opened at dispatch and closed at the next
    periodic sync.

    The span is a profiler annotation from construction to ``end()``
    (it takes its args when it opens) and, when ``tracer`` is a
    :class:`TraceRecorder`, one complete event in ``trace.json``.
    ``tracer=None`` is the telemetry-off form: annotation only."""

    __slots__ = ("_tracer", "name", "cat", "args", "_start", "_done",
                 "_annotation")

    def __init__(self, tracer: Optional["TraceRecorder"], name: str,
                 cat: str, args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._done = False
        self._start = tracer._now_us() if tracer is not None else 0.0
        self._annotation = TraceAnnotation(name, **(args or {}))

    def note(self, **args):
        """Args known only once the work is done (counts at the span's
        far boundary).  They reach the recorder's event; the annotation
        took its args when it opened."""
        if self._tracer is not None:
            self.args = {**(self.args or {}), **args}

    def end(self, **extra_args):
        if self._done:
            return
        self._done = True
        self._annotation.__exit__(None, None, None)
        tracer = self._tracer
        if tracer is None:
            return
        args = dict(self.args or {})
        args.update(extra_args)
        tracer._emit_complete(self.name, self.cat, self._start,
                              tracer._now_us() - self._start,
                              args or None)

    def __enter__(self) -> "SpanHandle":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


def span(tracer: Optional["TraceRecorder"], name: str,
         cat: str = "runtime", **args) -> SpanHandle:
    """The one span door of both engines: a context manager that is a
    profiler annotation always and a ``trace.json`` event when
    ``tracer`` is a recorder (``None`` = telemetry off)."""
    return SpanHandle(tracer, name, cat, args or None)


def _pairs(args: Optional[dict]) -> Optional[tuple]:
    return tuple(args.items()) if args else None


def _as_dict(ev: tuple) -> dict:
    """The Chrome trace event of a kept tuple, keys in the order the
    recorder has always written them."""
    ph, name, cat, pid, tid, ts, extra, args = ev
    if ph == "X":
        out = {"name": name, "cat": cat, "ph": ph, "pid": pid, "tid": tid,
               "ts": ts, "dur": extra}
    else:
        out = {"name": name, "cat": cat, "ph": ph, "id": extra, "pid": pid,
               "tid": tid, "ts": ts}
        if ph == "f":
            out["bp"] = "e"  # bind to the enclosing slice, like s/t do
    if args:
        out["args"] = dict(args)
    return out


class TraceRecorder:
    """Thread-safe, bounded trace-event buffer.

    ``max_events`` bounds memory for long runs; overflow increments a
    drop counter that ``export`` records as metadata instead of silently
    truncating (the no-silent-caps rule).

    An event is kept as a tuple of scalars, ``(ph, name, cat, pid, tid,
    ts, dur or id, args as a tuple of pairs)``, and becomes a dict only in
    ``events`` / ``export``: the collector stops tracking a tuple of
    scalars once it has seen it (the pairs, the args, the event: three
    passes at most), so a full collection walks one list and not 200,000
    dicts (PERF.md section 6, PR 54: the walk was
    what the ``process_stall`` watcher filed as ``gc`` stalls)."""

    def __init__(self, process_name: str = "deepspeed_tpu",
                 pid: int = 0, max_events: int = 200_000):
        self._lock = threading.Lock()
        self._events: List[tuple] = []
        self._dropped = 0
        self._origin = time.perf_counter()
        #: the unix time of ts 0, exported so trace.json can be laid
        #: beside an xplane (whose clock is the unix epoch's)
        self._origin_unix_ns = time.time_ns()
        self.pid = pid
        self.process_name = process_name
        self.max_events = max_events
        self._tids: Dict[int, int] = {}
        #: flows started but not yet finished: flow_id -> (name, cat);
        #: flush_flows terminates them so arrows never dangle
        self._open_flows: Dict[int, Tuple[str, str]] = {}

    # -- clock / ids ----------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._origin) * 1e6

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = self._tids[ident] = len(self._tids)
            return tid

    # -- recording ------------------------------------------------------
    def _append(self, ev: tuple, force: bool = False) -> bool:
        """``force`` bypasses the cap — used ONLY for flow terminators,
        whose count is bounded by the flow starts already admitted (a
        dropped ``f`` would leave an ``s`` dangling and make diagnose
        report phantom in-flight work on a healthy capped run)."""
        with self._lock:
            if not force and len(self._events) >= self.max_events:
                self._dropped += 1
                return False
            self._events.append(ev)
            return True

    def _emit_complete(self, name: str, cat: str, ts_us: float,
                       dur_us: float, args: Optional[dict]):
        self._append(("X", name, cat, self.pid, self._tid(),
                      round(ts_us, 3), round(max(dur_us, 0.0), 3),
                      _pairs(args)))

    def span(self, name: str, cat: str = "runtime", **args) -> SpanHandle:
        return SpanHandle(self, name, cat, args or None)

    def begin(self, name: str, cat: str = "runtime", **args) -> SpanHandle:
        return SpanHandle(self, name, cat, args or None)

    def _emit_async(self, ph: str, name: str, cat: str, span_id: int,
                    args: Optional[dict]):
        self._append((ph, name, cat, self.pid, self._tid(),
                      round(self._now_us(), 3), int(span_id), _pairs(args)))

    def async_begin(self, name: str, span_id: int, cat: str = "runtime",
                    **args) -> AsyncSpan:
        """Open an async (``b``/``e``) interval — overlap-safe and
        cross-thread; use for per-request lifetimes where many
        instances of the same name run concurrently."""
        return AsyncSpan(self, name, cat, span_id, args or None)

    def complete(self, name: str, start_t: float, dur_s: float,
                 cat: str = "runtime", **args):
        """A span after the fact: one complete event from a
        ``time.perf_counter`` stamp and a duration (the recorder's clock
        is that counter), for an interval only known once it is over."""
        self._emit_complete(name, cat, (start_t - self._origin) * 1e6,
                            dur_s * 1e6, args or None)

    # -- flow events (causal arrows between spans) ----------------------
    @staticmethod
    def _flow_id(ctx) -> int:
        return ctx if isinstance(ctx, int) else int(ctx.trace_id)

    def _emit_flow(self, ph: str, name: str, cat: str, ctx,
                   args: Optional[dict]) -> bool:
        ev = (ph, name, cat, self.pid, self._tid(),
              round(self._now_us(), 3), self._flow_id(ctx), _pairs(args))
        # terminators ride past the cap: an admitted "s" must never be
        # left dangling because its "f" arrived after the buffer filled
        return self._append(ev, force=(ph == "f"))

    def flow_start(self, name: str, ctx, cat: str = "flow", **args):
        """Open a causal flow INSIDE the producer's span (``ph: s`` —
        the arrow's tail binds to the enclosing slice).  ``ctx`` is a
        :class:`TraceContext` or a bare int flow id."""
        if self._emit_flow("s", name, cat, ctx, args or None):
            with self._lock:
                self._open_flows[self._flow_id(ctx)] = (name, cat)

    def flow_step(self, name: str, ctx, cat: str = "flow", **args):
        """Intermediate hand-off (``ph: t``) — e.g. each decode tick a
        serve request participates in."""
        self._emit_flow("t", name, cat, ctx, args or None)

    def flow_end(self, name: str, ctx, cat: str = "flow", **args):
        """Terminate the flow INSIDE the consumer's span (``ph: f`` with
        ``bp: e`` — the arrowhead binds to the enclosing slice)."""
        with self._lock:
            self._open_flows.pop(self._flow_id(ctx), None)
        self._emit_flow("f", name, cat, ctx, args or None)

    def flush_flows(self) -> int:
        """Terminate every still-open flow (a poisoned stage, a request
        in flight at shutdown) so the trace has no dangling arrows;
        ``export`` calls this.  Returns the number flushed."""
        with self._lock:
            pending = list(self._open_flows.items())
            self._open_flows.clear()
        for fid, (name, cat) in pending:
            self._emit_flow("f", name, cat, fid, {"flushed": True})
        return len(pending)

    # -- introspection / export -----------------------------------------
    def events(self) -> List[dict]:
        with self._lock:
            kept = list(self._events)
        return [_as_dict(ev) for ev in kept]

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def export(self, path: str):
        """Write the Chrome trace-event JSON object form."""
        self.flush_flows()
        events, dropped = self.events(), self.dropped
        meta = [{"name": "process_name", "ph": "M", "pid": self.pid,
                 "tid": 0, "ts": 0,
                 "args": {"name": self.process_name}}]
        payload = {"traceEvents": meta + events,
                   "displayTimeUnit": "ms",
                   "otherData": {"origin_unix_ns": self._origin_unix_ns}}
        if dropped:
            payload["otherData"]["dropped_events"] = dropped
        with open(path, "w") as f:
            json.dump(payload, f)
        return path
