"""TelemetryHub — the facade the engine owns.

One hub per engine wires registry + tracer + compile monitor + memory
sampler + exporters together and exposes exactly two cadences:

  ``record_step``  — every ``train_batch``; host-only (counter bump,
                     histogram observe, buffered JSONL write).  MUST
                     never touch a device buffer: the engine's async
                     dispatch overlap is the thing being measured.
  ``on_sync``      — at the engine's existing sync points (the periodic
                     ``steps_per_print`` metrics materialization).  This
                     is where the synced step-time histogram, memory
                     gauges, compile samples, Prometheus scrape file,
                     and flushes happen — telemetry rides the drain the
                     engine was already paying for.

``close()`` is idempotent and exports the Chrome trace.

:class:`StallWatch` (``TelemetryHub.watch_stalls``) is the one thread
telemetry starts: it only sleeps, and says when and why the whole
process stood still.
"""
from __future__ import annotations

import gc
import json
import os
import resource
import threading
import time
from typing import Callable, List, NamedTuple, Optional

from .compile_monitor import CompileMonitor
from .exporters import JsonlExporter, SummaryWriterBridge, write_prometheus
from .memory import MemorySampler
from .registry import MetricsRegistry
from . import tracing
from .tracing import TraceRecorder

EVENTS_FILE = "events.jsonl"
TRACE_FILE = "trace.json"
PROM_FILE = "metrics.prom"
FLIGHTREC_PREFIX = "flightrec_"
FLIGHTREC_VERSION = 1


def write_flight_record(directory: str, stages, step: int, reason: str,
                        error=None, extra: Optional[dict] = None) -> str:
    """Dump the fault plane's recent history as ``flightrec_<step>.json``
    (docs/observability.md: the flightrec schema).  ``stages`` maps
    stage name -> an object exposing ``flight_snapshot()`` (the
    :class:`~..runtime.stages.Stage` record).  tmp+rename so a reader
    (or a second dump racing a crash) never sees a torn record; the
    caller decides the trigger (poison, degradation, SIGTERM, anomaly,
    on demand)."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"{FLIGHTREC_PREFIX}{int(step)}.json")
    payload = {
        "version": FLIGHTREC_VERSION,
        "reason": reason,
        "step": int(step),
        "time": time.time(),
        "error": repr(error) if error is not None else None,
        "stages": {name: st.flight_snapshot()
                   for name, st in dict(stages).items()},
    }
    if extra:
        payload["extra"] = extra
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, default=repr)
    os.replace(tmp, path)
    return path


class Beat(NamedTuple):
    """What :class:`StallWatch` reads each time it wakes."""
    t: float                #: time.perf_counter
    cpu: float              #: time.process_time
    collections: List[int]  #: gc collections so far, by generation
    switches: int           #: involuntary context switches so far
    faults: int             #: major page faults so far
    compiles: float         #: jax_compiles_total


class StallWatch:
    """A freeze of the process, seen and sorted by cause.

    A thread that sleeps ``BEAT_S`` at a time; when it wakes more than
    ``STALL_S`` after its last beat, every thread of the process stood
    still that long (a thread that waits for the device releases the
    interpreter lock, and this one keeps its beat).  Each such silence is
    one ``process_stall`` event in ``events.jsonl``, one ``process_stall``
    span in ``trace.json`` and one count of
    ``process_stalls_total{cause=}``.  The event carries the wall
    seconds, the CPU seconds the process burnt in them
    (``time.process_time``), the collections of Python's collector by
    generation, the involuntary context switches and major page faults
    (``resource.getrusage``), the XLA compiles that ended in the interval
    and the engine span open when the beat came back (``phase_fn``).

    ``cause``, the first that holds: ``compile`` (a compile ended in the
    interval), ``gc`` (the oldest generation was collected), ``paging``
    (a major fault), ``busy`` (the process burnt CPU for at least half
    the silence: native code holding the interpreter lock) or
    ``descheduled`` (it did not: the host ran something else)."""
    BEAT_S = 0.02
    STALL_S = 0.1

    def __init__(self, hub: "TelemetryHub",
                 phase_fn: Optional[Callable[[], str]] = None):
        self._hub = hub
        self._phase_fn = phase_fn
        self._counter = hub.registry.counter(
            "process_stalls_total",
            "silences of the 20 ms watcher thread over 100 ms (the whole "
            "process stood still), by cause: compile, gc, paging, busy "
            "(CPU burnt under the interpreter lock) or descheduled")
        self._stop = threading.Event()
        # here, not at import: the runtime's engines import this module
        from ..runtime.stages import spawn
        self._thread = spawn(self._run, "telemetry_stall_watch")

    def _sample(self) -> Beat:
        usage = resource.getrusage(resource.RUSAGE_SELF)
        compiles = (self._hub.compile_monitor.compiles.value()
                    if self._hub.compile_monitor is not None else 0.0)
        return Beat(time.perf_counter(), time.process_time(),
                    [g["collections"] for g in gc.get_stats()],
                    usage.ru_nivcsw, usage.ru_majflt, compiles)

    def _run(self) -> None:
        last = self._sample()
        while not self._stop.wait(self.BEAT_S):
            now = self._sample()
            if now.t - last.t > self.STALL_S:
                self._record(last, now)
            last = now

    def _record(self, last: Beat, now: Beat) -> None:
        wall, cpu = now.t - last.t, now.cpu - last.cpu
        collections = [b - a for a, b in
                       zip(last.collections, now.collections)]
        data = {
            "wall_s": wall, "cpu_s": cpu, "gc_collections": collections,
            "involuntary_switches": now.switches - last.switches,
            "major_faults": now.faults - last.faults,
            "compiles": int(now.compiles - last.compiles),
            "phase": self._phase_fn() if self._phase_fn else None}
        if data["compiles"]:
            cause = "compile"
        elif collections[-1]:
            cause = "gc"
        elif data["major_faults"]:
            cause = "paging"
        else:
            cause = "busy" if cpu >= wall / 2 else "descheduled"
        data["cause"] = cause
        self._counter.inc(cause=cause)
        self._hub.jsonl.write_event("process_stall", data)
        if self._hub.tracer is not None:
            self._hub.tracer.complete("process_stall", last.t, wall,
                                      cat="runtime", **data)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)


class TelemetryHub:
    def __init__(self, output_path: str, *,
                 trace: bool = True,
                 compile_events: bool = True,
                 memory: bool = True,
                 storm_threshold: int = 3,
                 summary_writer=None,
                 process_index: int = 0):
        self.output_path = output_path
        os.makedirs(output_path, exist_ok=True)
        self.registry = MetricsRegistry()
        self.tracer = (TraceRecorder(pid=process_index)
                       if trace else None)
        self.jsonl = JsonlExporter(os.path.join(output_path, EVENTS_FILE))
        self.compile_monitor = None
        if compile_events:
            self.compile_monitor = CompileMonitor(
                self.registry, storm_threshold=storm_threshold)
            self.compile_monitor.install()
        self.memory_sampler = MemorySampler(self.registry) if memory else None
        self.bridge = (SummaryWriterBridge(self.registry, summary_writer)
                       if summary_writer is not None else None)

        self.steps_total = self.registry.counter(
            "train_steps_total", "train_batch calls")
        self.dispatch_seconds = self.registry.histogram(
            "train_dispatch_seconds",
            "host-side train_batch latency (enqueue, NOT device step "
            "time — see train_step_seconds)")
        self.step_seconds = self.registry.histogram(
            "train_step_seconds",
            "synced per-step wall time (interval average at each "
            "steps_per_print materialization)")
        self._interval_span = None
        self._stall_watch: Optional[StallWatch] = None
        self._closed = False

    def watch_stalls(self, phase_fn: Optional[Callable[[], str]] = None):
        """Start the watcher thread (:class:`StallWatch`), once;
        ``close()`` stops it."""
        if self._stall_watch is None and not self._closed:
            self._stall_watch = StallWatch(self, phase_fn)

    # -- per-step (host-only, no syncs) ---------------------------------
    def record_step(self, step: int, dispatch_s: float,
                    samples: Optional[int] = None):
        self.steps_total.inc()
        self.dispatch_seconds.observe(dispatch_s)
        data = {"step": int(step), "dispatch_s": float(dispatch_s)}
        if samples is not None:
            data["samples"] = int(samples)
        self.jsonl.write_event("step", data)

    def track_program(self, name: str, fn) -> bool:
        if self.compile_monitor is None:
            return False
        return self.compile_monitor.track(name, fn)

    def span(self, name: str, cat: str = "runtime", **args):
        """Context manager: a profiler annotation, and a ``trace.json``
        event unless tracing is disabled."""
        return tracing.span(self.tracer, name, cat, **args)

    # -- at the engine's existing sync points ---------------------------
    def on_sync(self, step: int, *, interval_s: Optional[float] = None,
                steps: Optional[int] = None,
                samples_per_step: Optional[int] = None,
                scalars: Optional[dict] = None):
        if self._closed:
            return
        avg = None
        if interval_s is not None and steps:
            avg = interval_s / steps
            self.step_seconds.observe(avg)
        sync_data = {"step": int(step)}
        if interval_s is not None:
            sync_data["interval_s"] = float(interval_s)
        if steps is not None:
            sync_data["steps"] = int(steps)
        if avg is not None:
            sync_data["step_avg_s"] = avg
        if samples_per_step is not None:
            sync_data["samples_per_step"] = int(samples_per_step)
            if avg:
                sync_data["samples_per_sec"] = samples_per_step / avg
        if scalars:
            sync_data["scalars"] = {k: float(v) for k, v in scalars.items()}
        self.jsonl.write_event("sync", sync_data)

        if self.tracer is not None:
            if self._interval_span is not None:
                self._interval_span.end(steps=steps)
            self._interval_span = self.tracer.begin(
                "train/steps_interval", cat="train")

        if self.memory_sampler is not None:
            stats = self.memory_sampler.sample()
            self.jsonl.write_event("memory", {"step": int(step),
                                              "stats": stats})
        if self.compile_monitor is not None:
            self.compile_monitor.sample()

        self.jsonl.write_snapshot(self.registry, step=step)
        self.jsonl.flush()
        try:
            write_prometheus(self.registry,
                             os.path.join(self.output_path, PROM_FILE))
        except OSError:
            # scrape file is best-effort on the training path; the JSONL
            # exporter degrades itself with a warning on the same class
            # of failure
            pass
        if self.bridge is not None:
            self.bridge.push(step)

    def dump_flight_record(self, stages, step: int, reason: str,
                           error=None,
                           extra: Optional[dict] = None) -> str:
        """Flight-record dump into this hub's output directory; see
        :func:`write_flight_record`.  Safe to call after ``close()``
        (post-mortems happen at shutdown)."""
        return write_flight_record(self.output_path, stages, step,
                                   reason, error=error, extra=extra)

    # -- shutdown -------------------------------------------------------
    def close(self):
        if self._closed:
            return
        self._closed = True
        if self._stall_watch is not None:
            self._stall_watch.stop()
        if self._interval_span is not None:
            self._interval_span.end()
            self._interval_span = None
        if self.compile_monitor is not None:
            self.compile_monitor.sample()
            self.compile_monitor.uninstall()
        try:
            write_prometheus(self.registry,
                             os.path.join(self.output_path, PROM_FILE))
        except OSError:
            pass
        self.jsonl.write_snapshot(self.registry)
        self.jsonl.close()
        if self.tracer is not None:
            try:
                self.tracer.export(
                    os.path.join(self.output_path, TRACE_FILE))
            except OSError:
                pass
