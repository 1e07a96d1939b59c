"""The device's queue in the host's own books (docs/observability.md,
"The device's queue").

An engine that sends programs to the device and later waits for their
outputs already reads the clock at each of those points.  This module
keeps what it reads, and the ORDER of the waits, and from them alone —
no thread, no device sync, no profiler — says three things for a whole
run, with telemetry on or off:

* **a record a program**: when it was sent, when the host's wait for its
  output returned, whether that wait returned at once (the program had
  finished before the host asked), how many programs were still
  unretired when it was sent;
* **seconds by program**: the device runs what it is sent in order, so
  while it is never without work a program's own time is ``ready_t -
  max(sent_t, the ready_t before it)``, to within the host's wake-up;
* **the seconds the queue ran dry**: a program sent when nothing sent
  before it is unretired finds a device that has had no work since the
  last ``ready_t`` at the latest.  That interval goes to the phase the
  host was in at its MIDDLE (the innermost engine span open then, or
  ``outside_step``: the caller's time between two steps).  It is a LOWER
  bound on the device's idleness: a program that finished while the host
  was busy elsewhere is seen late.

Pure host arithmetic on a clock that is handed in, so a test drives it on
a fake one.  The engine does the waiting (``jax.block_until_ready``) and
tells the book when each wait returned.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

#: the phase while no engine span is open: the caller's own time
OUTSIDE = "outside_step"


class Phase:
    """An engine span that is also a phase of the host's time: a context
    manager that hands out the span (so ``as sp`` and ``sp.note`` work as
    they did) and tells the book where it opened and closed."""

    __slots__ = ("_book", "_span")

    def __init__(self, book: "DeviceQueueBook", name: str, span):
        self._book = book
        self._span = span
        book._edge(name)

    def __enter__(self):
        return self._span

    def __exit__(self, *exc) -> None:
        self._span.end()
        self._book._edge(None)


class DeviceQueueBook:
    """Sends and retirements of one engine's programs, in device order.

    A record is a plain dict, born at ``sent`` with ``program``,
    ``bucket``, ``sent_t``, ``ahead``, ``dry_s``, ``dry_phase`` and
    completed at ``ready`` with ``ready_t``, ``at_once``, ``run_s``; the
    engine adds what else it knows of the call and files it."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 on_device: Optional[Callable[[str, str, float], None]] = None,
                 on_dry: Optional[Callable[[str, float], None]] = None):
        self.clock = clock
        #: (program, bucket) -> seconds of waits that did not return at once
        self.device_seconds: Dict[Tuple[str, str], float] = {}
        #: phase -> seconds the queue was dry with the host in that phase
        self.dry_seconds: Dict[str, float] = {}
        #: sent, the wait for its output not yet returned: (record, that
        #: output), oldest first: the order the device runs them in
        self.pending: Deque[Tuple[dict, Any]] = deque()
        self.last_ready_t: Optional[float] = None
        self._on_device = on_device
        self._on_dry = on_dry
        self._open: List[str] = []
        #: (time, innermost span open from then on) since the last
        #: ``ready``; bounded, for a caller that steps an idle engine
        self._marks: Deque[Tuple[float, str]] = deque(maxlen=1024)

    # -- phases -----------------------------------------------------------
    @property
    def phase(self) -> str:
        """The innermost engine span open now (any thread may read)."""
        try:
            return self._open[-1]
        except IndexError:
            return OUTSIDE

    def _edge(self, name: Optional[str]) -> None:
        if name is None:
            self._open.pop()
        else:
            self._open.append(name)
        self._marks.append((self.clock(), self.phase))

    def _phase_at(self, t: float) -> str:
        for since, name in reversed(self._marks):
            if since <= t:
                return name
        return self._marks[0][1]    # older marks fell off the bounded deque

    # -- the queue --------------------------------------------------------
    def sent(self, program: str, bucket: str, out) -> dict:
        """A program's call has returned (it is on the device's queue);
        ``out`` is the output the host will wait for."""
        t = self.clock()
        rec = {"program": program, "bucket": bucket, "sent_t": t,
               "ahead": len(self.pending), "dry_s": 0.0, "dry_phase": ""}
        if not self.pending and self.last_ready_t is not None:
            dry = t - self.last_ready_t
            phase = self._phase_at(self.last_ready_t + dry / 2)
            rec["dry_s"], rec["dry_phase"] = dry, phase
            self.dry_seconds[phase] = self.dry_seconds.get(phase, 0.0) + dry
            if self._on_dry is not None:
                self._on_dry(phase, dry)
        self.pending.append((rec, out))
        return rec

    def ahead_of(self, rec: dict) -> List[Tuple[dict, Any]]:
        """What was sent before ``rec`` and not waited for yet, oldest
        first: the device runs those first, so the host waits in that
        order."""
        out = []
        for item in self.pending:
            if item[0] is rec:
                break
            out.append(item)
        return out

    def ready(self, rec: dict, at_once: bool) -> None:
        """The host's wait for ``rec``'s output has returned."""
        t = self.clock()
        for i, item in enumerate(self.pending):
            if item[0] is rec:
                del self.pending[i]
                break
        start = rec["sent_t"] if self.last_ready_t is None \
            else max(rec["sent_t"], self.last_ready_t)
        rec["ready_t"], rec["at_once"], rec["run_s"] = t, at_once, t - start
        if not at_once:
            key = (rec["program"], rec["bucket"])
            self.device_seconds[key] = \
                self.device_seconds.get(key, 0.0) + rec["run_s"]
            if self._on_device is not None:
                self._on_device(*key, rec["run_s"])
        self.last_ready_t = t
        self._marks.clear()
        self._marks.append((t, self.phase))
