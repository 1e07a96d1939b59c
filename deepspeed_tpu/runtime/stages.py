"""One fault plane: the shared async-stage runtime.

PRs 2-6 grew four independent hand-rolled async subsystems —
``DevicePrefetcher`` (prefetch.py), ``StreamingUploader`` and the
offload pull worker (offload.py), and ``AsyncCheckpointWriter``
(resilience.py) — each with its own daemon thread, bounded queue,
poison path, drain ordering, fault-injection env var, and telemetry
wiring.  The half-swapped-tree and writer-drain bugs fixed in the
PR 3/PR 5 review rounds were all instances of the same missing
abstraction.  This module IS that abstraction (docs/stages.md): every
async stage in ``deepspeed_tpu/runtime/`` is built from the primitives
here, so failure semantics are one tested plane instead of four
slightly-different copies.

The primitives:

  ``StageWorker``      the daemon-thread handle (restart-on-crash
                       policy) — the ONLY way runtime code makes a
                       thread (jaxlint JL007 flags raw
                       ``threading.Thread`` in runtime/ outside this
                       file).
  ``Channel``          bounded FIFO with close/poison — the queue every
                       stage pair communicates through.  Poison carries
                       the ORIGINAL exception: downstream consumers
                       fail fast re-raising it, upstream producers stop.
  ``Stage``            the per-subsystem fault record: failure budget,
                       graceful degradation, surfaced post-close errors,
                       and the injection points of the unified fault
                       spec.  ``Stage.call`` wraps one unit of stage
                       work with the whole policy.
  ``WatchdogPool``     per-stage watchdog timeouts with
                       abandon-and-replace (the PR 3 ``_PullWorker``
                       idiom, generalized): one persistent worker
                       serves every guarded call; a timeout abandons
                       the wedged worker and the next call lazily gets
                       a fresh one.
  ``StageGraph``       THE documented drain order.  ``engine.close()``,
                       sync-save, and elastic restart all reduce to one
                       call — prefetch -> offload uploads -> checkpoint
                       writer -> telemetry flush (producers before
                       consumers of durability: batches are droppable,
                       an in-flight save is not).

Graceful degradation: a stage whose work keeps failing with a
TRANSIENT error (``OSError`` — the same class ``resilience.io_retry``
retries; anything else takes the subsystem's existing poison path
unchanged) is retried up to ``stages.max_stage_failures`` (default
3) consecutive times; when the budget is
exhausted the stage falls back to its inline/serial equivalent with ONE
loud warning and a ``stage_degraded_total`` counter instead of killing
the run: prefetch -> inline iteration, streamed offload -> serial
update, async save -> sync save.  A degraded stage bypasses the
injection plane entirely (its fallback is the code path that never had
the async machinery), so a genuinely broken resource still surfaces its
real error.

Fault injection (one chaos harness for every stage boundary):

  ``DS_STAGE_FAULT="<stage>:<point>:<n>[+][,...]"`` — the n-th hit
      (1-based, process-wide) of the named stage point raises an
      injected ``InjectedStageFault`` (an ``OSError``: transient class);
      a trailing ``+`` makes it STICKY (every hit >= n fails).
  ``DS_STAGE_DELAY_S="<stage>:<seconds>[,...]"`` — stage work sleeps
      this long inside its span/timing window (CPU overlap proofs).

  Back-compat aliases (kept and tested): ``DS_CKPT_FAULT=<point>:<n>[+]``
  == stage ``ckpt``; ``DS_PREFETCH_DELAY_S`` == delay of stage
  ``prefetch``; ``DS_OFFLOAD_H2D_DELAY_S`` == delay of stage
  ``offload_h2d``; ``DS_CKPT_DELAY_S`` == delay of stage ``ckpt``.

Stage names and points currently wired: ``prefetch:place``,
``offload_h2d:put``, ``offload_pull:pull``, the disk offload tier's
``disk_read:read`` / ``disk_write:write`` (runtime/disk_offload.py),
``ckpt_writer:job``, the ``ckpt`` write points
(leaf/shard_index/manifest/meta/rename/latest/read) that live inside
``runtime/checkpointing.py``, the serving engine's
``serve:admit`` / ``serve:step`` (deepspeed_tpu/inference/engine.py,
docs/serving.md), the multi-tenant adapter pool's
``adapter_fetch:fetch`` — one cold adapter's host->HBM upload
(deepspeed_tpu/inference/adapters.py, docs/serving.md "multi-tenant
serving"), and the KV tier's ``kv_spill:pageout`` /
``kv_spill:write`` / ``kv_fetch:read`` / ``kv_fetch:pagein`` — park
and resume of idle sessions' KV pages (deepspeed_tpu/inference/
kv_tier.py, docs/serving.md "KV tiering").
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils.logging import logger

__all__ = [
    "DEFAULT_MAX_STAGE_FAILURES", "FLIGHT_RING_SIZE", "InjectedStageFault",
    "WorkerAbandoned", "Channel", "Stage", "StageWorker", "StageGraph",
    "WatchdogPool", "fault_point", "injected_delay",
    "reset_fault_injection", "spawn",
]

#: default per-stage consecutive-failure budget before degradation
#: (``stages.max_stage_failures`` in the config block overrides).
DEFAULT_MAX_STAGE_FAILURES = 3

#: base delay between transient retries inside ``Stage.call`` (doubles
#: per consecutive failure, capped at 1s).  Without it one real blip —
#: microseconds long — would burn the whole budget before the condition
#: clears and permanently degrade the stage; with it the budget spans
#: ~0.35s+, the same order as ``checkpoint.io_retry``'s backoff.
RETRY_BACKOFF_BASE_S = 0.05
RETRY_BACKOFF_MAX_S = 1.0

#: per-stage flight-recorder ring length (docs/observability.md): the
#: recent structured events a ``flightrec_<step>.json`` dump preserves
#: for post-mortem — bounded so a multi-day run's recorder costs O(1)
#: memory per stage.
FLIGHT_RING_SIZE = 256


class InjectedStageFault(OSError):
    """The injected transient failure (``DS_STAGE_FAULT``).  An
    ``OSError`` so it rides the same transient class the retry planes
    (``io_retry``, the stage failure budget) already handle."""


class WorkerAbandoned(Exception):
    """Internal to the watchdog plane: a job hit a worker that was
    already stopped (another call timed out and abandoned it).
    ``WatchdogPool.call`` retries once on a fresh worker — this must
    never surface as a user-facing error on a healthy link."""


# ---------------------------------------------------------------------------
# unified fault injection
# ---------------------------------------------------------------------------
_FAULT_ENV = "DS_STAGE_FAULT"
_DELAY_ENV = "DS_STAGE_DELAY_S"
#: legacy per-subsystem delay knobs -> the stage they alias
_DELAY_ALIASES = {
    "prefetch": "DS_PREFETCH_DELAY_S",
    "offload_h2d": "DS_OFFLOAD_H2D_DELAY_S",
    "ckpt": "DS_CKPT_DELAY_S",
}

_fault_lock = threading.Lock()
_fault_hits: Dict[Tuple[str, str], int] = {}
# parsed-spec caches keyed by the raw env strings: the injection plane
# sits on per-leaf hot paths (offload pulls), so it must cost a dict
# lookup when armed and near-nothing when not
_fault_cache: Optional[Tuple[Tuple[str, str], dict]] = None
_delay_cache: Optional[Tuple[tuple, dict]] = None


def _parse_hits(n: str):
    sticky = n.endswith("+")
    if sticky:
        n = n[:-1]
    return int(n), sticky


def _fault_spec() -> dict:
    """{(stage, point): (n, sticky)} from ``DS_STAGE_FAULT`` plus the
    ``DS_CKPT_FAULT`` alias (stage ``ckpt``; unified entries win)."""
    global _fault_cache
    key = (os.environ.get(_FAULT_ENV, ""),
           os.environ.get("DS_CKPT_FAULT", ""))
    if _fault_cache is not None and _fault_cache[0] == key:
        return _fault_cache[1]
    spec: dict = {}
    for part in key[0].split(","):
        part = part.strip()
        if not part:
            continue
        bits = part.split(":")
        try:
            if len(bits) != 3:
                raise ValueError(part)
            spec[(bits[0].strip(), bits[1].strip())] = _parse_hits(
                bits[2].strip())
        except ValueError:
            logger.warning("%s: unparseable spec %r ignored (want "
                           "stage:point:n[+])", _FAULT_ENV, part)
    for part in key[1].split(","):
        part = part.strip()
        if not part or ":" not in part:
            continue
        point, n = part.split(":", 1)
        try:
            spec.setdefault(("ckpt", point.strip()),
                            _parse_hits(n.strip()))
        except ValueError:
            logger.warning("DS_CKPT_FAULT: unparseable spec %r ignored",
                           part)
    _fault_cache = (key, spec)
    return spec


def fault_point(stage: str, point: str, path: str = "") -> None:
    """Raise an :class:`InjectedStageFault` when the unified spec arms
    this (stage, point)'s current hit number.  No-op (one cached dict
    lookup) when nothing is armed."""
    arm = _fault_spec().get((stage, point))
    if arm is None:
        return
    n, sticky = arm
    with _fault_lock:
        hits = _fault_hits.get((stage, point), 0) + 1
        _fault_hits[(stage, point)] = hits
    if hits == n or (sticky and hits >= n):
        raise InjectedStageFault(
            f"injected fault at stage {stage!r} point {point!r}"
            f" (hit {hits}{'+' if sticky else ''})"
            + (f": {path}" if path else ""))


def injected_delay(stage: str) -> float:
    """Seconds of injected latency for ``stage`` work —
    ``DS_STAGE_DELAY_S`` spec entries first, then the stage's legacy
    alias env var."""
    global _delay_cache
    key = (os.environ.get(_DELAY_ENV, ""),) + tuple(
        os.environ.get(v, "") for v in _DELAY_ALIASES.values())
    if _delay_cache is None or _delay_cache[0] != key:
        spec: dict = {}
        for part in key[0].split(","):
            part = part.strip()
            if not part or ":" not in part:
                continue
            name, sec = part.rsplit(":", 1)
            try:
                spec[name.strip()] = float(sec)
            except ValueError:
                logger.warning("%s: unparseable spec %r ignored",
                               _DELAY_ENV, part)
        for name, env in _DELAY_ALIASES.items():
            raw = os.environ.get(env, "")
            if raw and name not in spec:
                try:
                    spec[name] = float(raw)
                except ValueError:
                    logger.warning("%s: unparseable value %r ignored",
                                   env, raw)
        _delay_cache = (key, spec)
    return _delay_cache[1].get(stage, 0.0)


def reset_fault_injection() -> None:
    """Clear the per-point hit counters (tests call this between cases;
    the env vars themselves are the test's to manage)."""
    with _fault_lock:
        _fault_hits.clear()


# ---------------------------------------------------------------------------
# StageWorker: the one thread constructor
# ---------------------------------------------------------------------------
class StageWorker:
    """Daemon worker thread with a restart-on-crash policy.

    ``loop`` is the stage's worker body.  Job-level failures are the
    stage's own business (caught inside the loop, routed to its poison/
    budget path); an exception ESCAPING the loop is a runtime bug that
    would otherwise kill the subsystem silently mid-training — the
    policy logs it loudly and restarts the loop up to ``restarts``
    times before letting it die.  Restarts are OPT-IN (default 0):
    every current worker body is non-reentrant (a restart would
    silently drop its in-flight item), so a loop must be written for
    re-entry before asking for them."""

    def __init__(self, loop: Callable[[], None], name: str,
                 restarts: int = 0):
        self.name = name
        self._loop = loop
        self._restarts = int(restarts)
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=name)
        self._thread.start()

    def _run(self):
        attempt = 0
        while True:
            try:
                self._loop()
                return
            except BaseException as e:
                if attempt >= self._restarts:
                    logger.error(
                        "stage worker %r crashed (no restarts left): %r",
                        self.name, e)
                    raise
                attempt += 1
                logger.error(
                    "stage worker %r crashed; restarting its loop "
                    "(%d/%d): %r", self.name, attempt, self._restarts, e)

    def is_alive(self) -> bool:
        return self._thread.is_alive()

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout=timeout)


def spawn(loop: Callable[[], None], name: str,
          restarts: int = 0) -> StageWorker:
    """Start a :class:`StageWorker` — the only sanctioned way runtime
    code makes a daemon thread (JL007)."""
    return StageWorker(loop, name, restarts=restarts)


# ---------------------------------------------------------------------------
# Channel: bounded queue with close/poison
# ---------------------------------------------------------------------------
class Channel:
    """Bounded FIFO connecting one stage to the next.

    The poison contract: ``poison(err)`` stores the ORIGINAL exception;
    consumers draining the channel receive items produced before the
    failure first, then re-raise exactly ``err`` (typed propagation —
    no wrapping); producers observe ``closed``/``err`` and stop.
    ``close()`` drops queued items and releases both sides.  All state
    is guarded by ``cond`` — stage-specific wait predicates may take
    the lock directly (``with chan.cond: chan.cond.wait_for(...)``)."""

    def __init__(self, capacity: Optional[int] = None):
        self.cond = threading.Condition()
        self.items: List[Any] = []
        self.capacity = capacity
        self.closed = False
        self.err: Optional[BaseException] = None

    def put(self, item, force: bool = False) -> bool:
        """Blocking bounded put; ``force`` bypasses the bound (end
        sentinels).  Returns False when the channel closed OR was
        poisoned while waiting — the producer's signal to stop (a
        consumer-side poison must release a producer parked on a full
        channel nobody will drain again)."""
        with self.cond:
            if not force:
                self.cond.wait_for(
                    lambda: self.closed or self.err is not None
                    or self.capacity is None
                    or len(self.items) < self.capacity)
            if self.closed or self.err is not None:
                return False
            self.items.append(item)
            self.cond.notify_all()
            return True

    def wait_space(self) -> bool:
        """Park until there is room to produce (or the channel closed/
        poisoned); True = go ahead, False = stop producing."""
        with self.cond:
            self.cond.wait_for(
                lambda: self.closed or self.err is not None
                or self.capacity is None
                or len(self.items) < self.capacity)
            return not self.closed and self.err is None

    def get(self, timeout: Optional[float] = None):
        """Pop the oldest item; queued items drain BEFORE a poison
        re-raises (the original exception) and before a close surfaces
        as ``RuntimeError("Channel is closed")``.  Consumers with richer
        semantics (the prefetcher's hit/miss stats) use ``cond``
        directly."""
        with self.cond:
            ok = self.cond.wait_for(
                lambda: self.items or self.err is not None or self.closed,
                timeout=timeout)
            if not ok:
                raise TimeoutError("Channel.get timed out")
            if self.items:
                item = self.items.pop(0)
                self.cond.notify_all()
                return item
            if self.err is not None:
                raise self.err
            raise RuntimeError("Channel is closed")

    def poison(self, err: BaseException) -> None:
        with self.cond:
            if self.err is None:
                self.err = err
            self.cond.notify_all()

    def close(self) -> None:
        with self.cond:
            self.closed = True
            self.items.clear()
            self.cond.notify_all()

    def qsize(self) -> int:
        with self.cond:
            return len(self.items)


# ---------------------------------------------------------------------------
# Stage: budget, degradation, surfaced errors, injection points
# ---------------------------------------------------------------------------
class Stage:
    """The persistent per-subsystem fault record.

    One ``Stage`` object outlives the (possibly per-step) worker
    objects of its subsystem — the engine owns one per async plane and
    threads it through, so the failure budget counts across steps and a
    degradation sticks for the rest of the run.

    ``transient`` classifies which failures are the runtime's to absorb
    (retry, then degrade): ``OSError`` — the same class ``io_retry``
    retries and the injection plane raises.  Anything else takes the
    subsystem's pre-existing poison path untouched, so the PR 3/4/5
    contracts (prefetch poison, uploader poison, writer
    poison-this-save-only) are bitwise what they were."""

    def __init__(self, name: str,
                 max_failures: Optional[int] = None,
                 allow_degraded: bool = True,
                 fallback: str = "its inline/serial equivalent",
                 transient=(OSError,)):
        self.name = name
        self.max_failures = (DEFAULT_MAX_STAGE_FAILURES
                             if max_failures is None else int(max_failures))
        self.allow_degraded = bool(allow_degraded)
        self.fallback = fallback
        self.transient = transient
        self.degraded = False
        self.failures = 0            # total transient failures absorbed
        self._consecutive = 0
        self._lock = threading.Lock()
        self._surfaced: Optional[BaseException] = None
        #: telemetry hook installed by the engine:
        #: counter_fn(name, help, amount) — None = log-only
        self.counter_fn: Optional[Callable[[str, str, float], None]] = None
        #: flight recorder: bounded ring of recent structured events
        #: (call outcomes, failures, degradation transitions, surfaced
        #: errors), each stamped with the channel depth when ``depth_fn``
        #: is installed.  deque.append is atomic; readers snapshot.
        self.events: deque = deque(maxlen=FLIGHT_RING_SIZE)
        #: optional queue-depth sampler (the owning subsystem installs
        #: its channel's qsize) — sampled into every recorded event so a
        #: dump shows the depth trajectory leading up to a failure.  May
        #: return a dict of named int gauges instead (keep a "depth"
        #: key for the primary trajectory): the serve stage samples
        #: queue depth AND the KV page pool's free-page count
        self.depth_fn: Optional[Callable[[], Any]] = None
        #: one-shot hook fired when the stage DEGRADES (the engine dumps
        #: a flight record); called outside the stage lock
        self.on_degrade: Optional[Callable[["Stage"], None]] = None

    # -- flight recorder -------------------------------------------------
    def record_event(self, kind: str, **fields) -> None:
        """Append one structured event to the bounded flight-recorder
        ring.  Host-only and cheap; the depth sample runs OUTSIDE the
        stage lock (depth_fn takes its subsystem's own lock), the
        append inside it so a concurrent ``flight_snapshot`` iteration
        never races a mutation.  A broken depth sampler must never
        break the stage."""
        ev = {"t": time.time(), "kind": kind}
        if self.depth_fn is not None:
            try:
                d = self.depth_fn()
                if isinstance(d, dict):
                    # multi-gauge sampler (the serve stage stamps queue
                    # depth, free-page count, and the live speculation
                    # accept ratio); "depth" stays the primary key
                    # diagnose's trajectory reads.  Float gauges (the
                    # accept ratio) keep their fraction — int() would
                    # truncate every ratio to 0
                    for dk, dv in d.items():
                        ev[dk] = float(dv) if isinstance(dv, float) \
                            else int(dv)
                else:
                    ev["depth"] = int(d)
            except Exception:
                pass
        ev.update(fields)
        with self._lock:
            self.events.append(ev)

    def flight_snapshot(self) -> dict:
        """Plain-data view of this stage's fault record + event ring —
        one entry of a ``flightrec_<step>.json`` dump."""
        with self._lock:
            return {"degraded": self.degraded, "failures": self.failures,
                    "max_failures": self.max_failures,
                    "fallback": self.fallback,
                    "surfaced": (repr(self._surfaced)
                                 if self._surfaced else None),
                    "events": list(self.events)}

    # -- hooks ----------------------------------------------------------
    def _count(self, name: str, help: str, n: float = 1):
        if self.counter_fn is not None:
            try:
                self.counter_fn(name, help, n)
            except Exception:  # a broken hook must never break a stage
                logger.exception("stage %r counter hook failed", self.name)

    # -- the injection boundary -----------------------------------------
    def check(self, point: str, path: str = "") -> None:
        """The stage boundary: injected delay + armed fault.  A
        DEGRADED stage skips it entirely — its fallback is the code
        path that never had the async machinery, so chaos specs cannot
        re-kill the inline equivalent (and a real failure there
        surfaces its real error)."""
        if self.degraded:
            return
        delay = injected_delay(self.name)
        if delay > 0:
            time.sleep(delay)
        fault_point(self.name, point, path)

    def is_transient(self, err: BaseException) -> bool:
        return isinstance(err, self.transient)

    # -- bookkeeping -----------------------------------------------------
    def note_ok(self) -> None:
        with self._lock:
            self._consecutive = 0

    def note_failure(self, err: BaseException,
                     attempts: Optional[int] = None) -> int:
        """Count one transient failure against the budget; returns the
        effective consecutive count (``>= max_failures`` means the
        budget is now exhausted).  The count is claimed under the lock —
        two workers sharing one Stage (train + eval prefetchers) each
        get their own exact value for backoff/logging.  ``attempts`` is
        the call-site's OWN retry count and acts as a floor: a sibling
        worker's interleaved successes reset the shared counter but
        must not let a persistently failing call-site retry unbounded.
        Crossing the threshold with ``allow_degraded`` marks the stage
        degraded — ONE loud warning + ``stage_degraded_total``."""
        with self._lock:
            self.failures += 1
            self._consecutive += 1
            n = self._consecutive
            if attempts is not None and attempts > n:
                n = attempts
            newly = (n >= self.max_failures and self.allow_degraded
                     and not self.degraded)
            if newly:
                self.degraded = True
        self.record_event("failure", error=repr(err), consecutive=n)
        self._count("stage_failures_total",
                    "transient stage failures absorbed by the runtime")
        if newly:
            logger.warning(
                "stage %r exceeded its failure budget (%d consecutive "
                "transient failures, stages.max_stage_failures=%d) — "
                "DEGRADING to %s for the rest of the run. Last error: %r",
                self.name, n, self.max_failures,
                self.fallback, err)
            self.record_event("degraded", error=repr(err),
                              fallback=self.fallback)
            self._count("stage_degraded_total",
                        "stages that fell back to their inline/serial "
                        "equivalent after exhausting the failure budget")
            if self.on_degrade is not None:
                try:  # a broken dump hook must never break the stage
                    self.on_degrade(self)
                except Exception:
                    logger.exception(
                        "stage %r on_degrade hook failed", self.name)
        return n

    # -- the policy wrapper ----------------------------------------------
    def call(self, point: str, fn: Callable[[], Any], path: str = ""):
        """Run one unit of stage work under the whole fault policy:
        injection boundary, transient retry up to the budget, then
        degradation (run ``fn`` once more OUTSIDE the injection plane —
        the inline equivalent) or, with degradation disabled, the
        original exception.  Non-transient failures propagate untouched
        on the first hit — the subsystem's own poison path."""
        if self.degraded:
            return fn()
        attempts = 0
        while True:
            try:
                t0 = time.perf_counter()
                self.check(point, path)
                out = fn()
                self.note_ok()
                self.record_event("ok", point=point,
                                  dur_s=round(time.perf_counter() - t0, 6))
                return out
            except BaseException as e:
                if not self.is_transient(e):
                    raise
                attempts += 1
                # this call-site's own attempt count floors the shared
                # counter: a sibling worker's interleaved successes
                # (train vs eval prefetcher on ONE Stage) must not let
                # a persistently failing site retry unbounded
                n = self.note_failure(e, attempts=attempts)
                if n < self.max_failures:
                    # transient retry within budget — spaced out so one
                    # real blip can't burn every attempt inside its own
                    # window (injected faults pay it too: the chaos
                    # tests prove the budget, not the timing); n is THIS
                    # thread's claimed count, race-free vs a sharing
                    # worker
                    time.sleep(min(
                        RETRY_BACKOFF_BASE_S * 2 ** (n - 1),
                        RETRY_BACKOFF_MAX_S))
                    continue
                if self.degraded:
                    return fn()  # the inline equivalent, no injection
                raise

    # -- surfaced errors (nowhere else to land) ---------------------------
    def surface(self, err: BaseException) -> None:
        """Record a failure whose natural reporting path is gone (an
        upload failing after ``close()``/``abort()`` began) so the
        engine's pre-step tick can land it in ``last_stage_error``
        instead of it vanishing with the daemon thread."""
        with self._lock:
            self._surfaced = err
        self.record_event("surfaced", error=repr(err))
        self._count("stage_errors_total",
                    "stage failures surfaced outside their normal "
                    "reporting path (post-close/post-abort)")
        logger.error("stage %r failure after close/abort (surfaced to "
                     "the engine tick): %r", self.name, err)

    def pop_error(self) -> Optional[BaseException]:
        with self._lock:
            err, self._surfaced = self._surfaced, None
            return err


# ---------------------------------------------------------------------------
# WatchdogPool: per-stage watchdog timeouts with abandon-and-replace
# ---------------------------------------------------------------------------
class _WatchdogWorker:
    """ONE persistent daemon thread serving every watchdogged call of a
    pool.  ``stop()`` flags it: jobs still queued (or submitted after —
    the sentinel race) fail fast with :class:`WorkerAbandoned` instead
    of being stranded, and the thread exits once its in-flight native
    call (if any) ever returns."""

    def __init__(self, name: str):
        self._cond = threading.Condition()
        self._q: list = []
        self._stopped = False
        spawn(self._run, name, restarts=0)

    def _run(self):
        while True:
            with self._cond:
                self._cond.wait_for(lambda: self._q or self._stopped)
                if self._stopped:
                    for _fn, box, done in self._q:  # never strand a job
                        box["e"] = WorkerAbandoned()
                        done.set()
                    self._q.clear()
                    return
                fn, box, done = self._q.pop(0)
            try:
                box["v"] = fn()
            except BaseException as e:  # surfaced to the waiting caller
                box["e"] = e
            finally:
                done.set()

    def submit(self, fn):
        box: dict = {}
        done = threading.Event()
        with self._cond:
            if self._stopped:
                box["e"] = WorkerAbandoned()
                done.set()
            else:
                self._q.append((fn, box, done))
                self._cond.notify_all()
        return box, done

    def stop(self):
        with self._cond:
            self._stopped = True
            self._cond.notify_all()


class WatchdogPool:
    """Abandon-and-replace watchdog calls over one persistent worker.

    A guarded call that stalls *inside one un-interruptible native
    call* (a bulk transfer over a failing link) cannot be interrupted
    by signals; running it on the pool's worker converts
    the forever-stall into a RuntimeError after ``timeout_s``.  The
    wedged worker is abandoned — replaced lazily on the next call — so
    later calls never queue behind a stalled one; a call landing on a
    worker another timeout just stopped retries ONCE on a fresh worker
    (that race must not masquerade as a stall).  Note the semantic
    shift vs thread-per-call: concurrent calls serialize through one
    worker, so a call's timeout window includes queue wait — acceptable
    where calls share one underlying link anyway."""

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self.worker: Optional[_WatchdogWorker] = None

    def call(self, fn: Callable[[], Any], timeout_s: float, what: str,
             timeout_msg: Optional[str] = None):
        for _attempt in range(2):
            with self._lock:
                worker = self.worker
                if worker is None:
                    worker = self.worker = _WatchdogWorker(self.name)
            box, done = worker.submit(fn)
            if not done.wait(timeout=timeout_s):
                with self._lock:
                    if self.worker is worker:
                        self.worker = None  # next call starts fresh
                worker.stop()
                raise RuntimeError(
                    timeout_msg if timeout_msg is not None else
                    f"{what} did not complete within {timeout_s:.0f}s: "
                    f"stage watchdog {self.name!r} abandoned the wedged "
                    "worker")
            if "e" in box:
                if isinstance(box["e"], WorkerAbandoned):
                    with self._lock:
                        if self.worker is worker:
                            self.worker = None
                    continue  # fresh worker, one retry
                raise box["e"]
            return box["v"]
        raise RuntimeError(
            f"{what}: watchdog {self.name!r} worker abandoned twice in a "
            "row — concurrent timeouts on this link; treat as stalled.")

    def stop(self):
        """Release the current worker (tests/teardown)."""
        with self._lock:
            worker, self.worker = self.worker, None
        if worker is not None:
            worker.stop()


# ---------------------------------------------------------------------------
# StageGraph: the documented drain order
# ---------------------------------------------------------------------------
class StageGraph:
    """Ordered registry of the engine's async planes — "drain
    everything" as one call.

    THE order (docs/stages.md) is registration order, and the engine
    registers: prefetch -> offload uploads -> checkpoint writer ->
    telemetry flush.  Rationale: stop producing work before draining
    consumers of it, and drain everything that EMITS telemetry before
    the exporters flush; prefetched batches are droppable, an in-flight
    checkpoint save is not.  ``close_all``/``drain_all`` are idempotent
    (every registered close is), never abort mid-order (a failing entry
    is collected and the rest still drain), and never raise — the
    collected errors are returned for the caller to surface."""

    def __init__(self):
        self._entries: List[Tuple[str, Callable, Optional[Callable]]] = []
        self._lock = threading.Lock()

    def register(self, name: str, close: Callable[[], None],
                 drain: Optional[Callable[[], None]] = None) -> None:
        with self._lock:
            self._entries.append((name, close, drain))

    def _run(self, which: str) -> List[Tuple[str, BaseException]]:
        with self._lock:
            entries = list(self._entries)
        errors: List[Tuple[str, BaseException]] = []
        for name, close, drain in entries:
            fn = close if which == "close" else (drain or close)
            try:
                fn()
            except BaseException as e:
                logger.error("stage graph: %s of %r failed: %r",
                             which, name, e)
                errors.append((name, e))
        return errors

    def drain_all(self) -> List[Tuple[str, BaseException]]:
        """Wait out in-flight work in drain order without tearing the
        stages down — the barrier form; the built-in sync save drains
        just the ckpt entry (its other drains are no-ops)."""
        return self._run("drain")

    def close_all(self) -> List[Tuple[str, BaseException]]:
        """Drain + stop every stage in drain order (engine.close)."""
        return self._run("close")

    @property
    def order(self) -> List[str]:
        with self._lock:
            return [name for name, _, _ in self._entries]
