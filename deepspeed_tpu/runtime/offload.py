"""ZeRO-Offload — host-resident optimizer tier.

The reference's offload path (reference: deepspeed/runtime/zero/
stage2.py:743-900 + csrc/adam/cpu_adam.cpp) stages gradients into pinned
host buffers during backward, runs the AVX CPU Adam over host fp32
partitions, and copies fp16 params back to the GPU with a fused kernel.

The TPU shape of the same idea, given XLA's execution model:

  device (one jitted function): forward + backward + grad unscale/clip +
      overflow check — everything that wants the MXU.
  host: fp32 master + both moments live in numpy (host RAM — the HBM
      those buffers would occupy is what ZeRO-Offload frees); the native
      CPU Adam (ops/cpu_adam.py) updates them and emits bf16 upload copies
      in the same pass, which are device_put back as the next step's
      compute params.

Two controllers' worth of scope:

  HostOffloadOptimizer — single-controller: the one host stages the FULL
      gradient and owns the full master (the dp=1 / single-process case).
  ShardedHostOffloadOptimizer — multi-host: each process stages ONLY its
      addressable shards of the dp-sharded master/gradients (the
      reference's per-DP-rank fp32 partitions, stage2.py:743-900) and
      C++-Adams them; compute params are reassembled ON DEVICE by one
      jitted all-gather, so no host ever touches another rank's bytes.

Loss-scale skip/update bookkeeping runs on host (it is per-step control
flow, exactly what the reference does in Python, stage2.py:1341-1362).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.cpu_adam import DeepSpeedCPUAdam, is_adam_float, lowp_np_dtype
from ..utils.logging import logger
from .stages import (Stage, WatchdogPool, fault_point, injected_delay,
                     spawn)

# ---------------------------------------------------------------------------
# telemetry hook: per-pull transfer spans.  Module-level because the pull
# helpers below are free functions shared by both offload tiers; the
# engine installs its hub's tracer at construction (last telemetry-
# enabled engine wins — acceptable for a process-wide transfer log).
# Spans stamp host wall-clock around calls that ALREADY block on the
# transfer, so no sync is added anywhere.
# ---------------------------------------------------------------------------
_TRANSFER_TRACER = None


def set_transfer_tracer(tracer):
    """Install (or clear, with None) the tracer that receives
    ``offload/d2h`` spans from the guarded pull helpers."""
    global _TRANSFER_TRACER
    _TRANSFER_TRACER = tracer


def _transfer_span(name: str, cat: str = "transfer", **args):
    tracer = _TRANSFER_TRACER
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(name, cat=cat, **args)


class UploadAborted(RuntimeError):
    """``StreamingUploader.finish()`` raced a concurrent ``abort()``:
    the upload set is incomplete by design — the caller's poison path
    (not a partial publish) is the only valid continuation."""


#: the shared watchdog plane for every guarded D2H pull in this process
#: (the PR 3 ``_PullWorker`` idiom, now the stage runtime's
#: ``WatchdogPool`` — see runtime/stages.py / docs/stages.md).
_PULL_POOL = WatchdogPool("ds-offload-pull")


def _watchdog_get(x, timeout_s: float, what: str = "D2H transfer"):
    """jax.device_get guarded by the shared watchdog pool.

    A bulk transfer over a slow or failing host link can stall *inside
    one native call*, un-interruptible by signals.  Running the pull on
    the pool's persistent worker converts the forever-stall into a
    RuntimeError after ``timeout_s``;
    the stalled worker is abandoned (replaced lazily on the next pull),
    which costs this process its device handle but keeps the failure
    clean and lets the caller fall back to another tier instead of
    hanging the session.
    """
    nbytes = getattr(x, "nbytes", 0)

    def _pull():
        # the ``offload_pull:pull`` chaos boundary (docs/stages.md) runs
        # ON the pool's worker, so an injected delay exercises the real
        # watchdog timeout/abandon path, not just the caller's wait
        delay = injected_delay("offload_pull")
        if delay > 0:
            time.sleep(delay)
        fault_point("offload_pull", "pull")
        return np.asarray(jax.device_get(x))

    return _PULL_POOL.call(
        _pull, timeout_s, what,
        timeout_msg=(
            f"{what} ({nbytes >> 20} MB) did not complete within "
            f"{timeout_s:.0f}s: bulk D2H appears stalled on this "
            "host link. Aborting the pull piece-wise instead of "
            "hanging the session; use offload_impl='xla' here."))


def pull_chunk_bytes() -> int:
    """Piece size for guarded device->host pulls (DS_OFFLOAD_PULL_CHUNK_MB,
    default 64 MB; <=0 disables chunking).  Exposed so the engine can
    skip ``copy_to_host_async`` for leaves that will be pulled piece-wise
    anyway — a full-leaf async copy alongside the slice pulls would move
    every large leaf over the wire twice."""
    return int(float(os.environ.get("DS_OFFLOAD_PULL_CHUNK_MB", "64"))
               * (1 << 20))


def chunked_device_get(x, chunk_mb: Optional[float] = None,
                       piece_timeout: Optional[float] = None,
                       what: str = "master pull", out=None):
    """Piece-wise device->host pull with a per-piece watchdog.

    The reference's offload path never moves its state in one shot — it
    staggers pinned-buffer copies tile by tile (reference:
    csrc/adam/cpu_adam.cpp:64-113).  Here the motivation is robustness as
    much as overlap: one monolithic ``device_get`` of a multi-GB stacked
    scan leaf is a single native call that can stall forever on a sick
    link, and nothing can interrupt it.  Pulling ~chunk_mb slices along
    the leading axis bounds each native call, so a sick link costs a
    clean per-tier RuntimeError within one piece-timeout instead of a
    hung process.

    Pieces are FLAT element ranges (the leaf is viewed 1-D on device, a
    free row-major rebitcast), so every piece is <= chunk_mb regardless
    of the leaf's shape — a (2, huge) or (1, huge) leaf must not sneak a
    multi-GB native call under the per-piece timeout, or slow links
    misclassify as stalled on exactly the leaves that matter.

    ``out``: optional preallocated destination (any assignment-compatible
    dtype) — pieces are written straight into its slices, keeping peak
    host memory at 1x the leaf (the offload host is RAM-pressured by
    design; a transient second copy is exactly what it cannot afford).

    Knobs: DS_OFFLOAD_PULL_CHUNK_MB (default 64, 0 disables chunking),
    DS_OFFLOAD_PULL_TIMEOUT seconds per piece (default 120, 0 disables
    the watchdog too).
    """
    if chunk_mb is not None:
        chunk_bytes = int(chunk_mb * (1 << 20))
    else:
        chunk_bytes = pull_chunk_bytes()
    if piece_timeout is None:
        piece_timeout = float(
            os.environ.get("DS_OFFLOAD_PULL_TIMEOUT", "120"))

    def _deliver(arr):
        if out is None:
            return arr
        out[...] = arr
        return out

    if not isinstance(x, jax.Array):
        return _deliver(np.asarray(x))
    with _transfer_span("offload/d2h", what=what,
                        bytes=int(getattr(x, "nbytes", 0))):
        if piece_timeout <= 0:
            return _deliver(np.asarray(jax.device_get(x)))
        if chunk_bytes <= 0 or x.nbytes <= chunk_bytes or x.ndim == 0:
            return _deliver(_watchdog_get(x, piece_timeout, what))
        dt = np.dtype(x.dtype)
        elems_per = max(1, chunk_bytes // dt.itemsize)
        flat = x.reshape(-1)
        n = flat.shape[0]
        if out is None:
            out = np.empty(x.shape, dt)
        if out.flags.c_contiguous and out.size == n:
            out_flat = out.reshape(-1)
        else:  # exotic destination: pull to a temp flat, assign once
            out_flat = np.empty(n, out.dtype)
        for start in range(0, n, elems_per):
            out_flat[start:start + elems_per] = _watchdog_get(
                flat[start:start + elems_per], piece_timeout,
                f"{what} piece [{start}:{start + elems_per}]")
        if out_flat.base is not out and out_flat is not out:
            out[...] = out_flat.reshape(out.shape)
        return out


class _PrefetchPuller:
    """Chunked, watchdogged, bounded-lookahead grad pull — ONE worker
    thread per step.

    The construction-time probe certifies the link ONCE; this guard holds
    for every step after.  Each leaf goes through ``chunked_device_get``,
    so stall detection is PROGRESS-based (per ~64 MB piece): a slow but
    working link keeps completing pieces and never misfires the watchdog,
    while a genuine stall raises within one piece-timeout — the
    distinction a whole-leaf deadline cannot make on multi-GB stacked
    scan leaves.

    The single daemon worker pulls leaves in flatten order AHEAD of the
    consumer (the C++ Adam loop), so later transfers overlap earlier
    leaves' compute without a thread spawn per leaf.  Lookahead is
    bounded: the worker stays at most LOOKAHEAD leaves past the highest
    index the consumer has asked for, keeping the prefetch buffer at a
    few leaves — not a full extra gradient tree on the RAM-pressured
    offload host.  Dtypes are preserved (casting is the consumer's
    business).  A pull failure poisons all remaining slots with the same
    error and surfaces to the engine's attempt chain.
    """

    LOOKAHEAD = 2

    def __init__(self, tree, what: str = "grad pull"):
        self._cond = threading.Condition()
        self._want = -1
        self._closed = False
        # best-effort transfer accounting (written by the worker, read by
        # the owner after consumption finishes) — feeds the pipeline's
        # d2h row in the engine's per-step breakdown
        self.seconds = 0.0
        self.bytes = 0
        order = []
        self._slots: dict = {}
        for idx, g in enumerate(jax.tree.leaves(tree)):
            ev, box = threading.Event(), {}
            self._slots.setdefault(id(g), []).append((idx, ev, box))
            order.append((idx, g, ev, box))

        def work():
            for pos, (idx, g, ev, box) in enumerate(order):
                with self._cond:
                    self._cond.wait_for(
                        lambda: self._closed
                        or self._want + self.LOOKAHEAD >= idx)
                    if self._closed:
                        return  # consumer is done; drop the tree refs
                try:
                    t0 = time.perf_counter()
                    box["v"] = chunked_device_get(g, what=what)
                    self.seconds += time.perf_counter() - t0
                    self.bytes += int(getattr(g, "nbytes", 0))
                except BaseException as e:
                    box["e"] = e
                    ev.set()
                    # the link is sick: fail every later slot immediately
                    # rather than burning one piece-timeout per leaf
                    for _, _, ev2, box2 in order[pos + 1:]:
                        box2["e"] = e
                        ev2.set()
                    return
                ev.set()

        spawn(work, name="ds-offload-grad-prefetch", restarts=0)

    def __call__(self, g):
        idx, ev, box = self._slots[id(g)].pop(0)
        with self._cond:
            if idx > self._want:
                self._want = idx
                self._cond.notify_all()
        # no outer deadline needed: the worker cannot wedge (every native
        # pull inside it is piece-watchdogged) — it always sets the event
        ev.wait()
        if "e" in box:
            raise box["e"]
        return box["v"]

    def close(self):
        """Release the worker.  The consumer may legitimately skip
        trailing leaves (the Adam loop never requests non-fp32 ones), and
        a parked worker would otherwise wait forever holding a reference
        to every grad Array — one leaked thread plus one pinned gradient
        tree PER STEP.  Call from a finally block once consumption is
        done; un-pulled slots are failed so a late (buggy) request raises
        instead of hanging."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        for lst in self._slots.values():
            for _idx, ev, box in lst:
                if not ev.is_set():
                    box.setdefault("e", RuntimeError(
                        "_PrefetchPuller closed before this leaf was "
                        "requested"))
                    ev.set()


def guarded_tree_pull(tree):
    """Dtype-preserving watchdogged pull of every leaf in ``tree``.
    Used for the DPU pending-grad stash (engine keeps HOST copies so the
    device grad tree can be freed) — preserving dtype keeps the stash at
    1x the grads' bytes."""
    puller = _PrefetchPuller(tree)
    try:
        return jax.tree.map(puller, tree)
    finally:
        puller.close()


def device_put_leaf(arr, sharding):
    """H2D for ONE updated leaf (single-controller streaming pipeline).
    A module hook rather than an inline ``jax.device_put`` so tests can
    inject transfer failures/delays without patching jax globally."""
    return jax.device_put(arr, sharding)


def _batched_device_put_pairs(blks, devices):
    """ONE batched transfer call placing ``blks[i]`` on ``devices[i]``
    (the list form of ``jax.device_put`` dispatches them together) —
    replicated small leaves must not pay one client round-trip per
    replica device.  ``devices`` entries may be Devices OR Shardings
    (both are valid ``device_put`` destinations).  The one
    implementation: the serial ``_assemble``, the streamed
    ``upload_block``, and the engine's ``_shard_batch`` all route
    through here."""
    if not blks:
        return []
    return list(jax.device_put(list(blks), list(devices)))


def _batched_device_put(blk, devices):
    """Replicate one host block onto every device in ``devices`` with a
    single batched call."""
    return _batched_device_put_pairs([blk] * len(devices), devices)


class StreamingUploader:
    """Third stage of the streaming offload update pipeline: a single
    worker thread that issues H2D uploads for updated leaves WHILE the
    CPU Adam continues on later leaves.

    The consumer loop (``HostOffloadOptimizer.step`` /
    ``ShardedHostOffloadOptimizer`` with an ``on_leaf`` callback) calls
    ``submit(idx, arr)`` the moment leaf ``idx``'s block is written; the
    worker runs ``put_fn(idx, arr)`` off-thread, so a put that blocks on
    the actual transfer still overlaps the remaining host compute — with
    D2H prefetch (``_PrefetchPuller``) this closes the pipeline: leaf
    i+1's grad pull, leaf i's Adam, and leaf i-1's upload are all in
    flight at once.

    ``finish()`` drains the queue, re-raises the first failure, and
    returns ``(results, timings)``: ``results[idx]`` is ``put_fn``'s
    value, ``timings`` is ``[(idx, t_start, t_end, nbytes), ...]`` in
    host ``perf_counter`` seconds — the engine's overlap accounting
    (``offload/overlap_ratio``) reads these against the Adam window.
    Each upload also emits a per-leaf ``offload/h2d_params`` span on the
    module transfer tracer.

    On a NON-TRANSIENT failure the worker stops touching the device and
    ``finish()`` raises; the caller must then POISON the optimizer and
    leave its old compute-param tree in place (the master already
    carries step t, the device would keep step t-1 — the half-swapped
    state the pipeline contract forbids).  TRANSIENT failures (OSError —
    the stage runtime's retryable class) are retried against the same
    leaf up to the ``offload_h2d`` stage's failure budget; exhausting it
    DEGRADES the stage: this upload still completes (the inline
    equivalent, outside the injection plane) and the engine takes the
    serial update path from the next step on.

    Fault injection rides the unified spec (docs/stages.md):
    ``DS_STAGE_FAULT=offload_h2d:put:n[+]`` injects put failures and
    ``DS_STAGE_DELAY_S=offload_h2d:sec`` (alias: the legacy
    ``DS_OFFLOAD_H2D_DELAY_S``) sleeps INSIDE each span/timing window,
    emulating a slow PCIe link so a CPU run can measure real overlap.
    """

    def __init__(self, put_fn, what: str = "offload/h2d_params",
                 stage: Optional[Stage] = None):
        self._put = put_fn
        self._what = what
        # the engine threads its persistent ``offload_h2d`` Stage record
        # through so the failure budget counts across steps; standalone
        # constructions get a private one
        self._stage = stage if stage is not None else Stage("offload_h2d")
        self._q: list = []
        self._cond = threading.Condition()
        self._closed = False
        self._aborted = False
        self._err: Optional[BaseException] = None
        self._err_surfaced = False  # guarded by _cond: surface() once
        self._finish_owns_err = False  # finish() claimed it for re-raise
        self._done = threading.Event()
        self.results: dict = {}
        self.timings: list = []
        spawn(self._work, name="ds-offload-h2d", restarts=0)

    def _put_and_drain(self, idx: int, arr):
        out = self._put(idx, arr)
        # drain the transfer INSIDE the span/timing window: device_put
        # only dispatches, so without this the timings (and
        # overlap_ratio) would measure enqueue latency (the JL006 bug
        # class) — and an async transfer failure would escape the poison
        # contract by surfacing after finish() already succeeded.
        # Off-thread, so the Adam loop still overlaps.
        jax.block_until_ready(out)
        return out

    def _work(self):
        while True:
            with self._cond:
                self._cond.wait_for(lambda: self._q or self._closed)
                if not self._q:
                    break  # closed and drained
                idx, arr, ctx = self._q.pop(0)
            if self._err is not None:
                continue  # poisoned: drain submissions, touch nothing
            nbytes = int(getattr(arr, "nbytes", 0))
            t0 = time.perf_counter()
            try:
                with _transfer_span(self._what, leaf=idx, bytes=nbytes):
                    tracer = _TRANSFER_TRACER
                    if ctx is not None and tracer is not None:
                        # arrowhead inside this upload's span
                        tracer.flow_end("offload/upload", ctx,
                                        cat="offload", leaf=idx)
                    # the stage boundary: injected delay + fault,
                    # transient retry up to the budget, then degradation
                    # (the put still completes; the engine checks
                    # stage.degraded before the NEXT step)
                    out = self._stage.call(
                        "put", lambda: self._put_and_drain(idx, arr))
            except BaseException as e:  # re-raised from finish()
                with self._cond:
                    self._err = e
                    # exactly-once vs a concurrent abort(): whoever
                    # claims the flag under the lock does the surfacing
                    surface = self._aborted and not self._err_surfaced
                    if surface:
                        self._err_surfaced = True
                if surface:
                    # abort() already ran: nobody will call finish(), so
                    # without this the failure would vanish with the
                    # daemon thread — route it through the shared
                    # surfaced-error path (engine tick -> last_stage_error)
                    self._stage.surface(e)
                continue
            self.results[idx] = out
            self.timings.append((idx, t0, time.perf_counter(), nbytes))
        self._done.set()

    def submit(self, idx: int, arr):
        """Enqueue leaf ``idx``'s updated host block (called from the
        Adam loop; never blocks on the transfer).  Each upload carries a
        TraceContext: the flow opened here (inside the Adam loop's leaf
        span) terminates inside the worker's ``offload/h2d_params``
        span, drawing the Adam->upload causal arrow in trace.json."""
        ctx = None
        tracer = _TRANSFER_TRACER
        if tracer is not None and hasattr(tracer, "flow_start"):
            from ..telemetry.tracing import TraceContext
            ctx = TraceContext.new()
            tracer.flow_start("offload/upload", ctx, cat="offload",
                              leaf=idx)
        with self._cond:
            self._q.append((idx, arr, ctx))
            self._cond.notify_all()

    def finish(self):
        """Close the queue, wait for every upload, raise the first
        failure.  NOT watchdogged: the upload direction shares the probe
        warning's contract (a stalled H2D hangs — see
        ``_probe_transfer_path``).  A concurrent ``abort()`` (a close
        landing mid-step from another thread/signal handler) raises
        :class:`UploadAborted` instead of returning partial results —
        the caller's except path must poison, never publish a
        half-uploaded step."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._done.wait()
        with self._cond:
            err = self._err
            # claim the error under the exactly-once flag: a concurrent
            # abort() must not ALSO surface it through the stage record
            # (one failure, one report).  Ownership is remembered so a
            # REPEATED finish() keeps raising (poison invariant: never
            # return partial results while an error is recorded).
            if err is not None and not self._err_surfaced:
                self._err_surfaced = True
                self._finish_owns_err = True
            owns = self._finish_owns_err
            aborted = self._aborted
        if err is not None and owns:
            raise err
        # err set but surfaced by abort()/the worker before finish()
        # could claim it: the real error is on the stage record; the
        # step still must poison — fall through to the abort raise
        # (aborted is necessarily True on that arm)
        if aborted:
            raise UploadAborted(
                "streamed offload upload aborted mid-step (engine close/"
                "abort): queued uploads were dropped; the step must "
                "poison, not publish")
        return self.results, self.timings

    def abort(self):
        """Release the worker without waiting (the Adam side failed, or
        the engine is closing mid-flight: the caller's exception is the
        one that matters; queued uploads are dropped).  The in-flight
        put, if any, finishes in the background — a failure there (or
        one already recorded that no ``finish()`` has claimed for
        re-raise) is surfaced through the stage record instead of being
        dropped on the floor; the ``_err_surfaced`` flag keeps the
        worker/abort/finish triple exactly-once."""
        with self._cond:
            self._closed = True
            self._aborted = True
            self._q.clear()
            err = self._err
            # exactly-once vs the worker's own post-abort surfacing
            surface = err is not None and not self._err_surfaced
            if surface:
                self._err_surfaced = True
            self._cond.notify_all()
        if surface:
            self._stage.surface(err)


class HostOffloadOptimizer:
    """Owns the host-side master params + moments and the upload cast."""

    def __init__(self, master_params, lr, betas, eps, weight_decay,
                 adamw_mode: bool = True, bias_correction: bool = True,
                 compute_dtype=jnp.bfloat16,
                 use_native: Optional[bool] = None):
        # pull master to host numpy once; it never goes back whole.  The
        # pull is piece-wise with a per-piece watchdog (chunked_device_get)
        # so a sick link fails this tier cleanly instead of wedging the
        # device inside one un-interruptible multi-GB native call.
        # fp32-promote only floating leaves — integer/bool buffers keep
        # their dtype and are never touched by Adam (same rule the engine
        # applies building the master, engine.py master cast).
        def to_host(x):
            if is_adam_float(x.dtype):
                # pull pieces straight into the fp32 master buffer —
                # cast-on-assign, no transient full-leaf copy
                out = np.empty(np.shape(x), np.float32)
                return chunked_device_get(x, what="master pull", out=out)
            return np.array(chunked_device_get(x, what="master pull"))

        self._probe_transfer_path(master_params)
        self._poisoned: Optional[BaseException] = None
        self.last_d2h_seconds = 0.0  # last step's grad-pull wall time
        self.master = jax.tree.map(to_host, master_params)
        self.opt = DeepSpeedCPUAdam(
            lr=lr, betas=betas, eps=eps, weight_decay=weight_decay,
            adamw_mode=adamw_mode, bias_correction=bias_correction,
            use_native=use_native)
        self.compute_dtype = compute_dtype
        self._out_dtype = ("bfloat16" if compute_dtype == jnp.bfloat16
                           else "float16" if compute_dtype == jnp.float16
                           else None)

    @staticmethod
    def _probe_transfer_path(master_params, min_mbps: float = None,
                             probe_timeout: float = None):
        """Fail FAST if bulk device->host transfers are broken.

        The host tier is single-controller: it pulls the full fp32 master
        to this process and re-uploads compute params every step.  A
        bulk transfer over a failing link can stall *indefinitely*,
        un-interruptible by SIGALRM because the wait is inside one
        native call.  Probing a single ~4 MB pull in a worker thread
        converts that forever-stall into a clean RuntimeError.  On a
        healthy host link the probe costs one microseconds-scale copy.

        A probe that COMPLETES but measures under min_mbps is a working
        (just slow) link: that case logs a loud warning and proceeds —
        each subsequent bulk pull is chunked + watchdogged, so a link
        that later degrades into a stall still fails cleanly.  Set
        DS_OFFLOAD_SLOW_LINK=error to restore the hard failure (a
        measurement should: a slow link there fails the run instead of
        eating the window).

        Knobs: DS_OFFLOAD_MIN_MBPS (default 8; 0 disables),
        DS_OFFLOAD_PROBE_TIMEOUT seconds (default 60),
        DS_OFFLOAD_SLOW_LINK = warn|error (default warn).
        """
        if min_mbps is None:
            min_mbps = float(os.environ.get("DS_OFFLOAD_MIN_MBPS", "8"))
        if probe_timeout is None:
            probe_timeout = float(
                os.environ.get("DS_OFFLOAD_PROBE_TIMEOUT", "60"))
        if min_mbps <= 0:
            return
        leaves = [x for x in jax.tree.leaves(master_params)
                  if hasattr(x, "nbytes")]
        if not leaves:
            return
        # largest leaf capped to ~4 MB worth of leading rows
        leaf = max(leaves, key=lambda x: x.nbytes)
        if leaf.nbytes > 4 << 20 and leaf.ndim >= 1 and leaf.shape[0] > 1:
            rows = max(1, int(leaf.shape[0] * (4 << 20) / leaf.nbytes))
            leaf = leaf[:rows]
        nbytes = leaf.nbytes
        if nbytes < 1 << 20:  # tiny models: nothing worth probing
            return
        # _watchdog_get runs the pull in an abandoned-on-timeout daemon
        # thread (see its docstring) AND propagates device_get exceptions
        # — a dead-link XlaRuntimeError must fail the probe, not be
        # swallowed into a fast-looking measurement.
        t0 = time.perf_counter()
        _watchdog_get(leaf, probe_timeout, "device->host transfer probe")
        dt = time.perf_counter() - t0
        mbps = (nbytes / (1 << 20)) / max(dt, 1e-9)
        if mbps < min_mbps:
            msg = (
                f"device->host transfer probe measured {mbps:.1f} MB/s "
                f"(< {min_mbps} MB/s): the host offload tier would take "
                "minutes per step at this bandwidth. Use "
                "offload_impl='xla', or set DS_OFFLOAD_MIN_MBPS=0 to "
                "skip this probe.")
            if os.environ.get("DS_OFFLOAD_SLOW_LINK", "warn") == "error":
                raise RuntimeError(msg)
            logger.warning(
                "%s Proceeding anyway (DS_OFFLOAD_SLOW_LINK=warn); every "
                "device->host pull (bulk + per-step grads) is chunked "
                "with a per-piece progress watchdog, so slow links keep "
                "working and only a genuine pull-side stall fails "
                "cleanly. The per-step param re-UPLOAD is not guarded — "
                "if the upload direction stalls, the process hangs; set "
                "DS_OFFLOAD_SLOW_LINK=error to hard-fail instead.", msg)

    @property
    def is_native(self) -> bool:
        return self.opt.is_native

    def compute_params(self):
        """Initial low-precision copies for the device (non-floating
        leaves pass through unchanged)."""
        from ..ops.cpu_adam import lowp_np_dtype
        dt = lowp_np_dtype(self._out_dtype)

        def cast(x):
            if dt is None or x.dtype != np.float32:
                return x.copy()
            return x.astype(dt)

        return jax.tree.map(cast, self.master)

    def step(self, host_grads, on_leaf: Optional[Callable] = None):
        """Update master/moments in place; return upload copies in the
        configured compute dtype (fp32 configs get fp32 copies — no silent
        bf16 downgrade).  Grad leaves may be numpy OR jax Arrays — the
        inner optimizer converts per leaf via a watchdogged pull, which
        lets the engine overlap D2H transfers with the C++ Adam compute
        while a link that degrades into a stall MID-TRAINING still fails
        cleanly (the construction-time probe only certifies the link once;
        this guard holds for every step after; see _PrefetchPuller).

        ``on_leaf(i, upload_leaf)`` (optional) fires the moment leaf
        ``i``'s block is written — the streaming pipeline's hook: the
        engine submits each leaf to its H2D uploader while the Adam loop
        continues, so the re-upload overlaps the remaining host compute
        instead of serializing after it.  The returned tree holds the
        same objects the callback saw.

        A mid-step pull failure leaves master/moments PARTIALLY updated
        (leaves before the failing one carry step t, later ones do not,
        and the inner step counter advanced) — an inconsistency the
        old always-hang behavior could not produce.  The optimizer
        therefore POISONS itself: further step()/state_tree() calls
        refuse with a clear error so the inconsistent state can neither
        keep training nor be serialized; load_state_tree (checkpoint
        restore) clears the poison."""
        if self._poisoned is not None:
            raise RuntimeError(
                "HostOffloadOptimizer is poisoned: a previous step failed "
                "mid-update, leaving master/moments inconsistent. Restore "
                f"from a checkpoint. Original error: {self._poisoned!r}")
        leaf_get = _PrefetchPuller(host_grads)
        p_leaves, treedef = jax.tree.flatten(self.master)
        outs: list = [None] * len(p_leaves)
        try:
            for i, out in self.opt.step_leaves(
                    self.master, host_grads, out_dtype=self._out_dtype,
                    leaf_get=leaf_get,
                    leaf_span=lambda i: _transfer_span(
                        "offload/adam_leaf", cat="offload", leaf=i)):
                # fp32 configs upload fp32 copies of the freshly-updated
                # master leaf (the no-downgrade rule, same values the old
                # post-step tree.map(copy) produced)
                up = out if out is not None else p_leaves[i].copy()
                outs[i] = up
                if on_leaf is not None:
                    on_leaf(i, up)
        except BaseException as e:
            self._poisoned = e
            raise
        finally:
            self.last_d2h_seconds = leaf_get.seconds
            leaf_get.close()
        return jax.tree.unflatten(treedef, outs)

    def poison(self, err: BaseException):
        """Mark the optimizer inconsistent from OUTSIDE the step — the
        engine's streaming pipeline calls this when an H2D upload fails
        AFTER the Adam completed: the host master already carries step t
        while the device would keep step t-1 params, a mismatch that
        must not keep training or serialize (load_state_tree clears)."""
        self._poisoned = err

    # -- checkpoint plumbing -------------------------------------------
    def state_tree(self):
        """Optimizer state as a pytree aligned with the master params
        (what the engine stores in TrainState.opt_state and the
        checkpointer serializes).  Refuses while poisoned — serializing a
        partially-updated master/moment set would turn a clean failure
        into silent divergence on restore."""
        if self._poisoned is not None:
            raise RuntimeError(
                "refusing to serialize inconsistent optimizer state (a "
                "step failed mid-update). Restore from an earlier "
                f"checkpoint. Original error: {self._poisoned!r}")
        leaves, treedef = jax.tree.flatten(self.master)
        mu, nu = [], []
        for i, leaf in enumerate(leaves):
            m, v = self.opt._moments(i, leaf)
            mu.append(m)
            nu.append(v)
        return {"step": np.asarray(self.opt.step_count, np.int64),
                "mu": jax.tree.unflatten(treedef, mu),
                "nu": jax.tree.unflatten(treedef, nu)}

    def load_state_tree(self, master_tree, opt_tree):
        """In-place restore (buffer identity preserved so the numpy views
        the native kernel updates stay the engine's state)."""
        self._poisoned = None  # restore re-establishes a consistent state
        def copy_into(dst, src):
            chunked_device_get(src, what="restore pull", out=dst)
        jax.tree.map(copy_into, self.master, master_tree)
        self.opt.step_count = int(np.asarray(
            jax.device_get(opt_tree["step"])))
        leaves = jax.tree.leaves(self.master)
        mu = jax.tree.leaves(opt_tree["mu"])
        nu = jax.tree.leaves(opt_tree["nu"])
        for i, leaf in enumerate(leaves):
            m, v = self.opt._moments(i, leaf)
            chunked_device_get(mu[i], what="restore pull", out=m)
            chunked_device_get(nu[i], what="restore pull", out=v)


def _index_key(index) -> tuple:
    """Hashable key for a shard's global index (a tuple of slices)."""
    return tuple((s.start, s.stop, s.step) for s in index)


class ShardedHostOffloadOptimizer:
    """Multi-host ZeRO-Offload host tier.

    Each process pulls ONLY its addressable shards of the dp-sharded fp32
    master into host numpy — the reference's per-DP-rank fp32 partitions
    (reference: deepspeed/runtime/zero/stage2.py:743-900, where each rank
    stages its own ``get_grad_position`` ranges into pinned buffers) —
    and the native C++ Adam updates them in place.  Per step, each
    process stages only its shard of the reduce-scattered gradients
    (staged bytes per host ~ total/dp), and the updated low-precision
    shards are re-assembled into a global array whose all-gather to the
    compute sharding runs ON DEVICE over ICI (one jitted identity in the
    engine) — no host ever handles another rank's bytes, removing the
    single-controller tier's process-0 staging and master bottleneck.

    Replicated leaves (biases, norms) are deduplicated by shard index:
    one host block + one set of moments per UNIQUE slice, shared across
    the local devices that hold a replica.
    """

    def __init__(self, master_global, lr, betas, eps, weight_decay,
                 adamw_mode: bool = True, bias_correction: bool = True,
                 compute_dtype=jnp.bfloat16,
                 use_native: Optional[bool] = None):
        leaves = jax.tree.leaves(master_global)
        self._treedef = jax.tree.structure(master_global)
        self._shardings = [l.sharding for l in leaves]
        self._shapes = [tuple(l.shape) for l in leaves]
        self._poisoned: Optional[BaseException] = None
        self.opt = DeepSpeedCPUAdam(
            lr=lr, betas=betas, eps=eps, weight_decay=weight_decay,
            adamw_mode=adamw_mode, bias_correction=bias_correction,
            use_native=use_native)
        self.compute_dtype = compute_dtype
        self._out_dtype = ("bfloat16" if compute_dtype == jnp.bfloat16
                           else "float16" if compute_dtype == jnp.float16
                           else None)
        # per leaf: ordered unique-index groups
        #   {"index": shard index, "devices": [Device], "block": fp32 np}
        self._local = []
        for leaf in leaves:
            groups: dict = {}
            order = []
            # fp32-promote only floating shards — the same to_host rule
            # as the single-controller tier: integer/bool buffers keep
            # their dtype, cpu_adam's fp32-only check skips them, and
            # they round-trip through assemble/checkpoint uncast.
            ldt = np.dtype(leaf.dtype)
            promote = is_adam_float(ldt)
            for s in leaf.addressable_shards:
                k = _index_key(s.index)
                if k not in groups:
                    pulled = chunked_device_get(
                        s.data, what="master shard pull")
                    blk = np.array(
                        pulled,
                        dtype=np.float32 if promote else ldt)
                    groups[k] = {"index": s.index, "devices": [],
                                 "block": blk}
                    order.append(k)
                groups[k]["devices"].append(s.device)
            self._local.append([groups[k] for k in order])
        # flat-order view of the unique groups — the streaming pipeline's
        # addressing: on_leaf/upload_block/assemble_uploaded all speak
        # this index
        self._flat_groups = [(li, gi, g)
                             for li, leaf in enumerate(self._local)
                             for gi, g in enumerate(leaf)]
        self.last_d2h_seconds = 0.0  # last step's grad-pull wall time

    # -- introspection --------------------------------------------------
    def staged_bytes(self) -> int:
        """Host bytes this process stages for the master (the per-host
        partition size the multi-host design bounds to ~ total/dp)."""
        return sum(g["block"].nbytes
                   for leaf in self._local for g in leaf)

    @property
    def is_native(self) -> bool:
        return self.opt.is_native

    @property
    def master(self):
        """Local-blocks pytree (leaves = lists of fp32 numpy blocks) —
        the engine's TrainState view between checkpoints.  Canonical
        (global-array) form comes from ``canonical_master()``."""
        return jax.tree.unflatten(
            self._treedef,
            [[g["block"] for g in leaf] for leaf in self._local])

    # -- assembly -------------------------------------------------------
    def _assemble(self, block_fn, np_dtype):
        """Global jax arrays from per-group host blocks.  ``block_fn(li,
        gi, g)`` returns the host block to place for group ``g`` (index
        ``gi`` within leaf ``li``); each local device holding that index
        receives a copy and ``make_array_from_single_device_arrays``
        stitches the global view (non-addressable shards belong to the
        other processes).  ``np_dtype`` applies to FLOATING blocks only;
        integer/bool blocks keep their own dtype (the single-controller
        tier's rule — Adam never touched them, so no cast is correct).

        All H2D puts are issued as ONE batched ``jax.device_put`` call:
        replicated small leaves (biases, norms) must not pay a client
        round-trip per replica device per leaf.  The stitch is
        ``assemble_uploaded`` — the same tail the streamed path uses."""
        blks, devs, group_sizes = [], [], []
        for li, leaf_groups in enumerate(self._local):
            for gi, g in enumerate(leaf_groups):
                blk = np.asarray(block_fn(li, gi, g))
                if is_adam_float(blk.dtype):
                    blk = np.asarray(blk, dtype=np_dtype)
                blks.extend([blk] * len(g["devices"]))
                devs.extend(g["devices"])
                group_sizes.append(len(g["devices"]))
        puts = _batched_device_put_pairs(blks, devs)
        uploaded, pos = [], 0
        for n in group_sizes:
            uploaded.append(puts[pos:pos + n])
            pos += n
        return self.assemble_uploaded(uploaded)

    def upload_block(self, flat_idx: int, blk):
        """H2D for ONE updated group (streaming pipeline): apply
        ``_assemble``'s float cast rule, then one batched put to every
        replica device of the group.  Returns the per-device arrays in
        the group's device order — ``assemble_uploaded`` stitches them
        once every group is in."""
        li, gi, g = self._flat_groups[flat_idx]
        blk = np.asarray(blk)
        if is_adam_float(blk.dtype):
            dt = lowp_np_dtype(self._out_dtype)
            blk = np.asarray(blk,
                             dtype=dt if dt is not None else np.float32)
        return _batched_device_put(blk, g["devices"])

    def assemble_uploaded(self, uploaded):
        """Global arrays from already-uploaded per-group device arrays
        (``uploaded[flat_idx]`` = what ``upload_block`` returned).  The
        streaming pipeline's tail: every transfer was issued leaf by
        leaf under the Adam loop; this only stitches the global views —
        no host bytes move here."""
        assert len(uploaded) == len(self._flat_groups), (
            len(uploaded), len(self._flat_groups))
        out, i = [], 0
        for leaf_groups, sharding, shape in zip(
                self._local, self._shardings, self._shapes):
            arrays = []
            for _ in leaf_groups:
                arrays.extend(uploaded[i])
                i += 1
            out.append(jax.make_array_from_single_device_arrays(
                shape, sharding, arrays))
        return jax.tree.unflatten(self._treedef, out)

    def compute_params(self):
        """Initial compute-dtype global params (dp-sharded like the
        master; the engine's jitted gather reshard them to the compute
        sharding — the fused ZeRO param all-gather on ICI)."""
        dt = lowp_np_dtype(self._out_dtype)
        np_dt = dt if dt is not None else np.float32
        # one allocation per block: cast floating blocks here (a no-op
        # for _assemble's float cast), copy uncast ones so the device
        # buffer never aliases the live master block; int/bool blocks
        # pass through at their own dtype either way
        return self._assemble(
            lambda li, gi, g: (g["block"].astype(np_dt)
                               if dt is not None and
                               is_adam_float(g["block"].dtype)
                               else g["block"].copy()), np_dt)

    # -- the step -------------------------------------------------------
    def _local_grad_shards(self, grads):
        """This process's per-group grad shards (single-device jax
        arrays) in the blocks' flat order.  ``grads``: global jax arrays
        whose sharding must match the master's (the engine constrains
        them with the ZeRO plan)."""
        flat_g = []
        for leaf_groups, gleaf in zip(self._local, jax.tree.leaves(grads)):
            by_key = {}
            for s in gleaf.addressable_shards:
                by_key.setdefault(_index_key(s.index), s)
            for g in leaf_groups:
                k = _index_key(g["index"])
                if k not in by_key:
                    raise ValueError(
                        "gradient sharding does not match the master "
                        "sharding — the sharded host tier requires the "
                        "ZeRO plan's grad placement (engine constrains "
                        "this; custom grad trees must match)")
                flat_g.append(by_key[k].data)
        return flat_g

    def pull_local(self, grads):
        """Pull this process's grad shards to host numpy (dedup by
        index, dtype-preserving, chunked + watchdogged) — the DPU stash
        form: the device grad tree can be freed while the host copies
        wait for the overlapped ``step_local``."""
        flat_g = self._local_grad_shards(grads)
        cb = pull_chunk_bytes()
        for a in flat_g:
            if hasattr(a, "copy_to_host_async") and (
                    cb <= 0 or getattr(a, "nbytes", 0) <= cb):
                a.copy_to_host_async()
        return guarded_tree_pull(flat_g)

    def step(self, grads, on_leaf: Optional[Callable] = None):
        """C++ Adam over THIS process's shards only.  Returns global
        compute-dtype params (master-sharded; gather happens in the
        engine's jitted identity), or None when ``on_leaf`` is given —
        the streaming pipeline: ``on_leaf(flat_idx, block)`` fires per
        updated group, the engine uploads each via ``upload_block`` and
        stitches with ``assemble_uploaded``.  Poisons on mid-step
        failure exactly like the single-controller tier."""
        if self._poisoned is not None:
            raise RuntimeError(
                "ShardedHostOffloadOptimizer is poisoned: a previous "
                "step failed mid-update. Restore from a checkpoint. "
                f"Original error: {self._poisoned!r}")
        flat_g = self._local_grad_shards(grads)
        # async D2H only for shards the puller fetches in ONE native call
        # — larger shards stream piece-wise (chunked_device_get); a full-
        # shard async copy alongside the slice pulls would move the same
        # bytes over the wire twice (the _start_small_leaf_d2h rule)
        cb = pull_chunk_bytes()
        for a in flat_g:
            if hasattr(a, "copy_to_host_async") and (
                    cb <= 0 or getattr(a, "nbytes", 0) <= cb):
                a.copy_to_host_async()
        return self._adam_over_blocks(flat_g, prefetch=True,
                                      on_leaf=on_leaf)

    def step_local(self, blocks, on_leaf: Optional[Callable] = None):
        """The DPU apply half: C++ Adam over host blocks that
        ``pull_local`` staged earlier (numpy; no device access).
        ``on_leaf``: same streaming hook as ``step``."""
        if self._poisoned is not None:
            raise RuntimeError(
                "ShardedHostOffloadOptimizer is poisoned: a previous "
                "step failed mid-update. Restore from a checkpoint. "
                f"Original error: {self._poisoned!r}")
        return self._adam_over_blocks(list(blocks), prefetch=False,
                                      on_leaf=on_leaf)

    def _adam_over_blocks(self, flat_g, prefetch: bool,
                          on_leaf: Optional[Callable] = None):
        flat_p = [g["block"] for leaf in self._local for g in leaf]
        assert len(flat_p) == len(flat_g), (len(flat_p), len(flat_g))
        puller = _PrefetchPuller(flat_g) if prefetch else None
        outs: list = [None] * len(flat_p)
        try:
            for i, out in self.opt.step_leaves(
                    flat_p, flat_g, out_dtype=self._out_dtype,
                    leaf_get=puller,
                    leaf_span=lambda i: _transfer_span(
                        "offload/adam_leaf", cat="offload", leaf=i)):
                # fp32 configs stream fp32 copies of the updated block
                # (the single-controller no-downgrade rule)
                up = out if out is not None else flat_p[i].copy()
                outs[i] = up
                if on_leaf is not None:
                    on_leaf(i, up)
        except BaseException as e:
            self._poisoned = e
            raise
        finally:
            self.last_d2h_seconds = puller.seconds if puller else 0.0
            if puller is not None:
                puller.close()
        if on_leaf is not None:
            return None  # uploads already in flight; engine assembles
        dt = lowp_np_dtype(self._out_dtype)
        np_dt = dt if dt is not None else np.float32
        it = iter(outs)
        nested = [[next(it) for _ in leaf] for leaf in self._local]
        return self._assemble(
            lambda li, gi, g, _l=nested: _l[li][gi], np_dt)

    def poison(self, err: BaseException):
        """Engine-side poison (an H2D upload failed after the Adam
        completed) — same contract as the single-controller tier."""
        self._poisoned = err

    # -- checkpoint plumbing --------------------------------------------
    def state_tree(self):
        """Cheap per-step view (local moment blocks); the canonical
        global-array form for saving comes from canonical_state()."""
        if self._poisoned is not None:
            raise RuntimeError(
                "refusing to serialize inconsistent optimizer state (a "
                "step failed mid-update). Restore from an earlier "
                f"checkpoint. Original error: {self._poisoned!r}")
        flat = [g["block"] for leaf in self._local for g in leaf]
        mu, nu = [], []
        for i, blk in enumerate(flat):
            m, v = self.opt._moments(i, blk)
            mu.append(m)
            nu.append(v)
        it_m, it_v = iter(mu), iter(nu)
        return {"step": np.asarray(self.opt.step_count, np.int64),
                "mu": jax.tree.unflatten(
                    self._treedef,
                    [[next(it_m) for _ in leaf] for leaf in self._local]),
                "nu": jax.tree.unflatten(
                    self._treedef,
                    [[next(it_v) for _ in leaf] for leaf in self._local])}

    def canonical_state(self):
        """(master, {step, mu, nu}) as GLOBAL fp32 jax arrays (master-
        sharded, non-fully-addressable) — the save-time form: the
        checkpointer writes per-process shard files and merges on load.
        Costs one device round-trip per leaf, paid only at save."""
        if self._poisoned is not None:
            raise RuntimeError(
                "refusing to serialize inconsistent optimizer state; "
                f"original error: {self._poisoned!r}")
        master = self._assemble(lambda li, gi, g: g["block"], np.float32)
        flat = [g["block"] for leaf in self._local for g in leaf]
        moments = [self.opt._moments(i, b) for i, b in enumerate(flat)]
        it = iter(moments)
        per_leaf = [[next(it) for _ in leaf] for leaf in self._local]

        def pick(which):
            return lambda li, gi, g, _p=per_leaf: _p[li][gi][which]
        mu = self._assemble(pick(0), np.float32)
        nu = self._assemble(pick(1), np.float32)
        return master, {"step": np.asarray(self.opt.step_count, np.int64),
                        "mu": mu, "nu": nu}

    def load_state_tree(self, master_tree, opt_tree):
        """In-place restore from canonical global arrays (or full numpy):
        each process scatters ONLY its local shards back into its blocks."""
        self._poisoned = None

        def scatter(tree, which=None, moments=False):
            leaves = jax.tree.leaves(tree)
            flat_i = 0
            for li, leaf_groups in enumerate(self._local):
                src = leaves[li]
                for g in leaf_groups:
                    if isinstance(src, jax.Array) and not getattr(
                            src, "is_fully_addressable", True):
                        by_key = {_index_key(s.index): s
                                  for s in src.addressable_shards}
                        blk = chunked_device_get(
                            by_key[_index_key(g["index"])].data,
                            what="restore shard pull")
                    else:
                        arr = (np.asarray(src) if not isinstance(
                            src, jax.Array) else chunked_device_get(
                                src, what="restore pull"))
                        blk = arr[g["index"]]
                    # cast-on-assign preserves the destination dtype
                    # (fp32 for floating blocks, own dtype otherwise —
                    # an explicit fp32 hop would corrupt wide ints)
                    if moments:
                        m, v = self.opt._moments(flat_i, g["block"])
                        dst = m if which == 0 else v
                        dst[...] = np.asarray(blk)
                    else:
                        g["block"][...] = np.asarray(blk)
                    flat_i += 1

        scatter(master_tree)
        if opt_tree is None:
            for m, v in self.opt._state.values():
                m[...] = 0.0
                v[...] = 0.0
            self.opt.step_count = 0
            return
        self.opt.step_count = int(np.asarray(
            jax.device_get(opt_tree["step"])))
        scatter(opt_tree["mu"], which=0, moments=True)
        scatter(opt_tree["nu"], which=1, moments=True)

    def canonical_templates(self):
        """Zero-filled global arrays shaped/sharded like canonical_state()
        — the load targets: the checkpoint loader reads only each
        process's addressable ranges into them (per-process shard files,
        merge-on-load).  Block-size transients only."""
        def zeros(li, gi, g):
            # block dtype = fp32 for floating leaves, own dtype for
            # int/bool (moments of untouched leaves are zeros_like)
            return np.zeros(np.shape(g["block"]), g["block"].dtype)
        master = self._assemble(zeros, np.float32)
        mu = self._assemble(zeros, np.float32)
        nu = self._assemble(zeros, np.float32)
        return master, {"step": np.asarray(self.opt.step_count, np.int64),
                        "mu": mu, "nu": nu}
