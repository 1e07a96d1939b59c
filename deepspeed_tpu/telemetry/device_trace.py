"""Whose device time is it, and what was the host doing in each idle gap:
one reader of a captured ``.xplane.pb``.

Two stages.  ``read(path)`` turns the capture into a plain event list
through ``jax.profiler.ProfileData`` (this module's only JAX import, made
inside ``read``); ``Reduction`` is pure Python over that list, so its
arithmetic is tested without a chip.  It opens what the engine's
``profiler`` block writes, what the anomaly trigger writes and what
``benchmark/run.py --trace 1`` leaves under ``benchmark_out/<cell>/trace``:
a file, or a directory searched for the newest ``*.xplane.pb``.

What a capture of a TPU v5e holds, one raw event of each line read on the
chip (PR 54, the A.X-K1 cell's traced window, 31.7 MB):

* plane ``/device:TPU:<n>``, line ``XLA Modules``: one event a program
  run, named ``jit_serve_decode(6601560532864832973)`` (the number is the
  program's id); stats ``device_offset_ps``, ``device_duration_ps``,
  ``run_id``, ``replica_id``, ``queue_id``, ``core_type``.
* line ``XLA Ops``: one event an executed instruction, nested in time where
  one holds others (a ``while`` around its body), named by the
  instruction's text WITHOUT its metadata (``%fusion.570 = s32[192]{..}
  fusion(..), kind=kLoop, calls=%fused_computation.1126``); its own stats
  are ``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
  Multiplier`` alone.  The ``op_name`` IS in the file, one level up: the
  plane's ``event_metadata`` table keeps, for each (program, instruction),
  the stats ``tf_op`` (= ``op_name`` + ``:``), ``program_id``,
  ``hlo_category``, ``flops``, ``bytes_accessed``, ``source``,
  ``source_stack``, ``shape_with_layout``.  ``ProfileData`` hands out an
  event's own stats and not its metadata's, so ``op_names`` reads that one
  table from the file's bytes (forty lines over the protobuf wire format,
  0.3 s) and ``read`` joins it on (the id of the run around the event, the
  event's name): the same text under two programs is two rows.  So the
  scope is read from the capture itself; no compiled text has to be
  written beside it, with telemetry on or off.
* lines ``Async XLA Ops`` (each ``*-start`` to its ``*-done``, first chip
  only), ``Scalar Unit``, ``TC Overlay``: not read.
* plane ``/host:CPU``: one line a thread; the line ``python`` holds the
  profiler's Python frames (``$engine.py:2689 _send_tick``) AND every
  ``TraceAnnotation``: the benchmark's ``bench/...`` and the program's
  ``serve/tick``, ``serve/admit``, ``serve/decode_prep``,
  ``serve/decode_step``, ``serve/decode_dispatch``, ``serve/token_pull``,
  ``serve/emit``, ``serve/prefill`` (+ ``_wait``, ``_run``), no stats, on
  the device planes' clock.  The runtime's own threads
  (``pjrt-tpu-tasks/..``: ``tpu::System::Execute``, ``H2D Dispatch``, ...)
  are not read.
* planes ``/host:metadata`` (no lines), ``Task Environment``
  (``profile_start_time``), ``#Chip0 ...``: not read.

The event list: ``{plane: {line: [event, ...]}}``.  An event of a device
plane's ``XLA Ops`` line is ``[label, start_ns, dur_ns, scope]``
(``label`` = ``<instruction> <opcode>[:<custom-call target>]``, the form
``benchmark/lib/trace.py::short_label`` gives, so both readers name an
operation alike; ``scope`` the ``jax.named_scope`` path of its
``op_name``, None where its metadata states none: a copy the compiler put
in); every other event is ``[name, start_ns, dur_ns]``.

No new span system and no new clock: the scopes are PR 26's
``jax.named_scope`` names, the spans the ``TraceAnnotation`` every engine
span already is (telemetry on or off), both on the capture's one clock.
Times come only from a chip's capture; ``utils/hlo.py::scope_cycles`` is
the no-chip half (the compiler's guess, by the same scopes).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from ..utils.hlo import UNSCOPED, cut, scope_path
from .cli import _percentile

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the program's span families (docs/observability.md "Span naming")
SPAN_FAMILIES = ("serve/", "train/", "eval/", "data/", "checkpoint/",
                 "offload/", "profiler/")
#: the annotation a program is sent under -> the program it sends
DISPATCHES = {"serve/decode_dispatch": "serve_decode",
              "serve/verify_dispatch": "serve_verify",
              "train/dispatch": "train_step",
              "eval/dispatch": "eval_step"}
OUTSIDE = "(outside spans)"
SHORT_GAPS = "(gaps under 20 us)"
MIN_GAP_NS = 20_000
_COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|"
                         r"collective-permute|all-to-all|async-collective")
_MOSAIC = "custom-call:tpu_custom_call"
_NUMBERED = re.compile(r"(\.\d+)+$")


# -- from the file to the event list ----------------------------------------

def newest_xplane(path: str) -> str:
    """``path`` itself, or the newest ``*.xplane.pb`` under a directory."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def label_of(text: str) -> str:
    """``%copy.50 = bf16[..]{..} copy(..)`` -> ``copy.50 copy``; a custom
    call also names its target (``tpu_custom_call`` is a Mosaic kernel)."""
    m = re.match(r"%(\S+) = ", text)
    if not m:
        return text[:80]
    op = re.search(r" ([a-z][a-z\-]*)\(", text)
    target = re.search(r'custom_call_target="([^"]+)"', text)
    return (m.group(1) + " " + (op.group(1) if op else "?")
            + (":" + target.group(1) if target else ""))


def read(path: str, window: Optional[str] = None) -> dict:
    """The plain event list of a capture: of each ``/device:TPU:<n>`` plane
    the lines ``XLA Ops`` (each event with the scope its metadata states)
    and ``XLA Modules``; of ``/host:CPU`` the annotations of the program's
    span families and any event named ``window``, by thread."""
    from jax.profiler import ProfileData
    path = newest_xplane(path)
    with open(path, "rb") as f:
        raw = f.read()
    named = op_names(raw)
    planes: Dict[str, Dict[str, list]] = {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if DEVICE_PLANE.match(plane.name):
            by_line = {line.name: line.events for line in plane.lines
                       if line.name in (OPS_LINE, MODULES_LINE)}
            modules = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in by_line.get(MODULES_LINE, ())]
            runs = _Runs(modules)
            table = named.get(plane.name, {})
            ops = []
            for e in by_line.get(OPS_LINE, ()):
                start = int(e.start_ns)
                op_name = table.get((runs.at(start, 3), e.name))
                ops.append([label_of(e.name), start, int(e.duration_ns),
                            scope_path(op_name) if op_name else None])
            planes[plane.name] = {OPS_LINE: ops, MODULES_LINE: modules}
        elif plane.name == HOST_PLANE:
            lines = {}
            for line in plane.lines:
                kept = [[e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                        if e.name.startswith(SPAN_FAMILIES)
                        or e.name == window]
                if kept:
                    lines[line.name] = kept
            planes[plane.name] = lines
    return planes


def _varint(raw: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = raw[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(raw: bytes, i: int, end: int):
    """(field number, wire type, value or start, end) of a protobuf
    message's fields between two offsets; nothing is copied."""
    while i < end:
        key, i = _varint(raw, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(raw, i)
            yield key >> 3, wire, value, i
        else:
            if wire == 2:
                size, i = _varint(raw, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an XSpace")
            yield key >> 3, wire, i, i + size
            i += size


def op_names(raw: bytes) -> Dict[str, Dict[Tuple[str, str], str]]:
    """plane -> (program id, event name) -> ``op_name``, from the
    serialized XSpace itself: the profiler keeps an operation's
    ``op_name`` (stat ``tf_op``) and its program (stat ``program_id``, the
    number in the ``XLA Modules`` event's name) in the plane's
    ``event_metadata`` table, which ``ProfileData`` does not hand out.
    Field numbers are tsl/profiler/protobuf/xplane.proto's: XSpace.planes
    1; XPlane.name 2, .event_metadata 4, .stat_metadata 5; a map entry's
    value 2; XEventMetadata.name 2, .stats 5; XStatMetadata.id 1, .name 2;
    XStat.metadata_id 1, .uint64_value 3, .int64_value 4, .str_value 5."""
    found: Dict[str, Dict[Tuple[str, str], str]] = {}
    for field, _, a, b in _fields(raw, 0, len(raw)):
        if field != 1:
            continue
        plane, events, stat_ids = "", [], {}
        for field, wire, a2, b2 in _fields(raw, a, b):
            if field == 2:
                plane = raw[a2:b2].decode()
            elif field in (4, 5) and wire == 2:
                for entry, _, a3, b3 in _fields(raw, a2, b2):
                    if entry == 2 and field == 4:
                        events.append((a3, b3))
                    elif entry == 2:
                        ident = name = None
                        for f, _, a4, b4 in _fields(raw, a3, b3):
                            if f == 1:
                                ident = a4
                            elif f == 2:
                                name = raw[a4:b4].decode()
                        stat_ids[name] = ident
        if not DEVICE_PLANE.match(plane):
            continue
        tf_op, program_id = stat_ids.get("tf_op"), stat_ids.get("program_id")
        table = found.setdefault(plane, {})
        for a3, b3 in events:
            name = op_name = program = None
            for f, _, a4, b4 in _fields(raw, a3, b3):
                if f == 2:
                    name = raw[a4:b4].decode()
                elif f == 5:
                    ident = value = None
                    for g, wire, a5, b5 in _fields(raw, a4, b4):
                        if g == 1:
                            ident = a5
                        elif g in (3, 4):
                            value = a5
                        elif g == 5:
                            value = (a5, b5)
                    if ident == tf_op and isinstance(value, tuple):
                        op_name = raw[value[0]:value[1]].decode()
                    elif ident == program_id:
                        program = str(value)
            if name and op_name:
                table[(program, name)] = op_name.rstrip(":")
    return found


# -- the reduction ------------------------------------------------------------

def program_of(module_name: str) -> str:
    """``jit_serve_decode(1234567)`` -> ``serve_decode``."""
    name = module_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def program_id(module_name: str) -> Optional[str]:
    """``jit_serve_decode(1234567)`` -> ``1234567``."""
    m = re.search(r"\((\d+)\)$", module_name)
    return m.group(1) if m else None


def kind_of(label: str) -> str:
    """``kernel:<ds_ name>`` (a Mosaic call, by its ``pallas_call`` name),
    ``collective``, ``copy`` or ``xla``."""
    instruction, _, op = label.partition(" ")
    if op == _MOSAIC:
        return "kernel:" + _NUMBERED.sub("", instruction)
    if _COLLECTIVE.search(label):
        return "collective"
    if op.startswith("copy"):
        return "copy"
    return "xla"


def op_family(label: str) -> str:
    """``fusion.123 fusion`` -> ``fusion fusion``: the ledger's
    ``breakdown.device_ops`` names (the number is the compiler's)."""
    return re.sub(r"[.\d]+( |$)", r"\1", label) or label


class _Runs:
    """The program runs of one chip, to look the run at a time up in."""

    def __init__(self, modules: Iterable[list]):
        self.runs = sorted((s, s + d, program_of(n), program_id(n))
                           for n, s, d in modules)
        self.starts = [r[0] for r in self.runs]

    def at(self, t: int, what: int = 2) -> Optional[str]:
        """The program (``what`` 2) or its id (3) of the run around t."""
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t < self.runs[i][1]:
            return self.runs[i][what]
        return None


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def self_times(events: List[list]) -> List[int]:
    """Self time of every event, in the order given: its duration less
    what its directly nested events cover (a ``while`` around its body's
    operations keeps what none of them covers)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    out = [0] * len(events)
    stack: List[list] = []      # [index, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            i, _, self_ns = stack.pop()
            out[i] = max(self_ns, 0)

    for i in order:
        _, start, dur = events[i][:3]
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([i, start + dur, dur])
    close(float("inf"))
    return out


def _innermost(events: List[list]) -> List[Tuple[int, int, str]]:
    """(from, to, name) of the innermost span open at each time of one
    thread; times no span covers are left out."""
    pieces: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []       # (end, name)
    at = 0

    def advance(upto: int):
        nonlocal at
        if stack and upto > at:
            pieces.append((at, upto, stack[-1][1]))
        at = max(at, upto)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            advance(stack[-1][0])
            stack.pop()
        advance(start)
        stack.append((start + dur, name))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return pieces


class Reduction:
    """Device seconds by program, scope and kind, idle seconds by span and
    the dispatch-to-run lag of one event list, over a window: the whole
    capture, or the first host annotation named ``window``."""

    def __init__(self, planes: dict, window: Optional[str] = None,
                 depth: int = 2):
        self.depth = depth
        self.host = planes.get(HOST_PLANE, {})
        self.devices = sorted(
            (p for p in planes if DEVICE_PLANE.match(p)),
            key=lambda p: int(DEVICE_PLANE.match(p).group(1)))
        if window is not None:
            found = [e for line in self.host.values() for e in line
                     if e[0] == window]
            if not found:
                raise ValueError(f"the capture holds no {window} annotation")
            self.t0, self.t1 = found[0][1], found[0][1] + found[0][2]
        else:
            edges = [(e[1], e[1] + e[2]) for d in self.devices
                     for line in planes[d].values() for e in line]
            if not edges:
                raise ValueError("the capture holds no device event")
            self.t0 = min(a for a, _ in edges)
            self.t1 = max(b for _, b in edges)
        self.window = window
        self.window_s = (self.t1 - self.t0) / 1e9
        #: chip -> [(program, label, scope or None, self_ns)]
        self.ops: Dict[str, List[tuple]] = {}
        self.busy_ns: Dict[str, int] = {}
        self.busy: Dict[str, List[Tuple[int, int]]] = {}
        #: chip -> program -> runs that START in the window
        self.runs: Dict[str, Dict[str, int]] = {}
        self._modules: Dict[str, List[list]] = {}
        for dev in self.devices:
            modules = planes[dev].get(MODULES_LINE, [])
            self._modules[dev] = modules
            at = _Runs(modules)
            clipped = [c for c in map(self._clip,
                                      planes[dev].get(OPS_LINE, [])) if c]
            selfs = self_times(clipped)
            self.ops[dev] = [
                (at.at(e[1]) or "(no program)", e[0], e[3], ns)
                for e, ns in zip(clipped, selfs)]
            self.busy[dev] = _union([(e[1], e[1] + e[2]) for e in clipped])
            self.busy_ns[dev] = sum(b - a for a, b in self.busy[dev])
            count: Dict[str, int] = {}
            for name, start, _ in modules:
                if self.t0 <= start < self.t1:
                    count[program_of(name)] = count.get(
                        program_of(name), 0) + 1
            self.runs[dev] = count

    def _clip(self, e):
        a, b = max(e[1], self.t0), min(e[1] + e[2], self.t1)
        if b <= a:
            return None
        return [e[0], a, b - a, e[3] if len(e) > 3 else None]

    # -- the device's side ---------------------------------------------------
    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.devices) / 1e9

    @property
    def idle_share_pct(self) -> float:
        return 100.0 * (1 - self.busy_s / self.window_s)

    def device_scope_seconds(self, by: str = "scope",
                             depth: Optional[int] = None
                             ) -> Dict[tuple, float]:
        """Self seconds of the window averaged over the chips, by
        (program, scope, kind) (``by="scope"``), by (operation family,
        scope) (``"op"``: the ledger's ``device_ops`` lines split by
        scope) or by (program, instruction, scope) (``"instruction"``).
        Every table sums to ``busy_s``; a scope is cut to ``depth`` names
        (the reduction's own where None)."""
        total: Dict[tuple, float] = {}
        chips = max(len(self.devices), 1)
        depth = self.depth if depth is None else depth
        cuts: Dict[Optional[str], str] = {}
        for dev in self.devices:
            for program, label, scope, ns in self.ops[dev]:
                if scope not in cuts:
                    cuts[scope] = cut(scope, depth) if scope else UNSCOPED
                if by == "scope":
                    key = (program, cuts[scope], kind_of(label))
                elif by == "op":
                    key = (op_family(label), cuts[scope])
                else:
                    key = (program, label, cuts[scope])
                total[key] = total.get(key, 0.0) + ns / 1e9 / chips
        return total

    def program_runs(self) -> Dict[str, int]:
        """Runs of each program that start in the window, first chip."""
        return dict(self.runs[self.devices[0]]) if self.devices else {}

    def unscoped(self, n: int = 12) -> List[list]:
        """[program, instruction, seconds] of what no scope owns (and of
        what two scopes share), largest first."""
        rows = [[p, label, s, sec] for (p, label, s), sec
                in self.device_scope_seconds("instruction").items()
                if s == UNSCOPED or s.startswith("mixed:")]
        return sorted(rows, key=lambda r: -r[3])[:n]

    # -- the host's side -----------------------------------------------------
    def loop_thread(self) -> List[list]:
        """The events of the thread that runs the loop: the one that holds
        the window's annotation, else the one with the most dispatches."""
        best, most = [], -1
        for line in self.host.values():
            if self.window and any(e[0] == self.window for e in line):
                return line
            n = sum(1 for e in line if e[0] in DISPATCHES)
            if n > most:
                best, most = line, n
        return best

    def device_idle_seconds(self) -> Dict[str, dict]:
        """span -> {seconds, gaps, longest_s}: every idle gap of the first
        chip of at least 20 us, split over the innermost program spans
        open on the loop's thread by how much of it each overlaps;
        ``(outside spans)`` for the caller's time; the shorter gaps
        together as ``(gaps under 20 us)``, so that the seconds sum to the
        chip's idle time in the window."""
        if not self.devices:
            return {}
        busy = self.busy[self.devices[0]]
        edges = [self.t0] + [t for ab in busy for t in ab] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = [p for p in _innermost(
            [e for e in self.loop_thread()
             if e[0].startswith(SPAN_FAMILIES)]) if p[1] > self.t0]
        starts = [p[0] for p in spans]
        out: Dict[str, dict] = {}

        def add(name: str, ns: int):
            row = out.setdefault(name, {"seconds": 0.0, "gaps": 0,
                                        "longest_s": 0.0})
            row["seconds"] += ns / 1e9
            row["gaps"] += 1
            row["longest_s"] = max(row["longest_s"], ns / 1e9)

        for a, b in gaps:
            if b - a < MIN_GAP_NS:
                add(SHORT_GAPS, b - a)
                continue
            covered = 0
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(spans) and spans[i][0] < b:
                ns = min(b, spans[i][1]) - max(a, spans[i][0])
                if ns > 0:
                    add(spans[i][2], ns)
                    covered += ns
                i += 1
            if b - a > covered:
                add(OUTSIDE, b - a - covered)
        return out

    def dispatch_lag(self, slack_ns: int = 5_000_000) -> Dict[str, dict]:
        """annotation -> {n, min_ms, median_ms, p99_ms} of (start of the
        program run on the first chip) - (start of the dispatch annotation
        that sent it), both inside the window: each dispatch takes the
        first run of its program not taken yet that starts no more than
        ``slack_ns`` before it.  Below zero the device's clock reads
        earlier than the host's: no run starts before it is sent."""
        if not self.devices:
            return {}
        out: Dict[str, dict] = {}
        thread = self.loop_thread()
        for annotation, program in DISPATCHES.items():
            sent = sorted(e[1] for e in thread if e[0] == annotation
                          and self.t0 <= e[1] < self.t1)
            runs = sorted(s for n, s, _ in self._modules[self.devices[0]]
                          if program_of(n).startswith(program)
                          and s >= self.t0 - slack_ns)
            lags, j = [], 0
            for t in sent:
                while j < len(runs) and runs[j] < t - slack_ns:
                    j += 1
                if j == len(runs):
                    break
                lags.append((runs[j] - t) / 1e6)
                j += 1
            if lags:
                lags.sort()
                out[annotation] = {
                    "n": len(lags), "min_ms": lags[0],
                    "median_ms": _percentile(lags, 0.5),
                    "p99_ms": _percentile(lags, 0.99)}
        return out

    def summary(self, by: str = "scope", top: int = 40) -> dict:
        table = sorted(self.device_scope_seconds(by).items(),
                       key=lambda kv: -kv[1])
        runs = self.program_runs()
        rows = []
        for key, seconds in table[:top]:
            row = {"key": list(key), "seconds": seconds,
                   "busy_pct": 100.0 * seconds / self.busy_s
                   if self.busy_s else 0.0}
            if by != "op" and runs.get(key[0]):
                row["ms_a_run"] = 1e3 * seconds / runs[key[0]]
            rows.append(row)
        kernels = sum(s for (_, _, kind), s
                      in self.device_scope_seconds("scope").items()
                      if kind.startswith("kernel:"))
        return {
            "window": self.window or "(whole capture)",
            "window_s": self.window_s, "busy_s": self.busy_s,
            "idle_share_pct": self.idle_share_pct,
            "devices": len(self.devices), "by": by,
            "kernel_share_pct": 100.0 * kernels / self.busy_s
            if self.busy_s else 0.0,
            "program_runs": runs, "device_scope_seconds": rows,
            "rows_left_out": max(len(table) - top, 0),
            "unscoped": [{"program": p, "instruction": i, "scope": s,
                          "seconds": sec}
                         for p, i, s, sec in self.unscoped()],
            "device_idle_seconds": self.device_idle_seconds(),
            "dispatch_lag": self.dispatch_lag()}


def queue_dry_seconds(records: Iterable[dict], t0: float,
                      t1: float) -> Dict[str, float]:
    """``queue_dry_seconds{phase}`` of the records of a
    ``DeviceQueueBook`` (``ServeEngine.aux_log``: ``sent_t``, ``dry_s``,
    ``dry_phase``) cut to a window on the book's clock: what PERF.md
    calls a lower bound on ``device_idle_seconds``, to lay beside it."""
    out: Dict[str, float] = {}
    for r in records:
        if r.get("dry_s"):
            a, b = max(r["sent_t"] - r["dry_s"], t0), min(r["sent_t"], t1)
            if b > a:
                out[r["dry_phase"]] = out.get(r["dry_phase"], 0.0) + b - a
    return out


def render(summary: dict, out) -> None:
    """The three tables, for a terminal."""
    s = summary
    print(f"window {s['window']}: {s['window_s']:.4f} s, busy "
          f"{s['busy_s']:.4f} s on {s['devices']} chip(s), idle "
          f"{s['idle_share_pct']:.3f} %, Mosaic kernels "
          f"{s['kernel_share_pct']:.2f} % of busy", file=out)
    runs = ", ".join(f"{p} x{n}" for p, n in sorted(s["program_runs"].items()))
    print(f"program runs in the window: {runs or 'none'}", file=out)
    print(f"\ndevice seconds by {s['by']} (self time, mean over chips)",
          file=out)
    for row in s["device_scope_seconds"]:
        per = (f"  {row['ms_a_run']:9.4f} ms a run"
               if "ms_a_run" in row else "")
        print(f"  {row['seconds']:9.5f} s  {row['busy_pct']:6.2f} %{per}  "
              + "  ".join(row["key"]), file=out)
    if s["rows_left_out"]:
        print(f"  ... {s['rows_left_out']} smaller rows left out", file=out)
    if s["unscoped"]:
        print("\n(unscoped) and mixed: by instruction", file=out)
        for u in s["unscoped"]:
            print(f"  {u['seconds']:9.5f} s  {u['program']}  "
                  f"{u['instruction']}  {u['scope']}", file=out)
    print("\ndevice idle seconds by span (first chip, gaps split by overlap)",
          file=out)
    for name, row in sorted(s["device_idle_seconds"].items(),
                            key=lambda kv: -kv[1]["seconds"]):
        print(f"  {row['seconds']:9.5f} s  {row['gaps']:6d} gaps  longest "
              f"{row['longest_s'] * 1e3:8.3f} ms  {name}", file=out)
    for name, lag in s["dispatch_lag"].items():
        print(f"\n{name} -> its program's run: n {lag['n']}, min "
              f"{lag['min_ms']:.3f} ms, median {lag['median_ms']:.3f} ms, "
              f"p99 {lag['p99_ms']:.3f} ms", file=out)
