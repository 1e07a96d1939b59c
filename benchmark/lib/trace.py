"""From a profiler trace to numbers: busy and idle time of the device, time
per operation, collective and kernel shares, and what the host was doing in
the longest idle gaps.

Two stages, so that the arithmetic can be checked without a chip or JAX:
``events_from_xplane`` turns an ``.xplane.pb`` into a plain event list (a
dict of planes -> lines -> [name, start_ns, dur_ns]); ``Reduction`` works on
that list alone.  ``fixtures/`` keeps one small recorded event list with the
values this code must give for it (``run.py --selfcheck``).

What the trace looks like on a v5e (read by hand, PR 25): one plane per
chip named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO operation, nested in time where an operation contains others
(``while`` around its body), named by the instruction's whole text; line
``XLA Modules`` holds one event per program run.  (``Async XLA Ops``,
from each ``*-start`` to its ``*-done``, is written for the first chip
only and is not read.)  All planes share one clock.  The host plane ``/host:CPU`` has one line per thread with the
profiler's Python events (``$file.py:123 func``) and this benchmark's own
``bench/...`` annotations.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_EVENT = "bench/traced_window"
# host events worth naming in a gap: this benchmark's annotations and the
# Python frames of the program's serving and training loops
HOST_KEEP = re.compile(
    r"^bench/|^\$(engine|scheduler|kv_cache|serve_job|train_job|run)"
    r"\.py:\d+ ")
MIN_GAP_NS = 20_000


def short_label(text: str) -> str:
    """``%copy.50 = bf16[..]{..} copy(..)`` -> ``copy.50 copy``; a custom
    call also names its target (``tpu_custom_call`` is a Mosaic kernel)."""
    m = re.match(r"%(\S+) = ", text)
    if not m:
        return text[:80]
    op = re.search(r" ([a-z][a-z\-]*)\(", text)
    target = re.search(r'custom_call_target="([^"]+)"', text)
    return (m.group(1) + " " + (op.group(1) if op else "?")
            + (":" + target.group(1) if target else ""))


def events_from_xplane(path: str) -> dict:
    """Plain event list of the device planes' op and module lines and of
    the host lines that carry ``bench/`` annotations."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, list]] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [short_label(e.name), int(e.start_ns),
                         int(e.duration_ns)] for e in line.events]
            out[plane.name] = lines
        elif plane.name == "/host:CPU":
            lines = {}
            for line in plane.lines:
                evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events if HOST_KEEP.match(e.name)]
                if any(n.startswith("bench/") for n, _, _ in evs):
                    lines[line.name] = evs
            out[plane.name] = lines
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _self_times(events) -> Dict[str, int]:
    """Self time per operation name: an event's duration less the time its
    directly nested events cover."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: Dict[str, int] = {}
    stack: List[list] = []      # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0) + max(self_ns, 0)

    for name, start, dur in evs:
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def op_family(label: str) -> str:
    """``fusion.123 fusion`` -> ``fusion fusion``: the number is the
    compiler's, and changes from build to build."""
    return re.sub(r"[.\d]+( |$)", r"\1", label) or label


class Reduction:
    def __init__(self, planes: dict):
        host = planes.get("/host:CPU", {})
        self.host_lines = list(host.values())
        win = [e for line in self.host_lines for e in line
               if e[0] == WINDOW_EVENT]
        if not win:
            raise ValueError(f"trace has no {WINDOW_EVENT} annotation")
        self.t0 = win[0][1]
        self.t1 = win[0][1] + win[0][2]
        self.window_s = (self.t1 - self.t0) / 1e9
        self.devices = sorted(p for p in planes if DEVICE_PLANE.match(p))
        self.busy_ns: Dict[str, int] = {}
        self.self_ns: Dict[str, Dict[str, int]] = {}
        self.gaps: Dict[str, List[Tuple[int, int]]] = {}
        for dev in self.devices:
            ops = [self._clip(e) for e in planes[dev].get(OPS_LINE, [])]
            ops = [e for e in ops if e is not None]
            busy = _union([(s, s + d) for _, s, d in ops])
            self.busy_ns[dev] = sum(b - a for a, b in busy)
            self.self_ns[dev] = _self_times(ops)
            edges = [self.t0] + [t for ab in busy for t in ab] + [self.t1]
            self.gaps[dev] = [(edges[i], edges[i + 1])
                              for i in range(0, len(edges), 2)
                              if edges[i + 1] - edges[i] >= MIN_GAP_NS]

    def _clip(self, e):
        name, s, d = e
        a, b = max(s, self.t0), min(s + d, self.t1)
        return [name, a, b - a] if b > a else None

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.devices) / 1e9

    def share(self, match: str, of: str = "window"):
        """Percent of the window (or of busy time) that is self time of
        operations whose label matches, per device, median over devices."""
        vals = []
        for dev in self.devices:
            ns = sum(t for n, t in self.self_ns[dev].items()
                     if re.search(match, n))
            den = self.t1 - self.t0 if of == "window" else self.busy_ns[dev]
            if den > 0:
                vals.append(100.0 * ns / den)
        if not vals:
            return None
        vals.sort()
        mid = len(vals) // 2
        return vals[mid] if len(vals) % 2 else (vals[mid - 1] + vals[mid]) / 2

    def top_ops(self, n: int = 10) -> List[list]:
        """[operation family, seconds of self time averaged over chips]."""
        tot: Dict[str, float] = {}
        for dev in self.devices:
            for name, ns in self.self_ns[dev].items():
                fam = op_family(name)
                tot[fam] = tot.get(fam, 0.0) + ns / 1e9 / len(self.devices)
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def _host_timeline(self):
        """Change points (time, innermost kept host event) of the thread
        that carries the window annotation."""
        line = next(l for l in self.host_lines
                    if any(e[0] == WINDOW_EVENT for e in l))
        evs = sorted((e for e in line if e[0] != WINDOW_EVENT),
                     key=lambda e: (e[1], -e[2]))
        points: List[Tuple[int, str]] = []
        stack: List[Tuple[int, str]] = []     # (end, name)

        def close(upto):
            while stack and stack[-1][0] <= upto:
                end, _ = stack.pop()
                points.append((end, stack[-1][1] if stack
                               else "(no host span)"))

        for name, start, dur in evs:
            close(start)
            stack.append((start + dur, name))
            points.append((start, name))
        close(float("inf"))
        return points

    def _host_label(self, points, t: int) -> str:
        i = bisect.bisect_right(points, (t, "\uffff")) - 1
        name = points[i][1] if i >= 0 else "(no host span)"
        return re.sub(r":\d+ ", " ", name)

    def idle_gaps(self, n: int = 10) -> List[list]:
        """[what the host was doing, seconds of device idleness under it] on
        the first chip, largest first."""
        if not self.devices:
            return []
        tot: Dict[str, float] = {}
        points = self._host_timeline()
        for a, b in self.gaps[self.devices[0]]:
            label = self._host_label(points, (a + b) // 2)
            tot[label] = tot.get(label, 0.0) + (b - a) / 1e9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def summary(self) -> dict:
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "idle_share_pct": 100.0 * (1 - self.busy_s / self.window_s),
                "devices": len(self.devices),
                "device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}
