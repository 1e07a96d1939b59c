"""Trace-driven workload plane (docs/serving.md "workload plane").

``workload``  — declarative open-loop workload specs (arrival process
                x length distributions x template mix x session gaps)
                compiled to deterministic schedules.
``convert``   — public Azure / Mooncake trace rows to the replayable
                ``load_trace`` JSONL shape
                (``python -m tools.loadgen convert <src> <dst>``).
"""
from .workload import (ArrivalSpec, LengthSpec, Workload, WorkloadItem,
                       load_trace, schedule_fingerprint)

__all__ = [
    "ArrivalSpec", "LengthSpec", "Workload", "WorkloadItem",
    "load_trace", "schedule_fingerprint",
]
