"""Peaks, operation counts and the declared reductions ("readers") that turn
a run's series and trace into metric values.

A run collects ``series``: a dict of name -> list of numbers (spans, per-tick
and per-request samples) or a single number (counters, rates).  A metric is
``metrics/<name>.json`` holding one ``reader``; ``read_metric`` evaluates it.
A reader that finds nothing to read returns None and the metric is left out
of the result line.
"""
from __future__ import annotations

import json
import os
import statistics

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" system architecture page:
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
# of chip-to-chip interconnect.  A kind that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            f"with its source to PEAKS (known: {sorted(PEAKS)})")
    return PEAKS[device_kind][what]


# -- operations per token (forward + backward, recompute not counted) -------

def gpt2_param_count(m: dict) -> int:
    d, L, V, T = m["n_embd"], m["n_layer"], m["vocab_size"], m["n_positions"]
    per_block = 4 * d + 3 * d * d + 3 * d + d * d + d + 8 * d * d + 5 * d
    return V * d + T * d + L * per_block + 2 * d


def gpt2_train_flops_per_token(m: dict, seq: int) -> float:
    """6 N for the matmuls (N = every parameter; the tied embedding counts
    once, as the output projection) + 12 L d T for causal attention scores
    and values, the convention of bench.py::_flops_per_token."""
    return 6.0 * gpt2_param_count(m) + 12.0 * m["n_layer"] * m["n_embd"] * seq


def bert_train_flops_per_token(m: dict, seq: int) -> float:
    """6 x (block matmul weights + tied MLM decoder) + 12 L d T, the
    convention of bench_bert.py::_flops_per_sample divided by T.  The MLM
    decoder is counted at every position because the program computes it at
    every position."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    per_layer = 4 * d * d + 2 * d * m["intermediate_size"]
    n = L * per_layer + m["vocab_size"] * d
    return 6.0 * n + 12.0 * L * d * seq


# -- small statistics ---------------------------------------------------------

def percentile(values, q: float):
    """Linear-interpolated percentile of a non-empty list, q in 0..100."""
    vs = sorted(values)
    if not vs:
        return None
    if len(vs) == 1:
        return float(vs[0])
    pos = (len(vs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vs) - 1)
    return float(vs[lo] + (vs[hi] - vs[lo]) * (pos - lo))


def _numbers(series: dict, name: str):
    v = series.get(name)
    if v is None:
        return None
    if isinstance(v, (int, float)):
        return [float(v)]
    return [float(x) for x in v]


def _read(reader: dict, series: dict, trace):
    kind = reader["kind"]
    if kind == "ratio":
        num = _read(reader["num"], series, trace)
        den = _read(reader["den"], series, trace)
        if num is None or den is None or den == 0:
            return None
        return reader.get("scale", 1.0) * num / den
    if kind == "trace_share":
        if trace is None:
            return None
        return trace.share(reader["match"], reader.get("of", "window"))
    vals = _numbers(series, reader["series"])
    if not vals:
        return None
    if kind == "percentile":
        return percentile(vals, reader["q"])
    if kind == "median":
        return float(statistics.median(vals))
    if kind == "mean":
        return float(statistics.fmean(vals))
    if kind == "sum":
        return float(sum(vals))
    if kind == "max":
        return float(max(vals))
    if kind == "value":
        return vals[-1]
    raise ValueError(f"unknown reader kind {kind!r} (percentile, median, "
                     "mean, sum, max, value, ratio, trace_share)")


def say(msg: str) -> None:
    """One of the earlier lines of stdout."""
    print(f"[bench] {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def read_metric(name: str, series: dict, trace=None):
    """Value of metric ``name`` from this run, or None where its reader finds
    nothing (no such series in this cell, no trace in this run)."""
    spec = load_json("metrics", name + ".json")
    return _read(spec["reader"], series, trace)


def metric_names(bench: dict, group: str, workload: str):
    """Names in ``group`` ('end_to_end' | 'per_layer') that the cell reports:
    those with no ``workloads`` key or that list the cell."""
    return [m["name"] for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]
