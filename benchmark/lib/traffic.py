"""The one general traffic generator.  A mix is a data file of parameters
(``traffic/<mix>.json``); this file turns it into a schedule.

Arrival kinds and the clipped lognormal lengths are those of
tools/loadgen/workload.py (ArrivalSpec poisson / gamma_burst, LengthSpec),
which later PRs may change; this copy is the yardstick and they may not.

The schedule belongs to the MIX, not to the seed: sizes are the evenly
spaced quantiles of their distribution (so their mean and tail are the
distribution's, not a small sample's), inter-arrival gaps are drawn once and
scaled so that the mean rate over the horizon is exactly the mix's, and both
are put in an order fixed by the mix's ``shape_seed``.  ``--seed`` draws the
token ids (and the weights).  Two seeds therefore offer the same requests at
the same times with other contents, and a difference between two runs is
the system's, not the load's: with tens of requests in a window, a tail read
from another draw of Poisson arrivals would differ by more than any bound.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    at_s: float                 # due time, seconds after the schedule starts
    prompt: Tuple[int, ...]
    max_new_tokens: int


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` sizes: the distribution's quantiles at (i + 0.5) / n, shuffled."""
    kind = spec["kind"]
    if kind == "fixed":
        return np.full((n,), int(spec["value"]), np.int64)
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        v = np.exp(math.log(spec["median"]) + float(spec["sigma"]) * z)
        v = np.clip(np.rint(v), spec["lo"], spec["hi"]).astype(np.int64)
        return v[rng.permutation(n)]
    raise ValueError(f"unknown length kind {kind!r} (fixed, lognormal)")


def _gaps(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` inter-arrival gaps with mean exactly 1 / rate."""
    kind, rate = spec["kind"], float(spec["rate"])
    if kind == "poisson":
        gaps = rng.exponential(1.0, n)
    elif kind == "gamma_burst":
        cv = float(spec["cv"])          # shape < 1 clumps arrivals
        gaps = rng.gamma(1.0 / cv ** 2, cv ** 2, n)
    elif kind == "uniform":
        gaps = np.ones((n,))
    else:
        raise ValueError(f"unknown arrival kind {kind!r} "
                         "(poisson, gamma_burst, uniform)")
    return gaps * (n / rate / gaps.sum())


def build_schedule(mix: dict, seed: int, horizon_s: float,
                   vocab: int) -> List[Item]:
    """Requests due in [0, horizon_s), in due order."""
    shape = np.random.default_rng([int(mix["shape_seed"]), 0])
    n = max(int(math.ceil(float(mix["arrival"]["rate"]) * horizon_s)), 1)
    gaps = _gaps(mix["arrival"], n, shape)
    at = np.cumsum(gaps) - gaps[0] / 2
    # requests already in flight when the schedule starts: due at 0, with
    # what is left of their output (an evenly spread share of a length), so
    # that the lead-in reaches the steady population quickly
    n_burst = int(mix.get("lead_burst", 0))
    prompt_len = _lengths(mix["prompt_len"], n_burst + n, shape)
    out_len = _lengths(mix["output_len"], n_burst + n, shape)
    left = (shape.permutation(n_burst) + 0.5) / max(n_burst, 1)
    out_len[:n_burst] = np.maximum(np.rint(out_len[:n_burst] * left), 1)
    at = np.concatenate([np.zeros(n_burst), at])

    tok = np.random.default_rng([int(seed), 2])
    shared = mix.get("shared_prefix")
    prefixes = []
    if shared:
        prefixes = [tok.integers(0, vocab, (int(shared["tokens"]),))
                    for _ in range(int(shared["groups"]))]
    items = []
    for i in range(len(at)):
        if at[i] >= horizon_s:
            break
        p = int(prompt_len[i])
        ids = tok.integers(0, vocab, (p,))
        if prefixes and shape.random() < float(shared["share"]):
            head = prefixes[int(shape.integers(len(prefixes)))][:p - 1]
            ids[:len(head)] = head
        items.append(Item(float(at[i]), tuple(int(t) for t in ids),
                          int(out_len[i])))
    return items


def zipf_tokens(rng, vocab: int, shape, exponent: float) -> np.ndarray:
    """Token ids with a Zipf-like unigram distribution (rank r has weight
    r**-exponent), as natural text has; exponent 0 is uniform."""
    if exponent <= 0:
        return rng.integers(0, vocab, shape, dtype=np.int32)
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    cdf = np.cumsum(w / w.sum())
    ids = np.searchsorted(cdf, rng.random(shape), side="right")
    return np.minimum(ids, vocab - 1).astype(np.int32)
