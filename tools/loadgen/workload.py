"""Open-loop workload generation (docs/serving.md "workload plane").

One declarative spec — arrival process x prompt/output length
distributions x template/prefix mix x session idle gaps — compiled by
:meth:`Workload.build` into a flat arrival schedule of
:class:`WorkloadItem` (``at_s`` offset, prompt token ids, generation
budget).  The schedule is meant to be replayed OPEN-LOOP: arrivals
fire on the clock regardless of completions, which is what makes
queueing (and therefore goodput) measurable at all.

Determinism is a hard contract: ``build(seed)`` uses two independent
``numpy`` generators — one for the arrival process, one for the
payload (lengths, token ids, template choice) — so two workloads that
differ ONLY in arrival shape serve byte-identical prompts, and the
same seed reproduces the same schedule byte for byte across runs
(pinned in tests/test_loadgen.py).
"""
from __future__ import annotations

import dataclasses
import json
from typing import List, Optional, Sequence, Tuple

import numpy as np

ARRIVAL_KINDS = ("uniform", "poisson", "gamma_burst", "trace")
LENGTH_KINDS = ("fixed", "choice", "lognormal")


@dataclasses.dataclass(frozen=True)
class WorkloadItem:
    """One scheduled request: arrive at ``t0 + at_s``, submit
    ``prompt``, generate up to ``max_new_tokens``."""
    at_s: float
    prompt: Tuple[int, ...]
    max_new_tokens: int
    session: int = 0
    #: tenant LoRA adapter id (0 = base model) — forwarded to
    #: ``submit(adapter_id=...)`` by whoever replays the schedule
    tenant: int = 0


@dataclasses.dataclass
class ArrivalSpec:
    """The arrival process.

    ``uniform``      one request every ``period`` seconds (period 0 =
                     the saturation snapshot: everything due at t0)
    ``poisson``      exponential inter-arrivals at mean ``rate``/s
    ``gamma_burst``  gamma inter-arrivals at mean ``rate``/s with
                     coefficient of variation ``cv`` > 1 — the
                     heavy-tailed clumping of production traces
                     (Mooncake/Splitwise, PAPERS.md): most gaps ~0
                     (a burst), occasional long quiets
    ``trace``        replay explicit offsets (seconds from t0), e.g.
                     from :func:`load_trace`
    """
    kind: str = "uniform"
    period: float = 0.0          # uniform: seconds between arrivals
    rate: float = 10.0           # poisson/gamma_burst: mean requests/s
    cv: float = 4.0              # gamma_burst: inter-arrival CV (>1)
    trace: Tuple[float, ...] = ()

    def offsets(self, n: int, rng: np.random.Generator) -> List[float]:
        if self.kind == "uniform":
            return [i * self.period for i in range(n)]
        if self.kind == "poisson":
            gaps = rng.exponential(1.0 / self.rate, n)
        elif self.kind == "gamma_burst":
            # shape < 1 clumps arrivals: var = cv^2 / rate^2
            shape = 1.0 / (self.cv ** 2)
            gaps = rng.gamma(shape, self.cv ** 2 / self.rate, n)
        elif self.kind == "trace":
            if len(self.trace) < n:
                raise ValueError(
                    f"trace has {len(self.trace)} offsets but the "
                    f"workload asks for {n} requests")
            t0 = self.trace[0]
            return [float(t) - t0 for t in self.trace[:n]]
        else:
            raise ValueError(f"unknown arrival kind {self.kind!r} "
                             f"(one of {ARRIVAL_KINDS})")
        # first request arrives at t0;
        # the remaining gaps carry the process's shape
        offs = np.cumsum(gaps) - gaps[0]
        return [float(t) for t in offs]


@dataclasses.dataclass
class LengthSpec:
    """A token-count distribution: ``fixed`` (always ``value``),
    ``choice`` (weighted discrete ``choices`` of (length, weight)),
    or ``lognormal`` (heavy-tailed around ``median``, clamped to
    [``lo``, ``hi``])."""
    kind: str = "fixed"
    value: int = 8
    choices: Tuple[Tuple[int, float], ...] = ()
    median: float = 8.0
    sigma: float = 0.8
    lo: int = 1
    hi: int = 64

    def sample(self, rng: np.random.Generator) -> int:
        if self.kind == "fixed":
            return int(self.value)
        if self.kind == "choice":
            lens = [c[0] for c in self.choices]
            w = np.array([c[1] for c in self.choices], dtype=float)
            return int(lens[rng.choice(len(lens), p=w / w.sum())])
        if self.kind == "lognormal":
            v = rng.lognormal(mean=float(np.log(self.median)),
                              sigma=self.sigma)
            return int(min(max(round(v), self.lo), self.hi))
        raise ValueError(f"unknown length kind {self.kind!r} "
                         f"(one of {LENGTH_KINDS})")


@dataclasses.dataclass
class TenantSpec:
    """The multi-tenant dimension: each request draws a tenant (LoRA
    adapter id ``1..n_tenants``) from a Zipf-like power law of
    exponent ``s`` — a few hot tenants and a long cold tail, the
    S-LoRA/Punica serving regime (PAPERS.md).  Adapter id 0 (the base
    model) is expressed by leaving ``Workload.tenants`` unset, never
    drawn."""
    n_tenants: int = 8
    s: float = 1.2

    def sample(self, rng: np.random.Generator) -> int:
        ranks = np.arange(1, self.n_tenants + 1, dtype=float)
        w = ranks ** -float(self.s)
        return int(rng.choice(self.n_tenants, p=w / w.sum())) + 1


@dataclasses.dataclass
class Workload:
    """The full spec.  ``mix`` (when non-empty) overrides the two
    LengthSpecs with a deterministic per-index cycle of
    ``(prompt_len, gen_tokens)`` classes — how the paged/quant legs
    express their exact short/long geometry.  ``template_ratio`` of
    requests share one of ``templates`` random prefixes of
    ``template_len`` tokens (unique suffix fills the sampled prompt
    length) — the prefix-cache mix.  ``session_len`` > 0 groups
    consecutive arrivals into sessions and inserts ``idle_gap_s`` of
    think-time between them (the schedule shifts; the process's gaps
    within a session are untouched)."""
    n_requests: int
    arrival: ArrivalSpec = dataclasses.field(default_factory=ArrivalSpec)
    prompt_len: LengthSpec = dataclasses.field(
        default_factory=lambda: LengthSpec(value=8))
    gen_tokens: LengthSpec = dataclasses.field(
        default_factory=lambda: LengthSpec(value=16))
    mix: Tuple[Tuple[int, int], ...] = ()
    vocab: int = 256
    template_ratio: float = 0.0
    template_len: int = 0
    templates: int = 1
    session_len: int = 0
    idle_gap_s: float = 0.0
    tenants: Optional[TenantSpec] = None

    def build(self, seed: int = 0) -> List[WorkloadItem]:
        arr_rng = np.random.default_rng([int(seed), 0])
        pay_rng = np.random.default_rng([int(seed), 1])
        # the tenant draw rides its own payload-side stream: arrival
        # shape never changes the tenant sequence, and enabling tenants
        # leaves lengths/prompts (pay_rng's draws) bitwise unchanged
        ten_rng = np.random.default_rng([int(seed), 2])
        offs = self.arrival.offsets(self.n_requests, arr_rng)
        tmpl = [
            [int(t) for t in pay_rng.integers(0, self.vocab,
                                              (self.template_len,))]
            for _ in range(self.templates)
        ] if self.template_len > 0 else []
        items: List[WorkloadItem] = []
        gap = 0.0
        for i, at in enumerate(offs):
            session = i // self.session_len if self.session_len else 0
            if self.session_len and i and i % self.session_len == 0:
                gap += self.idle_gap_s
            if self.mix:
                p_len, gen = self.mix[i % len(self.mix)]
            else:
                p_len = self.prompt_len.sample(pay_rng)
                gen = self.gen_tokens.sample(pay_rng)
            if tmpl and pay_rng.random() < self.template_ratio:
                base = tmpl[int(pay_rng.integers(self.templates))]
                tail = max(int(p_len) - len(base), 1)
                prompt = base + [int(t) for t in pay_rng.integers(
                    0, self.vocab, (tail,))]
            else:
                prompt = [int(t) for t in pay_rng.integers(
                    0, self.vocab, (int(p_len),))]
            items.append(WorkloadItem(
                at_s=round(float(at) + gap, 6),
                prompt=tuple(prompt),
                max_new_tokens=int(gen),
                session=session,
                tenant=(self.tenants.sample(ten_rng)
                        if self.tenants else 0)))
        return items


def schedule_fingerprint(items: Sequence[WorkloadItem]) -> str:
    """Canonical JSON of a built schedule — the byte-identity handle
    the determinism tests (and any trace export) compare."""
    return json.dumps([dataclasses.asdict(it) for it in items],
                      sort_keys=True)


def load_trace(path: str) -> Tuple[ArrivalSpec, List[dict]]:
    """Read a replayable trace (one JSON object per line:
    ``{"at_s": ..., "prompt_len": ..., "gen_tokens": ...}`` — the
    Mooncake-style shape, lengths optional) tolerantly: torn lines are
    skipped, matching the summarize idiom.  Returns the trace-replay
    ArrivalSpec plus the raw records for length overrides."""
    offsets: List[float] = []
    records: List[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("at_s") is None:
                continue
            offsets.append(float(rec["at_s"]))
            records.append(rec)
    return ArrivalSpec(kind="trace", trace=tuple(offsets)), records
