"""Bench scenarios over the one replay harness (docs/serving.md
"workload plane").

Each scenario is a WORKLOAD CONFIG plus metric extraction — the drive
loop lives in harness.py, the schedule in workload.py.  The five
legacy ``bench_serve.py`` legs (serve / paged / spec / quant / fleet)
live here now with their committed headlines intact, joined by the
workload plane's own headline:

``run_goodput`` replays the SAME payload under two arrival shapes at
the SAME mean rate — uniform vs a heavy-tailed Gamma-burst trace
(rescaled to the uniform span, then replayed through the trace path)
— and scores both against per-phase SLOs.  Throughput stays flat
(same tokens, same span); goodput collapses under burst because
queue-wait/TTFT absorbs the clumping.  That gap is
``BENCH_loadgen_goodput.json``'s pinned headline: the observability
gap a throughput-only bench can never see.  A chaos leg (replica kill
+ autoscale mid-trace under burst arrival) asserts the fleet ledger's
zero-lost-requests invariant from completion records.
"""
from __future__ import annotations

import json
import os
from typing import List, Optional

import numpy as np

from .harness import replay_engine, replay_fleet
from .workload import ArrivalSpec, LengthSpec, TenantSpec, Workload


def _write_bench(out_dir, name, rec):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


def _build_model():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    cfg = GPT2Config(vocab_size=256, n_positions=64, d_model=64,
                     n_layer=2, n_head=4, remat=None, attn_impl="dense")
    return GPT2Model(cfg)


def _init_model():
    import jax
    model = _build_model()
    return model, model.init(jax.random.PRNGKey(0))


def _kv_budget_bytes(model, slots, max_seq_len):
    """The fixed KV-byte budget: what ``slots`` legacy fp strides cost,
    read from the cache spec (dtype itemsize included — fp16 and int8
    legs report TRUE bytes, not a hardcoded 4 bytes/elem)."""
    from deepspeed_tpu.inference.kv_cache import KVCacheSpec
    import jax.numpy as jnp
    cfg = model.config
    return KVCacheSpec(layers=cfg.n_layer, slots=slots,
                       heads=cfg.n_head, max_len=max_seq_len,
                       head_dim=cfg.d_head, dtype=jnp.float32).bytes


def _pages_for_budget(model, budget_bytes, page_len, quant=False):
    """(pages, page_bytes): allocatable pages a byte budget buys (+1
    for the scratch page, which spends no budget — it is masked-write
    storage, not request capacity), from the paged spec's
    ``page_bytes`` — the quant arm's sidecar-inclusive quantum, so the
    int8 leg's extra pages are real bytes, never a 4-bytes/elem
    assumption."""
    from deepspeed_tpu.inference.kv_cache import PagedKVCacheSpec
    import jax.numpy as jnp
    cfg = model.config
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, slots=1, heads=cfg.n_head, pages=1,
        page_len=page_len, head_dim=cfg.d_head, max_pages=1,
        dtype=(jnp.int8 if quant else jnp.float32), quant=quant)
    return budget_bytes // spec.page_bytes + 1, spec.page_bytes


def _mixed_stats(eng) -> dict:
    """The mixed-leg ``collect`` seam: TRUE device bytes from the
    engine's memory plane, cross-checked against the REAL array bytes
    so a spec-accounting bug (e.g. a sidecar miscount) cannot silently
    skew a fixed-byte headline."""
    data_bytes = sum(int(eng.cache[key].nbytes) for key in eng.cache
                     if key != "lengths")
    assert data_bytes == eng.cache_spec.bytes, \
        (data_bytes, eng.cache_spec.bytes)
    return {"kv_bytes": eng.kv_bytes, "param_bytes": eng.param_bytes}


# ---------------------------------------------------------------------------
# serve: continuous batching vs sequential decode
# ---------------------------------------------------------------------------


def run_ab(slots=8, n_requests=16, prompt_len=8, gen_tokens=16,
           tick_delay_s=0.02, arrival_s=0.0, out_dir="."):
    """Batched (slot pool) vs sequential (slots=1) under the same load
    and the same injected per-tick device time."""
    model, params = _init_model()
    wl = Workload(n_requests,
                  arrival=ArrivalSpec("uniform", period=arrival_s),
                  prompt_len=LengthSpec(value=prompt_len),
                  gen_tokens=LengthSpec(value=gen_tokens))
    items = wl.build(seed=0)

    def leg(n_slots, tag):
        run = replay_engine(
            model, params,
            {"slots": n_slots, "max_seq_len": 64,
             "prefill_len": max(prompt_len, 1),
             "flush_interval_ticks": 10},
            items, telemetry=True, warmup=(items[0].prompt, 2),
            delay_s=tick_delay_s, tag=tag)
        return {
            "slots": n_slots,
            "requests": n_requests,
            "tokens": run.tokens,
            "wall_s": run.wall_s,
            "tokens_per_s": run.tokens / run.wall_s,
            "token_p50_s": run.report.get("serve_token_p50_s"),
            "token_p99_s": run.report.get("serve_token_p99_s"),
        }

    batched = leg(slots, "batched")
    sequential = leg(1, "sequential")
    rec = {
        "metric": "serve_continuous_batching_speedup",
        "value": batched["tokens_per_s"] / sequential["tokens_per_s"],
        "tick_delay_s": tick_delay_s,
        "batched": batched,
        "sequential": sequential,
    }
    _write_bench(out_dir, "BENCH_serve.json", rec)
    return rec


# ---------------------------------------------------------------------------
# paged: page-table indirection + prefix reuse A/B (docs/serving.md)
# ---------------------------------------------------------------------------


def _short_long_mix(short, long, long_every):
    """The deterministic short/long cycle every mixed leg drives:
    every ``long_every``-th request is long, the rest short — as a
    Workload ``mix`` of (prompt_len, gen_tokens) classes."""
    return tuple([(short["prompt"], short["gen"])] * (long_every - 1)
                 + [(long["prompt"], long["gen"])])


def _run_mixed(model, params, serving, items, tag):
    """One saturation-snapshot leg (everything due at t0, no injected
    time): max concurrently ADMITTED requests is the number the KV
    layout, not the wall clock, decides."""
    run = replay_engine(model, params, serving, items,
                        collect=_mixed_stats, tag=tag)
    tokens = [r.tokens for r in run.requests]
    truncated = sum(r.finish_reason == "kv_capacity"
                    for r in run.requests)
    return {"tag": tag, "kv_bytes": run.stats["kv_bytes"],
            "param_bytes": run.stats["param_bytes"],
            "max_concurrent": run.max_concurrent, "ticks": run.ticks,
            "requests": len(run.requests),
            "kv_capacity_finishes": truncated,
            "tokens_total": sum(len(t) for t in tokens)}, tokens


def _run_prefix(model, params, serving, items, tick_delay_s, tag):
    """Template-sharing prompts under injected per-page prefill device
    time; total prefill seconds comes from the same windows the
    ``serve/prefill`` tracer spans cover (req.prefill_s)."""
    run = replay_engine(
        model, params, serving, items,
        warmup=(items[0].prompt[:1], 1), delay_s=tick_delay_s, tag=tag,
        collect=lambda eng: {
            "prefix_hits": eng.prefix.hits if eng.prefix else 0})
    reqs = run.requests
    out = {
        "prefill_total_s": sum(r.prefill_s for r in reqs),
        "computed_tokens": [r.computed_len for r in reqs],
        "shared_tokens": [r.shared_len for r in reqs],
        "prefix_hits": run.stats["prefix_hits"],
    }
    return out, [r.tokens for r in reqs]


def run_paged_ab(kv_budget_slots=4, max_seq_len=64, page_len=8,
                 n_requests=24, long_every=4, template_len=24,
                 prefix_k=6, tick_delay_s=0.03, out_dir="."):
    """The paged A/B: (1) admitted concurrency at a fixed KV-byte
    budget under a short/long mix, (2) prefix-reuse prefill compute.
    ``kv_budget_slots`` sets the budget: the slot count whose fixed
    strides exactly spend it on the legacy arm."""
    model, params = _init_model()

    # -- leg 1: admitted slots at fixed KV bytes ------------------------
    budget_bytes = _kv_budget_bytes(model, kv_budget_slots, max_seq_len)
    pages, _ = _pages_for_budget(model, budget_bytes, page_len)
    mix = _short_long_mix(dict(prompt=4, gen=4),       # 8 live -> 1 page
                          dict(prompt=template_len, gen=16), long_every)
    items = Workload(n_requests, mix=mix).build(seed=0)
    legacy, tok_l = _run_mixed(
        model, params,
        {"slots": kv_budget_slots, "max_seq_len": max_seq_len,
         "prefill_len": template_len + page_len, "queue_capacity": 256},
        items, "legacy")
    paged, tok_p = _run_mixed(
        model, params,
        {"slots": 4 * kv_budget_slots, "max_seq_len": max_seq_len,
         "prefill_len": template_len + page_len, "queue_capacity": 256,
         "page_len": page_len, "pages": pages},
        items, "paged")
    # over-subscribing the pool may TRUNCATE a long request at pool
    # exhaustion (the pool-aware kv_capacity finish — the documented
    # backpressure, docs/serving.md); it must never DIVERGE: every
    # paged stream matches the legacy arm token for token up to its
    # length
    truncated = 0
    for tl, tp in zip(tok_l, tok_p):
        assert tp == tl[:len(tp)], "paged arm diverged from legacy"
        truncated += tp != tl
    paged["truncated"] = truncated

    # -- leg 2: prefix reuse — compute ∝ 1 template + K deltas ----------
    prefix_items = Workload(
        prefix_k, prompt_len=LengthSpec(value=template_len + 4),
        gen_tokens=LengthSpec(value=2), template_ratio=1.0,
        template_len=template_len).build(seed=0)
    serving = {"slots": 4, "max_seq_len": max_seq_len,
               "prefill_len": template_len + page_len,
               "page_len": page_len, "queue_capacity": 256}
    on, tok_on = _run_prefix(
        model, params, {**serving, "prefix_cache": True}, prefix_items,
        tick_delay_s, "prefix_on")
    off, tok_off = _run_prefix(
        model, params, {**serving, "prefix_cache": False}, prefix_items,
        tick_delay_s, "prefix_off")
    assert tok_on == tok_off, "prefix cache changed the token streams"

    rec = {
        "metric": "serve_paged_admitted_ratio",
        "value": paged["max_concurrent"] / legacy["max_concurrent"],
        "page_len": page_len,
        "paged": paged,
        "legacy": legacy,
        "prefix": {
            "k": prefix_k,
            "template_len": template_len,
            "tick_delay_s": tick_delay_s,
            "on": on,
            "off": off,
            "prefill_ratio": (on["prefill_total_s"]
                              / max(off["prefill_total_s"], 1e-9)),
        },
    }
    _write_bench(out_dir, "BENCH_serve_paged.json", rec)
    return rec


# ---------------------------------------------------------------------------
# quant: int8 weights + int8 KV pages A/B (docs/serving.md)
# ---------------------------------------------------------------------------


def _token_agreement(a, b):
    """Positionwise greedy-stream agreement over two request lists —
    REPORTED, never asserted equal: quantization is a tolerance tier,
    not a bitwise one (docs/serving.md)."""
    total = same = 0
    for ta, tb in zip(a, b):
        for x, y in zip(ta, tb):
            total += 1
            same += x == y
    return same / max(total, 1)


def run_quant_ab(kv_budget_slots=4, max_seq_len=64, page_len=8,
                 slots=64, n_requests=96, long_every=4, out_dir="."):
    """The quantized-serving A/B (docs/serving.md "quantized serving"):
    admitted concurrency at a fixed KV-byte budget, int8 vs fp pages
    (page-exact geometry — 0 truncations by construction), plus the
    int8-weights params-HBM leg.  Greedy token agreement vs the fp leg
    is REPORTED for every arm, never asserted equal."""
    from deepspeed_tpu.runtime.utils import collect_memory_stats
    model, params = _init_model()

    budget_bytes = _kv_budget_bytes(model, kv_budget_slots, max_seq_len)
    pages_fp, _ = _pages_for_budget(model, budget_bytes, page_len)
    pages_q, _ = _pages_for_budget(model, budget_bytes, page_len,
                                   quant=True)
    # page-exact geometry: short = 1 page live, long = 3 pages live —
    # decode never crosses a page boundary, so the pool can never dry
    # mid-request (0 kv_capacity finishes, asserted below); gen=4
    # keeps every request alive across several ticks so the sampled
    # max-concurrency sees the full admitted wave
    mix = _short_long_mix(dict(prompt=page_len - 4, gen=4),
                          dict(prompt=3 * page_len - 4, gen=4),
                          long_every)
    items = Workload(n_requests, mix=mix).build(seed=0)
    base = {"slots": slots, "max_seq_len": max_seq_len,
            "prefill_len": 3 * page_len - 4, "queue_capacity": 256,
            "page_len": page_len, "prefix_cache": False}
    fp, tok_fp = _run_mixed(
        model, params, {**base, "pages": pages_fp}, items, "fp")
    q, tok_q = _run_mixed(
        model, params,
        {**base, "pages": pages_q,
         "quantization": {"kv": "int8"}}, items, "int8")
    # allocatable pages spend <= the budget by construction of
    # _pages_for_budget; the REAL accounting guard is the per-leg
    # array-bytes == spec-bytes assert in _mixed_stats, plus: the int8
    # pool (sidecar included) must not cost more device bytes than the
    # fp pool it beats
    assert q["kv_bytes"] <= fp["kv_bytes"], (q["kv_bytes"],
                                             fp["kv_bytes"])
    truncations = fp["kv_capacity_finishes"] + q["kv_capacity_finishes"]
    assert truncations == 0, "page-exact workload truncated"

    # weights leg: same workload, int8 weights over fp pages
    w8, tok_w8 = _run_mixed(
        model, params,
        {**base, "pages": pages_fp,
         "quantization": {"weights": "int8"}}, items, "weights_int8")
    params_ratio = fp["param_bytes"] / w8["param_bytes"]

    rec = {
        "metric": "serve_quant_admitted_ratio",
        "value": q["max_concurrent"] / fp["max_concurrent"],
        "kv_budget_bytes": budget_bytes,
        "page_len": page_len,
        "truncations": truncations,
        "int8": q,
        "fp": fp,
        "weights": {
            "leg": w8,
            "param_bytes_fp": fp["param_bytes"],
            "param_bytes_int8": w8["param_bytes"],
            "params_hbm_ratio": params_ratio,
            # allocator-stats snapshot (empty device list on the CPU
            # oracle; real HBM on TPU) — the same plane
            # collect_memory_stats() feeds the telemetry gauges
            "collect_memory_stats": collect_memory_stats(),
        },
        "token_agreement_vs_fp": {
            "kv_int8": _token_agreement(tok_fp, tok_q),
            "weights_int8": _token_agreement(tok_fp, tok_w8),
        },
    }
    _write_bench(out_dir, "BENCH_serve_quant.json", rec)
    return rec


# ---------------------------------------------------------------------------
# spec: draft-verify speculative decoding A/B (docs/serving.md)
# ---------------------------------------------------------------------------


def _steady_decode_per_token(records, warm_rid):
    """Per-token decode time from the completion records' timestamps —
    the same windows the decode/verify spans cover.  STEADY-STATE
    only: a request's first decode interval absorbs the co-admitted
    requests' prefill delay (every admission charges one unit in BOTH
    legs), so counting starts at the second nonzero interval — a spec
    block is one nonzero interval followed by its burst of
    zero-stamped tokens, so this drops exactly the first (polluted)
    block on either leg."""
    dec_s = dec_n = 0.0
    for rec in records:
        if rec.get("kind") == "serve_request" and rec.get("tokens") \
                and rec.get("rid") != warm_rid:
            nonzero = 0
            for t in rec.get("token_times_s") or []:
                if t > 0:
                    nonzero += 1
                if nonzero >= 2:
                    dec_s += float(t)
                    dec_n += 1
    return dec_s / max(dec_n, 1)


def run_spec_ab(k=4, slots=6, n_requests=6, prompt_len=8,
                gen_tokens=None, pass_delay_s=0.25, out_dir="."):
    """Speculative vs plain decode under the same injected per-pass
    device time.  The draft shares the target's params (acceptance
    ~= k), so wall/token should collapse toward 1/(k+1); the headline
    ratio is expected ∝ 1/mean-accepted-length.

    Geometry keeps the proof clean: slots cover the whole workload
    (every admission — whose prefill delay is identical in both legs —
    lands before the first decode tick, so the decode-phase intervals
    are pure per-pass time) and the DEFAULT generation budget is
    derived block-aligned from the given k (``gen_tokens - 1``
    divisible by ``k + 1``: no half-used final pass skewing the mean
    accepted length)."""
    if gen_tokens is None:
        gen_tokens = 4 * (k + 1) + 1
    model, params = _init_model()
    items = Workload(n_requests,
                     prompt_len=LengthSpec(value=prompt_len),
                     gen_tokens=LengthSpec(value=gen_tokens)
                     ).build(seed=0)
    base_serving = {"slots": slots, "max_seq_len": 64,
                    "prefill_len": max(prompt_len, 4),
                    "queue_capacity": 256,
                    "flush_interval_ticks": 10}
    spec_serving = dict(base_serving)
    spec_serving.update({
        "speculate_k": k,
        # the draft IS the target config here: with shared params the
        # proposals match and acceptance runs near k — the CPU stand-in
        # for a distilled draft
        "draft": {"d_model": 64, "n_layer": 2, "n_head": 4},
    })

    def leg(serving, draft_params, tag):
        run = replay_engine(
            model, params, serving, items, telemetry=True,
            warmup=(items[0].prompt[:4], 2),
            reset_spec_counters=(draft_params is not None),
            delay_s=pass_delay_s, draft_params=draft_params, tag=tag,
            collect=lambda eng: {
                "passes": eng._spec_passes,
                "accepted": eng._spec_accepted_n})
        tokens = [r.tokens for r in run.requests]
        n_tokens = sum(len(t) for t in tokens)
        passes = run.stats["passes"]
        mal = ((run.stats["accepted"] + passes) / passes
               if passes else 1.0)
        return {
            "tag": tag,
            "requests": len(tokens),
            "tokens": n_tokens,
            "wall_s": run.wall_s,
            "wall_per_token_s": run.wall_s / max(n_tokens, 1),
            "decode_s_per_token": _steady_decode_per_token(
                run.records, run.warm_rid),
            "mean_accepted_len": mal,
        }, tokens

    spec, tok_s = leg(spec_serving, params, "spec")
    base, tok_b = leg(base_serving, None, "baseline")
    # greedy parity: speculation must never change what is emitted
    assert tok_s == tok_b, "speculative stream diverged from baseline"
    rec = {
        # headline: decode-phase wall per token from the per-request
        # token timestamps (prefill admission pays the same one unit
        # per request in both legs and is excluded by construction —
        # it is reported inside each leg's wall_s)
        "metric": "serve_spec_wall_per_token_ratio",
        "value": (spec["decode_s_per_token"]
                  / max(base["decode_s_per_token"], 1e-9)),
        "speculate_k": k,
        "pass_delay_s": pass_delay_s,
        "expected_ratio_1_over_mal": 1.0 / spec["mean_accepted_len"],
        "total_wall_ratio": (spec["wall_per_token_s"]
                             / base["wall_per_token_s"]),
        "spec": spec,
        "baseline": base,
    }
    _write_bench(out_dir, "BENCH_serve_spec.json", rec)
    return rec


# ---------------------------------------------------------------------------
# fleet: router + replicated engines + SLO autoscaling A/B
# ---------------------------------------------------------------------------


def _fleet_config(replicas, *, min_replicas=1, max_replicas=None,
                  slots=4, slo_p99_s=30.0, up_window_s=1.0,
                  down_window_s=600.0):
    """One fleet ds_config: tiny deterministic model (every replica
    inits identical params from the shared seed), short hysteresis
    windows sized for a CPU bench, scale-down effectively off (the
    legs measure throughput/failover, not retirement)."""
    return {
        "serving": {"slots": slots, "max_seq_len": 64,
                    "prefill_len": 8, "queue_capacity": 512,
                    "flush_interval_ticks": 10},
        "telemetry": {"enabled": False},
        "fleet": {"replicas": replicas, "min_replicas": min_replicas,
                  "max_replicas": max_replicas or max(replicas, 1),
                  "slo_p99_s": slo_p99_s,
                  "scale_up_window_s": up_window_s,
                  "scale_down_window_s": down_window_s,
                  "spawn_timeout_s": 120.0, "backoff_base_s": 0.2,
                  "heartbeat_timeout_s": 60.0},
        "fleet_model": {"vocab_size": 256, "n_positions": 64,
                        "d_model": 64, "n_layer": 2, "n_head": 4,
                        "attn_impl": "dense", "seed": 0},
    }


def _assert_zero_lost(records):
    """The ledger's zero-lost-requests invariant, asserted from
    completion records alone: every submit has a completion, and every
    failed completion had already started streaming (typed
    ReplicaFailure, not silently-dropped queued work).  Returns
    (completions by rid, failover count, midstream failures)."""
    completions = {r["rid"]: r for r in records
                   if r.get("kind") == "fleet_request"}
    submits = [r for r in records if r.get("kind") == "fleet_submit"]
    assert len(completions) == len(submits), \
        f"dangling requests: {len(submits) - len(completions)}"
    lost = [r for r in completions.values()
            if r.get("error") and not r.get("started")]
    assert not lost, f"queued-but-unstarted requests lost: {lost}"
    failovers = sum(int(r.get("failed_over") or 0) for r in records
                    if r.get("kind") == "replica_dead")
    midstream = [r for r in completions.values() if r.get("error")]
    return completions, failovers, midstream


def _fleet_workload(n_requests, gen_tokens, *, arrival=None, seed=0):
    return Workload(
        n_requests, arrival=arrival or ArrivalSpec("uniform"),
        prompt_len=LengthSpec(value=6),
        gen_tokens=LengthSpec(value=gen_tokens)).build(seed=seed)


def _run_fleet_scaling_leg(n_replicas, n_requests, gen_tokens,
                           tick_delay_s, tag):
    """One scaling leg: warm every replica (compile happens off the
    clock), then serve the saturation workload (all requests due at
    t0) under injected per-tick device time."""
    items = _fleet_workload(n_requests, gen_tokens)
    run = replay_fleet(_fleet_config(n_replicas), items,
                       delay_s=tick_delay_s, tag=tag)
    assert all(r.error is None for r in run.requests), \
        [repr(r.error) for r in run.requests if r.error]
    return {"replicas": n_replicas, "requests": n_requests,
            "tokens": run.tokens, "wall_s": run.wall_s,
            "tokens_per_s": run.tokens / run.wall_s,
            "queue_wait_p99_s": run.queue_wait_p99_s}


def _run_fleet_killtrace(slo_p99_s, n_requests, arrival_s, gen_tokens,
                         tick_delay_s, kill_after_s):
    """The replica-kill + autoscale-up trace: 2 replicas under open-
    loop load sized ABOVE one replica's capacity, one replica
    SIGKILLed mid-stream.  Queued-but-unstarted requests fail over
    (zero lost — asserted from the completion records), queue-wait p99
    breaches the SLO while one replica carries everything, the
    autoscaler spawns a replacement, and the tail-phase p99 lands back
    under the SLO."""
    from deepspeed_tpu.telemetry.cli import _percentile
    items = _fleet_workload(
        n_requests, gen_tokens,
        arrival=ArrivalSpec("uniform", period=arrival_s), seed=1)
    cfg = _fleet_config(2, min_replicas=1, max_replicas=3, slots=2,
                        slo_p99_s=slo_p99_s, up_window_s=0.5)
    run = replay_fleet(cfg, items, delay_s=tick_delay_s,
                       kill_after_s=kill_after_s, tag="kill")
    completions, failovers, midstream = _assert_zero_lost(run.records)
    assert failovers > 0, "the kill never hit queued work"
    recover_t = run.recover_after_s
    assert recover_t is not None, "autoscale never spawned"

    # p99 attribution by phase (telemetry/cli.py's one interpolation —
    # the bench no longer carries its own percentile copy): degraded =
    # submitted after the kill while only one replica served;
    # recovered = submitted after the autoscaled replacement came up.
    # The SLO claim is about the tail.
    def _phase_p99(lo, hi):
        return _percentile(sorted(
            completions[r.rid]["queue_wait_s"]
            for r, t in zip(run.requests, run.submit_ts)
            if lo <= t < hi and r.rid in completions
            and completions[r.rid].get("queue_wait_s") is not None),
            0.99)

    p99_degraded = _phase_p99(kill_after_s, recover_t)
    # the recovered phase starts one backlog-drain grace after the
    # replacement came up (the surplus capacity needs a moment to eat
    # the degraded phase's queue); the claim is the TAIL holds the SLO
    drain_grace_s = min(2.0, (run.wall_s - recover_t) / 3)
    p99_recovered = _phase_p99(recover_t + drain_grace_s, 1e9)
    assert p99_recovered is not None and p99_recovered < slo_p99_s, \
        (p99_recovered, slo_p99_s)
    return {
        "slo_p99_s": slo_p99_s,
        "requests": n_requests,
        "arrival_s": arrival_s,
        "tick_delay_s": tick_delay_s,
        "killed_replica": run.killed,
        "kill_after_s": kill_after_s,
        "recover_after_s": recover_t,
        "wall_s": run.wall_s,
        "failovers": failovers,
        "midstream_failed": len(midstream),
        "unstarted_lost": 0,
        "queue_wait_p99_degraded_s": p99_degraded,
        "queue_wait_p99_recovered_s": p99_recovered,
    }


def run_fleet_ab(n_requests=16, gen_tokens=16, tick_delay_s=0.04,
                 slo_p99_s=1.5, out_dir="."):
    """The fleet A/B: aggregate tokens/s at 1 vs 2 replicas under
    identical injected per-tick device time (the headline, >= 1.8x
    expected — each replica is an independent slot pool paying its own
    ticks), plus the replica-kill + autoscale-up trace."""
    one = _run_fleet_scaling_leg(1, n_requests, gen_tokens,
                                 tick_delay_s, "one")
    two = _run_fleet_scaling_leg(2, n_requests, gen_tokens,
                                 tick_delay_s, "two")
    # 160 requests at 0.12s spacing = a 19s open-loop window: the kill
    # lands early, the autoscaled replacement comes up mid-window (its
    # subprocess pays a full jax import + compile, ~8-13s depending on
    # host load — the window must outlast the SLOW case), and the tail
    # requests measure the RECOVERED fleet's queue wait
    kill = _run_fleet_killtrace(
        slo_p99_s=slo_p99_s, n_requests=160, arrival_s=0.12,
        gen_tokens=9, tick_delay_s=tick_delay_s, kill_after_s=1.2)
    rec = {
        "metric": "fleet_scaling_tokens_ratio",
        "value": two["tokens_per_s"] / one["tokens_per_s"],
        "tick_delay_s": tick_delay_s,
        "one_replica": one,
        "two_replicas": two,
        "killtrace": kill,
    }
    _write_bench(out_dir, "BENCH_fleet.json", rec)
    return rec


# ---------------------------------------------------------------------------
# disaggregated fleet: prefill/decode roles + chunked prefill A/B
# ---------------------------------------------------------------------------


def _disagg_fleet_config(*, roles=None, chunk=0, slots=8):
    """The disagg A/B's shared base: the fleet config with the paged
    layout (migration needs pages) and a prefill bucket wide enough
    for the long-prompt class; the disagg arm adds roles + chunking on
    top of the IDENTICAL serving plane."""
    cfg = _fleet_config(2, slots=slots)
    cfg["serving"].update({"prefill_len": 32, "page_len": 8,
                           "pages": 128})
    if chunk:
        cfg["serving"]["prefill_chunk_len"] = chunk
    if roles:
        cfg["fleet"]["roles"] = dict(roles)
    return cfg


def _disagg_decode_phases(records, min_decode_tokens):
    """Per-request TPOT over the short-decode class (the requests whose
    cadence the decode SLO defends), attributed from the router ledger
    alone."""
    from deepspeed_tpu.telemetry.goodput import phases_from_record
    return [ph for ph in (phases_from_record(r) for r in records)
            if ph is not None and ph.get("error") is None
            and ph["tpot_s"] is not None
            and ph["tokens"] > min_decode_tokens]


def _run_disagg_leg(cfg, items, tick_delay_s, tag, min_decode_tokens):
    from deepspeed_tpu.telemetry.cli import _percentile
    run = replay_fleet(cfg, items, delay_s=tick_delay_s, tag=tag)
    assert all(r.error is None for r in run.requests), \
        [repr(r.error) for r in run.requests if r.error]
    _assert_zero_lost(run.records)
    phases = _disagg_decode_phases(run.records, min_decode_tokens)
    tpots = sorted(ph["tpot_s"] for ph in phases)
    ttfts = sorted(ph["ttft_s"] for ph in phases
                   if ph["ttft_s"] is not None)
    migrations = [r for r in run.records
                  if r.get("kind") == "migration"]
    return {
        "tag": tag,
        "requests": len(run.requests),
        "tokens": run.tokens,
        "wall_s": run.wall_s,
        "decode_requests_scored": len(tpots),
        "decode_tpot_p50_s": _percentile(tpots, 0.50),
        "decode_tpot_p99_s": _percentile(tpots, 0.99),
        "ttft_p99_s": _percentile(ttfts, 0.99),
        "migrations_handed": sum(1 for m in migrations
                                 if m.get("custody") == "decode"),
    }


def run_fleet_disagg(n_requests=36, arrival_s=0.08, gen_tokens=16,
                     long_prompt=24, long_gen=2, chunk=8,
                     tick_delay_s=0.02, out_dir="."):
    """The disaggregation A/B (BENCH_fleet_disagg.json): the SAME
    mixed trace — a steady stream of short-prompt/long-decode requests
    interleaved with long-prompt/short-decode ones — replayed against

    * a HOMOGENEOUS 2-replica fleet (every replica admits and
      decodes: each long-prompt prefill stalls that replica's decode
      loop for an injected device-time unit), and
    * a DISAGGREGATED fleet — ``roles: {prefill: 1, decode: 1}`` with
      CHUNKED prefill (one delay unit per chunk, docs/stages.md):
      prefill work lands on the prefill replica, finished prefixes
      migrate over the binary wire frames, and the decode replica's
      loop never shares a tick with an admission.

    The headline is the decode-cadence tail ratio
    ``disagg decode TPOT p99 / homogeneous`` (LOWER is better, < 1
    asserted): the disagg arm holds decode p99 flat under prefill
    interference that degrades the homogeneous fleet.  The disagg arm
    pays for it in TTFT (chunks + migration) — reported, not pinned:
    that is the DistServe trade, bought deliberately."""
    items = Workload(
        n_requests, arrival=ArrivalSpec("uniform", period=arrival_s),
        mix=((6, gen_tokens), (6, gen_tokens),
             (long_prompt, long_gen))).build(seed=0)
    min_scored = max(gen_tokens // 2, long_gen + 1)
    homog = _run_disagg_leg(
        _disagg_fleet_config(), items, tick_delay_s, "homog",
        min_scored)
    disagg = _run_disagg_leg(
        _disagg_fleet_config(roles={"prefill": 1, "decode": 1},
                             chunk=chunk),
        items, tick_delay_s, "disagg", min_scored)
    assert disagg["migrations_handed"] > 0, \
        "disagg arm never migrated a request"
    ratio = (disagg["decode_tpot_p99_s"]
             / max(homog["decode_tpot_p99_s"], 1e-9))
    # the phenomenon, asserted: phase separation must actually defend
    # the decode tail on the same trace, else the bench stopped
    # showing what it pins
    assert ratio < 1.0, (disagg["decode_tpot_p99_s"],
                         homog["decode_tpot_p99_s"])
    rec = {
        "metric": "fleet_disagg_decode_p99_ratio",
        "value": ratio,
        "tick_delay_s": tick_delay_s,
        "arrival_s": arrival_s,
        "prefill_chunk_len": chunk,
        "mix": {"short": [6, gen_tokens],
                "long": [long_prompt, long_gen]},
        "homogeneous": homog,
        "disagg": disagg,
    }
    _write_bench(out_dir, "BENCH_fleet_disagg.json", rec)
    return rec


# ---------------------------------------------------------------------------
# lora: multi-tenant adapter serving vs one-merged-model-per-tenant
# ---------------------------------------------------------------------------


def _lora_serving(slots, prefill_len, rank, n_tenants, hbm_slots,
                  targets):
    return {"slots": slots, "max_seq_len": 64,
            "prefill_len": prefill_len, "page_len": 8, "pages": 128,
            "queue_capacity": 256, "flush_interval_ticks": 10,
            "lora": {"rank": rank, "alpha": 2.0 * rank,
                     "max_adapters": max(2 * n_tenants, 16),
                     "hbm_adapter_slots": hbm_slots,
                     "targets": list(targets)}}


def _pool_stats(eng):
    return {"adapter_bytes": eng.adapter_bytes,
            "param_bytes": eng.param_bytes,
            "resident": eng.adapters.resident(),
            "hits": eng.adapters.hits,
            "faults": eng.adapters.faults,
            "evictions": eng.adapters.evictions,
            "decode_programs": eng._decode_fn._cache_size(),
            "scale": eng.lora_scale}


def _ttft_p99(requests):
    from deepspeed_tpu.telemetry.cli import _percentile
    return _percentile(sorted(r.token_times[0] for r in requests
                              if r.token_times), 0.99)


def run_lora(n_tenants=12, hbm_slots=4, rank=4, n_requests=48,
             prompt_len=8, gen_tokens=8, slots=8, zipf_s=1.2,
             targets=("qkv_w", "out_w"), out_dir="."):
    """The multi-tenant LoRA headline (BENCH_serve_lora.json,
    docs/serving.md "multi-tenant serving"): one base model + a paged
    HBM adapter pool serves ``n_tenants`` tenants for
    ``adapter_pool_bytes`` extra HBM; the baseline serves each tenant
    with a dense-MERGED param copy (``W + BA``, the S-LoRA strawman)
    for ``n_tenants * param_bytes``.  The pinned headline is the
    admitted-tenants-per-HBM-byte ratio (>= 10x asserted here AND by
    the benchgate pin).

    Rides along: (1) per-tenant CORRECTNESS — the hottest tenant's
    heterogeneous-batch streams replayed against its merged-model
    engine, token for token; (2) the zero-recompile contract over the
    Zipf tenant mix (decode compiles ONE program); (3) the
    cold-adapter tail — TTFT p99 with every admission faulting +
    evicting (more tenants than HBM slots) vs the all-hit leg."""
    import dataclasses as _dc
    from deepspeed_tpu.inference.adapters import (adapter_param_shapes,
                                                  merge_adapter,
                                                  synth_adapter)

    model, params = _init_model()
    serving = _lora_serving(slots, 2 * prompt_len, rank, n_tenants,
                            hbm_slots, targets)
    wl = Workload(n_requests,
                  prompt_len=LengthSpec(value=prompt_len),
                  gen_tokens=LengthSpec(value=gen_tokens),
                  tenants=TenantSpec(n_tenants=n_tenants, s=zipf_s))
    items = wl.build(seed=0)
    assert len({it.tenant for it in items}) > 1, "degenerate Zipf draw"
    run = replay_engine(model, params, serving, items,
                        warmup=(items[0].prompt, 2),
                        collect=_pool_stats, tag="lora")
    stats = run.stats
    assert stats["decode_programs"] == 1, \
        f"tenant mix recompiled decode: {stats['decode_programs']}"
    streams = {}
    for it, r in zip(items, run.requests):
        streams.setdefault(it.tenant, []).append((it, r.tokens))

    # -- correctness arm: hottest tenant vs its dense-merged engine ----
    hot = max(streams, key=lambda t: len(streams[t]))
    shapes = adapter_param_shapes(model.config.n_layer,
                                  model.config.d_model, rank,
                                  tuple(targets))
    merged_params = merge_adapter(params, synth_adapter(hot, shapes),
                                  2.0 * rank / rank)
    merged_serving = {k: v for k, v in serving.items() if k != "lora"}
    merged_items = [_dc.replace(it, tenant=0)
                    for it, _ in streams[hot]]
    merged = replay_engine(model, merged_params, merged_serving,
                           merged_items, warmup=(items[0].prompt, 2),
                           tag="merged")
    for (_, toks), ref in zip(streams[hot], merged.requests):
        assert toks == ref.tokens, \
            "heterogeneous tenant stream diverged from merged model"

    # -- the headline: admitted tenants per HBM byte -------------------
    # lora arm: n_tenants served for adapter_pool_bytes extra HBM.
    # merged arm: each tenant costs a FULL param copy resident in HBM.
    param_bytes = stats["param_bytes"]
    adapter_bytes = stats["adapter_bytes"]
    tenants_per_byte_lora = n_tenants / adapter_bytes
    tenants_per_byte_merged = n_tenants / (n_tenants * param_bytes)
    value = tenants_per_byte_lora / tenants_per_byte_merged
    assert value >= 10.0, (value, param_bytes, adapter_bytes)

    # -- cold-adapter tail under eviction pressure ---------------------
    # every request a FRESH tenant (> hbm slots: each admission faults
    # and evicts an LRU resident) vs every request the SAME tenant
    # (one fault, then pure hits)
    n_cold = 2 * hbm_slots + 4
    cold_serving = _lora_serving(slots, 2 * prompt_len, rank,
                                 n_cold, hbm_slots, targets)
    base_items = Workload(
        n_cold, prompt_len=LengthSpec(value=prompt_len),
        gen_tokens=LengthSpec(value=gen_tokens)).build(seed=1)
    cold_items = [_dc.replace(it, tenant=i + 1)
                  for i, it in enumerate(base_items)]
    hot_items = [_dc.replace(it, tenant=1) for it in base_items]
    cold = replay_engine(model, params, cold_serving, cold_items,
                         warmup=(base_items[0].prompt, 2),
                         collect=_pool_stats, tag="cold")
    hotleg = replay_engine(model, params, cold_serving, hot_items,
                           warmup=(base_items[0].prompt, 2),
                           collect=_pool_stats, tag="hot")
    assert cold.stats["evictions"] > 0, "cold leg never evicted"
    assert hotleg.stats["faults"] == 1, hotleg.stats["faults"]

    rec = {
        "metric": "serve_lora_tenants_per_byte",
        "value": value,
        "rank": rank,
        "targets": list(targets),
        "n_tenants": n_tenants,
        "hbm_adapter_slots": hbm_slots,
        "zipf_s": zipf_s,
        "param_bytes": param_bytes,
        "adapter_pool_bytes": adapter_bytes,
        "tenants_per_hbm_byte": {
            "lora": tenants_per_byte_lora,
            "merged_per_tenant": tenants_per_byte_merged,
        },
        "zipf_leg": {
            "requests": n_requests,
            "tokens": run.tokens,
            "wall_s": run.wall_s,
            "distinct_tenants": len(streams),
            "decode_programs": stats["decode_programs"],
            "pool": {k: stats[k] for k in
                     ("resident", "hits", "faults", "evictions")},
            "ttft_p99_s": _ttft_p99(run.requests),
        },
        "parity_tenant": hot,
        "cold_fault": {
            "tenants": n_cold,
            "evictions": cold.stats["evictions"],
            "faults": cold.stats["faults"],
            "ttft_p99_s": _ttft_p99(cold.requests),
            "hot_ttft_p99_s": _ttft_p99(hotleg.requests),
        },
    }
    _write_bench(out_dir, "BENCH_serve_lora.json", rec)
    return rec


# ---------------------------------------------------------------------------
# goodput: uniform vs burst arrival at the same mean rate (the workload
# plane's own headline) + the chaos leg
# ---------------------------------------------------------------------------


def _burst_trace(n_requests, rate, cv, seed):
    """A heavy-tailed Gamma-burst schedule RESCALED to the uniform
    span, returned as a replayable trace: same mean rate by
    construction (last arrival pinned to ``(n-1)/rate``), clumping
    shape preserved — so the A/B isolates arrival SHAPE, the only
    variable goodput should react to."""
    raw = ArrivalSpec("gamma_burst", rate=rate, cv=cv).offsets(
        n_requests, np.random.default_rng([int(seed), 0]))
    span = (n_requests - 1) / rate
    scale = span / max(raw[-1], 1e-9)
    return tuple(round(t * scale, 6) for t in raw)


def _goodput_leg(model, params, slots, items, tick_delay_s, slo, tag):
    from deepspeed_tpu.telemetry.goodput import (phases_from_record,
                                                 score)
    run = replay_engine(
        model, params,
        {"slots": slots, "max_seq_len": 64, "prefill_len": 8,
         "queue_capacity": 256, "flush_interval_ticks": 10},
        items, telemetry=True, warmup=(items[0].prompt, 2),
        delay_s=tick_delay_s, slo=slo, tag=tag)
    rep = run.report
    # the plane proven end-to-end, twice over: (1) the tracker's
    # scalar flush round-trips through the artifact into the
    # summarize report; (2) rescoring the completion records (minus
    # the warmup request — its TTFT is XLA compile time, off the
    # clock by design) reproduces the live tracker's verdict exactly
    assert rep.get("serve_goodput") is not None
    assert abs(rep["serve_goodput"] - run.goodput["goodput"]) < 1e-9, \
        (rep["serve_goodput"], run.goodput["goodput"])
    phases = [ph for ph in (phases_from_record(r) for r in run.records)
              if ph is not None and ph["rid"] != run.warm_rid]
    recs = score(phases, slo[0], slo[1])
    assert abs(recs["goodput"] - run.goodput["goodput"]) < 1e-9, \
        (recs["goodput"], run.goodput["goodput"])
    arrivals = sorted(ph["arrival_s"] for ph in phases
                      if ph["arrival_s"] is not None)
    return {
        "tag": tag,
        "requests": len(run.requests),
        "tokens": run.tokens,
        "wall_s": run.wall_s,
        "tokens_per_s": run.tokens / run.wall_s,
        "goodput": recs["goodput"],
        "ttft_miss": recs["ttft_miss"],
        "tpot_miss": recs["tpot_miss"],
        "ttft_p50_s": recs["ttft_p50_s"],
        "ttft_p99_s": recs["ttft_p99_s"],
        "tpot_p50_s": recs["tpot_p50_s"],
        "tpot_p99_s": recs["tpot_p99_s"],
        "queue_wait_p99_s": recs["queue_wait_p99_s"],
        "arrival_span_s": (round(arrivals[-1] - arrivals[0], 6)
                          if arrivals else None),
    }


def _run_chaos_leg(n_requests, rate, cv, gen_tokens, tick_delay_s,
                   kill_after_s, slo, seed):
    """Replica kill + autoscale mid-trace UNDER BURST ARRIVAL: the
    chaos scenario.  Zero-lost-requests asserted from the ledger;
    goodput scored from the same fleet_request records (reported — a
    kill mid-burst is exactly when goodput should sag)."""
    from deepspeed_tpu.telemetry.goodput import (phases_from_record,
                                                 score)
    trace = _burst_trace(n_requests, rate, cv, seed)
    items = Workload(
        n_requests, arrival=ArrivalSpec("trace", trace=trace),
        prompt_len=LengthSpec(value=6),
        gen_tokens=LengthSpec(value=gen_tokens)).build(seed=seed)
    cfg = _fleet_config(2, min_replicas=1, max_replicas=3, slots=2,
                        slo_p99_s=1.5, up_window_s=0.5)
    # the kill waits for the victim to hold a real backlog (slots=2
    # streaming + 2 queued): under burst arrival a fixed kill time can
    # land in an inter-burst quiet where nothing would fail over
    run = replay_fleet(cfg, items, delay_s=tick_delay_s,
                       kill_after_s=kill_after_s,
                       kill_min_outstanding=4, tag="chaos")
    completions, failovers, midstream = _assert_zero_lost(run.records)
    assert failovers > 0, "the kill never hit queued work"
    assert run.recover_after_s is not None, "autoscale never spawned"
    measured = {r.rid for r in run.requests}
    phases = [ph for ph in (phases_from_record(r) for r in run.records)
              if ph is not None and ph["rid"] in measured]
    gp = score(phases, slo[0], slo[1])
    return {
        "requests": n_requests,
        "killed_replica": run.killed,
        "kill_after_s": kill_after_s,
        "recover_after_s": run.recover_after_s,
        "wall_s": run.wall_s,
        "failovers": failovers,
        "midstream_failed": len(midstream),
        "unstarted_lost": 0,
        "goodput": gp["goodput"],
        "slo_ttft_s": slo[0],
        "slo_tpot_s": slo[1],
        "ttft_miss": gp["ttft_miss"],
        "tpot_miss": gp["tpot_miss"],
        "queue_wait_p99_s": gp["queue_wait_p99_s"],
    }


def run_goodput(n_requests=48, prompt_len=6, gen_tokens=8, slots=4,
                tick_delay_s=0.02, rate=10.0, burst_cv=6.0,
                slo_ttft_s=0.2, slo_tpot_s=0.1, seed=0,
                trace_path=None, chaos=True, out_dir="."):
    """The workload plane's headline A/B (BENCH_loadgen_goodput.json):
    the SAME payload replayed under uniform arrival and under a
    heavy-tailed Gamma-burst trace at the SAME mean rate.  Throughput
    stays flat (same tokens over the same span); goodput collapses
    under burst because the clumps queue behind the slot pool and blow
    the TTFT SLO.  The pinned headline is the goodput GAP
    (uniform - burst) — higher means the plane resolves the phenomenon
    a throughput bench can't see.  ``trace_path`` replays an external
    trace (``load_trace`` format) as the burst leg instead."""
    # the fleet leg first: its replicas are child processes, and a
    # parent that has started a JAX backend holds the device they need
    chaos_rec = _run_chaos_leg(
        n_requests=40, rate=8.0, cv=4.0, gen_tokens=6,
        tick_delay_s=0.04, kill_after_s=1.0,
        slo=(1.5, 0.5), seed=seed) if chaos else None
    model, params = _init_model()
    slo = (slo_ttft_s, slo_tpot_s)
    payload = dict(prompt_len=LengthSpec(value=prompt_len),
                   gen_tokens=LengthSpec(value=gen_tokens))
    uniform_items = Workload(
        n_requests, arrival=ArrivalSpec("uniform", period=1.0 / rate),
        **payload).build(seed=seed)
    if trace_path is not None:
        from .workload import load_trace
        arrival, _ = load_trace(trace_path)
        trace = arrival.trace[:n_requests]
    else:
        trace = _burst_trace(n_requests, rate, burst_cv, seed)
    burst_items = Workload(
        n_requests, arrival=ArrivalSpec("trace", trace=trace),
        **payload).build(seed=seed)
    # identical payload by construction (independent payload stream)
    assert [it.prompt for it in uniform_items] \
        == [it.prompt for it in burst_items]

    uniform = _goodput_leg(model, params, slots, uniform_items,
                           tick_delay_s, slo, "uniform")
    burst = _goodput_leg(model, params, slots, burst_items,
                         tick_delay_s, slo, "burst")
    # the phenomenon, asserted: burst arrival must not change
    # throughput much (same tokens, same span) while goodput drops —
    # otherwise the bench quietly stopped showing what it pins
    assert burst["tokens_per_s"] > 0.6 * uniform["tokens_per_s"], \
        (burst["tokens_per_s"], uniform["tokens_per_s"])
    assert uniform["goodput"] - burst["goodput"] >= 0.2, \
        (uniform["goodput"], burst["goodput"])
    rec = {
        "metric": "loadgen_goodput_burst_gap",
        "value": uniform["goodput"] - burst["goodput"],
        "slo_ttft_s": slo_ttft_s,
        "slo_tpot_s": slo_tpot_s,
        "rate_rps": rate,
        "burst_cv": burst_cv,
        "tick_delay_s": tick_delay_s,
        "seed": seed,
        "throughput_ratio_burst_over_uniform": (
            burst["tokens_per_s"] / uniform["tokens_per_s"]),
        "uniform": uniform,
        "burst": burst,
    }
    if chaos:
        rec["chaos"] = chaos_rec
    _write_bench(out_dir, "BENCH_loadgen_goodput.json", rec)
    return rec




# ---------------------------------------------------------------------------
# kv_tier: park idle sessions on host/disk and resume them (docs/
# serving.md "KV tiering")
# ---------------------------------------------------------------------------


def run_kv_tier(n_sessions=8, prompt_len=17, cont_len=8, gen_tokens=4,
                page_len=8, pages=12, slots=4, idle_park_ticks=3,
                host_budget_pages=2, think_s=0.4, out_dir="."):
    """The KV-tiering headline A/B (BENCH_kv_tier.json): ``n_sessions``
    two-turn conversations over the SAME small page pool — a wave of
    first turns, ``idle_gap_s`` of think-time (the ``Workload`` session
    machinery), then a wave of continuations whose prompts extend turn
    one.  The tiered arm parks idle prefix pages to host RAM and disk
    (both tiers exercised: ``host_budget_pages`` < the parked set) and
    resumes every session from the tier; the HBM-only arm must evict
    cached prefixes under the same pool pressure and recompute.  The
    pinned headline is the ratio of sessions resumed with their full
    prefix at the SAME fixed HBM page budget — sessions per HBM byte.

    Riders: (1) bitwise parity — the tiered arm's token streams equal
    the HBM-only arm's (park/resume or recompute, never a diverged
    stream); (2) the tier actually moved bytes through BOTH tiers
    (spill and fetch counters, disk hits); (3) zero lost requests."""
    import dataclasses as _dc

    model, params = _init_model()
    S = n_sessions
    wl = Workload(2 * S,
                  arrival=ArrivalSpec("uniform", period=0.05),
                  prompt_len=LengthSpec(value=prompt_len),
                  gen_tokens=LengthSpec(value=gen_tokens),
                  session_len=S, idle_gap_s=think_s)
    items = wl.build(seed=0)
    # rewrite payloads into per-session two-turn conversations: item i
    # is conversation i's first turn, item S+i extends it by cont_len
    # tokens — identical across arms by construction
    convs = []
    for s in range(S):
        rng = np.random.default_rng([11, s])
        base = [int(t) for t in rng.integers(1, 256, (prompt_len,))]
        cont = [int(t) for t in rng.integers(1, 256, (cont_len,))]
        convs.append((tuple(base), tuple(base + cont)))
    items = [_dc.replace(it,
                         prompt=convs[i % S][0 if i < S else 1])
             for i, it in enumerate(items)]
    assert items[S].at_s - items[S - 1].at_s >= think_s, \
        "session gap did not land between the turn waves"
    warm_rng = np.random.default_rng([11, 999])
    warm = [int(t) for t in warm_rng.integers(1, 256, (6,))]

    serving = {"slots": slots, "max_seq_len": 64,
               "prefill_len": prompt_len + cont_len + 7,
               "page_len": page_len, "pages": pages,
               "queue_capacity": 64}
    full_prefix = (prompt_len // page_len) * page_len

    def _tier_stats(eng):
        t = eng.kv_tier
        if t is None:
            return {"spill_bytes": 0, "fetch_bytes": 0,
                    "parked_pages_total": 0, "resumed_pages": 0,
                    "corrupt": 0, "hbm_kv_bytes": eng.kv_bytes}
        return {"spill_bytes": t.spill_bytes,
                "fetch_bytes": t.fetch_bytes,
                "parked_pages_total": t.parked_pages_total,
                "resumed_pages": t.resumed_pages_total,
                "corrupt": t.corrupt_total,
                "resume_p99_s": t.resume_p99_s(),
                "hbm_kv_bytes": eng.kv_bytes}

    import tempfile
    disk_dir = tempfile.mkdtemp(prefix="loadgen_kvtier_")
    tiered = replay_engine(
        model, params,
        {**serving, "kv_tier": {"idle_park_ticks": idle_park_ticks,
                                "host_budget_pages": host_budget_pages,
                                "disk_dir": disk_dir}},
        items, warmup=(warm, 2), idle_tick=True,
        collect=_tier_stats, tag="kv_tiered")
    base = replay_engine(
        model, params, serving, items, warmup=(warm, 2),
        idle_tick=True, collect=_tier_stats, tag="kv_base")

    # bitwise parity: tiered resume (or its recompute fallback) must
    # never diverge a stream
    for rt, rb in zip(tiered.requests, base.requests):
        assert rt.tokens == rb.tokens, \
            "tiered arm diverged from the HBM-only arm"

    def _resumed(run):
        return sum(1 for r in run.requests[S:]
                   if r.shared_len >= full_prefix)

    resumed_tiered = _resumed(tiered)
    resumed_base = _resumed(base)
    ts = tiered.stats
    assert ts["spill_bytes"] > 0 and ts["fetch_bytes"] > 0, ts
    assert ts["corrupt"] == 0, ts
    assert resumed_tiered > resumed_base, \
        (resumed_tiered, resumed_base)
    hbm_bytes = ts["hbm_kv_bytes"]
    value = ((resumed_tiered / hbm_bytes)
             / max(resumed_base / hbm_bytes, 1.0 / hbm_bytes))

    rec = {
        "metric": "kv_tier_sessions_per_hbm_byte",
        "value": value,
        "n_sessions": S,
        "page_len": page_len,
        "pages": pages,
        "idle_park_ticks": idle_park_ticks,
        "host_budget_pages": host_budget_pages,
        "think_s": think_s,
        "hbm_kv_bytes": hbm_bytes,
        "sessions_resumed": {"tiered": resumed_tiered,
                             "hbm_only": resumed_base},
        "sessions_per_hbm_byte": {
            "tiered": resumed_tiered / hbm_bytes,
            "hbm_only": resumed_base / hbm_bytes,
        },
        "tiered": {"tokens": tiered.tokens, "wall_s": tiered.wall_s,
                   "ticks": tiered.ticks, **ts},
        "hbm_only": {"tokens": base.tokens, "wall_s": base.wall_s,
                     "ticks": base.ticks},
    }
    _write_bench(out_dir, "BENCH_kv_tier.json", rec)
    return rec

#: scenario registry — ``python -m tools.loadgen <name>``
SCENARIOS = {
    "serve": run_ab,
    "paged": run_paged_ab,
    "spec": run_spec_ab,
    "quant": run_quant_ab,
    "fleet": run_fleet_ab,
    "fleet_disagg": run_fleet_disagg,
    "goodput": run_goodput,
    "lora": run_lora,
    "kv_tier": run_kv_tier,
}
