"""Job kind ``serve_open_loop``: one ServeEngine on one chip under an open
loop of requests, each timed from when it was DUE.

One thread: between two serving ticks it submits whatever has come due, so a
request can be handed over up to one tick late; that lateness is inside the
TTFT (it is measured from the due time) and is printed.  Arrivals start
``lead_s`` before the window so that it opens in steady state; the lead-in
is set-up.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from . import traffic
from .yardstick import percentile, say

# Probe and stream tolerance, max abs difference of float32 logits against
# the float32 reference on the same bf16 weights: activations and logits are
# bf16 (8 bits of mantissa), logits of random weights reach |4|, where one
# bf16 step is 0.0156-0.031; 48 layers of bf16 rounding measured 0.03-0.06
# here (my chip run, PR 25).  Weights or KV held in 8 bits, or a skipped
# layer, miss it by far.
LOGIT_TOL = 0.125
PROBE_TICKS = 4
PROBE_REQUESTS = 3
STREAM_REQUESTS = 4


def _make_params(model, seed: int, dtype):
    """Weights on the device in one jitted call from the seed, in the type
    they are served in."""
    return jax.jit(lambda k: jax.tree.map(
        lambda x: x.astype(dtype), model.init(k)))(jax.random.PRNGKey(seed))


def _probe(family, params, serving: dict, items, impl: str, scope,
           ref_len: int):
    """Prefill and PROBE_TICKS decode ticks of a few requests through the
    model's paged serving entry points (same kernels and decode arm as the
    engine, a pool of one request) against the plain reference's logits on
    the same context.  Returns (max abs diff, largest |reference logit|)."""
    from deepspeed_tpu.inference.kv_cache import (PagedKVCacheSpec,
                                                  init_paged_cache)
    mcfg = family.model.config
    page_len, slots = serving["page_len"], serving["slots"]
    bucket = serving["prefill_len"]
    max_pages = -(-serving["max_seq_len"] // page_len)
    spec = PagedKVCacheSpec(
        layers=mcfg.n_layer, slots=slots, heads=mcfg.n_head,
        pages=1 + max_pages, page_len=page_len, head_dim=mcfg.d_head,
        max_pages=max_pages, dtype=params["wte"].dtype, quant=False)
    cache = init_paged_cache(spec)
    active = np.zeros((slots,), bool)
    active[0] = True
    model = family.model

    def run(params, cache, prompt, n, forced, row, table):
        logits, k, v = model.prefill_paged(
            params, prompt, n, np.int32(0), row, cache["k"], cache["v"])

        def tick(carry, token):
            k, v, lengths = carry
            tokens = jnp.zeros((slots,), jnp.int32).at[0].set(token)
            lg, k, v, lengths = model.decode_step_paged(
                params, tokens, k, v, table, lengths, active, impl=impl)
            return (k, v, lengths), lg[0]

        lengths = cache["lengths"].at[0].set(n)
        _, rest = jax.lax.scan(tick, (k, v, lengths), forced)
        first = jax.lax.dynamic_index_in_dim(logits[0], n - 1, 0, False)
        return jnp.concatenate([first[None], rest]).astype(jnp.float32)

    run = jax.jit(run)
    rng = np.random.default_rng(12345)
    diff = top = 0.0
    for it in items:
        prompt = list(it.prompt)[:bucket]
        forced = rng.integers(0, family.vocab, (PROBE_TICKS,)).astype(np.int32)
        n_pages = -(-(len(prompt) + PROBE_TICKS) // page_len)
        row = np.zeros((max_pages,), np.int32)
        row[:n_pages] = 1 + np.arange(n_pages)
        table = np.zeros((slots, max_pages), np.int32)
        table[0] = row
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        with scope():
            got = np.asarray(run(params, cache, padded, np.int32(len(prompt)),
                                 forced, row, table))
        ref = np.asarray(family.reference_logits(
            params, prompt + [int(t) for t in forced], ref_len))
        want = ref[len(prompt) - 1:]
        diff = max(diff, float(np.abs(got - want).max()))
        top = max(top, float(np.abs(want).max()))
    return diff, top


def _stream_slack(family, params, reqs, ref_len: int):
    """How far below the reference's top logit an emitted token sits, at
    most, over whole finished streams of the real engine (teacher-forced on
    the engine's own tokens; 0 where every token is the reference argmax)."""
    slack, positions = 0.0, 0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        ref = np.asarray(family.reference_logits(params, seq[:-1], ref_len))
        rows = ref[len(r.prompt) - 1:]
        at = np.arange(len(r.tokens))
        slack = max(slack, float(
            (rows.max(axis=1) - rows[at, r.tokens]).max()))
        positions += len(r.tokens)
    return slack, positions


def _token_times(r):
    """Absolute host times of a request's tokens."""
    return r.submit_t + np.cumsum(r.token_times)


def run(ctx) -> dict:
    from deepspeed_tpu.inference import ServeEngine
    from deepspeed_tpu.parallel import build_mesh

    family, mix = ctx.family, ctx.mix
    serving = dict(ctx.cfg_file["serving"])
    lead_s = float(mix["lead_s"])
    grace_s = float(mix.get("first_token_grace_s", 0))
    if ctx.rehearse:
        serving.update(ctx.cfg_file["rehearse"]["serving"])
    devices = jax.devices()[:1]
    mesh = build_mesh(pp=1, dp=1, tp=1, devices=devices)
    dtype = jnp.dtype(ctx.cfg_file["dtype"])
    params = _make_params(family.model, ctx.seed, dtype)
    horizon = lead_s + ctx.seconds
    items = traffic.build_schedule(mix, ctx.seed, horizon, family.vocab)
    if not items:
        raise ValueError("the traffic mix gave no request in the horizon")

    eng = ServeEngine(family.model,
                      {"serving": serving, "telemetry": {"enabled": False}},
                      mesh=mesh, params=params)
    series, checks = {}, {}
    # one reference program for every replay: prompts reach prefill_len and
    # outputs 256, and causal attention lets shorter ones be padded
    ref_len = min(serving["prefill_len"] + 256, serving["max_seq_len"])
    try:
        diff, top = _probe(family, params, serving, items[:PROBE_REQUESTS],
                           eng.decode_impl, eng._pallas_scope, ref_len)
        say(f"probe: prefill + {PROBE_TICKS} ticks of {PROBE_REQUESTS} "
            f"requests vs the float32 reference: max |logit diff| "
            f"{diff:.4f}, largest |logit| {top:.2f}, tolerance {LOGIT_TOL}")
        checks["probe_logits_within_tolerance"] = bool(
            np.isfinite(diff) and diff <= LOGIT_TOL)

        # warm both programs of the engine on the shapes the traffic uses
        # (one prefill bucket, one decode program)
        for it in items[:2]:
            eng.submit(list(it.prompt), max_new_tokens=3)
        eng.run_until_idle()
        if eng.prefix is not None:
            eng.prefix.clear()
        jax.block_until_ready(eng.cache)

        result = _open_loop(ctx, eng, items, lead_s, grace_s, series)
        done = [r for r in result["all_reqs"]
                if r.done.is_set() and r.error is None
                and len(r.prompt) + len(r.tokens) <= ref_len]
        slack, positions = _stream_slack(family, params,
                                         done[:STREAM_REQUESTS], ref_len)
        say(f"streams: {min(len(done), STREAM_REQUESTS)} finished requests "
            f"replayed through the float32 reference ({positions} "
            f"positions): an emitted token sits at most {slack:.4f} below "
            f"the reference's top logit, tolerance {LOGIT_TOL}")
        checks["streams_within_tolerance"] = bool(
            positions > 0 and slack <= LOGIT_TOL)
        checks["no_compile_in_window"] = series["compiles_in_window"] == 0
        stats = devices[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
    finally:
        eng.close()
    return {"series": series, "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": peak, "trace_dir": result["trace_dir"],
            "window_start": result["window_start"]}


def _open_loop(ctx, eng, items, lead_s, grace_s, series) -> dict:
    mix = ctx.mix
    # after the window the steady cell waits until every request due in it
    # has its first token (no metric needs a request to finish); the
    # saturated cell is cut
    drain = mix["after_window"] == "first_tokens"
    if mix["after_window"] not in ("first_tokens", "cut"):
        raise ValueError("after_window is 'first_tokens' or 'cut'")
    n = len(items)
    reqs = [None] * n
    late_ms, tick_ms, decode_tick_ms, occupancy = [], [], [], []
    tick_ends = []
    trace_at = float(mix.get("trace_at_s", 1.0))
    trace_len = min(float(mix.get("trace_len_s", 3.0)), ctx.seconds / 2)
    tracing = "off" if not ctx.trace else "armed"
    trace_dir, window_ann = None, None
    clock = time.perf_counter
    ann = jax.profiler.TraceAnnotation

    def busy():
        return bool(eng.scheduler.active or eng._pending
                    or eng.queue.qsize())

    nxt = 0
    t0 = clock()
    w0, w1 = t0 + lead_s, t0 + lead_s + ctx.seconds
    compiles_at_w0 = None
    stop_at = w1 + (grace_s if drain else 0.0)
    while True:
        now = clock()
        if compiles_at_w0 is None and now >= w0:
            compiles_at_w0 = ctx.compiles.count()
        if tracing == "armed" and now >= w0 + trace_at:
            trace_dir = os.path.join(ctx.out_dir, "trace")
            jax.profiler.start_trace(trace_dir)
            window_ann = ann("bench/traced_window")
            window_ann.__enter__()
            tracing, trace_end = "on", clock() + trace_len
            continue
        if tracing == "on" and now >= trace_end:
            window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = "done"
            continue
        with ann("bench/submit"):
            while nxt < n and items[nxt].at_s + t0 <= now \
                    and items[nxt].at_s + t0 < w1:
                it = items[nxt]
                reqs[nxt] = eng.submit(list(it.prompt),
                                       max_new_tokens=it.max_new_tokens)
                late_ms.append((reqs[nxt].submit_t - (t0 + it.at_s)) * 1e3)
                nxt += 1
        if now >= w1:
            in_window = [r for r, it in zip(reqs, items)
                         if r is not None and it.at_s >= lead_s]
            if not drain or now >= stop_at \
                    or all(r.token_times or r.done.is_set()
                           for r in in_window):
                break
        if busy():
            waiting = eng.queue.qsize() + len(eng._pending)
            t = clock()
            with ann("bench/step"):
                produced = eng.step()
            tick_ends.append(clock())
            dt = (tick_ends[-1] - t) * 1e3
            if w0 <= t < w1:
                tick_ms.append(dt)
                occupancy.append(len(eng.scheduler.active))
                admitted = waiting - eng.queue.qsize() - len(eng._pending)
                if admitted == 0 and produced > 0:
                    decode_tick_ms.append(dt)
        else:
            due = t0 + items[nxt].at_s if nxt < n else w1
            with ann("bench/wait"):
                time.sleep(max(min(due, w1) - clock(), 0.0))
    t_end = clock()
    if tracing == "on":
        window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles = ctx.compiles.count() - (compiles_at_w0 or 0)

    # tokens come out a tick at a time, so the rate is taken over whole
    # ticks: from the first tick that ends in the window to the first that
    # ends after it (a count over exactly --seconds would jump by a tick's
    # 32 tokens, 1.1 %, with a millisecond of jitter)
    rate_from = min(t for t in tick_ends if t >= w0)
    rate_to = min((t for t in tick_ends if t >= w1), default=tick_ends[-1])
    rate_tokens = 0
    sent = [(r, it) for r, it in zip(reqs, items) if r is not None]
    window = [(r, it) for r, it in sent if it.at_s >= lead_s]
    ttft, queue_wait, prefill, gaps = [], [], [], []
    tokens_out, failed = 0, 0
    for r, it in sent:
        due = t0 + it.at_s
        if r.token_times:
            times = _token_times(r)
            tokens_out += int(np.sum((times >= w0) & (times < w1)))
            rate_tokens += int(np.sum((times > rate_from)
                                      & (times <= rate_to)))
            ends = times[1:]
            gaps.extend((np.asarray(r.token_times[1:])[
                (ends >= w0) & (ends < w1)] * 1e3).tolist())
        if it.at_s < lead_s:
            continue
        if r.token_times:
            ttft.append((r.submit_t + r.token_times[0] - due) * 1e3)
            queue_wait.append((r.admit_t - due) * 1e3)
            prefill.append(r.prefill_s * 1e3)
        if r.error is not None or r.finish_reason not in (None, "eos",
                                                          "length"):
            failed += 1
        elif drain and not r.token_times:
            failed += 1
    backlog = sum(1 for r, _ in sent if not r.token_times)
    say(f"open loop: {len(sent)} requests submitted, {len(window)} due in "
        f"the window; generator lateness ms p50 "
        f"{percentile(late_ms, 50):.2f} p99 {percentile(late_ms, 99):.2f} "
        f"max {max(late_ms):.2f}; {len(tick_ms)} ticks in the window, "
        f"{len(decode_tick_ms)} decode-only; {tokens_out} tokens out, "
        f"{rate_tokens} in the {rate_to - rate_from:.3f} s of whole ticks; "
        f"{backlog} submitted requests without a first token at the end; "
        f"{compiles} compilations in the window; loop ran "
        f"{t_end - t0:.1f} s")
    def pcts(v, qs):
        return "/".join(f"{percentile(v, q):.0f}" for q in qs) if v else "-"

    say(f"samples: ttft {len(ttft)} (p50/p90 {pcts(ttft, (50, 90))} ms), "
        f"gaps {len(gaps)} (p50/p95 {pcts(gaps, (50, 95))} ms), queue wait "
        f"p50/p90 {pcts(queue_wait, (50, 90))} ms, prefill p50 "
        f"{pcts(prefill, (50,))} ms, tick p50 all/decode-only "
        f"{pcts(tick_ms, (50,))}/{pcts(decode_tick_ms, (50,))} ms, longest "
        f"{pcts(tick_ms, (100,))} ms, mean "
        f"active slots {np.mean(occupancy) if occupancy else 0:.1f} of "
        f"{eng.slots}")
    series.update({
        "ttft_ms": ttft, "itl_ms": gaps, "queue_wait_ms": queue_wait,
        "prefill_ms": prefill, "tick_ms": tick_ms,
        "decode_tick_ms": decode_tick_ms,
        "active_slots": occupancy, "slots": eng.slots,
        "tokens_out": tokens_out, "window_s": ctx.seconds,
        "serve_tokens_per_s": rate_tokens / (rate_to - rate_from),
        "compiles_in_window": compiles,
        "generator_late_ms": late_ms, "backlog_at_cut": backlog,
    })
    with open(os.path.join(ctx.out_dir, f"requests-{ctx.seed}.jsonl"),
              "w") as f:
        for r, it in sent:
            f.write(json.dumps({
                "due_s": it.at_s, "late_ms": (r.submit_t - t0 - it.at_s) * 1e3,
                "prompt": len(r.prompt), "budget": it.max_new_tokens,
                "tokens": len(r.tokens), "finish": r.finish_reason,
                "ttft_ms": ((r.submit_t + r.token_times[0] - t0 - it.at_s)
                            * 1e3 if r.token_times else None),
                "shared": r.shared_len}) + "\n")
    return {"all_reqs": [r for r, _ in sent],
            "attempted": len(window), "failed": failed,
            "trace_dir": trace_dir, "window_start": w0}
