"""The quickest proof that the system still starts on the chip.

GPT-2 124M at published widths (12 layers of 768, 12 heads of 64, vocab
50257, 1024 positions; bf16; random weights from a fixed seed) takes a few
training steps through ``deepspeed_tpu.initialize`` / ``train_batch``
(ZeRO-2, flash-attention kernel) and answers a handful of requests through
``ServeEngine.submit`` / ``run_until_idle`` (paged KV, Pallas decode
kernel).  Each phase checks its output against a plain reference (dense
attention) and fails loudly: no phase failure is caught.

    python chip_smoke.py            # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4  # four chips: data-parallel ZeRO-2 only,
                                    # against one device in the same process

One process, no children, no network.  Refuses to run without a TPU.  The
last line of stdout is ``{"ok": ..., "device": {...}}``; everything else
worth reading (step time, tokens/s, compile seconds, peak HBM — smoke
observations, not benchmark results) is printed on earlier lines.

The phases are plain functions of their sizes, so tests/test_chip_smoke.py
calls them in-process at toy size on the CPU mesh.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference import ServeEngine, init_paged_cache
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.runtime.utils import collect_memory_stats
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

KERNEL = "tpu_custom_call"  # how a Mosaic kernel shows in compiled HLO text
SEED = 0            # weights, batches and prompts
LOSS_BAND = 0.5     # first loss to ln(vocab): tied random embeddings echo the
#                     input token a little, so the start sits above ln V
REF_TOL = 0.02      # first loss to the dense float32 reference: bf16
#                     activations and logits, averaged over batch*seq tokens
LOSS_TOL = 0.02     # dp=N loss to the one-device loss, every step: same bf16
#                     program per row, only the reduction order differs
LOGIT_TOL = 0.1     # serve logits to the dense engine's, max abs: bf16
#                     activations, logits of a few units
AGREE_FLOOR = 0.9   # share of equal tokens where both engines saw one context


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    """A phase check.  Raises: the script catches no phase failure."""
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {msg}")
    say(f"ok: {msg}")


def gpt2_124m() -> GPT2Config:
    return GPT2Config(d_model=768, n_layer=12, n_head=12, vocab_size=50257,
                      n_positions=1024, attn_impl="flash")


def _on_tpu(devices) -> bool:
    return devices[0].platform == "tpu"


def _memory_lines(phase: str) -> list:
    """Print and return what each device's allocator reports (nothing on
    the CPU backend)."""
    devs = collect_memory_stats()["devices"]
    for dev in devs:
        say(f"{phase}: device {dev['id']} bytes_in_use "
            f"{dev['bytes_in_use']} peak_bytes_in_use "
            f"{dev['peak_bytes_in_use']} (process peak so far) of "
            f"bytes_limit {dev['bytes_limit']}")
    return devs


def _train_config(micro_batch: int, steps: int, tel_dir: str) -> dict:
    return {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": 1,
        # one report and one telemetry sync (memory_stats, compile
        # samples), on the last step — steady steps stay un-synced
        "steps_per_print": steps,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2},
        "telemetry": {"enabled": True, "output_path": tel_dir},
    }


def _reference_loss(cfg: GPT2Config, params, tokens: np.ndarray,
                    chunk: int) -> float:
    """The plain reference: the same params through dense attention in
    float32 at full matmul precision, one forward, ``chunk`` rows at a
    time (the [rows, seq, vocab] float32 logits bound the chunk)."""
    ref = GPT2Model(dataclasses.replace(cfg, attn_impl="dense", remat=None))
    fwd = jax.jit(lambda p, t: ref.loss_fn(p, t, jax.random.PRNGKey(0),
                                           train=False))
    assert len(tokens) % chunk == 0, (len(tokens), chunk)
    with jax.default_matmul_precision("highest"):
        parts = [float(fwd(params, tokens[i:i + chunk]))
                 for i in range(0, len(tokens), chunk)]
    return float(np.mean(parts))


def _timed_steps(engine, tokens, steps: int):
    """Losses and per-step seconds of ``steps`` train_batch calls, each
    ended by block_until_ready."""
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(engine.train_batch(tokens))
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return losses, secs


def _compile_step(engine, tokens):
    """Compile the engine's own jitted train step ahead of its first call
    and return (program text, seconds).  With a persistent compilation
    cache the first train_batch then reads this program back."""
    placed = engine._shard_batch(tokens)
    t0 = time.perf_counter()
    with engine._pallas_scope():
        compiled = engine._train_step.lower(engine.state, placed).compile()
    secs = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    say(f"compiled train step needs {mem.temp_size_in_bytes} bytes of "
        f"temporaries beside {mem.argument_size_in_bytes} of arguments "
        "(compiler's count)")
    return compiled.as_text(), secs


def train_phase(cfg: GPT2Config, *, micro_batch: int, seq: int, steps: int,
                ref_chunk: int = 4, fall: float = 0.1) -> dict:
    """A few ZeRO-2 bf16 Adam steps on one device through the normal entry
    points, on one fixed seeded batch.

    Pass = every loss finite; first loss within LOSS_BAND of ln(vocab);
    the last loss more than ``fall`` below the first on the repeated batch
    (Adam's first steps are not monotone); first loss within REF_TOL of
    the float32 dense reference; one compiled step program, holding the
    kernel when the device is a TPU."""
    devices = jax.devices()[:1]
    tokens = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (micro_batch, seq + 1), dtype=np.int32)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_train_") as tel_dir:
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2Model(cfg), mesh=build_mesh(devices=devices),
            config=_train_config(micro_batch, steps, tel_dir), seed=SEED)
        try:
            ref_loss = _reference_loss(cfg, engine.state.master_params,
                                       tokens, ref_chunk)
            text, compile_s = _compile_step(engine, tokens)
            losses, secs = _timed_steps(engine, tokens, steps)
            recompiles = engine.telemetry.registry.counter(
                "recompiles_total").value(program="train_step")
            programs = engine._train_step._cache_size()
            interpret = engine._pallas_interpret
        finally:
            engine.close()

    steady = secs[1:]
    step_s = float(np.median(steady))
    say(f"train: losses {[round(x, 4) for x in losses]}")
    say(f"train: reference (dense, float32) first loss {ref_loss:.4f}, "
        f"engine {losses[0]:.4f}, |diff| {abs(losses[0] - ref_loss):.4f}")
    say(f"train: compile {compile_s:.1f} s; first train_batch "
        f"{secs[0]:.2f} s; steady step median {step_s * 1e3:.1f} ms over "
        f"{len(steady)} steps = {micro_batch * seq / step_s:,.0f} tokens/s "
        "(smoke observation, not a benchmark result)")
    _memory_lines("train")

    ln_v = math.log(cfg.vocab_size)
    check(all(np.isfinite(losses)), "train: every loss finite")
    check(abs(losses[0] - ln_v) <= LOSS_BAND,
          f"train: first loss {losses[0]:.3f} within {LOSS_BAND} of "
          f"ln {cfg.vocab_size} = {ln_v:.3f}")
    check(losses[-1] < losses[0] - fall,
          f"train: loss fell by more than {fall} over {steps} steps on the "
          "repeated batch")
    check(abs(losses[0] - ref_loss) <= REF_TOL,
          f"train: first loss within {REF_TOL} of the dense float32 "
          "reference")
    check(programs == 1 and recompiles == 0,
          f"train: one compiled step program ({programs}), "
          f"{recompiles:g} recompiles after the first step")
    if _on_tpu(devices):
        check(interpret is False, "train: engine does not interpret Pallas")
        check(KERNEL in text, f"train: {text.count(KERNEL)} {KERNEL} in "
                              "the compiled step")
    return {"losses": losses, "ref_loss": ref_loss, "step_s": step_s,
            "compile_s": compile_s, "kernels": text.count(KERNEL)}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _serve_config(*, slots, page_len, max_seq_len, prefill_len, decode_impl,
                  tel_dir) -> dict:
    return {"serving": {"slots": slots, "max_seq_len": max_seq_len,
                        "prefill_len": prefill_len, "page_len": page_len,
                        "prefix_cache": True, "decode_impl": decode_impl},
            "telemetry": {"enabled": True, "output_path": tel_dir}}


def _wave(vocab: int, page_len: int, prefill_len: int, seed: int):
    """One mixed wave of (prompt, max_new_tokens): a 1-token prompt, one
    spanning several pages, two sharing a two-page prefix, one filling
    the prefill bucket — with unequal generation lengths so slots free at
    different ticks."""
    rng = np.random.default_rng(seed)

    def toks(n):
        return [int(t) for t in rng.integers(0, vocab, (n,))]

    shared = toks(2 * page_len)
    several = min(3 * page_len + 5, prefill_len)
    return [(toks(1), 12),
            (toks(several), 8),
            (shared + toks(page_len // 2), 16),
            (shared + toks(page_len + 3), 10),
            (toks(prefill_len), 6)]


def _run_wave(eng: ServeEngine, wave):
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in wave]
    eng.run_until_idle()
    return reqs, time.perf_counter() - t0


def _make_probe(eng: ServeEngine, ticks: int):
    """A teacher-forced replay of one request through the model's paged
    serving entry points, under the engine's own scope, params, cache spec
    and decode arm; one compiled program for every request.

    ``probe(prompt, forced, prefix_len)`` prefills ``prefix_len`` prompt
    tokens, then the rest against them (the prefix-hit arm of the prefill
    the engine takes for a request with ``shared_len`` > 0; at 0 the first
    prefill writes nothing and the second is the plain one), then feeds
    ``forced`` one decode tick each, so two engines see the same inputs
    whatever their argmax.  Returns float32 [1 + len(forced), vocab]: row
    t scores generated token t."""
    spec = eng.cache_spec
    cache = init_paged_cache(spec)
    active = np.zeros((spec.slots,), bool)
    active[0] = True

    def run(params, cache, head, n_head, tail, n_tail, forced, row, table):
        _, k, v = eng.model.prefill_paged(
            params, head, n_head, np.int32(0), row, cache["k"], cache["v"])
        logits, k, v = eng.model.prefill_paged(
            params, tail, n_tail, n_head, row, k, v)

        def tick(carry, token):
            k, v, lengths = carry
            tokens = jnp.zeros((spec.slots,), jnp.int32).at[0].set(token)
            lg, k, v, lengths = eng.model.decode_step_paged(
                params, tokens, k, v, table, lengths, active,
                impl=eng.decode_impl)
            return (k, v, lengths), lg[0]

        lengths = cache["lengths"].at[0].set(n_head + n_tail)
        _, rest = jax.lax.scan(tick, (k, v, lengths), forced)
        first = logits[0, n_tail - 1]
        return jnp.concatenate([first[None], rest]).astype(jnp.float32)

    run = jax.jit(run)

    def bucket(tokens):
        out = np.zeros((1, eng.prefill_len), np.int32)
        out[0, :len(tokens)] = tokens
        return out, np.int32(len(tokens))

    def probe(prompt, forced, prefix_len: int):
        n_pages = -(-(len(prompt) + ticks) // spec.page_len)
        row = np.zeros((spec.max_pages,), np.int32)
        row[:n_pages] = 1 + np.arange(n_pages)
        table = np.zeros((spec.slots, spec.max_pages), np.int32)
        table[0] = row
        padded = np.zeros((ticks,), np.int32)
        padded[:len(forced)] = forced
        with eng._pallas_scope():
            out = run(eng.params, cache, *bucket(prompt[:prefix_len]),
                      *bucket(prompt[prefix_len:]), padded, row, table)
        return np.asarray(out)[:1 + len(forced)]

    return probe


def _program_texts(eng: ServeEngine) -> dict:
    """Compiled text of the engine's own prefill and decode programs, at
    the operand shapes the serve tick passes."""
    spec = eng.cache_spec
    i32 = np.int32(0)
    with eng._pallas_scope():
        prefill = eng._prefill_fn.lower(
            eng.params, eng.cache, np.zeros((1, eng.prefill_len), np.int32),
            i32, i32, np.zeros((spec.max_pages,), np.int32), i32)
        decode = eng._decode_fn.lower(
            eng.params, eng.cache, np.zeros((spec.slots,), np.int32),
            np.zeros((spec.slots,), bool), eng._table)
        return {"prefill": prefill.compile().as_text(),
                "decode": decode.compile().as_text()}


def serve_phase(cfg: GPT2Config, *, slots: int, page_len: int,
                max_seq_len: int, prefill_len: int) -> dict:
    """Two waves of mixed requests through a paged ServeEngine on the
    Pallas decode arm, beside a second engine with dense attention on the
    same bf16 params.

    Greedy streams of two attention programs are not held to ``==``: with
    random weights the top two of 50257 logits are often closer than
    their bf16 difference, and one flipped argmax changes every later
    token of its stream.  So each request is compared as far as the two
    engines saw ONE context — up to and including its first differing
    token — and replayed teacher-forced on that context through both
    models (``_make_probe``; the Pallas side with the request's own
    prefix hit, the dense side in one plain prefill).

    Pass = every request finishes with eos or length; the replayed logits
    within LOGIT_TOL of the dense engine's at every position; every token
    either engine emitted on a shared context is the dense replay's
    argmax or within LOGIT_TOL of it (so each divergence is a near-tie,
    and the engines' own prefill and decode programs are held by more
    than the share); the share of equal tokens on shared contexts at
    least AGREE_FLOOR; one compiled decode and one compiled prefill
    program with zero recompiles after the mixed load; the page pool
    back to idle."""
    devices = jax.devices()[:1]
    mesh = build_mesh(pp=1, dp=1, tp=1, devices=devices)
    model = GPT2Model(cfg)
    params = jax.jit(lambda k: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(k)))(
            jax.random.PRNGKey(SEED))
    dense_model = GPT2Model(dataclasses.replace(cfg, attn_impl="dense"))
    waves = [_wave(cfg.vocab_size, page_len, prefill_len, SEED + 1 + i)
             for i in range(2)]
    sizes = dict(slots=slots, page_len=page_len, max_seq_len=max_seq_len,
                 prefill_len=prefill_len)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_serve_") as tel_dir:
        eng = ServeEngine(model, _serve_config(
            decode_impl="pallas", tel_dir=tel_dir + "/pallas", **sizes),
            mesh=mesh, params=params)
        ref = ServeEngine(dense_model, _serve_config(
            decode_impl="dense", tel_dir=tel_dir + "/dense", **sizes),
            mesh=mesh, params=params)
        try:
            idle_free = eng.pool.free_count
            (first, first_s), (steady, steady_s) = (
                _run_wave(eng, w) for w in waves)
            ref_reqs = [r for w in waves for r in _run_wave(ref, w)[0]]
            reqs = first + steady

            ticks = max(n for w in waves for _, n in w)
            probe, ref_probe = _make_probe(eng, ticks), _make_probe(ref, ticks)
            replays = []
            for (prompt, _), r, q in zip(sum(waves, []), reqs, ref_reqs):
                both = list(zip(r.tokens, q.tokens))
                split = next((t for t, (a, b) in enumerate(both) if a != b),
                             None)
                shared = q.tokens[:len(both) - 1 if split is None else split]
                replays.append((split, probe(prompt, shared, r.shared_len),
                                ref_probe(prompt, shared, 0)))

            eng.telemetry.compile_monitor.sample()
            reg = eng.telemetry.registry
            recompiles = {p: reg.counter("recompiles_total").value(program=p)
                          for p in ("serve_decode", "serve_prefill",
                                    "serve_copy_page")}
            programs = (eng._decode_fn._cache_size(),
                        eng._prefill_fn._cache_size())
            texts = _program_texts(eng) if _on_tpu(devices) else {}
            interpret = eng._pallas_interpret
            used, entries = eng.pool.used_count, eng.prefix.entries
            eng.prefix.clear()
            free_after = eng.pool.free_count
        finally:
            eng.close()
            ref.close()

    # Per request: rows 0..n-1 of the replay are the positions where both
    # engines saw one context.  ``slack`` is how far below the dense
    # replay's top logit an emitted token sits (0 where it is the argmax).
    diff = slack = top = 0.0
    equal = rows = 0
    for i, (r, q, (split, got, want)) in enumerate(
            zip(reqs, ref_reqs, replays)):
        n = len(want)
        diff = max(diff, float(np.abs(got - want).max()))
        top = max(top, float(np.abs(want).max()))
        at = np.arange(n)
        for tokens in (r.tokens[:n], q.tokens[:n]):
            slack = max(slack, float(
                (want.max(axis=1) - want[at, tokens]).max()))
        equal += n if split is None else split
        rows += n
        if split is not None:
            a, b = r.tokens[split], q.tokens[split]
            say(f"serve: request {i} (prompt {len(r.prompt)} tokens, prefix "
                f"hit {r.shared_len}) diverges at generated token {split} "
                f"of {len(q.tokens)}: {a} against the dense engine's {b}; "
                f"dense replay logits {want[split, a]:.4f} and "
                f"{want[split, b]:.4f}, margin "
                f"{abs(want[split, b] - want[split, a]):.4f}; Pallas replay "
                f"{got[split, a]:.4f} and {got[split, b]:.4f}")
    agree = equal / rows
    pairs = [(a, b) for r, q in zip(reqs, ref_reqs)
             for a, b in zip(r.tokens, q.tokens)]
    same = sum(r.tokens == q.tokens for r, q in zip(reqs, ref_reqs))
    n_tok = sum(len(r.tokens) for r in steady)
    gaps = [t for r in steady for t in r.token_times[1:]]
    ref_gaps = [t for r in ref_reqs[len(first):] for t in r.token_times[1:]]
    say(f"serve: {len(reqs)} requests, finish reasons "
        f"{sorted({r.finish_reason for r in reqs})}, prefix hits "
        f"{sum(r.shared_len > 0 for r in reqs)}")
    say(f"serve: teacher-forced logits vs the dense engine over {rows} "
        f"positions of {len(reqs)} requests (prefill + decode ticks): max "
        f"abs diff {diff:.4f}, largest |logit| {top:.2f}; an emitted token "
        f"sits at most {slack:.4f} below the dense replay's top logit")
    say(f"serve: token streams agree with the dense engine on {agree:.4f} "
        f"of the {rows} positions with a shared context "
        f"({sum(a == b for a, b in pairs) / len(pairs):.4f} of all "
        f"{len(pairs)} generated positions, those past a stream's first "
        f"flip included); {same} of {len(reqs)} streams agree in full")
    say(f"serve: first wave {first_s:.1f} s (compiles included); steady "
        f"wave {steady_s:.3f} s for {n_tok} tokens = "
        f"{n_tok / steady_s:,.1f} tokens/s, median inter-token gap "
        f"{np.median(gaps) * 1e3:.2f} ms, the dense engine's "
        f"{np.median(ref_gaps) * 1e3:.2f} ms (smoke observations, not "
        "benchmark results)")
    _memory_lines("serve")

    check(all(r.error is None and r.finish_reason in ("eos", "length")
              for r in reqs + ref_reqs),
          "serve: every request finished with eos or length")
    check(all(np.isfinite(got).all() for _, got, _ in replays)
          and diff <= LOGIT_TOL,
          f"serve: prefill (prefix hits included) and decode logits within "
          f"{LOGIT_TOL} of the dense engine at every replayed position")
    check(slack <= LOGIT_TOL,
          f"serve: every token emitted on a shared context is the dense "
          f"replay's argmax or within {LOGIT_TOL} of it — each divergence "
          "is a near-tie")
    check(agree >= AGREE_FLOOR,
          f"serve: stream agreement {agree:.4f} >= {AGREE_FLOOR} on shared "
          "contexts")
    check(programs == (1, 1) and not any(recompiles.values()),
          f"serve: one decode and one prefill program {programs}, "
          f"recompiles {recompiles}")
    check(used == entries and free_after == idle_free,
          f"serve: page pool back to idle ({free_after} free of "
          f"{idle_free}; {entries} pages were prefix-cache entries)")
    if _on_tpu(devices):
        check(interpret is False, "serve: engine does not interpret Pallas")
        for name, text in texts.items():
            check(KERNEL in text, f"serve: {text.count(KERNEL)} {KERNEL} "
                                  f"in the compiled {name} program")
    return {"agree": agree, "logit_max_diff": diff, "slack": slack,
            "steady_s": steady_s, "tokens": n_tok}


# ---------------------------------------------------------------------------
# four chips: data-parallel ZeRO-2 against one device
# ---------------------------------------------------------------------------

def _state_leaves(engine):
    return [x for x in jax.tree.leaves((engine.state.master_params,
                                        engine.state.opt_state))
            if getattr(x, "ndim", 0) >= 1]


def zero_dp_phase(cfg: GPT2Config, devices, *, global_batch: int, seq: int,
                  steps: int) -> dict:
    """ZeRO-2 over ``build_mesh(dp=len(devices))`` against the same global
    batch on ``devices[:1]``, same seed, same process.

    Pass = the two loss trajectories agree within LOSS_TOL at every
    step; master params and optimizer state are really partitioned
    (1/dp shard shapes on distinct devices); on a backend that reports
    memory, every device holds some bytes and none holds the whole
    state; the compiled step holds the collectives stage 2 implies."""
    devices = list(devices)
    dp = len(devices)
    assert global_batch % dp == 0, (global_batch, dp)
    tokens = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (global_batch, seq + 1), dtype=np.int32)

    def build(devs, tel_dir):
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2Model(cfg), mesh=build_mesh(dp=len(devs), devices=devs),
            config=_train_config(global_batch // len(devs), steps, tel_dir),
            seed=SEED)
        return engine

    with tempfile.TemporaryDirectory(prefix="chip_smoke_dp_") as tel_dir:
        one = build(devices[:1], tel_dir)
        try:
            one_losses, one_secs = _timed_steps(one, tokens, steps)
        finally:
            one.close()
        del one
        gc.collect()

        engine = build(devices, tel_dir)
        try:
            text, compile_s = _compile_step(engine, tokens)
            losses, secs = _timed_steps(engine, tokens, steps)
            leaves = _state_leaves(engine)
            state_bytes = sum(x.nbytes for x in leaves)
            split = [x for x in leaves
                     if x.sharding.shard_shape(x.shape) != x.shape]
            split_bytes = sum(x.nbytes for x in split)
            shard_ok = all(
                math.prod(x.sharding.shard_shape(x.shape)) * dp == x.size
                and len({s.device for s in x.addressable_shards}) == dp
                for x in split)
            mem = _memory_lines(f"dp{dp}")
            programs = engine._train_step._cache_size()
        finally:
            engine.close()

    gap = max(abs(a - b) for a, b in zip(losses, one_losses))
    collectives = {c: text.count(c) for c in
                   ("reduce-scatter", "all-gather", "all-reduce")}
    tok = global_batch * seq
    say(f"dp{dp}: losses {[round(x, 4) for x in losses]}")
    say(f"dp1: losses {[round(x, 4) for x in one_losses]}; largest gap "
        f"{gap:.5f}")
    say(f"dp{dp}: compile {compile_s:.1f} s; steady step median "
        f"{np.median(secs[1:]) * 1e3:.1f} ms = "
        f"{tok / np.median(secs[1:]):,.0f} tokens/s; dp1 "
        f"{np.median(one_secs[1:]) * 1e3:.1f} ms = "
        f"{tok / np.median(one_secs[1:]):,.0f} tokens/s "
        "(smoke observations, not benchmark results)")
    say(f"dp{dp}: master+optimizer state {state_bytes} bytes, "
        f"{split_bytes} of them in {len(split)} partitioned leaves; "
        f"collectives in the compiled step {collectives}")

    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          f"dp{dp}: losses finite and falling")
    check(gap <= LOSS_TOL, f"dp{dp}: loss trajectory within {LOSS_TOL} of "
                           "the one-device run")
    check(split_bytes >= 0.99 * state_bytes and shard_ok,
          f"dp{dp}: master params and optimizer state partitioned 1/{dp} "
          f"over {dp} distinct devices")
    check(collectives["all-gather"] > 0 and
          (collectives["reduce-scatter"] > 0 or collectives["all-reduce"] > 0),
          f"dp{dp}: gradient reduction and parameter all-gather in the "
          "compiled step")
    check(programs == 1, f"dp{dp}: one compiled step program")
    if mem:
        check(len(mem) == dp and
              all(0 < d["bytes_in_use"] < state_bytes for d in mem),
              f"dp{dp}: every device holds bytes and none the whole state")
    if _on_tpu(devices):
        check(KERNEL in text, f"dp{dp}: {text.count(KERNEL)} {KERNEL} in "
                              "the compiled step")
    return {"losses": losses, "one_losses": one_losses,
            "collectives": collectives}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp=4 ZeRO-2 phase and its "
                         "one-device comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or len(devices) < args.chips:
        say(f"refusing to run: needs {args.chips} TPU chip(s), JAX "
            f"reports {device}")
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    say(f"device {device}; compile cache at {enable_compile_cache()}")

    ok = False
    try:
        cfg = gpt2_124m()
        if args.chips == 4:
            zero_dp_phase(cfg, devices[:4], global_batch=16, seq=1024,
                          steps=5)
        else:
            train_phase(cfg, micro_batch=16, seq=1024, steps=6)
            serve_phase(cfg, slots=8, page_len=16, max_seq_len=1024,
                        prefill_len=128)
        ok = True
    finally:
        # a failed phase is not caught: its traceback follows on stderr
        # and the exit code is the interpreter's for an uncaught error
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
