"""Job kind ``train``: ``deepspeed_tpu.initialize`` + ``train_batch`` on 1 or
4 chips, batches made from the seed and fed from the host each step as a
user's loader would.  The window is whole groups of ``sync_every`` steps,
each group ended by ``block_until_ready`` on its last loss (a user logging
every few steps); the rate is every token of the window over all its time.

``groups_ahead`` (the mix's, 0 where it says nothing) is how many groups the
host has dispatched beyond the one whose loss it waits for, as a loop that
logs a loss some steps old: with 0 the chip waits for the host at every group
boundary, so a host that is held up for a second costs the rate that second;
with 2 the chip has two groups queued and loses nothing until the host has
been away for longer than they last.  Every group is still waited for and
every second counted: the window closes when the last one dispatched is done.
"""
from __future__ import annotations

import collections
import math
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from .yardstick import peak, say

# eval loss of the engine (bf16 activations, its kernels) against the float32
# reference on the same bf16 weights and rows, a mean over >= 16k tokens:
# PR 23 read 0.0001 on GPT-2 124M; 0.02 is what chip_smoke.py holds.
REF_TOL = 0.02


def _program_temp_bytes(engine, batch):
    """The compiler's count of the step program's temporaries, which the
    allocator's peak does not see on this backend (PR 23).  None where the
    engine keeps its step elsewhere than a later PR left it."""
    try:
        placed = engine._shard_batch(batch)
        with engine._pallas_scope():
            mem = engine._train_step.lower(
                engine.state, placed).compile().memory_analysis()
        return int(mem.temp_size_in_bytes)
    except AttributeError as e:
        say(f"step program's temporaries not read: {e}")
        return None


def run(ctx) -> dict:
    import deepspeed_tpu
    from deepspeed_tpu.parallel import build_mesh

    family, mix = ctx.family, ctx.mix
    chips = ctx.chips
    devices = jax.devices()[:chips]
    mesh = build_mesh(dp=chips, devices=devices)
    rows, seq = int(mix["micro_batch_per_chip"]) * chips, int(mix["seq_len"])
    sync_every = int(mix["sync_every"])
    config = dict(mix["ds_config"])
    config.update({
        "train_micro_batch_size_per_gpu": int(mix["micro_batch_per_chip"]),
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
    })
    engine, *_ = deepspeed_tpu.initialize(
        model=family.model, mesh=mesh, config=config, seed=ctx.seed)
    rng = np.random.default_rng([int(ctx.seed), 3])
    batches = [family.make_batch(rng, rows, seq, mix["data"])
               for _ in range(int(mix["distinct_batches"]))]
    series, checks = {}, {}
    trace_dir = None
    try:
        # correct, part 1: the engine's forward loss on the first batch
        # against the plain reference on the same (compute-dtype) weights
        cdtype = jnp.bfloat16 if config.get("bf16", {}).get("enabled") \
            else jnp.float32
        ref_params = jax.device_put(
            jax.jit(lambda p: jax.tree.map(lambda x: x.astype(cdtype), p))(
                engine.state.master_params), devices[0])
        ref_loss = family.reference_loss(ref_params, batches[0],
                                         int(mix["reference_chunk_rows"]))
        del ref_params
        eval_loss = float(engine.eval_batch(batches[0]))
        say(f"reference (float32, dense) loss {ref_loss:.5f}, engine eval "
            f"loss {eval_loss:.5f}, |diff| {abs(eval_loss - ref_loss):.5f}, "
            f"tolerance {REF_TOL}")
        checks["eval_loss_matches_reference"] = bool(
            math.isfinite(eval_loss) and abs(eval_loss - ref_loss) <= REF_TOL)

        temp_bytes = _program_temp_bytes(engine, batches[0])
        # warm-up: the step program (compiled or read from the cache) and
        # one more step, so that nothing of the window is a first call
        first_loss = float(engine.train_batch(batches[0]))
        jax.block_until_ready(engine.train_batch(batches[1 % len(batches)]))
        in_use = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                     for d in devices)

        step, group_ms, losses = 2, [], []
        trace_group = int(mix.get("trace_group", 1))
        ahead = int(mix.get("groups_ahead", 0))
        pending = collections.deque()   # last loss of each group in flight
        compiles0 = ctx.compiles.count()
        w0 = t = time.perf_counter()

        def retire():
            """Wait for the oldest group in flight; its time runs from the
            end of the group before it."""
            nonlocal t
            with jax.profiler.TraceAnnotation("bench/wait"):
                loss = float(jax.block_until_ready(pending.popleft()))
            now = time.perf_counter()
            group_ms.append((now - t) * 1e3)
            losses.append(loss)
            t = now

        def open_ms():
            """What is measured plus what the groups in flight should take
            (an estimate: the runtime may hold the host back in dispatch, and
            a group counted as in flight may be done already)."""
            typical = sorted(group_ms)[len(group_ms) // 2] if group_ms else 0
            return sum(group_ms) + len(pending) * typical

        dispatched = 0
        # dispatch while the estimate is short of the window; otherwise wait
        # for one group and look again.  The window closes with nothing in
        # flight, so it is never shorter than --seconds.
        while pending or open_ms() < ctx.seconds * 1e3:
            if open_ms() >= ctx.seconds * 1e3:
                retire()
                continue
            traced = ctx.trace and dispatched == trace_group
            if traced:
                # the traced group runs alone: the profiler's own start and
                # stop are not training time
                while pending:
                    retire()
                trace_dir = os.path.join(ctx.out_dir, "trace")
                jax.profiler.start_trace(trace_dir)
                t = time.perf_counter()
                window_ann = jax.profiler.TraceAnnotation(
                    "bench/traced_window")
                window_ann.__enter__()
            for _ in range(sync_every):
                with jax.profiler.TraceAnnotation("bench/step"):
                    loss = engine.train_batch(batches[step % len(batches)])
                step += 1
            pending.append(loss)
            dispatched += 1
            if traced:
                retire()
                window_ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                t = time.perf_counter()
            while len(pending) > ahead:
                retire()
        # groups follow each other without a gap, but for the profiler's
        # own start and stop in a traced run, which are not training time
        elapsed = sum(group_ms) / 1e3
        compiles = ctx.compiles.count() - compiles0
        steps = len(group_ms) * sync_every
        tokens_per_s = steps * rows * seq / elapsed
        say(f"train: {steps} steps of {rows} x {seq} tokens in "
            f"{elapsed:.2f} s; first loss {first_loss:.4f}, window losses "
            f"{losses[0]:.4f} .. {losses[-1]:.4f}; {compiles} compilations "
            "in the window")

        checks["losses_finite"] = bool(
            math.isfinite(first_loss) and all(map(math.isfinite, losses)))
        checks["loss_fell"] = bool(losses[-1] < first_loss)
        checks["no_compile_in_window"] = compiles == 0
        if chips > 1:
            checks["state_sharded_alike"] = _sharded_alike(engine, chips)
        stats = [d.memory_stats() or {} for d in devices]
        peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        if temp_bytes is not None:
            say(f"memory: allocator peak {peak_bytes}, resident "
                f"{in_use} + step temporaries {temp_bytes} (compiler)")
            peak_bytes = max(peak_bytes, in_use + temp_bytes)
        kind = devices[0].device_kind
        flops = family.train_flops_per_token(seq)
        series.update({
            "step_ms": [g / sync_every for g in group_ms],
            "train_tokens_per_s": tokens_per_s,
            "model_flops_per_s": tokens_per_s * flops,
            "peak_flops_per_s": (chips * peak(kind, "bf16_flops")
                                 if not ctx.rehearse else None),
            "compiles_in_window": compiles,
            "steps": steps, "tokens_per_step": rows * seq,
        })
    finally:
        engine.close()
    return {"series": series, "checks": checks, "attempted": steps,
            "failed": sum(not math.isfinite(x) for x in losses),
            "memory_peak_bytes": peak_bytes, "trace_dir": trace_dir,
            "window_start": w0}


def _sharded_alike(engine, chips: int) -> bool:
    """Every partitioned leaf of master params and optimizer state has the
    same shard shape on ``chips`` distinct devices, and most bytes are
    partitioned."""
    leaves = [x for x in jax.tree.leaves((engine.state.master_params,
                                          engine.state.opt_state))
              if getattr(x, "ndim", 0) >= 1]
    total = sum(x.nbytes for x in leaves)
    split = [x for x in leaves if x.sharding.shard_shape(x.shape) != x.shape]
    ok = all(len({s.data.shape for s in x.addressable_shards}) == 1
             and len({s.device for s in x.addressable_shards}) == chips
             for x in split)
    say(f"state: {sum(x.nbytes for x in split)} of {total} bytes in "
        f"{len(split)} partitioned leaves, alike on every chip: {ok}")
    return bool(ok and sum(x.nbytes for x in split) >= 0.99 * total)
