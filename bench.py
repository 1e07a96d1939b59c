"""GPT-2 training throughput on one TPU chip, plus the A/B legs that
tier-1 tests import (offload pipeline, offload tier, prefetch, checkpoint,
stage chaos, elastic smoke).

Default run: GPT-2 1.5B, ZeRO-2 + host offload (a 1.5B fp32 master + Adam
moments, ~18.7 GB, cannot live in one chip's HBM, so they sit in pinned
host memory) with block rematerialization.  Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline"}; vs_baseline = measured MFU /
0.45.  A run that cannot do what was asked — no TPU, an unknown device
kind, a failing step — exits non-zero and says why; nothing is replaced
by a smaller or a CPU run.  This is not the benchmark (ROADMAP S1).

Environment knobs:
  BENCH_SMALL=1      run GPT-2 124M on one chip instead
  BENCH_15B_IMPL     the one offload structure to run (see _bench_15b)
"""
import json
import os
import sys
import time

import numpy as np

from deepspeed_tpu.utils.compile_cache import enable_compile_cache

_T0 = time.perf_counter()


def _mark(msg):
    """Timestamped progress marker on stderr: shows where time went if a
    phase is slow (compile, init, transfers)."""
    print(f"[bench {time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


# Published bf16 peak FLOPs per chip by device kind.  Resolution must be
# loud: an assumed peak silently misstates MFU.
_PEAKS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e (Trillium)
    "TPU v6e": 918e12,
}


def _chip_peak_bf16_flops(device) -> float:
    kind = getattr(device, "device_kind", "")
    for name, peak in sorted(_PEAKS.items(), key=lambda kv: -len(kv[0])):
        if kind.lower().startswith(name.lower()):
            return peak
    raise RuntimeError(
        f"unknown device_kind {kind!r}: refusing to assume a peak-FLOPs "
        f"figure (MFU would be meaningless). Known kinds: "
        f"{sorted(_PEAKS)}.")


def _flops_per_token(cfg, seq):
    # fwd+bwd matmul flops: 6N + causal attention 12*L*d*T.  Remat
    # recompute is NOT counted — MFU measures useful flops only.
    return 6 * cfg.num_params + 12 * cfg.n_layer * cfg.d_model * seq


def calibrated_time(fn, iters=None, min_window_s=None):
    """Time fn() with an iteration count calibrated so the measured window
    dwarfs dispatch jitter — 20 iterations of a ~35 us kernel measure
    noise, not the kernel.  On the CPU backend the window is skipped
    (accuracy there is irrelevant and calibration would inflate cheap
    cases to thousands of iterations).  Shared by bench_flash /
    bench_sparse."""
    import jax
    on_tpu = jax.devices()[0].platform != "cpu"
    if iters is None:
        iters = 10 if on_tpu else 2
    if min_window_s is None:
        min_window_s = 0.2 if on_tpu else 0.0
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    while dt < min_window_s and iters < 1 << 16:
        iters = int(iters * max(2.0, min_window_s / max(dt, 1e-6) * 1.3))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
    return dt / iters


def _device_resident(engine, batch):
    """Upload a repeating batch ONCE: _shard_batch passes device arrays
    through, so steps pay zero H2D.  Single-process only — multi-host
    _shard_batch assembles from process-local numpy, so there we leave
    the batch alone."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    if jax.process_count() > 1:
        return batch
    return jax.device_put(batch, NamedSharding(engine.mesh, P()))


def _run(engine, tokens, steps, warmup=1):
    tokens = _device_resident(engine, tokens)
    for _ in range(warmup):
        np.asarray(engine.train_batch(tokens))
    t0 = time.perf_counter()
    loss = None
    for _ in range(steps):
        loss = engine.train_batch(tokens)
    loss = float(np.asarray(loss))
    dt = (time.perf_counter() - t0) / steps
    assert np.isfinite(loss), f"non-finite loss {loss}"
    if os.environ.get("BENCH_PROFILE") == "1":
        # one traced step AFTER measurement (tracing skews timing):
        # the xplane shows host-section vs device vs transfer time —
        # the data that decides whether delayed-param-update is needed
        try:
            import jax
            _mark("profiling one step -> bench_trace/")
            with jax.profiler.trace("bench_trace"):
                np.asarray(engine.train_batch(tokens))
            _mark("profile captured")
        except Exception as e:  # profiling must never kill the bench
            _mark(f"profile failed: {e}")
    return dt, loss


def _15b_knobs():
    """Tuning knobs, validated before the engine is built.  Larger ga
    amortizes the per-step host<->HBM master/moment traffic over more
    compute."""
    micro = int(os.environ.get("BENCH_15B_MICRO", "4"))
    # ga=32 → 128 seqs × 1024 = 131k tokens per optimizer step, ~1/4 of
    # GPT-2 1.5B's real 0.5M-token batches — a legitimate config that
    # amortizes the once-per-step host master/moment traffic 2× better
    # than the previous default of 16.
    ga = int(os.environ.get("BENCH_15B_GA", "32"))
    steps = int(os.environ.get("BENCH_15B_STEPS", "2"))
    if micro < 1 or ga < 1 or steps < 1:
        raise ValueError(f"bad BENCH_15B knobs: {micro=} {ga=} {steps=}")
    return micro, ga, steps


def _bench_15b(jax, impl: str = "xla"):
    """North star: GPT-2 1.5B, ZeRO-2 + host offload, one chip.

    ``impl``: 'xla_split' — pinned_host master/moments with the optimizer
    update as one compiled program per piece (program boundaries bound
    HBM liveness; docs/chip_notes.md on the fused program's temporaries);
    'xla_split_dpu' — the same with the delayed parameter update;
    'xla_split4' — the same with four gradient chunks; 'xla' — same
    residency with ONE fused host-compute update program; 'host' — numpy
    staging + native C++ Adam (plain jit step, no host-compute
    sections)."""
    import jax.numpy as jnp  # noqa: F401
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    micro, ga, steps = _15b_knobs()
    # OOM insurance: BENCH_15B_CHUNKS=K bounds device grad bytes to the
    # largest of K groups (offload_grad_chunks capacity mode) at K
    # forward recomputes — a fallback knob, not the default
    chunks = int(os.environ.get("BENCH_15B_CHUNKS", "0"))
    # BENCH_15B_DPU=1 overlaps the host Adam with the next step's
    # compute (one-step param staleness) — flip on if the measured gap
    # to 45% MFU matches the host-section time
    dpu = os.environ.get("BENCH_15B_DPU", "0") == "1"
    # BENCH_15B_STREAM=1: ZeRO-Infinity-style param streaming (host-
    # resident stacked block params, one layer fetched per scan tick) —
    # the deepest OOM fallback, and the capacity mode's throughput
    # number when measured deliberately (xla tier only)
    split = impl.startswith("xla_split")
    impl_cfg = "xla" if split else impl
    # 'xla_split_dpu': split update + delayed parameter update — the
    # per-piece host Adam overlaps the next step's grad program (the
    # reference's peak-throughput offload mode; ~10-15% of step time
    # at 1.5B if the update runs serially)
    dpu = dpu or impl == "xla_split_dpu"
    # 'xla_split4': split update + 4 gradient chunks — for when the
    # single grad program's liveness (bf16 params + grads + packed pieces
    # + activations ≈ 14 GB at 1.5B) is too tight.  BENCH_15B_CHUNKS
    # already says how many chunks: asking for both is a contradiction.
    if impl == "xla_split4":
        if os.environ.get("BENCH_15B_CHUNKS") is not None:
            raise RuntimeError(
                "BENCH_15B_CHUNKS pins the chunk count; "
                "BENCH_15B_IMPL=xla_split4 contradicts it — use xla_split")
        chunks = 4
    stream = (os.environ.get("BENCH_15B_STREAM", "0") == "1"
              and impl_cfg == "xla")
    cfg_model = GPT2Config(d_model=1600, n_layer=48, n_head=25,
                           vocab_size=50257, n_positions=1024,
                           remat="block", scan_layers=True,
                           stream_scan=stream)
    seq = 1024
    mesh = build_mesh(devices=jax.devices()[:1])
    ds_cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": ga,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": dict(
            {"stage": 2, "cpu_offload": True, "offload_impl": impl_cfg},
            **({"offload_grad_chunks": chunks}
               if impl_cfg == "xla" and chunks > 1 else {}),
            **({"param_streaming": True} if stream else {}),
            **({"offload_split_update": True} if split else {}),
            **({"delayed_param_update": True} if dpu else {})),
    }, world_size=1)
    if impl == "host":
        # strict probe semantics for the bench: a slow-but-working link
        # must fail the run, not eat the measurement window at
        # minutes/step (library default is warn-and-proceed)
        os.environ.setdefault("DS_OFFLOAD_SLOW_LINK", "error")
    _mark(f"1.5B[{impl}]: constructing engine (param init + host staging)")
    engine = DeepSpeedEngine(GPT2Model(cfg_model), ds_cfg, mesh=mesh)
    _mark(f"1.5B[{impl}]: engine ready; compiling + first step")
    tokens = np.random.default_rng(0).integers(
        0, cfg_model.vocab_size, (micro * ga, seq + 1), dtype=np.int32)
    dt, _ = _run(engine, tokens, steps)
    _mark(f"1.5B[{impl}]: measured {dt:.2f}s/step")
    tokens_per_sec = micro * ga * seq / dt
    return cfg_model, seq, tokens_per_sec, f"gpt2_1p5b_zero2_offload_{impl}"


def _bench_124m(jax):
    """BENCH_SMALL=1: GPT-2 124M, ZeRO-0, unrolled, no remat."""
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    cfg_model = GPT2Config(d_model=768, n_layer=12, n_head=12,
                           vocab_size=50257, n_positions=1024,
                           remat=None, scan_layers=False)
    batch, seq, steps = 16, 1024, 10
    mesh = build_mesh(devices=jax.devices()[:1])
    ds_cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 0},
    }, world_size=1)
    _mark("124M: constructing engine")
    engine = DeepSpeedEngine(GPT2Model(cfg_model), ds_cfg, mesh=mesh)
    _mark("124M: engine ready; compiling + warmup")
    tokens = np.random.default_rng(0).integers(
        0, cfg_model.vocab_size, (batch, seq + 1), dtype=np.int32)
    dt, _ = _run(engine, tokens, steps, warmup=2)
    _mark(f"124M: measured {dt:.3f}s/step")
    tokens_per_sec = batch * seq / dt
    return cfg_model, seq, tokens_per_sec, "gpt2_124m_zero0"


def bench_offload_pipeline(jax, pipeline_on: bool, steps: int = None):
    """A/B one leg of the streaming offload update pipeline (host tier):
    per-stage step-time breakdown (d2h / cpu_adam / h2d / hidden) plus
    measured step wall time.  The breakdown comes from the engine's
    ``last_offload_breakdown`` host timestamps — d2h is the prefetch
    puller's transfer time (already overlapped with the Adam), h2d the
    per-leaf upload time, hidden the part of h2d that ran under the Adam
    window (the pipeline's win; 0 by construction on the serial leg).

    Size is platform-scaled: tiny on CPU (a smoke the tier-1 suite runs
    with an injected slow-transfer delay to prove overlap > 0), mid-size
    on TPU via BENCH_PIPE_* knobs."""
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    on_tpu = jax.devices()[0].platform != "cpu"
    if on_tpu:
        d_model = int(os.environ.get("BENCH_PIPE_D_MODEL", "1024"))
        n_layer = int(os.environ.get("BENCH_PIPE_LAYERS", "12"))
        micro = int(os.environ.get("BENCH_PIPE_MICRO", "4"))
        seq, vocab, remat = 1024, 50257, "block"
        steps = steps or int(os.environ.get("BENCH_PIPE_STEPS", "3"))
    else:
        d_model, n_layer, micro = 64, 2, 2
        seq, vocab, remat = 64, 256, None
        steps = steps or 2
    cfg_model = GPT2Config(d_model=d_model, n_layer=n_layer,
                           n_head=max(2, d_model // 64), vocab_size=vocab,
                           n_positions=seq, remat=remat)
    mesh = build_mesh(devices=jax.devices()[:1])
    ds_cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2, "cpu_offload": True,
                              "offload_impl": "host",
                              "offload_pipeline": pipeline_on},
    }, world_size=1)
    _mark(f"offload-pipeline[{'on' if pipeline_on else 'off'}]: "
          "constructing engine")
    engine = DeepSpeedEngine(GPT2Model(cfg_model), ds_cfg, mesh=mesh)
    tokens = np.random.default_rng(0).integers(
        0, vocab, (micro, seq + 1), dtype=np.int32)
    tokens = _device_resident(engine, tokens)
    np.asarray(engine.train_batch(tokens))  # warmup/compile
    acc = {"d2h_s": 0.0, "cpu_adam_s": 0.0, "h2d_s": 0.0,
           "h2d_hidden_s": 0.0, "h2d_tail_s": 0.0, "overlap_ratio": 0.0}
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = float(np.asarray(engine.train_batch(tokens)))
        bd = engine.last_offload_breakdown
        for k in acc:
            acc[k] += bd[k]
    dt = (time.perf_counter() - t0) / steps
    assert np.isfinite(loss), f"non-finite loss {loss}"
    out = {k: round(v / steps, 6) for k, v in acc.items()}
    out["step_s"] = round(dt, 6)
    out["pipeline"] = "on" if pipeline_on else "off"
    _mark(f"offload-pipeline[{out['pipeline']}]: {dt:.3f}s/step, "
          f"overlap {out['overlap_ratio'] * 100:.0f}%")
    return out


def _offload_pipeline_ab(jax, mode: str):
    """``--offload-pipeline={on,off,ab}``: run the requested leg(s) and
    print ONE JSON line with the per-stage breakdown(s)."""
    legs = {"on": [True], "off": [False], "ab": [True, False]}[mode]
    results = [bench_offload_pipeline(jax, leg) for leg in legs]
    rec = {"metric": "offload_pipeline_step_breakdown",
           "unit": "s/step",
           "legs": results}
    if len(results) == 2:
        off_t, on_t = results[1]["step_s"], results[0]["step_s"]
        rec["speedup"] = round(off_t / on_t, 4) if on_t > 0 else 0.0
    try:
        with open("BENCH_offload_pipeline.json", "w") as f:
            json.dump(rec, f, indent=1)
    except OSError:
        pass
    print(json.dumps(rec), flush=True)


def bench_offload_tier(jax, tier: str, steps: int = None,
                       disk_delay_s: float = None):
    """One leg of the offload-tier A/B (host RAM vs ZeRO-Infinity disk
    tier, runtime/disk_offload.py): measured step wall time, final
    loss, and — on the disk leg — the state-I/O overlap breakdown from
    the engine's host timestamps, with ``DS_STAGE_DELAY_S`` injecting
    per-leaf disk latency so a CPU run proves the three-tier pipeline
    hides real I/O time under the C++ Adam (the repo's established
    injected-delay overlap idiom).  Also records the capacity
    accounting: ``total_state_bytes`` (master+moments on disk) vs
    ``peak_resident_bytes`` (the io_depth-bounded host window).

    Size is platform-scaled like ``bench_offload_pipeline``: tiny on
    CPU (the tier-1 smoke), mid-size on TPU via BENCH_PIPE_* knobs."""
    import shutil
    import tempfile

    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    on_tpu = jax.devices()[0].platform != "cpu"
    if on_tpu:
        d_model = int(os.environ.get("BENCH_PIPE_D_MODEL", "1024"))
        n_layer = int(os.environ.get("BENCH_PIPE_LAYERS", "12"))
        micro = int(os.environ.get("BENCH_PIPE_MICRO", "4"))
        seq, vocab, remat = 1024, 50257, "block"
        steps = steps or int(os.environ.get("BENCH_PIPE_STEPS", "3"))
    else:
        d_model, n_layer, micro = 64, 2, 2
        seq, vocab, remat = 64, 256, None
        steps = steps or 2
    if disk_delay_s is None:
        disk_delay_s = float(os.environ.get("BENCH_DISK_DELAY_S",
                                            "0.003"))
    cfg_model = GPT2Config(d_model=d_model, n_layer=n_layer,
                           n_head=max(2, d_model // 64), vocab_size=vocab,
                           n_positions=seq, remat=remat)
    mesh = build_mesh(devices=jax.devices()[:1])
    ds = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2, "cpu_offload": True,
                              "offload_impl": "host"},
    }
    disk_dir = None
    prev_delay = os.environ.get("DS_STAGE_DELAY_S")
    try:
        if tier == "disk":
            disk_dir = tempfile.mkdtemp(prefix="ds_bench_disk_")
            ds["offload"] = {"tier": "disk", "disk_dir": disk_dir,
                             "io_depth": 2}
            if disk_delay_s > 0:
                # injected per-leaf disk latency: the overlap claim is
                # then about REAL I/O time, not 9p-filesystem noise
                os.environ["DS_STAGE_DELAY_S"] = (
                    f"disk_read:{disk_delay_s},"
                    f"disk_write:{disk_delay_s}")
        _mark(f"offload-tier[{tier}]: constructing engine")
        engine = DeepSpeedEngine(GPT2Model(cfg_model),
                                 DeepSpeedConfig(ds, world_size=1),
                                 mesh=mesh)
        tokens = np.random.default_rng(0).integers(
            0, vocab, (micro, seq + 1), dtype=np.int32)
        tokens = _device_resident(engine, tokens)
        np.asarray(engine.train_batch(tokens))  # warmup/compile
        t0 = time.perf_counter()
        acc = {"disk_read_s": 0.0, "disk_write_s": 0.0,
               "disk_hidden_s": 0.0, "disk_overlap_ratio": 0.0}
        for _ in range(steps):
            loss = float(np.asarray(engine.train_batch(tokens)))
            bd = engine.last_offload_breakdown
            for k in acc:
                acc[k] += bd.get(k, 0.0)
        dt = (time.perf_counter() - t0) / steps
        assert np.isfinite(loss), f"non-finite loss {loss}"
        out = {"tier": tier, "step_s": round(dt, 6),
               "loss": loss}
        if tier == "disk":
            out.update({k: round(v / steps, 6) for k, v in acc.items()})
            opt = engine._host_opt
            out["total_state_bytes"] = int(opt.total_state_bytes)
            out["peak_resident_bytes"] = int(opt.peak_resident_bytes)
        engine.close()
        _mark(f"offload-tier[{tier}]: {dt:.3f}s/step"
              + (f", disk overlap "
                 f"{out.get('disk_overlap_ratio', 0) * 100:.0f}%"
                 if tier == "disk" else ""))
        return out
    finally:
        if prev_delay is None:
            os.environ.pop("DS_STAGE_DELAY_S", None)
        else:
            os.environ["DS_STAGE_DELAY_S"] = prev_delay
        if disk_dir is not None:
            shutil.rmtree(disk_dir, ignore_errors=True)


def _offload_tier_ab(jax, mode: str):
    """``--offload-tier={host,disk,ab}``: run the requested leg(s),
    print ONE JSON line, and (ab) pin the headline — the disk leg's
    measured state-I/O overlap ratio under injected latency — into
    ``BENCH_offload_disk.json`` for the benchgate.  The ab legs also
    assert the correctness bar: disk-tier loss BITWISE == host-tier."""
    legs = {"host": ["host"], "disk": ["disk"],
            "ab": ["disk", "host"]}[mode]
    results = [bench_offload_tier(jax, leg) for leg in legs]
    rec = {"metric": "offload_disk_overlap_ratio",
           "unit": "ratio",
           "value": next((r.get("disk_overlap_ratio", 0.0)
                          for r in results if r["tier"] == "disk"), 0.0),
           "legs": results}
    if len(results) == 2:
        losses = {r["tier"]: r["loss"] for r in results}
        rec["loss_bitwise_equal"] = losses["disk"] == losses["host"]
        assert rec["loss_bitwise_equal"], (
            f"disk-tier loss diverged from host tier: {losses}")
        # only the full A/B pins the benchgate artifact: a single-leg
        # host run has no disk overlap and would clobber the committed
        # headline with 0.0 (read as a regression)
        try:
            with open("BENCH_offload_disk.json", "w") as f:
                json.dump(rec, f, indent=1)
        except OSError:
            pass
    print(json.dumps(rec), flush=True)


def bench_prefetch(jax, prefetch_on: bool, steps: int = None,
                   collate_delay_s: float = None):
    """A/B one leg of the async input pipeline: the same seeded
    dataloader (with a deliberately slow collate emulating real
    tokenize/augment cost — both legs pay it) feeds the engine with
    prefetch ON (collate + H2D placement on the daemon worker, hidden
    under the previous step) vs OFF (inline on the step path).  Reports
    measured step wall time plus the pipeline's own numbers
    (``prefetch_wait_s`` per step, ``hit_ratio``) from the engine's
    prefetcher stats.

    Size is platform-scaled like ``bench_offload_pipeline``: tiny on
    CPU (the tier-1 smoke injects BENCH_PREFETCH_COLLATE_S to prove
    hiding), mid-size on TPU via BENCH_PREFETCH_* knobs."""
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader

    on_tpu = jax.devices()[0].platform != "cpu"
    if on_tpu:
        d_model = int(os.environ.get("BENCH_PREFETCH_D_MODEL", "768"))
        n_layer = int(os.environ.get("BENCH_PREFETCH_LAYERS", "8"))
        micro = int(os.environ.get("BENCH_PREFETCH_MICRO", "8"))
        seq, vocab = 1024, 50257
        steps = steps or int(os.environ.get("BENCH_PREFETCH_STEPS", "5"))
    else:
        d_model, n_layer, micro = 64, 2, 2
        seq, vocab = 64, 256
        steps = steps or 3
    if collate_delay_s is None:
        collate_delay_s = float(
            os.environ.get("BENCH_PREFETCH_COLLATE_S",
                           "0" if on_tpu else "0.02"))

    def slow_collate(samples):
        # emulated host-side collate cost (tokenize/augment/pad) — paid
        # by BOTH legs; the on leg hides it on the worker
        if collate_delay_s > 0:
            time.sleep(collate_delay_s)
        return np.stack([np.asarray(s) for s in samples])

    cfg_model = GPT2Config(d_model=d_model, n_layer=n_layer,
                           n_head=max(2, d_model // 64), vocab_size=vocab,
                           n_positions=seq, remat=None)
    mesh = build_mesh(devices=jax.devices()[:1])
    ds_cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "data_prefetch": {"enabled": prefetch_on, "depth": 2},
    }, world_size=1)
    rng = np.random.default_rng(0)
    dataset = [rng.integers(0, vocab, (seq + 1,), dtype=np.int32)
               for _ in range(micro * 4)]
    _mark(f"prefetch[{'on' if prefetch_on else 'off'}]: "
          "constructing engine")
    engine = DeepSpeedEngine(GPT2Model(cfg_model), ds_cfg, mesh=mesh,
                             training_data=dataset,
                             collate_fn=slow_collate)
    # finite dataset, repeated: the A/B must measure steady state, not
    # epoch boundaries
    engine.training_dataloader = RepeatingLoader(engine.training_dataloader)
    np.asarray(engine.train_batch())  # warmup/compile
    pf = engine._train_prefetcher
    s0 = pf.stats() if pf is not None else None
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = float(np.asarray(engine.train_batch()))
    dt = (time.perf_counter() - t0) / steps
    assert np.isfinite(loss), f"non-finite loss {loss}"
    out = {"prefetch": "on" if prefetch_on else "off",
           "step_s": round(dt, 6),
           "collate_delay_s": collate_delay_s}
    if pf is not None:
        s1 = pf.stats()
        n = max(s1["consumed"] - s0["consumed"], 1)
        out["prefetch_wait_s"] = round(
            (s1["wait_s"] - s0["wait_s"]) / n, 6)
        hm = (s1["hits"] - s0["hits"]) + (s1["misses"] - s0["misses"])
        out["hit_ratio"] = round(
            (s1["hits"] - s0["hits"]) / hm, 4) if hm else 0.0
    engine.close()
    _mark(f"prefetch[{out['prefetch']}]: {dt:.3f}s/step"
          + (f", wait {out['prefetch_wait_s']:.3f}s"
             if "prefetch_wait_s" in out else ""))
    return out


def _prefetch_ab(jax, mode: str):
    """``--prefetch={on,off,ab}``: run the requested leg(s) and print
    ONE JSON line; the A/B also records the off/on speedup."""
    legs = {"on": [True], "off": [False], "ab": [True, False]}[mode]
    results = [bench_prefetch(jax, leg) for leg in legs]
    rec = {"metric": "input_prefetch_step_breakdown",
           "unit": "s/step",
           "legs": results}
    if len(results) == 2:
        off_t, on_t = results[1]["step_s"], results[0]["step_s"]
        rec["speedup"] = round(off_t / on_t, 4) if on_t > 0 else 0.0
    try:
        with open("BENCH_prefetch.json", "w") as f:
            json.dump(rec, f, indent=1)
    except OSError:
        pass
    print(json.dumps(rec), flush=True)


def bench_ckpt(jax, use_async: bool, steps: int = None,
               interval: int = None):
    """A/B one leg of the fault-tolerant checkpoint pipeline: the same
    training loop with a checkpoint interval active, saving sync vs
    async.  Reports steps/sec, the exposed per-save stall (the stall the
    step loop actually paid — async pays only the snapshot D2H), and the
    background write time the async writer hid, PROVEN from tracer
    timestamps: hidden = how far each ``checkpoint/write`` span ran past
    its originating ``checkpoint/save`` span's end.

    Size is platform-scaled like the other A/B benches: tiny on CPU with
    ``DS_CKPT_DELAY_S`` injected write latency (the tier-1 smoke's
    overlap proof), mid-size on TPU via BENCH_CKPT_* knobs."""
    import tempfile
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    on_tpu = jax.devices()[0].platform != "cpu"
    if on_tpu:
        d_model = int(os.environ.get("BENCH_CKPT_D_MODEL", "1024"))
        n_layer = int(os.environ.get("BENCH_CKPT_LAYERS", "12"))
        micro = int(os.environ.get("BENCH_CKPT_MICRO", "4"))
        seq, vocab = 1024, 50257
        steps = steps or int(os.environ.get("BENCH_CKPT_STEPS", "8"))
        interval = interval or int(os.environ.get("BENCH_CKPT_INTERVAL",
                                                  "4"))
    else:
        d_model, n_layer, micro = 64, 2, 2
        seq, vocab = 64, 256
        steps = steps or 6
        interval = interval or 2
        # injected write latency: the thing the async leg hides (both
        # legs pay it; operators can override/disable)
        os.environ.setdefault("DS_CKPT_DELAY_S", "0.15")
    cfg_model = GPT2Config(d_model=d_model, n_layer=n_layer,
                           n_head=max(2, d_model // 64), vocab_size=vocab,
                           n_positions=seq, remat=None)
    mesh = build_mesh(devices=jax.devices()[:1])
    save_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    tel_dir = tempfile.mkdtemp(prefix="bench_ckpt_tel_")
    ds_cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "telemetry": {"enabled": True, "output_path": tel_dir,
                      "compile_events": False, "memory": False},
        "checkpoint": {"keep_last_n": 2},
    }, world_size=1)
    mode = "async" if use_async else "sync"
    _mark(f"ckpt[{mode}]: constructing engine")
    engine = DeepSpeedEngine(GPT2Model(cfg_model), ds_cfg, mesh=mesh)
    tokens = np.random.default_rng(0).integers(
        0, vocab, (micro, seq + 1), dtype=np.int32)
    tokens = _device_resident(engine, tokens)
    np.asarray(engine.train_batch(tokens))  # warmup/compile
    save_stall = 0.0
    saves = 0
    t0 = time.perf_counter()
    for i in range(steps):
        loss = engine.train_batch(tokens)
        if (i + 1) % interval == 0:
            s0 = time.perf_counter()
            engine.save_checkpoint(save_dir, async_write=use_async)
            save_stall += time.perf_counter() - s0
            saves += 1
    loss = float(np.asarray(loss))
    dt = (time.perf_counter() - t0) / steps
    assert np.isfinite(loss), f"non-finite loss {loss}"
    engine._ckpt_writer.drain()  # async leg: land the last write
    # overlap proof from tracer timestamps: each checkpoint/async_write
    # span's originating save is the LATEST checkpoint/save span that
    # started before the write did (coalescing can drop intermediate
    # saves, so a positional zip would misalign); hidden time = how far
    # the write ran past that save call's return, averaged over WRITTEN
    # checkpoints (submissions that coalesced away never wrote)
    hidden = 0.0
    ev = [e for e in engine.telemetry.tracer.events() if e.get("ph") == "X"]
    save_spans = [e for e in ev if e["name"] == "checkpoint/save"]
    write_spans = [e for e in ev if e["name"] == "checkpoint/async_write"]
    for w in write_spans:
        cands = [s for s in save_spans if s["ts"] <= w["ts"]]
        if not cands:
            continue
        s = max(cands, key=lambda e: e["ts"])
        hidden += max(0.0, (w["ts"] + w["dur"]) - (s["ts"] + s["dur"])) / 1e6
    engine.close()
    out = {"ckpt": mode,
           "step_s": round(dt, 6),
           "saves": saves,
           "writes": len(write_spans) if use_async else saves,
           "save_exposed_s": round(save_stall / max(saves, 1), 6),
           "ckpt_hidden_s": round(hidden / max(len(write_spans), 1), 6),
           "delay_s": float(os.environ.get("DS_CKPT_DELAY_S", "0") or 0)}
    _mark(f"ckpt[{mode}]: {dt:.3f}s/step, exposed "
          f"{out['save_exposed_s']:.3f}s/save, hidden "
          f"{out['ckpt_hidden_s']:.3f}s/save")
    return out


def _ckpt_ab(jax, mode: str):
    """``--ckpt={sync,async,ab}``: steps/sec with a checkpoint interval
    active; the A/B records the exposed-stall comparison and speedup."""
    legs = {"async": [True], "sync": [False],
            "ab": [True, False]}[mode]
    results = [bench_ckpt(jax, leg) for leg in legs]
    rec = {"metric": "ckpt_step_breakdown",
           "unit": "s/step",
           "legs": results}
    if len(results) == 2:
        sync_t, async_t = results[1]["step_s"], results[0]["step_s"]
        rec["speedup"] = round(sync_t / async_t, 4) if async_t > 0 else 0.0
        rec["exposed_stall_ratio"] = round(
            results[0]["save_exposed_s"]
            / max(results[1]["save_exposed_s"], 1e-9), 4)
    try:
        with open("BENCH_ckpt.json", "w") as f:
            json.dump(rec, f, indent=1)
    except OSError:
        pass
    print(json.dumps(rec), flush=True)


def bench_stage_chaos_leg(jax, chaos: bool, steps: int = 6):
    """One leg of ``--stage-chaos`` (docs/stages.md).  A tiny host-
    offload GPT-2 engine with every async plane active — input
    prefetch, the streamed offload update pipeline, an async save
    submitted every step.  ``chaos=True`` arms a STICKY injected fault
    at every stage boundary (``DS_STAGE_FAULT``), so each stage
    exhausts its failure budget and degrades to its inline/serial
    equivalent mid-run; ``chaos=False`` is the serial/inline/sync
    reference the degraded run must match bitwise."""
    import shutil
    import tempfile

    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
    from deepspeed_tpu.runtime.stages import reset_fault_injection

    d_model, n_layer, micro, seq, vocab = 64, 2, 2, 64, 256
    cfg_model = GPT2Config(d_model=d_model, n_layer=n_layer, n_head=2,
                           vocab_size=vocab, n_positions=seq, remat=None)
    mesh = build_mesh(devices=jax.devices()[:1])
    ds_cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2, "cpu_offload": True,
                              "offload_impl": "host",
                              # the reference leg IS the serial update
                              "offload_pipeline": chaos},
        "data_prefetch": {"enabled": chaos},
    }, world_size=1)
    leg = "chaos" if chaos else "reference"
    reset_fault_injection()
    # pop the FULL chaos env set: a stray DS_CKPT_FAULT / delay knob in
    # the operator's shell must not leak into either leg of the proof
    saved_env = {k: os.environ.pop(k, None)
                 for k in ("DS_STAGE_FAULT", "DS_STAGE_DELAY_S",
                           "DS_CKPT_FAULT", "DS_CKPT_DELAY_S",
                           "DS_PREFETCH_DELAY_S",
                           "DS_OFFLOAD_H2D_DELAY_S", "DS_PREFETCH",
                           "DS_OFFLOAD_PIPELINE")}
    if chaos:
        # sticky: every hit of every async boundary fails until the
        # stage's budget (default 3) is exhausted and it degrades
        os.environ["DS_STAGE_FAULT"] = ("prefetch:place:1+,"
                                        "offload_h2d:put:1+,"
                                        "ckpt_writer:job:1+")
    save_dir = tempfile.mkdtemp(prefix="bench_stage_chaos_")
    try:
        rng = np.random.default_rng(0)
        dataset = [rng.integers(0, vocab, (seq + 1,), dtype=np.int32)
                   for _ in range(micro * 4)]
        _mark(f"stage-chaos[{leg}]: constructing engine")
        engine = DeepSpeedEngine(GPT2Model(cfg_model), ds_cfg, mesh=mesh,
                                 training_data=dataset)
        try:
            engine.training_dataloader = RepeatingLoader(
                engine.training_dataloader)
            losses, failed_saves = [], 0
            t0 = time.perf_counter()
            for i in range(steps):
                losses.append(float(np.asarray(engine.train_batch())))
                # chaos leg: async until the writer degrades to sync
                engine.save_checkpoint(save_dir, tag=f"s{i}",
                                       async_write=chaos)
                err = engine._ckpt_writer.drain()
                if err is not None:
                    failed_saves += 1
            wall = time.perf_counter() - t0
            degraded = sorted(n for n, st in engine._stage_records.items()
                              if st.degraded)
            # the post-degradation save must have LANDED (sync fallback)
            final_saved = os.path.isdir(
                os.path.join(save_dir, f"s{steps - 1}"))
        finally:
            # an exception mid-leg must not leave the degraded engine's
            # daemon workers alive into the next leg (GC-finalizer luck)
            engine.close()
        out = {"leg": leg, "losses": losses,
               "steps_per_s": round(steps / wall, 4),
               "degraded_stages": degraded,
               "failed_async_saves": failed_saves,
               "final_save_landed": bool(final_saved)}
        _mark(f"stage-chaos[{leg}]: {steps / wall:.2f} steps/s, "
              f"degraded={degraded}, failed saves={failed_saves}")
        return out
    finally:
        shutil.rmtree(save_dir, ignore_errors=True)
        os.environ.pop("DS_STAGE_FAULT", None)
        for k, v in saved_env.items():
            if v is not None:
                os.environ[k] = v
        reset_fault_injection()


def _stage_chaos(jax):
    """``--stage-chaos``: the graceful-degradation CI proof — repeated
    sticky faults on every async stage; training must complete DEGRADED
    (all three stages fell back, the post-degradation save landed) with
    throughput > 0 and the final loss bitwise-equal to the serial/
    inline/sync reference leg."""
    from deepspeed_tpu.runtime.stages import DEFAULT_MAX_STAGE_FAILURES
    chaos = bench_stage_chaos_leg(jax, chaos=True)
    ref = bench_stage_chaos_leg(jax, chaos=False)
    ok = (chaos["degraded_stages"] == ["ckpt_writer", "offload_h2d",
                                       "prefetch"]
          # the writer fails one save per budget unit before degrading
          and chaos["failed_async_saves"] == DEFAULT_MAX_STAGE_FAILURES
          and chaos["final_save_landed"]
          and chaos["steps_per_s"] > 0
          and chaos["losses"] == ref["losses"])
    rec = {"metric": "stage_chaos_degraded_run",
           "unit": "bool",
           "value": int(ok),
           "steps_per_s_degraded": chaos["steps_per_s"],
           "degraded_stages": chaos["degraded_stages"],
           "failed_async_saves": chaos["failed_async_saves"],
           "final_save_landed": chaos["final_save_landed"],
           "loss_bitwise_equal_serial": chaos["losses"] == ref["losses"],
           "final_loss": chaos["losses"][-1]}
    try:
        with open("BENCH_stage_chaos.json", "w") as f:
            json.dump(rec, f, indent=1)
    except OSError:
        pass
    print(json.dumps(rec), flush=True)
    if not ok:
        raise RuntimeError(f"stage chaos smoke FAILED: {rec}")


def _elastic_smoke():
    """``--elastic-smoke``: the elastic-training kill/resume proof as a
    bench leg (docs/elastic.md).  Launches ``ds --elastic`` supervising
    the tests/elastic_worker.py trainer on localhost at 4 slots, the
    worker hard-kills itself after step 3 (prefetcher ON at depth 2 —
    in-flight batches genuinely abandoned), the probe reports the host
    shrunk to 2 slots, and the supervisor relaunches.  Asserts resume
    at the REDUCED width with trajectory continuity against a
    dp2-from-start reference given the same sample order, plus
    sample-exactness (no replay, no skip).  CPU-only by design — it
    proves supervisor/resume mechanics, not throughput: its children
    are held to the CPU backend and this process starts no backend."""
    import shutil
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    work = tempfile.mkdtemp(prefix="bench_elastic_")
    env = dict(os.environ)
    env["PYTHONPATH"] = (repo + os.pathsep + os.path.join(repo, "tests")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["JAX_PLATFORMS"] = "cpu"
    env["DS_CKPT_FSYNC"] = "0"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # the workers shard dp4 -> dp2 over virtual CPU devices
        env["XLA_FLAGS"] = (flags
                            + " --xla_force_host_platform_device_count=8")
    for k in ("DS_ELASTIC_RESTART", "DS_ELASTIC_WORLD_SLOTS",
              "DS_HEARTBEAT_DIR"):
        env.pop(k, None)
    worker = os.path.join(repo, "tests", "elastic_worker.py")

    def lines(path):
        with open(path) as f:
            return [json.loads(l) for l in f]

    try:
        hf = os.path.join(work, "hostfile")
        with open(hf, "w") as f:
            f.write("localhost slots=4\n")
        probe = os.path.join(work, "probe.sh")
        with open(probe, "w") as f:
            f.write("#!/bin/sh\necho slots=2\n")
        os.chmod(probe, 0o755)
        out = os.path.join(work, "out")
        ckpt = os.path.join(work, "ckpt")
        os.makedirs(out), os.makedirs(ckpt)
        _mark("elastic-smoke: supervised run (kill after step 3 of 6)")
        t0 = time.perf_counter()
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "bin", "ds"),
             "--hostfile", hf, "--launcher", "local", "--elastic",
             "--max-restarts", "2", "--backoff-base", "0.1",
             "--probe-cmd", f"{probe} {{host}}",
             worker, out, ckpt, "6", "3"],
            env=env, timeout=600, capture_output=True, text=True)
        if r.returncode != 0:
            raise RuntimeError(
                f"elastic supervised run failed rc={r.returncode}: "
                f"{(r.stderr or r.stdout)[-1500:]}")
        supervised_s = time.perf_counter() - t0
        _mark("elastic-smoke: dp2-from-start reference run")
        ref_out = os.path.join(work, "ref")
        ref_ckpt = os.path.join(work, "refck")
        os.makedirs(ref_out), os.makedirs(ref_ckpt)
        e = dict(env)
        e["DS_ELASTIC_WORLD_SLOTS"] = "2"
        r = subprocess.run(
            [sys.executable, worker, ref_out, ref_ckpt, "6", "0"],
            env=e, timeout=600, capture_output=True, text=True)
        if r.returncode != 0:
            raise RuntimeError(
                f"reference run failed: {(r.stderr or r.stdout)[-1500:]}")

        t1 = lines(os.path.join(out, "traj_r1.jsonl"))
        ref = lines(os.path.join(ref_out, "traj_r0.jsonl"))
        widths = sorted({rec["dp"] for rec in t1})
        resumed_at_reduced = widths == [2]
        steps = [rec["step"]
                 for rec in lines(os.path.join(out, "traj_r0.jsonl"))] \
            + [rec["step"] for rec in t1]
        continuous = steps == list(range(6))
        drift = max(abs(a["loss"] - b["loss"])
                    for a, b in zip(t1, ref[3:]))
        samples = (lines(os.path.join(out, "samples_r0.jsonl"))[:3]
                   + lines(os.path.join(out, "samples_r1.jsonl")))[:6]
        sample_exact = samples == lines(
            os.path.join(ref_out, "samples_r0.jsonl"))[:6]
        rec = {"metric": "elastic_kill_resume_smoke",
               "unit": "bool",
               "value": int(resumed_at_reduced and continuous
                            and sample_exact and drift < 1e-4),
               "resumed_at_dp": widths,
               "trajectory_continuous": continuous,
               "sample_exact": sample_exact,
               "max_loss_drift_vs_dp2_from_start": round(drift, 9),
               "supervised_wall_s": round(supervised_s, 3)}
        try:
            with open("BENCH_elastic.json", "w") as f:
                json.dump(rec, f, indent=1)
        except OSError:
            pass
        print(json.dumps(rec), flush=True)
        if not rec["value"]:
            raise RuntimeError(f"elastic smoke FAILED: {rec}")
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main():
    import argparse

    import jax

    parser = argparse.ArgumentParser(
        description="GPT-2 1.5B ZeRO-2 offload north-star bench "
                    "(one JSON line); env knobs in the module docstring")
    parser.add_argument("--offload-pipeline", choices=("on", "off", "ab"),
                        default=None,
                        help="A/B the streaming offload update pipeline: "
                             "per-stage step-time breakdown (d2h / "
                             "cpu_adam / h2d / hidden) instead of the "
                             "north-star bench")
    parser.add_argument("--offload-tier", choices=("host", "disk", "ab"),
                        default=None, dest="offload_tier",
                        help="A/B the offload state tier (host RAM vs "
                             "the ZeRO-Infinity disk tier): step time, "
                             "bitwise-loss check, and the disk leg's "
                             "state-I/O overlap ratio under injected "
                             "per-leaf disk latency "
                             "(BENCH_offload_disk.json)")
    parser.add_argument("--prefetch", choices=("on", "off", "ab"),
                        default=None,
                        help="A/B the async input pipeline (prefetched "
                             "collate + H2D batch placement): step time "
                             "+ prefetch wait/hit breakdown instead of "
                             "the north-star bench")
    parser.add_argument("--ckpt", choices=("sync", "async", "ab"),
                        default=None,
                        help="A/B fault-tolerant checkpointing: steps/sec "
                             "with a checkpoint interval active, sync vs "
                             "async saves (exposed-stall comparison + "
                             "tracer-proven hidden write time) instead "
                             "of the north-star bench")
    parser.add_argument("--stage-chaos", action="store_true",
                        dest="stage_chaos",
                        help="graceful-degradation smoke: sticky "
                             "injected faults at every async stage "
                             "boundary (prefetch/offload-upload/async "
                             "save); asserts training completes "
                             "degraded, throughput > 0, final loss "
                             "bitwise-equal to the serial reference "
                             "(docs/stages.md)")
    parser.add_argument("--elastic-smoke", action="store_true",
                        dest="elastic_smoke",
                        help="kill/resume supervisor smoke: ds --elastic "
                             "on localhost, worker hard-killed mid-run, "
                             "assert resume at reduced width with "
                             "trajectory continuity + sample-exactness "
                             "(CPU subprocesses only)")
    # strict parse: a typo'd flag must fail loudly, not silently launch
    # the multi-hour north-star run
    args = parser.parse_args()

    if args.elastic_smoke:
        # dispatched BEFORE device enumeration: the smoke supervises CPU
        # children, and a parent that held the chip would starve any
        # child that needed it
        _elastic_smoke()
        return

    _mark(f"compile cache at {enable_compile_cache()}")
    devices = jax.devices()
    _mark(f"devices: {[d.device_kind for d in devices]}")

    if args.offload_pipeline is not None:
        _offload_pipeline_ab(jax, args.offload_pipeline)
        return

    if args.offload_tier is not None:
        _offload_tier_ab(jax, args.offload_tier)
        return

    if args.prefetch is not None:
        _prefetch_ab(jax, args.prefetch)
        return

    if args.ckpt is not None:
        _ckpt_ab(jax, args.ckpt)
        return

    if args.stage_chaos:
        _stage_chaos(jax)
        return

    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX reports {devices[0].platform!r} "
            f"({devices[0].device_kind}). Nothing was run. The A/B legs "
            "(--offload-pipeline, --offload-tier, --prefetch, --ckpt, "
            "--stage-chaos, --elastic-smoke) run on any backend.")
    peak = _chip_peak_bf16_flops(devices[0])
    if os.environ.get("BENCH_SMALL"):
        result = _bench_124m(jax)
    else:
        impl = os.environ.get("BENCH_15B_IMPL", "xla_split_dpu").strip()
        valid = ("xla_split_dpu", "xla_split", "xla_split4", "xla", "host")
        if impl not in valid:
            raise ValueError(f"BENCH_15B_IMPL={impl!r}; valid: "
                             + ", ".join(valid))
        result = _bench_15b(jax, impl=impl)
    cfg, seq, tps, name = result

    mfu = tps * _flops_per_token(cfg, seq) / peak
    rec = {
        "metric": f"{name}_seq{seq}_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
    }
    with open("BENCH_north_star.json", "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
