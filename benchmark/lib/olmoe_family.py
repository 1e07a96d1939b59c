"""Family ``olmoe`` (``configs/olmoe-1b-7b.json``: ``"family_module":
"lib.olmoe_family:Olmoe"``) and the job that adds the expert layer's series
to ``serve_open_loop`` (``traffic/serve-longform-saturated.json``:
``"job_module": "lib.olmoe_family:run"``).

The byte count of the expert kernels, the yardstick of
``moe_expert_roofline.saturated``, is here: ``expert_kernel_bytes``.
"""
from __future__ import annotations

import time

import numpy as np

import jax

from . import olmoe_reference, serve_job, yardstick
from .yardstick import say

# the source's keys that are not numbers (families._published keeps numbers)
_FLAGS = ("norm_topk_prob", "attention_bias", "tie_word_embeddings",
          "clip_qkv", "hidden_act")


class Olmoe:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.olmoe import OlmoeConfig, OlmoeModel
        self.m = m = {k: v for k, v in cfg_file.items()
                      if isinstance(v, (int, float))}
        if rehearse:
            m.update(cfg_file["rehearse"]["sizes"])
        keys = ("vocab_size", "hidden_size", "intermediate_size",
                "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "num_experts", "num_experts_per_tok",
                "rms_norm_eps", "rope_theta", "max_position_embeddings")
        # attn_impl is the program's default, so a changed default shows;
        # the weights are drawn in the served dtype (a float32 copy of
        # 5 B parameters does not fit beside them)
        self.model = OlmoeModel(OlmoeConfig(
            **{k: m[k] for k in keys}, **{k: cfg_file[k] for k in _FLAGS},
            param_dtype=cfg_file["dtype"]))
        self.vocab = m["vocab_size"]
        self._logits_fn = None

    def reference_logits(self, params, tokens, pad_to: int):
        """float32 logits [len(tokens), V] of one sequence, padded to
        ``pad_to`` so that every call is one program (causal attention and
        per-token experts keep the padding out of the rows returned)."""
        if self._logits_fn is None:
            self._logits_fn = jax.jit(
                lambda p, t: olmoe_reference.olmoe_logits(p, t, self.m)[0])
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            return self._logits_fn(params, padded)[:len(tokens)]

    def expert_kernel_bytes(self, experts_hit: int, rows: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_moe_gate_up`` + ``ds_moe_down`` must move for
        ``rows`` (token, expert) assignments over ``experts_hit`` experts
        (both summed over layers): each hit expert's three matrices once;
        per row, x in and h out (gate_up), h in and y out (down)."""
        d, f = self.m["hidden_size"], self.m["intermediate_size"]
        return itemsize * (experts_hit * 3 * d * f + rows * 2 * (d + f))


class _StallWatch:
    """Where a far-off run lost its time, without a trace: the longest call
    of each phase of the engine's tick, and the longest silence of a thread
    that only sleeps 20 ms at a time.  A tick that stalls while that thread
    keeps its beat waited for the device (the main thread is inside
    ``block_until_ready``, the interpreter lock released); a silence as
    long as the tick means the whole process stood still."""
    PHASES = ("_admit", "_decode_prepare", "_decode_dispatch",
              "_pull_tokens", "_emit_tokens")

    def __init__(self, eng):
        import threading
        self.longest = {}               # phase -> (seconds, ended at)
        self.total = {n: [0.0, 0] for n in self.PHASES}   # seconds, calls
        self.silence = (0.0, 0.0)
        self._stop = threading.Event()
        for name in self.PHASES:
            setattr(eng, name, self._timed(name, getattr(eng, name)))
        self._thread = threading.Thread(target=self._beat, daemon=True)
        self._thread.start()

    def _timed(self, name, fn):
        clock = time.perf_counter

        def call(*a, **k):
            t = clock()
            try:
                return fn(*a, **k)
            finally:
                end = clock()
                tot = self.total[name]
                tot[0] += end - t
                tot[1] += 1
                if end - t > self.longest.get(name, (0.0, 0.0))[0]:
                    self.longest[name] = (end - t, end)
        return call

    def _beat(self):
        last = time.perf_counter()
        while not self._stop.wait(0.02):
            now = time.perf_counter()
            if now - last > self.silence[0]:
                self.silence = (now - last, now)
            last = now

    def stop(self):
        self._stop.set()
        self._thread.join()

    def report(self, w0: float, seconds: float) -> str:
        def at(t):
            return f"{t - w0:+.1f} s" + ("" if 0 <= t - w0 < seconds
                                          else " (outside the window)")
        parts = [f"{n[1:]} {d * 1e3:.0f} ms at {at(t)} (mean "
                 f"{self.total[n][0] / max(self.total[n][1], 1) * 1e3:.3f})"
                 for n, (d, t) in sorted(self.longest.items())]
        return ("stalls: longest call of each tick phase (and its mean over "
                "the loop; pull_tokens waits for the device, the others are "
                "the host's): " + ", ".join(parts)
                + f"; longest silence of the 20 ms heartbeat thread "
                f"{self.silence[0] * 1e3:.0f} ms at {at(self.silence[1])}")


def run(ctx) -> dict:
    """``serve_job.run`` as it is, plus what the program counted per call
    (``ServeEngine.aux_log``): ``moe_experts_hit_pct`` and
    ``moe_load_imbalance`` per decode tick of the window and, traced,
    ``moe_min_pct_of_traced_window``: the time the expert kernels' bytes
    need at the chip's HBM peak, as a percentage of the traced window."""
    inner = serve_job._open_loop
    start_trace, stop_trace = jax.profiler.start_trace, jax.profiler.stop_trace
    traced = []

    def started(*a, **k):
        out = start_trace(*a, **k)
        traced.append(time.perf_counter())      # the window opens after
        return out

    def stopping(*a, **k):
        traced.append(time.perf_counter())      # and closes before
        return stop_trace(*a, **k)

    def open_loop(ctx, eng, items, lead_s, grace_s, series):
        log0 = len(eng.aux_log)
        watch = _StallWatch(eng)
        try:
            res = inner(ctx, eng, items, lead_s, grace_s, series)
        finally:
            watch.stop()
        say(watch.report(res["window_start"], ctx.seconds))
        calls = list(eng.aux_log)[log0:]
        fam = ctx.family
        w0, w1 = res["window_start"], res["window_start"] + ctx.seconds
        all_experts = fam.m["num_experts"] * fam.m["num_hidden_layers"]
        ticks = [v for t, kind, v in calls
                 if kind == "decode" and w0 <= t < w1]
        series["moe_experts_hit_pct"] = [
            100.0 * v["moe_experts_hit"] / all_experts for v in ticks]
        series["moe_load_imbalance"] = [v["moe_load_imbalance"]
                                        for v in ticks]
        if ticks:
            say(f"experts: {len(ticks)} decode ticks in the window, hit "
                f"{np.mean(series['moe_experts_hit_pct']):.2f} % of "
                f"{all_experts} a tick, busiest over mean "
                f"{np.mean(series['moe_load_imbalance']):.2f}")
        if len(traced) == 2 and not ctx.rehearse:
            a, b = traced
            item = jax.numpy.dtype(ctx.cfg_file["dtype"]).itemsize
            need = sum(fam.expert_kernel_bytes(
                v["moe_experts_hit"], v["moe_rows"], item)
                for t, _, v in calls if a <= t < b)
            peak = yardstick.peak(jax.devices()[0].device_kind,
                                  "hbm_bytes_per_s")
            series["moe_min_pct_of_traced_window"] = \
                100.0 * need / peak / (b - a)
            say(f"experts, traced {b - a:.3f} s: {need / 1e9:.2f} GB to "
                f"move, {need / peak:.3f} s at {peak / 1e9:.0f} GB/s")
        return res

    serve_job._open_loop = open_loop
    jax.profiler.start_trace, jax.profiler.stop_trace = started, stopping
    try:
        return serve_job.run(ctx)
    finally:
        serve_job._open_loop = inner
        jax.profiler.start_trace, jax.profiler.stop_trace = \
            start_trace, stop_trace
