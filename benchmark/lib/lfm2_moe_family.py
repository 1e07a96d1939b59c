"""Family ``lfm2_moe`` (``configs/lfm2-24b-a2b.json``: ``"family_module":
"lib.lfm2_moe_family:Lfm2Moe"``) and the job that serves it under
``serve_open_loop`` (``traffic/serve-manystream-saturated.json``:
``"job_module": "lib.lfm2_moe_family:run"``).

The yardsticks of this configuration's kernels are here:
``expert_kernel_bytes`` (``moe_expert_roofline.saturated``: each hit
expert's three matrices once + each row in and out of both kernels) and
``full_decode_bytes`` (``full_decode_roofline.saturated``, under MiMo's
counter ``full_kv_tokens``: keys and values 64 wide on 8 key heads AS
PUBLISHED, which is what the paired pool holds, + the queries in and the
outputs out at their own 64; that file's ``what`` names MiMo's widths).
``moe_rows_per_expert.saturated`` reads the series ``moe_rows_per_expert``:
a decode tick's live assignments over the experts it hit.

The job is its own ``run``, made of ``serve_job``'s parts (its open loop,
its constants) as ``lib/olmo_hybrid_family.py::run`` is: the probe hands the
model its ``state`` and ``slot``, and the limits and controls are this
configuration's.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from . import lfm2_moe_reference, serve_job, traffic, yardstick
from .nemotron_h_family import _relative, _trace_times
from .olmoe_family import _StallWatch
from .yardstick import say

# The limits of this cell's checks (``judge``).  Each lies between two
# readings taken on the chip at the published widths (PERF.md section 6,
# PR 65): the largest the program gave over its seeds, and the smallest a
# control gives.  The controls are read in EVERY run through the same
# ``judge`` in the program's place, and the run is not correct unless each
# comes out as not correct (``run``):
#
# * the reference with the convolution's taps REVERSED (the filter read
#   newest-first), against itself, on the probe's sequences;
# * the reference choosing its experts by score WITHOUT ``expert_bias``;
# * the reference with its residual stream rounded to float8 (e5m2) from
#   the embedding on and after every layer, one precision below the
#   bfloat16 the configuration states: on the probe's sequences, and the
#   tokens it would have emitted on the streams'.  (The issue asked for
#   "the reference in bfloat16 matmuls": that IS the program's precision
#   and reads as the program does; the precision below it is this one, as
#   ``lib/nemotron_h_family.py``'s.)
CONTROL_ACT = jnp.float8_e5m2
# Probe logits over prefill + a page and more of decode ticks of
# PROBE_REQUESTS requests on the engine's own pools and state, a tick a
# program call, against the reference: the LARGEST |program - reference|
# and the MEAN over a request's positions and tokens (the largest of the
# requests': the controls are not all read on every request).  Activations
# and logits are bfloat16 and logits of random weights reach |5|, where a
# bfloat16 step is 0.031; the largest is where the bfloat16 rounding of a
# router's input swaps a fourth choice the float32 reference did not swap
# (``lib/nemotron_h_family.py`` says the same of its router), the mean the
# level of the noise.  Over 16 runs of the finished change, a seed each (my
# chip runs, PR 65): largest, program 0.70-1.03, the controls' smallest
# 1.76 (no bias), 1.80 (e5m2), 6.45 (taps); mean, program 0.046-0.067,
# controls 0.276-0.99.  The largest is one token's reading and fresh seeds
# read higher, so its limit leaves it the more room; the mean is what
# fails a control whose largest should come out low.
LOGIT_TOL = 1.4
LOGIT_MEAN_TOL = 0.13
# Streams: how far below the reference's top logit a token sits that the
# timed engine emitted (the only reading drawn from the window itself), on
# average over the 1,570-2,538 positions of the four finished requests
# replayed.  Program 0.0124-0.0156, control (e5m2) 0.279-0.300.
STREAM_MEAN_TOL = 0.06
# The FIRST conv layer's kept rows of the probe's slot after the prompt and
# the ticks against the reference's ``z`` at the last two positions,
# largest |diff| over largest |reference|.  The first layer, because its
# input (the embedding) is the same on both sides; what differs is one
# bfloat16 matmul's rounding of ``B`` and ``u``.  It catches rows that are
# stale, started from what the slot held, taken in past the prompt's true
# length, shifted twice or written to another slot (all of order 1).
# Program 4.7e-3 to 6.5e-3, control (e5m2) 5.7e-2 to 9.4e-2.
STATE_VS_REFERENCE_TOL = 2e-2
#: decode ticks of a probe: past a page boundary whatever the prompt's
#: length (``page_len`` + a few), forced tokens
PROBE_MARGIN = 4


def judge(readings: dict) -> dict:
    """The cell's limits on whatever readings are handed in, the program's
    or a control's in its place: check -> within its limit."""
    limits = {"probe_logits": LOGIT_TOL, "probe_logits_mean": LOGIT_MEAN_TOL,
              "probe_state": STATE_VS_REFERENCE_TOL,
              "streams_mean": STREAM_MEAN_TOL}
    return {f"{k}_within_tolerance":
            bool(np.isfinite(v) and v <= limits[k])
            for k, v in readings.items()}


#: the reference's switches (reverse_taps, no bias, e5m2 activations) of
#: the reference itself and of its three controls, by name
_MEMBERS = {"reference": (0, 0, 0), "taps_reversed": (1, 0, 0),
            "no_expert_bias": (0, 1, 0), "low_activations": (0, 0, 1)}


class Lfm2Moe:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.lfm2_moe import (Lfm2MoeConfig,
                                                   Lfm2MoeModel)
        fields = {f.name for f in dataclasses.fields(Lfm2MoeConfig)}
        m = {k: v for k, v in cfg_file.items() if k in fields}
        if rehearse:
            m.update(cfg_file["rehearse"]["sizes"])
        self.model = Lfm2MoeModel(Lfm2MoeConfig(
            **m, param_dtype=cfg_file["dtype"]))
        cfg = self.model.config
        self.m = dataclasses.asdict(cfg)
        self.vocab = cfg.vocab_size
        self.full_layers, self.conv_layers, self.moe_layers = (
            cfg.count("full"), cfg.count("conv"), cfg.count("moe"))

        def forward(p, t, sw, **kw):
            return lfm2_moe_reference.lfm2_moe_logits(
                p, t, self.m, reverse_taps=sw[0] > 0, use_bias=sw[1] == 0,
                round_acts=sw[2] > 0, act_dtype=CONTROL_ACT, **kw)

        def probe(p, t, n, first, sw, rows):
            logits, z = forward(p, t, sw, logit_rows=(first, rows))
            return logits[0], jax.lax.dynamic_slice_in_dim(
                z[0, 0], n - (cfg.conv_L_cache - 1), cfg.conv_L_cache - 1)

        def stream(p, t):
            ref, low = (forward(p, t, jnp.asarray(_MEMBERS[name]))[0][0]
                        for name in ("reference", "low_activations"))
            below = ref.max(axis=-1, keepdims=True) - ref
            nxt = jnp.roll(t[0], -1)
            return (jnp.take_along_axis(below, nxt[:, None], 1)[:, 0],
                    jnp.take_along_axis(
                        below, low.argmax(axis=-1)[:, None], 1)[:, 0])

        self._probe = jax.jit(probe, static_argnums=(5,))
        self._stream = jax.jit(stream)

    def make_params(self, seed: int, dtype):
        """``serve_job._make_params`` with ``expert_bias`` kept float32
        (zero, as the source starts it: with it the busiest expert reads
        1.6 x the mean on the chip, PR 65, so nothing is balanced)."""
        params = serve_job._make_params(self.model, seed, dtype)
        moe = dict(params["moe"])
        moe["router_bias"] = tuple(b.astype(jnp.float32)
                                   for b in moe["router_bias"])
        return dict(params, moe=moe)

    def with_bias(self, params, seed: int, std: float = 0.2):
        """``params`` with an ``expert_bias`` drawn normal(0, ``std``): for
        the check that the bias steers the choice (the source starts it at
        zero, where that check would read nothing)."""
        rng = np.random.default_rng([int(seed), 5])
        moe = dict(params["moe"])
        moe["router_bias"] = tuple(
            jnp.asarray(rng.normal(0, std, b.shape), jnp.float32)
            for b in moe["router_bias"])
        return dict(params, moe=moe)

    def reference(self, params, tokens, pad_to: int, first: int, rows: int,
                  members=("reference",)):
        """One sequence padded to ``pad_to`` (the causal layers keep the
        padding out of the rows before it) through the reference and the
        named controls, one program for all: name -> (logits of ``rows``
        rows from ``first`` [rows, V], the first conv layer's ``z`` at the
        sequence's last two positions)."""
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            return {name: tuple(np.asarray(x) for x in self._probe(
                params, padded, np.int32(len(tokens)), np.int32(first),
                np.asarray(_MEMBERS[name], np.int32), rows))
                for name in members}

    def stream_slack(self, params, seq, prompt_len: int, pad_to: int):
        """How far below the reference's top logit the tokens of ``seq``
        after the prompt sit, and those the low-activation control would
        have emitted: two arrays [len(seq) - prompt_len]."""
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            own, low = (np.asarray(x) for x in self._stream(params, padded))
        at = slice(prompt_len - 1, len(seq) - 1)
        return own[at], low[at]

    def expert_kernel_bytes(self, experts_hit: int, rows: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_moe_gate_up`` + ``ds_moe_down`` must move for
        ``rows`` (token, expert) assignments over ``experts_hit`` experts
        (both summed over layers): each hit expert's three matrices once;
        per row, x in and h out (gate_up), h in and y out (down)."""
        d, f = self.m["hidden_size"], self.m["moe_intermediate_size"]
        return itemsize * (experts_hit * 3 * d * f + rows * 2 * (d + f))

    def full_decode_bytes(self, full_kv_tokens: int, slots: int,
                          itemsize: int) -> int:
        """HBM bytes ``ds_paged_decode_attn`` must move in a tick that read
        ``full_kv_tokens`` live keys (summed over the full layers) for
        ``slots`` live slots, AS PUBLISHED: every live key and value once
        (8 key heads of 64 each), + a layer call's queries in and outputs
        out (32 heads of 64 each; the zeros the paired layout puts beside
        them are the program's)."""
        cfg = self.model.config
        kv = 2 * cfg.num_key_value_heads * cfg.key_dim
        qo = 2 * cfg.num_attention_heads * cfg.key_dim
        return itemsize * (kv * full_kv_tokens
                           + qo * self.full_layers * slots)


def _probe(family, eng, params, items, bucket: int, ref_len: int,
           seed: int):
    """Prefill and a page and more of decode ticks of a few requests through
    the model's paged serving entry points, on the engine's own pools and
    request state (the cell's slots and pages; same kernels; one request
    live, in the middle slot) against the plain reference on the same
    context: logits of every step, the first conv layer's kept rows at the
    end, and that the slots beside it keep what they held.  The LAST
    request runs on weights whose ``expert_bias`` is drawn non-zero
    (``Lfm2Moe.with_bias``), program and reference alike.  Returns (the
    program's readings, each control's by name, largest |reference logit|,
    whether the slots beside it kept what they held)."""
    model = family.model
    slots, page_len, max_pages = eng.slots, eng.page_len, eng.max_pages
    slot, n_ticks = slots // 2, page_len + PROBE_MARGIN
    active = np.zeros((slots,), bool)
    active[slot] = True

    def poison(params, cache):
        # rows that are not zero where the request lands and beside it: the
        # prefill must not read them, and none may write beside it
        return (dict(cache, state={
            name: jax.lax.dynamic_update_slice_in_dim(
                leaf, jnp.full(leaf.shape[:1] + (3,) + leaf.shape[2:], 0.5,
                               leaf.dtype), slot - 1, axis=1)
            for name, leaf in cache["state"].items()}),)

    def prefill(params, cache, tokens, n, row):
        logits, k, v, state = model.prefill_paged(
            params, tokens, n, np.int32(0), row, cache["k"], cache["v"],
            state=cache["state"], slot=np.int32(slot))
        lengths = jnp.zeros_like(cache["lengths"]).at[slot].set(n)
        return (dict(cache, k=k, v=v, state=state, lengths=lengths),
                jax.lax.dynamic_index_in_dim(logits[0], n - 1, 0, False))

    def tick(params, cache, token, table):
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(token)
        lg, k, v, state, lengths = model.decode_step_paged(
            params, tokens, cache["k"], cache["v"], table, cache["lengths"],
            active, state=cache["state"], impl=eng.decode_impl)
        return (dict(cache, k=k, v=v, state=state, lengths=lengths),
                lg[slot].astype(jnp.float32))

    def after(params, cache):
        """Frees the slot and reads the first conv layer's rows of it and
        whether the slots beside it hold what they held."""
        conv = cache["state"]["conv"]
        beside = jnp.all(jnp.stack([jnp.all(conv[:, s] == 0.5)
                                    for s in (slot - 1, slot + 1)]))
        return (dict(cache, lengths=jnp.zeros_like(cache["lengths"])),
                conv[0, slot], beside)

    at = jax.tree.map(lambda a: a.sharding, eng.cache)

    def on_cache(fn):
        """``nemotron_h_family._on_the_engines_cache`` with the params an
        operand (the biased request runs on its own): the engine's cache
        donated and handed back where it lay."""
        def program(p, cache, *operands):
            cache, *results = fn(p, cache, *operands)
            return cache, tuple(results)

        jitted = jax.jit(program, donate_argnums=(1,),
                         out_shardings=(at, None))

        def call(p, *operands):
            with eng._pallas_scope():
                eng.cache, results = jitted(p, eng.cache, *operands)
            return results
        return call

    poison, prefill, tick, after = (on_cache(fn) for fn in (
        poison, prefill, tick, after))
    rng = np.random.default_rng(12345)
    keys = ("probe_logits", "probe_logits_mean", "probe_state")
    sound = dict.fromkeys(keys, 0.0)
    controls = {name: dict.fromkeys(keys, 0.0) for name in _MEMBERS
                if name != "reference"}
    top, untouched = 0.0, True
    biased = family.with_bias(params, seed)
    for i, it in enumerate(items):
        last = i == len(items) - 1
        # the biased weights rest beside the engine's own only while the
        # last request runs: the bias vectors alone differ
        run_params = dict(eng.params, moe=dict(
            eng.params["moe"], router_bias=biased["moe"]["router_bias"])) \
            if last else eng.params
        ref_params = biased if last else params
        prompt = list(it.prompt)[:min(bucket, ref_len - n_ticks)]
        forced = rng.integers(0, family.vocab, (n_ticks,)).astype(np.int32)
        n_pages = -(-(len(prompt) + n_ticks) // page_len)
        row = np.zeros((max_pages,), np.int32)
        row[:n_pages] = 1 + np.arange(n_pages)
        table = np.zeros((slots, max_pages), np.int32)
        table[slot] = row
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        poison(run_params)
        first, = prefill(run_params, padded, np.int32(len(prompt)), row)
        got = np.stack([np.asarray(first, np.float32)] + [
            np.asarray(tick(run_params, token, table)[0])
            for token in forced])
        got_rows, beside = after(run_params)
        # every forced token is fed by a tick: the rows are of all of seq
        seq = prompt + [int(t) for t in forced]
        members = ["reference", "taps_reversed", "low_activations"] \
            + (["no_expert_bias"] if last else [])
        ref = family.reference(ref_params, seq, ref_len, len(prompt) - 1,
                               n_ticks + 1, members)
        want, want_rows = ref["reference"]
        top = max(top, float(np.abs(want).max()))
        untouched &= bool(beside)
        # at rest the rows lie side by side; ``z``'s are [rows, d]
        readings = [(sound, np.asarray(got), np.asarray(
            got_rows, np.float32).reshape(want_rows.shape))]
        readings += [(controls[name], *ref[name]) for name in members[1:]]
        for into, logits, rows in readings:
            diff = np.abs(logits - want)
            into["probe_logits"] = max(into["probe_logits"],
                                       float(diff.max()))
            into["probe_logits_mean"] = max(into["probe_logits_mean"],
                                            float(diff.mean()))
            into["probe_state"] = max(into["probe_state"],
                                      _relative(rows, want_rows))
    return sound, controls, top, untouched


def _streams(family, params, reqs, ref_len: int):
    """How far below the reference's top logit a token sits, over whole
    finished streams of the timed engine (teacher-forced on the engine's
    own tokens), for the tokens the engine emitted and, the control, for
    those the low-activation reference would have.  Returns ((largest,
    mean), the control's, positions)."""
    own, low = [], []
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        a, b = family.stream_slack(params, seq, len(r.prompt), ref_len)
        own.append(a)
        low.append(b)
    if not own:
        return (0.0, 0.0), (0.0, 0.0), 0
    own, low = np.concatenate(own), np.concatenate(low)
    return ((float(own.max()), float(own.mean())),
            (float(low.max()), float(low.mean())), len(own))


def _counters(ctx, eng, calls, res, traced, series) -> None:
    """What the program counted per call (``ServeEngine.aux_log``) into
    ``series``: ``moe_experts_hit_pct`` (of experts x expert layers),
    ``moe_load_imbalance`` and ``moe_rows_per_expert`` per decode tick of
    the window and, traced, ``moe_`` / ``full_min_pct_of_traced_window``:
    the time the expert kernels' and the paged kernel's bytes need at the
    chip's HBM peak, as percentages of the traced window."""
    fam = ctx.family
    w0, w1 = res["window_start"], res["window_start"] + ctx.seconds
    all_experts = fam.m["num_experts"] * fam.moe_layers
    ticks = [v for t, kind, v in calls if kind == "decode" and w0 <= t < w1]
    prefills = [v for t, kind, v in calls
                if kind == "prefill" and w0 <= t < w1]
    series["moe_experts_hit_pct"] = [
        100.0 * v["moe_experts_hit"] / all_experts for v in ticks]
    series["moe_load_imbalance"] = [v["moe_load_imbalance"] for v in ticks]
    series["moe_rows_per_expert"] = [
        v["moe_rows"] / max(v["moe_experts_hit"], 1) for v in ticks]
    if ticks:
        say(f"counters: {len(ticks)} decode ticks in the window, hit "
            f"{np.mean(series['moe_experts_hit_pct']):.2f} % of "
            f"{all_experts} experts a tick, "
            f"{np.mean(series['moe_rows_per_expert']):.2f} rows a hit "
            f"expert, busiest over mean "
            f"{np.mean(series['moe_load_imbalance']):.2f}; "
            f"{np.mean([v['conv_slot_layers'] for v in ticks]) / max(fam.conv_layers, 1):.0f} "
            f"slots' convolution rows kept a tick, "
            f"{np.mean([v['full_kv_tokens'] for v in ticks]) / max(fam.full_layers, 1):.0f} "
            f"live keys a full layer a tick; {len(prefills)} prefill calls")
    pad = eng.prefill_pad_tokens
    ran = eng.prefill_tokens + pad
    say(f"prefill bucket: {pad} of {ran} tokens the prefills ran were "
        f"padding ({100.0 * pad / max(ran, 1):.1f} %)")
    if len(traced) == 2 and not ctx.rehearse:
        a, b = traced
        item = jnp.dtype(ctx.cfg_file["dtype"]).itemsize
        in_trace = [(kind, v) for t, kind, v in calls if a <= t < b]
        moe = sum(fam.expert_kernel_bytes(
            round(v["moe_experts_hit"]), round(v["moe_rows"]), item)
            for _, v in in_trace)
        full = sum(fam.full_decode_bytes(
            round(v["full_kv_tokens"]),
            round(v["conv_slot_layers"] / max(fam.conv_layers, 1)), item)
            for kind, v in in_trace if kind == "decode")
        hbm = yardstick.peak(jax.devices()[0].device_kind, "hbm_bytes_per_s")
        series["moe_min_pct_of_traced_window"] = 100.0 * moe / hbm / (b - a)
        series["full_min_pct_of_traced_window"] = \
            100.0 * full / hbm / (b - a)
        say(f"traced {b - a:.3f} s, {len(in_trace)} calls: experts "
            f"{moe / 1e9:.2f} GB to move = {moe / hbm:.3f} s at "
            f"{hbm / 1e9:.0f} GB/s; full-layer keys and values (published "
            f"size) {full / 1e9:.2f} GB = {full / hbm:.3f} s")


def run(ctx) -> dict:
    """``serve_job.run``'s order (parameters, engine, probe, warm-up, the
    open loop, the streams) with this file's probe, limits and controls,
    and the program's counters beside the loop's series."""
    from deepspeed_tpu.inference import ServeEngine
    from deepspeed_tpu.parallel import build_mesh

    family, mix = ctx.family, ctx.mix
    serving = dict(ctx.cfg_file["serving"])
    if ctx.rehearse:
        serving.update(ctx.cfg_file["rehearse"]["serving"])
    lead_s = float(mix["lead_s"])
    grace_s = float(mix.get("first_token_grace_s", 0))
    devices = jax.devices()[:1]
    mesh = build_mesh(pp=1, dp=1, tp=1, devices=devices)
    params = family.make_params(ctx.seed, jnp.dtype(ctx.cfg_file["dtype"]))
    items = traffic.build_schedule(mix, ctx.seed, lead_s + ctx.seconds,
                                   family.vocab)
    if not items:
        raise ValueError("the traffic mix gave no request in the horizon")
    eng = ServeEngine(family.model,
                      {"serving": serving, "telemetry": {"enabled": False}},
                      mesh=mesh, params=params)
    series, traced = {}, []
    # one reference program for every replay, as in serve_job.run
    ref_len = min(serving["prefill_len"] + 256, serving["max_seq_len"])
    try:
        sound, controls, top, untouched = _probe(
            family, eng, params, items[:serve_job.PROBE_REQUESTS],
            serving["prefill_len"], ref_len, ctx.seed)

        # warm the engine's programs on the shapes the traffic uses: every
        # rung (the shortest and the longest prompt and one between), the
        # tick
        by_len = sorted(items, key=lambda it: len(it.prompt))
        for it in (by_len[0], by_len[len(by_len) // 2], by_len[-1]):
            eng.submit(list(it.prompt), max_new_tokens=3)
        eng.run_until_idle()
        jax.block_until_ready(eng.cache)
        eng.prefill_pad_tokens = eng.prefill_tokens = 0

        log0 = len(eng.aux_log)
        watch = _StallWatch(eng)
        try:
            with _trace_times(traced):
                result = serve_job._open_loop(ctx, eng, items, lead_s,
                                              grace_s, series)
        finally:
            watch.stop()
        say(watch.report(result["window_start"], ctx.seconds))
        _counters(ctx, eng, list(eng.aux_log)[log0:], result, traced,
                  series)
        done = [r for r in result["all_reqs"]
                if r.done.is_set() and r.error is None
                and len(r.prompt) + len(r.tokens) <= ref_len]
        done = done[:serve_job.STREAM_REQUESTS]
        streams, streams_low, positions = _streams(family, params, done,
                                                   ref_len)
        stats = devices[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        say(f"memory: {', '.join(f'{k} {v / 1e9:.3f} GB' for k, v in eng.state_bytes.items())}, "
            f"weights {eng.param_bytes / 1e9:.3f} GB, peak in use "
            f"{peak / 1e9:.3f} GB")
    finally:
        eng.close()

    sound["streams_mean"] = streams[1]
    controls["low_activations"]["streams_mean"] = streams_low[1]
    n = serve_job.PROBE_REQUESTS
    said = "; ".join(
        f"{name} {c['probe_logits']:.4f} / {c['probe_logits_mean']:.5f} / "
        f"{c['probe_state']:.3e}" for name, c in controls.items())
    say(f"probe: prefill + {eng.page_len + PROBE_MARGIN} ticks of {n} "
        f"requests (the last on an expert_bias drawn non-zero) on the "
        f"engine's own {eng.slots} slots vs the float32 reference: max "
        f"|logit diff| {sound['probe_logits']:.4f}, mean "
        f"{sound['probe_logits_mean']:.5f}, largest |logit| {top:.2f}, "
        f"tolerances {LOGIT_TOL} / {LOGIT_MEAN_TOL}; the first conv "
        f"layer's kept rows at the end, largest |diff| over largest "
        f"|reference|: {sound['probe_state']:.3e}, tolerance "
        f"{STATE_VS_REFERENCE_TOL:.1e}; the slots beside it untouched: "
        f"{untouched}; controls (max / mean / rows): {said}")
    say(f"streams: {len(done)} finished requests replayed through the "
        f"float32 reference ({positions} positions): an emitted token sits "
        f"at most {streams[0]:.4f} and on average {streams[1]:.5f} below "
        f"the reference's top logit, tolerance of the average "
        f"{STREAM_MEAN_TOL} (control, {jnp.dtype(CONTROL_ACT).name} "
        f"activations: {streams_low[0]:.4f} / {streams_low[1]:.5f})")
    checks = judge(sound)
    checks["streams_mean_within_tolerance"] &= positions > 0
    checks["probe_left_other_slots_alone"] = bool(untouched)
    checks["no_compile_in_window"] = series["compiles_in_window"] == 0
    if not ctx.rehearse:
        # the limits are of the published widths: only there must each
        # control come out as not correct, by the same judge
        for name, readings in controls.items():
            checks[f"control_{name}_not_correct"] = \
                not all(judge(readings).values())
    return {"series": series, "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": peak, "trace_dir": result["trace_dir"],
            "window_start": result["window_start"]}
