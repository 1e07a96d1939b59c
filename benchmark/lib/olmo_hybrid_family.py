"""Family ``olmo_hybrid`` (``configs/olmo-hybrid-7b.json``:
``"family_module": "lib.olmo_hybrid_family:OlmoHybrid"``) and the job that
serves it under ``serve_open_loop`` (``traffic/serve-assistant-saturated
.json``: ``"job_module": "lib.olmo_hybrid_family:run"``).

The yardsticks of this configuration's kernels are here:
``gdn_decode_bytes`` (``gdn_decode_roofline.saturated``: the float32 state
of each live slot and linear layer in and out AS PUBLISHED, 2 x 30 x 96 x
192 x 4 B, + the kernel's small operands; bandwidth bounds it; a state that
rested padded would show as lost roofline) and ``full_decode_bytes``
(``full_decode_roofline.saturated``, under MiMo's counter
``full_kv_tokens``: keys and values 128 wide on 30 key heads, + the queries
in and the outputs out; that file's ``what`` names MiMo's widths).

The job is its own ``run``, made of ``serve_job``'s parts (its open loop,
its constants) as ``lib/kimi_linear_family.py::run`` is: the probe hands
the model its ``state`` and ``slot`` and runs one request's prompt in
chunks, and the limits and controls are this configuration's.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from . import olmo_hybrid_reference, serve_job, traffic, yardstick
from .kimi_linear_family import _streams
from .nemotron_h_family import (_on_the_engines_cache, _relative,
                                _trace_times)
from .olmoe_family import _StallWatch
from .yardstick import say

# The limits of this cell's checks (``judge``).  Each lies between two
# readings taken on the chip at the published widths (PERF.md section 6,
# PR 59): the largest the program gave over its seeds, and what a control
# gives.  The controls are read in EVERY run through the same ``judge`` in
# the program's place, and the run is not correct unless each comes out as
# not correct (``run``):
#
# * the reference with ``b = sigmoid(W_b x)``, WITHOUT the factor 2 that
#   ``linear_allow_neg_eigval`` puts on the step (``step_factor`` 1),
#   against itself: its logits and first linear layer's state on the
#   probe's sequences, and the tokens it would have emitted on the streams';
# * the reference's recurrence with a bfloat16 state, one precision below
#   the float32 the configuration states for it, in the place of
#   ``ds_gdn_decode`` and the chunked form.
#
# Probe logits over prefill (one request's in two chunks, the second from
# the slot's state and the request's pages) + a page and more of decode
# ticks of PROBE_REQUESTS requests on the engine's own pools and state, a
# tick a program call, against the reference: the LARGEST |program -
# reference| and the MEAN over every position and token.  Activations and
# logits are bfloat16 and logits of random weights reach |5|, where a
# bfloat16 step is 0.031; the largest is one token's rounding through eight
# post-norms (each sublayer's output is scaled to unit RMS, so a sublayer's
# relative error is the stream's absolute one), the mean the level of the
# noise.  Over 14 runs of the finished change, a seed each (my chip runs,
# PR 59): largest, program 0.20-0.38, control 4.59-5.74; mean, program
# 0.0240-0.0260, control 0.66-0.79.  (With the chunked form's inverse as a
# product of powers, KDA's, one seed in seven read 1.14 / 0.053: PERF.md
# section 6; by halves the same seed reads 0.23 / 0.024.)
LOGIT_TOL = 1.0
LOGIT_MEAN_TOL = 0.12
# Streams: how far below the reference's top logit a token sits that the
# timed engine emitted (the only reading drawn from the window itself), on
# average over the 1,100-2,300 positions of the four finished requests
# replayed.  Program 0.0013-0.0023, control 1.19-1.80.
STREAM_MEAN_TOL = 0.03
# The first linear layer's float32 state after the prompt and the ticks
# against the reference's, largest |diff| over largest |reference|.  The
# first layer, because its input (the embedding) is the same on both sides;
# what differs is bfloat16 matmuls' rounding of q, k, v and the gates.  It
# catches a state that is stale, started from what the slot held, taken in
# past the prompt's true length, written to another slot or laid out by
# another rule than it is read by (all of order 1).  Program 2.3e-3 to
# 3.3e-3, control (half the step) 0.49-0.60.
STATE_VS_REFERENCE_TOL = 5e-2
# The state's own arithmetic, on identical inputs: ARITHMETIC_TICKS decode
# updates through ds_gdn_decode on the engine's own state, and the chunked
# form from a state that is not zero, both with steps up to 2, against the
# reference's token-by-token recurrence at full precision; largest |diff|
# over largest |reference|.  float32 on both sides: they differ by
# summation order (1.5e-7 and 2.1e-5 read); the same recurrence with a
# bfloat16 state by 2**-9 a step (8.4e-3).
STATE_TOL = 1e-4
ARITHMETIC_TICKS = 256
#: decode ticks of a probe: past a page boundary whatever the prompt's
#: length (``page_len`` + a few), forced tokens
PROBE_MARGIN = 4


def judge(readings: dict) -> dict:
    """The cell's limits on whatever readings are handed in, the program's
    or a control's in its place: check -> within its limit."""
    limits = {"probe_logits": LOGIT_TOL, "probe_logits_mean": LOGIT_MEAN_TOL,
              "probe_state": STATE_VS_REFERENCE_TOL,
              "streams_mean": STREAM_MEAN_TOL,
              "state_arithmetic": STATE_TOL}
    return {f"{k}_within_tolerance":
            bool(np.isfinite(v) and v <= limits[k])
            for k, v in readings.items()}


class OlmoHybrid:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                      OlmoHybridModel)
        fields = {f.name for f in dataclasses.fields(OlmoHybridConfig)}
        m = {k: v for k, v in cfg_file.items() if k in fields}
        if rehearse:
            m.update(cfg_file["rehearse"]["sizes"])
        self.model = OlmoHybridModel(OlmoHybridConfig(
            **m, param_dtype=cfg_file["dtype"]))
        cfg = self.model.config
        self.m = dataclasses.asdict(cfg)
        self.vocab = cfg.vocab_size
        self.gdn_layers, self.full_layers = cfg.count("gdn"), cfg.count("full")
        # one program for both members: the step's factor is traced
        self._reference = jax.jit(
            lambda p, t, n, factor: olmo_hybrid_reference.olmo_hybrid_logits(
                p, t, self.m, step_factor=factor, length=n))

    def make_params(self, seed: int, dtype):
        return serve_job._make_params(self.model, seed, dtype)

    def reference(self, params, tokens, pad_to: int):
        """One sequence padded to ``pad_to`` (causal layers keep the
        padding out of the rows before it; the recurrence stops at the
        true length) through the reference and its control, one program
        for both: (logits [2, T, V], the linear layers' states BY HEAD
        after the sequence [2, layers, H, dk, dv]) for T = ``len(tokens)``;
        member 0 is the float32 reference, member 1 the control whose step
        lacks its factor 2."""
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            got = [self._reference(params, padded, np.int32(len(tokens)),
                                   np.float32(factor))
                   for factor in (2.0, 1.0)]
        return (np.stack([np.asarray(g[0][0, :len(tokens)]) for g in got]),
                np.stack([np.asarray(g[1][0]) for g in got]))

    def gdn_decode_bytes(self, slot_layers: int) -> int:
        """HBM bytes ``ds_gdn_decode`` must move for ``slot_layers`` (live
        slot, linear layer) pairs: the float32 state in and out at its
        PUBLISHED size, and the kernel's small operands as it takes them
        (``k`` and ``q`` a column a head; ``a``, ``a b`` and ``b v`` a lane
        a value in, ``o`` out).  The convolutions, the projections and the
        gates are XLA's, not this kernel's."""
        H, dk, dv = (self.m["linear_num_key_heads"],
                     self.m["linear_key_head_dim"],
                     self.m["linear_value_head_dim"])
        return slot_layers * 4 * (2 * H * dk * dv + 2 * H * dk + 4 * H * dv)

    def full_decode_bytes(self, full_kv_tokens: int, slots: int,
                          itemsize: int) -> int:
        """HBM bytes ``ds_paged_decode_attn`` must move in a tick that
        read ``full_kv_tokens`` live keys (summed over the full layers)
        for ``slots`` live slots: every live key and value once, + a layer
        call's queries in and outputs out."""
        row = self.m["num_key_value_heads"] * 2 * self.model.config.d_head
        return itemsize * row * (full_kv_tokens + self.full_layers * slots)


def _by_head(family, rest):
    """A state at rest ``[.., dk, H dv]`` -> by head ``[.., H, dk, dv]``
    (numpy; the benchmark's own spelling of the layout, not the
    program's)."""
    rest = np.asarray(rest)
    H = family.m["linear_num_key_heads"]
    *lead, dk, lanes = rest.shape
    return np.moveaxis(rest.reshape(*lead, dk, H, lanes // H), -2, -3)


def _probe(family, eng, params, items, bucket: int, ref_len: int):
    """Prefill and a page and more of decode ticks of a few requests
    through the model's paged serving entry points, on the engine's own
    pools and request state (the cell's slots and pages; same kernels; one
    request live, in the middle slot) against the plain reference on the
    same context: logits of every step, the first linear layer's state at
    the end, and that the slots beside it keep what they held.  The LAST
    request's prompt is LONGER than the bucket and runs in two chunks, the
    second from the slot's state and the request's pages.  Returns (the
    program's readings, the control's, largest |reference logit|, whether
    the slots beside it kept what they held)."""
    model = family.model
    slots, page_len, max_pages = eng.slots, eng.page_len, eng.max_pages
    slot, n_ticks = slots // 2, page_len + PROBE_MARGIN
    active = np.zeros((slots,), bool)
    active[slot] = True

    def poison(params, cache):
        # a state that is not zero where the request lands and beside it:
        # the first chunk must not read it, and none may write it
        return (dict(cache, state={
            name: jax.lax.dynamic_update_slice_in_dim(
                leaf, jnp.full(leaf.shape[:1] + (3,) + leaf.shape[2:], 0.5,
                               leaf.dtype), slot - 1, axis=1)
            for name, leaf in cache["state"].items()}),)

    def chunk(params, cache, tokens, n, done, row):
        logits, k, v, state = model.prefill_paged(
            params, tokens, n, done, row, cache["k"], cache["v"],
            state=cache["state"], slot=np.int32(slot))
        return (dict(cache, k=k, v=v, state=state),
                jax.lax.dynamic_index_in_dim(logits[0], n - 1, 0, False))

    def tick(params, cache, token, table):
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(token)
        lg, k, v, state, lengths = model.decode_step_paged(
            params, tokens, cache["k"], cache["v"], table, cache["lengths"],
            active, state=cache["state"], impl=eng.decode_impl)
        return (dict(cache, k=k, v=v, state=state, lengths=lengths),
                lg[slot].astype(jnp.float32))

    def after(params, cache, n):
        """Sets the slot's length (``n`` before the ticks, 0 after them)
        and reads the first linear layer's state of the slot and whether
        the slots beside it hold what they held."""
        state = cache["state"]
        beside = jnp.all(jnp.stack([
            jnp.all(leaf[:, s] == 0.5)
            for leaf in state.values() for s in (slot - 1, slot + 1)]))
        lengths = jnp.zeros_like(cache["lengths"]).at[slot].set(n)
        return dict(cache, lengths=lengths), state["gdn"][0, slot], beside

    # a chunk and a tick a program each, called from the host as the
    # engine calls its own (``lib/kimi_linear_family.py::_probe`` says why)
    poison, chunk, tick, after = (_on_the_engines_cache(eng, fn)
                                  for fn in (poison, chunk, tick, after))
    longest = ref_len - n_ticks
    rng = np.random.default_rng(12345)
    keys = ("probe_logits", "probe_logits_mean", "probe_state")
    sound, control = dict.fromkeys(keys, 0.0), dict.fromkeys(keys, 0.0)
    top, untouched = 0.0, True
    for i, it in enumerate(items):
        prompt = list(it.prompt)[:bucket]
        if i == len(items) - 1:
            # longer than any prefill program: the bucket whole, then a
            # second chunk with rows of its own
            prompt = (prompt * (1 + longest // len(prompt)))[:longest - 7]
        forced = rng.integers(0, family.vocab, (n_ticks,)).astype(np.int32)
        n_pages = -(-(len(prompt) + n_ticks) // page_len)
        row = np.zeros((max_pages,), np.int32)
        row[:n_pages] = 1 + np.arange(n_pages)
        table = np.zeros((slots, max_pages), np.int32)
        table[slot] = row
        poison()
        for done in range(0, len(prompt), bucket):
            part = prompt[done:done + bucket]
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(part)] = part
            first, = chunk(padded, np.int32(len(part)), np.int32(done), row)
        after(np.int32(len(prompt)))
        got = np.stack([np.asarray(first, np.float32)] + [
            np.asarray(tick(token, table)[0]) for token in forced])
        got_state, beside = after(np.int32(0))
        # every forced token is fed by a tick: the state holds all of seq
        seq = prompt + [int(t) for t in forced]
        ref, ref_state = family.reference(params, seq, ref_len)
        want = ref[0, len(prompt) - 1:]
        top = max(top, float(np.abs(want).max()))
        untouched &= bool(beside)
        for readings, logits, first_layer in (
                (sound, np.asarray(got), _by_head(family, got_state)),
                (control, ref[1, len(prompt) - 1:], ref_state[1, 0])):
            diff = np.abs(logits - want)
            readings["probe_logits"] = max(readings["probe_logits"],
                                           float(diff.max()))
            readings["probe_logits_mean"] += float(diff.mean()) / len(items)
            readings["probe_state"] = max(
                readings["probe_state"],
                _relative(first_layer, ref_state[0, 0]))
    return sound, control, top, untouched


def _state_arithmetic(family, eng) -> dict:
    """The recurrence's own arithmetic at the published widths, on inputs
    both sides share: ARITHMETIC_TICKS updates of 4 slots (one of them
    inactive) of the LAST linear layer through ``gdn_decode`` on the
    engine's own state (the timed kernel at the cell's slots, every other
    slot inactive) and one sequence through ``gdn_chunked`` from a state
    that is not zero (the prefill's form), against the reference's
    token-by-token ``recurrence`` in float32 and, the control, with a
    bfloat16 state.  Largest |diff| over largest |reference| of the final
    states."""
    from deepspeed_tpu.ops.pallas.kda import gdn_chunked, gdn_decode
    H, dk, dv = (family.m["linear_num_key_heads"],
                 family.m["linear_key_head_dim"],
                 family.m["linear_value_head_dim"])
    T = ARITHMETIC_TICKS
    slots, layer = eng.slots, family.gdn_layers - 1
    where = np.array([0, 1, slots // 2, slots - 1])
    live = np.array([True, True, False, True])
    active = np.zeros((slots,), bool)
    active[where[live]] = True
    rng = np.random.default_rng(2059)
    f32 = np.float32

    def unit(x):
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(f32)

    # as the model makes them: unit keys, queries scaled, A in [1, 16]
    # times a step log-uniform in [0.001, 0.1], b = 2 sigmoid(.) in (0, 2)
    q = unit(rng.normal(size=(4, T, H, dk))) * f32(dk ** -0.5)
    k = unit(rng.normal(size=(4, T, H, dk)))
    v = rng.normal(0, 0.5, (4, T, H, dv)).astype(f32)
    g = (-rng.uniform(1.0, 16.0, (1, 1, H)) * np.exp(rng.uniform(
        np.log(1e-3), np.log(0.1), (4, T, H)))).astype(f32)
    b = rng.uniform(0.0, 2.0, (4, T, H)).astype(f32)
    h0 = rng.normal(0, 0.1, (4, H, dk, dv)).astype(f32)
    # by head -> at rest, spelled here and not taken from the program
    rest0 = np.moveaxis(h0, 1, 2).reshape(4, dk, H * dv)

    def spread(t):
        return jnp.zeros((slots,) + t.shape[1:], t.dtype).at[where].set(t)

    # a program a step, called from the host as the engine calls its tick:
    # the state is donated and rewritten where it lies (a scan that carried
    # all of it held a second copy, 3.4 GB, and the chip had 2.3 free)
    def seed(params, cache, rest0):
        gdn = cache["state"]["gdn"].at[layer, where].set(rest0)
        return (dict(cache, state=dict(cache["state"], gdn=gdn)),)

    def tick(params, cache, t, q, k, v, g, b):
        gdn = cache["state"]["gdn"]
        q_t, k_t, v_t, g_t, b_t = (
            spread(jax.lax.dynamic_index_in_dim(x, t, 1, keepdims=False))
            for x in (q, k, v, g, b))
        flat, _ = gdn_decode(
            gdn.reshape((-1,) + gdn.shape[2:]), jnp.exp(g_t), k_t, v_t, q_t,
            b_t, active, base=layer * slots)
        return (dict(cache, state=dict(cache["state"],
                                       gdn=flat.reshape(gdn.shape))),)

    def read(params, cache):
        # a slice a slot: a gather by index would lay the whole leaf out
        # again first (3.4 GB of temporaries, described-v5e compile)
        gdn = cache["state"]["gdn"]
        return cache, jnp.stack([gdn[layer, int(w)] for w in where])

    seed, tick, read = (_on_the_engines_cache(eng, fn)
                        for fn in (seed, tick, read))
    on_device = [jnp.asarray(x) for x in (q, k, v, g, b)]
    seed(rest0)
    for t in range(T):
        tick(np.int32(t), *on_device)
    got, = read()
    got = _by_head(family, got)
    with eng._pallas_scope():
        _, chunked = jax.jit(gdn_chunked)(q[0], k[0], v[0], g[0], b[0],
                                          h0[0])
    with jax.default_matmul_precision("highest"):
        def reference(state_dtype):
            fn = jax.jit(jax.vmap(
                lambda h, *t: olmo_hybrid_reference.recurrence(
                    *t, state_dtype=state_dtype, h0=h)[0]))
            return np.asarray(fn(h0, q, k, v, g, b))
        want, want_low = reference(jnp.float32), reference(jnp.bfloat16)
    return {"decode": _relative(got[live], want[live]),
            "chunked": _relative(chunked, want[0]),
            "bfloat16_state": _relative(want_low[live], want[live]),
            "idle_untouched": bool(np.array_equal(got[~live], h0[~live]))}


def _counters(ctx, eng, calls, res, traced, series) -> None:
    """What the program counted per call (``ServeEngine.aux_log``):
    printed for the window, and, traced, the time the state update's bytes
    and the paged kernel's need at the chip's HBM peak as percentages of
    the traced window (``gdn_`` / ``full_min_pct_of_traced_window``)."""
    fam = ctx.family
    w0, w1 = res["window_start"], res["window_start"] + ctx.seconds
    ticks = [v for t, kind, v in calls if kind == "decode" and w0 <= t < w1]
    prefills = [v for t, kind, v in calls
                if kind == "prefill" and w0 <= t < w1]
    whole = sum(1 for v in prefills if v.get("final_chunk", True))
    if ticks:
        say(f"counters: {len(ticks)} decode ticks in the window, "
            f"{np.mean([v['gdn_slot_layers'] for v in ticks]):.0f} states "
            f"rewritten a tick, "
            f"{np.mean([v['full_kv_tokens'] for v in ticks]) / fam.full_layers:.0f} "
            f"live keys a full layer a tick; {len(prefills)} prefill calls "
            f"for {whole} prompts, "
            f"{int(sum(v['gdn_chunk_tokens'] for v in prefills)) // max(fam.gdn_layers, 1)} "
            f"tokens through the chunked form")
    pad = eng.prefill_pad_tokens
    ran = eng.prefill_tokens + pad
    say(f"prefill bucket: {pad} of {ran} tokens the prefills ran were "
        f"padding ({100.0 * pad / max(ran, 1):.1f} %)")
    if len(traced) == 2 and not ctx.rehearse:
        a, b = traced
        item = jnp.dtype(ctx.cfg_file["dtype"]).itemsize
        decode = [v for t, kind, v in calls if kind == "decode" and a <= t < b]
        gdn = sum(fam.gdn_decode_bytes(round(v["gdn_slot_layers"]))
                  for v in decode)
        full = sum(fam.full_decode_bytes(
            round(v["full_kv_tokens"]),
            round(v["gdn_slot_layers"] / fam.gdn_layers), item)
            for v in decode)
        hbm = yardstick.peak(jax.devices()[0].device_kind, "hbm_bytes_per_s")
        series["gdn_min_pct_of_traced_window"] = 100.0 * gdn / hbm / (b - a)
        series["full_min_pct_of_traced_window"] = \
            100.0 * full / hbm / (b - a)
        say(f"traced {b - a:.3f} s, {len(decode)} decode ticks: delta-rule "
            f"states {gdn / 1e9:.2f} GB to move (published size) = "
            f"{gdn / hbm:.3f} s at {hbm / 1e9:.0f} GB/s; full-layer keys "
            f"and values {full / 1e9:.2f} GB = {full / hbm:.3f} s")


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
        sound, control, top, untouched = _probe(
            family, eng, params, items[:serve_job.PROBE_REQUESTS],
            serving["prefill_len"], ref_len)
        ar = _state_arithmetic(family, eng)

        # warm the engine's programs on the shapes the traffic uses: both
        # rungs (the longest prompt takes the upper one), the tick
        longest = max(items, key=lambda it: len(it.prompt))
        shortest = min(items, key=lambda it: len(it.prompt))
        for it in (items[0], shortest, longest):
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

    sound["streams_mean"], control["streams_mean"] = (streams[1],
                                                      streams_low[1])
    sound["state_arithmetic"] = max(ar["decode"], ar["chunked"])
    low_state = {"state_arithmetic": ar["bfloat16_state"]}
    n = serve_job.PROBE_REQUESTS
    say(f"probe: prefill (one of {n} longer than the bucket, in two chunks) "
        f"+ {eng.page_len + PROBE_MARGIN} ticks of {n} requests on the "
        f"engine's own {eng.slots} slots vs the float32 reference: max "
        f"|logit diff| {sound['probe_logits']:.4f}, mean "
        f"{sound['probe_logits_mean']:.5f}, largest |logit| {top:.2f}, "
        f"tolerances {LOGIT_TOL} / {LOGIT_MEAN_TOL} (control, the reference "
        f"with b = sigmoid(.) without its factor 2: "
        f"{control['probe_logits']:.4f} / "
        f"{control['probe_logits_mean']:.5f}); the first linear layer's "
        f"state at the end, largest |diff| over largest |reference|: "
        f"{sound['probe_state']:.3e}, tolerance "
        f"{STATE_VS_REFERENCE_TOL:.1e} (control "
        f"{control['probe_state']:.3e}); the slots beside it untouched: "
        f"{untouched}")
    say(f"probe, the recurrence alone on shared inputs, "
        f"{ARITHMETIC_TICKS} steps up to 2 from a state that is not zero, "
        f"largest |diff| over largest |reference|: ds_gdn_decode "
        f"{ar['decode']:.3e}, chunked form {ar['chunked']:.3e}, tolerance "
        f"{STATE_TOL:.1e} (control, the reference with a bfloat16 state: "
        f"{ar['bfloat16_state']:.3e}); an inactive slot bit for bit: "
        f"{ar['idle_untouched']}")
    say(f"streams: {len(done)} finished requests replayed through the "
        f"float32 reference ({positions} positions): an emitted token sits "
        f"at most {streams[0]:.4f} and on average {streams[1]:.5f} below "
        f"the reference's top logit, tolerance of the average "
        f"{STREAM_MEAN_TOL} (control: {streams_low[0]:.4f} / "
        f"{streams_low[1]:.5f})")
    checks = judge(sound)
    checks["streams_mean_within_tolerance"] &= positions > 0
    checks["probe_left_other_slots_alone"] = bool(
        untouched and ar["idle_untouched"])
    checks["no_compile_in_window"] = series["compiles_in_window"] == 0
    if not ctx.rehearse:
        # the limits are of the published widths: only there must each
        # control come out as not correct, by the same judge
        checks["control_step_without_factor_2_not_correct"] = \
            not all(judge(control).values())
        checks["control_bfloat16_state_not_correct"] = \
            not all(judge(low_state).values())
    return {"series": series, "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": peak, "trace_dir": result["trace_dir"],
            "window_start": result["window_start"]}
