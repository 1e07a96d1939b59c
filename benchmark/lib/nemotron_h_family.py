"""Family ``nemotron_h`` (``configs/nemotron-3-super-120b-a12b.json``:
``"family_module": "lib.nemotron_h_family:NemotronH"``) and the job that
serves it under ``serve_open_loop`` (``traffic/serve-reasoning-saturated
.json``: ``"job_module": "lib.nemotron_h_family:run"``).

The yardsticks of this configuration's kernels are here:
``expert_kernel_bytes`` (``moe_expert_roofline.saturated``) and
``ssm_decode_bytes`` (``ssm_decode_roofline.saturated``).

Notes for a reader of the metric files this cell shares with OLMoE (they
are not edited): ``moe_expert_share`` / ``moe_expert_roofline`` say "two
Mosaic kernels (ds_moe_gate_up, ds_moe_down)" and "three matrices"; their
readers match ``^ds_moe_``, and for this configuration that is
``ds_moe_up_relu2`` and ``ds_moe_down``, TWO matrices an expert (no gate),
at the latent width.  ``moe_experts_hit`` is of the experts HELD here
(128 x 5 layers), not of the 512 the router ranges over.

The job is its own ``run``, made of ``serve_job``'s parts (its open loop,
its parameters, its constants) and replacing none of them: ``serve_job
.run`` cannot serve this model, whose probe must hand the model its
``state`` and ``slot``, whose weights need the selection bias a trained
router has, and whose limits are its own.  What it repeats of ``serve_job
.run`` (some 40 lines) goes when a ``benchmark`` PR gives that function
hooks (PERF.md section 7, edit 5).
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

import jax
import jax.numpy as jnp

from . import nemotron_h_reference, serve_job, traffic, yardstick
from .olmoe_family import _StallWatch
from .yardstick import say

# what the model's config takes from the file: every field of
# NemotronHConfig the file has, but for the module the file states as
# published and the model does not build (``not_run``)
_NOT_RUN = ("num_nextn_predict_layers",)

# The limits of this cell's checks (``judge``).  Each lies between two
# readings taken on the chip at the published widths (PERF.md section 6,
# PR 34): the largest the program gave over its seeds, and what a
# precision below gives.  The readings below are taken in EVERY run, put
# through the same ``judge`` in the program's place, and the run is not
# correct unless each control comes out as not correct (``run``):
#
# * CONTROL_ACT: the reference with its residual stream rounded to float8
#   (e5m2) from the embedding on and after every layer, one precision
#   below the bfloat16 the configuration states, against itself in
#   float32: its logits and first mixer's state on the probe's sequences,
#   and the tokens it would have emitted on the streams';
# * the reference's recurrence with a bfloat16 state, in the place of
#   ``ds_ssm_decode`` and the chunked scan.
CONTROL_ACT = jnp.float8_e5m2
# Probe logits, max |program - reference|.  serve_job's 0.125 was set on
# GPT-2 XL (0.03-0.06 there).  Activations and logits are bfloat16 and
# logits of random weights reach |7|, where a bfloat16 step is 0.031; what
# is new here is the router: it picks 22 of 512 sigmoid scores whose 22nd
# and 23rd lie ~1e-3 apart, so the bfloat16 rounding of its input swaps a
# choice in most tokens of most layers, and a swapped expert moves that
# layer's output by several per cent where the float32 reference, on its
# float32 input, did not swap.  Program 0.10-0.45 over 34 seeds; the
# control 1.46-1.69 over 10.
LOGIT_TOL = 0.75
# Streams: how far below the reference's top logit a token sits that the
# timed engine emitted (the only reading drawn from the window itself).
# Program 0.12-0.25 over 34 seeds; the control 1.19-1.74 over 10.
STREAM_TOL = 0.5
# The first mixer's float32 state after the prompt and PROBE_TICKS decode
# ticks against the reference's, largest |diff| over largest |reference|.
# The first mixer, because its input (the embedding) is the same on both
# sides; what differs is one bfloat16 matmul's rounding of xBC and dt
# (program 4.9e-3 to 1.3e-2; the control 7.4e-2 to 1.2e-1).  It catches a state
# that is stale, taken in past the prompt's true length or written to
# another slot (all of order 1), not the state's own arithmetic: a
# bfloat16 state reads 3e-3 to 8e-3 here, BELOW the rounding of the
# inputs.
STATE_VS_REFERENCE_TOL = 3e-2
# The state's own arithmetic, on identical inputs: ARITHMETIC_TICKS decode
# updates through ds_ssm_decode on the engine's own state, and the chunked
# prefill scan, against the reference's token-by-token recurrence at full
# precision; largest |diff| over largest |reference|.  float32 kernels
# differ from it by summation order (0 and 2.8e-5 read); the same
# recurrence with a bfloat16 state by a random walk of 2**-9 a step
# (1.3e-2).
STATE_TOL = 1e-4
ARITHMETIC_TICKS = 256
#: the batch the router's bias is balanced on, [sequences, tokens]
BALANCE_TOKENS = (4, 256)


def judge(readings: dict) -> dict:
    """The cell's limits on whatever readings are handed in, the program's
    or a control's in its place: check -> within its limit."""
    limits = {"probe_logits": LOGIT_TOL, "streams": STREAM_TOL,
              "probe_state": STATE_VS_REFERENCE_TOL,
              "state_arithmetic": STATE_TOL}
    return {f"{k}_within_tolerance":
            bool(np.isfinite(v) and v <= limits[k])
            for k, v in readings.items()}


class NemotronH:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                                     NemotronHModel)
        import dataclasses
        fields = {f.name for f in dataclasses.fields(NemotronHConfig)}
        m = {k: v for k, v in cfg_file.items()
             if k in fields and k not in _NOT_RUN}
        # in the file n_routed_experts counts the experts HELD here; the
        # router's width is the published count
        m["n_routed_experts"] = cfg_file["published"]["n_routed_experts"]
        held = tuple(cfg_file["experts_held"])
        if rehearse:
            sizes = dict(cfg_file["rehearse"]["sizes"])
            held = tuple(sizes.pop("experts_held"))
            m.update(sizes)
        self.m = m = {**m, "experts_held": held}
        self.model = NemotronHModel(NemotronHConfig(
            **m, param_dtype=cfg_file["dtype"]))
        self.vocab = m["vocab_size"]
        self.pattern = m["hybrid_override_pattern"]
        self._reference = jax.jit(lambda p, t, n: jax.vmap(
            lambda low: nemotron_h_reference.nemotron_h_logits(
                p, t, self.m, act_dtype=CONTROL_ACT, length=n,
                round_acts=low))(jnp.array([False, True])))

    def make_params(self, seed: int, dtype):
        """``serve_job._make_params``, then the selection bias a trained
        router has, for weights drawn from a seed: the source's own load
        balancing, run by the REFERENCE on tokens drawn from the seed
        (``nemotron_h_reference.balance_router_bias``; the configuration's
        ``assumed.e_score_correction_bias``)."""
        params = serve_job._make_params(self.model, seed, dtype)
        tokens = np.random.default_rng([int(seed), 3]).integers(
            0, self.vocab, BALANCE_TOKENS).astype(np.int32)
        with jax.default_matmul_precision("highest"):
            bias = jax.jit(
                lambda p, t: nemotron_h_reference.balance_router_bias(
                    p, t, self.m))(params, tokens)
        return dict(params, moe=dict(params["moe"], router_bias=bias))

    def reference(self, params, tokens, pad_to: int):
        """One sequence padded to ``pad_to`` (the causal mixers and
        attention keep the padding out of the rows before it) through the
        reference, in one program for every call: (logits [2, T, V], the
        mixers' states after the sequence [2, Lm, H, P, N]) for T =
        ``len(tokens)``; member 0 is the float32 reference, member 1 the
        control with CONTROL_ACT activations."""
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            logits, states = self._reference(params, padded,
                                             np.int32(len(tokens)))
        return logits[:, 0, :len(tokens)], states[:, :, 0]

    def expert_kernel_bytes(self, experts_hit: int, rows: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_moe_up_relu2`` + ``ds_moe_down`` must move for
        ``rows`` (token, held expert) assignments over ``experts_hit``
        held experts (both summed over layers): each hit expert's TWO
        matrices once; per row, u in and h out (up), h in and r out
        (down)."""
        lat, f = self.m["moe_latent_size"], self.m["moe_intermediate_size"]
        return itemsize * (experts_hit * 2 * lat * f
                           + rows * 2 * (lat + f))

    def ssm_decode_bytes(self, slot_layers: int) -> int:
        """HBM bytes ``ds_ssm_decode`` must move for ``slot_layers``
        (active slot, mixer layer) pairs: the float32 state in and out,
        and the kernel's small operands as it takes them (the decay
        broadcast over the state's lanes, dt x, B, C, and y out).  The
        conv window and the projections are XLA's, not this kernel's."""
        H, P = self.m["mamba_num_heads"], self.m["mamba_head_dim"]
        G, N = self.m["n_groups"], self.m["ssm_state_size"]
        return slot_layers * 4 * (2 * H * P * N + H * N + 2 * P * H
                                  + 2 * G * N)


def _relative(got, want) -> float:
    """Largest |got - want| over largest |want|."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _on_the_engines_cache(eng, fn):
    """``fn(params, cache, *operands) -> (cache, *results)`` as a program
    that takes the engine's own cache (pool and request state, at the
    cell's slots and pages) donated, as the engine's programs do, and
    hands it back where it lay."""
    at = jax.tree.map(lambda a: a.sharding, eng.cache)

    def program(*operands):
        cache, *results = fn(*operands)
        return cache, tuple(results)

    jitted = jax.jit(program, donate_argnums=(1,), out_shardings=(at, None))

    def call(*operands):
        with eng._pallas_scope():
            eng.cache, results = jitted(eng.params, eng.cache, *operands)
        return results

    return call


def _probe(family, eng, params, items, bucket: int, ref_len: int):
    """``serve_job._probe`` for a model with request state: prefill and
    PROBE_TICKS decode ticks of a few requests through the model's paged
    serving entry points, on the engine's own pool and request state (the
    cell's slots and pages; same kernels and decode arm; one request live,
    in the middle slot) against the plain reference on the same context:
    logits of every step, the first mixer's recurrent state at the end,
    and that the slots beside it keep what they held.  Returns (the
    program's readings, the control's, largest |reference logit|, whether
    the slots beside it kept what they held)."""
    model = family.model
    slots, page_len, max_pages = eng.slots, eng.page_len, eng.max_pages
    slot, ticks = slots // 2, serve_job.PROBE_TICKS
    active = np.zeros((slots,), bool)
    active[slot] = True

    def run(params, cache, prompt, n, forced, row, table):
        # a state that is not zero where the request lands and beside it:
        # the prefill must overwrite it, not add to it
        state = {name: jax.lax.dynamic_update_slice_in_dim(
            leaf, jnp.full(leaf.shape[:1] + (3,) + leaf.shape[2:], 0.5,
                           leaf.dtype), slot - 1, axis=1)
            for name, leaf in cache["state"].items()}
        logits, k, v, state = model.prefill_paged(
            params, prompt, n, np.int32(0), row, cache["k"], cache["v"],
            state=state, slot=np.int32(slot))

        def tick(carry, token):
            k, v, state, lengths = carry
            tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(token)
            lg, k, v, state, lengths = model.decode_step_paged(
                params, tokens, k, v, table, lengths, active, state=state,
                impl=eng.decode_impl)
            return (k, v, state, lengths), lg[slot]

        lengths = jnp.zeros_like(cache["lengths"]).at[slot].set(n)
        (k, v, state, _), rest = jax.lax.scan(
            tick, (k, v, state, lengths), forced)
        first = jax.lax.dynamic_index_in_dim(logits[0], n - 1, 0, False)
        beside = jnp.all(jnp.stack([
            jnp.all(leaf[:, s] == 0.5)
            for leaf in state.values() for s in (slot - 1, slot + 1)]))
        return (dict(cache, k=k, v=v, state=state),
                jnp.concatenate([first[None], rest]).astype(jnp.float32),
                state["ssm"][0, slot], beside)

    run = _on_the_engines_cache(eng, run)
    rng = np.random.default_rng(12345)
    sound = {"probe_logits": 0.0, "probe_state": 0.0}
    control = dict(sound)
    top, untouched = 0.0, True
    for it in items:
        prompt = list(it.prompt)[:bucket]
        forced = rng.integers(0, family.vocab, (ticks,)).astype(np.int32)
        n_pages = -(-(len(prompt) + ticks) // page_len)
        row = np.zeros((max_pages,), np.int32)
        row[:n_pages] = 1 + np.arange(n_pages)
        table = np.zeros((slots, max_pages), np.int32)
        table[slot] = row
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        got, got_state, beside = run(padded, np.int32(len(prompt)), forced,
                                     row, table)
        # every forced token is fed by a tick: the state holds all of seq
        seq = prompt + [int(t) for t in forced]
        ref, ref_state = (np.asarray(x) for x in family.reference(
            params, seq, ref_len))
        want = ref[0, len(prompt) - 1:]
        top = max(top, float(np.abs(want).max()))
        untouched &= bool(beside)
        for readings, logits, first_mixer in (
                (sound, np.asarray(got), got_state),
                (control, ref[1, len(prompt) - 1:], ref_state[1, 0])):
            readings["probe_logits"] = max(
                readings["probe_logits"],
                float(np.abs(logits - want).max()))
            readings["probe_state"] = max(
                readings["probe_state"],
                _relative(first_mixer, ref_state[0, 0]))
    return sound, control, top, untouched


def _state_arithmetic(family, eng) -> dict:
    """The recurrence's own arithmetic at the published widths, on inputs
    both sides share: ARITHMETIC_TICKS updates of 4 slots (one of them
    inactive) of the LAST mixer through ``ssm_decode`` on the engine's own
    state (the timed kernel at the cell's slots, every other slot
    inactive) and one sequence through ``ssd_chunked`` (the prefill's
    scan), against the reference's token-by-token ``recurrence`` in
    float32 and, the control, with a bfloat16 state.  Largest |diff| over
    largest |reference| of the final states."""
    from deepspeed_tpu.ops.pallas.ssm import ssd_chunked, ssm_decode
    m = family.m
    H, P = m["mamba_num_heads"], m["mamba_head_dim"]
    G, N = m["n_groups"], m["ssm_state_size"]
    T = ARITHMETIC_TICKS
    T -= T % m["chunk_size"] if T > m["chunk_size"] else 0
    slots, layer = eng.slots, family.pattern.count("M") - 1
    where = np.array([0, 1, slots // 2, slots - 1])
    live = np.array([True, True, False, True])
    active = np.zeros((slots,), bool)
    active[where[live]] = True
    rng = np.random.default_rng(2034)
    f32 = np.float32
    # as the model draws them: A in [1, 16], dt log-uniform in its range
    a = -rng.uniform(1.0, 16.0, (H,)).astype(f32)
    dt = np.exp(rng.uniform(np.log(m["time_step_min"]),
                            np.log(m["time_step_max"]),
                            (4, T, H))).astype(f32)
    xs = rng.normal(0, 0.5, (4, T, H, P)).astype(f32)
    bm, cm = (rng.normal(0, 0.5, (4, T, G, N)).astype(f32) for _ in "bc")
    h0 = rng.normal(0, 0.1, (4, H, P, N)).astype(f32)

    def decode(params, cache, h0, xs, dt, bm, cm):
        ssm = cache["state"]["ssm"].at[layer, where].set(h0)
        flat = ssm.reshape((-1,) + ssm.shape[2:])

        def spread(t):
            return jnp.zeros((slots,) + t.shape[1:], t.dtype).at[
                where].set(t)

        def tick(flat, step):
            x_t, dt_t, b_t, c_t = step
            flat, _ = ssm_decode(
                flat, spread(jnp.exp(dt_t * a)),
                spread(dt_t[..., None] * x_t), spread(b_t), spread(c_t),
                active, base=layer * slots)
            return flat, None

        flat, _ = jax.lax.scan(tick, flat, tuple(
            jnp.moveaxis(t, 1, 0) for t in (xs, dt, bm, cm)))
        ssm = flat.reshape(ssm.shape)
        return (dict(cache, state=dict(cache["state"], ssm=ssm)),
                ssm[layer, where])

    got, = _on_the_engines_cache(eng, decode)(h0, xs, dt, bm, cm)
    got = np.asarray(got)
    with eng._pallas_scope():
        _, chunked = jax.jit(lambda *t: ssd_chunked(
            *t, min(m["chunk_size"], T)))(xs[0], dt[0], a, bm[0], cm[0])
    with jax.default_matmul_precision("highest"):
        def reference(state_dtype):
            fn = jax.jit(lambda h, *t: nemotron_h_reference.recurrence(
                *t, state_dtype=state_dtype, h0=h)[0])
            return lambda h: np.asarray(fn(h, xs, dt, a, bm, cm))
        ref = reference(jnp.float32)
        want, want_low = ref(h0), reference(jnp.bfloat16)(h0)
        from_zero = ref(np.zeros_like(h0))[0]
    return {"decode": _relative(got[live], want[live]),
            "chunked": _relative(chunked, from_zero),
            "bfloat16_state": _relative(want_low[live], want[live]),
            "idle_untouched": bool(np.array_equal(got[~live], h0[~live]))}


def _streams(family, params, reqs, ref_len: int):
    """``serve_job._stream_slack`` with the control beside it: how far
    below the reference's top logit a token sits, at most, over whole
    finished streams of the timed engine (teacher-forced on the engine's
    own tokens) for the tokens the engine emitted and, the control, for
    those the CONTROL_ACT reference would have.  Returns (slack, the
    control's, positions)."""
    slack, control, positions = 0.0, 0.0, 0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        ref = np.asarray(family.reference(params, seq[:-1], ref_len)[0])
        rows, low = ref[:, len(r.prompt) - 1:]
        at = np.arange(len(r.tokens))
        below = rows.max(axis=1)[:, None] - rows
        slack = max(slack, float(below[at, r.tokens].max()))
        control = max(control, float(below[at, low.argmax(axis=1)].max()))
        positions += len(r.tokens)
    return slack, control, positions


@contextlib.contextmanager
def _trace_times(traced: list):
    """Notes when the profiler's window opened and closed (the open loop
    starts and stops it): the window opens after ``start_trace`` returns
    and closes before ``stop_trace`` is called."""
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def started(*a, **k):
        out = start(*a, **k)
        traced.append(time.perf_counter())
        return out

    def stopping(*a, **k):
        traced.append(time.perf_counter())
        return stop(*a, **k)

    jax.profiler.start_trace, jax.profiler.stop_trace = started, stopping
    try:
        yield
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop


def _counters(ctx, eng, calls, res, traced, series) -> None:
    """What the program counted per call (``ServeEngine.aux_log``) into
    ``series``: ``moe_experts_hit_pct`` (of the held experts x ``E``
    layers), ``moe_load_imbalance`` and ``moe_rows_elsewhere_pct`` per
    decode tick of the window and, traced, ``moe_min_pct_of_traced_window``
    and ``ssm_min_pct_of_traced_window``: the time the expert kernels' and
    the state update's bytes need at the chip's HBM peak, as a percentage
    of the traced window."""
    fam = ctx.family
    w0, w1 = res["window_start"], res["window_start"] + ctx.seconds
    e_layers, m_layers = fam.pattern.count("E"), fam.pattern.count("M")
    held = fam.m["experts_held"][1] * e_layers
    per_slot = fam.m["num_experts_per_tok"] * e_layers
    ticks = [v for t, kind, v in calls if kind == "decode" and w0 <= t < w1]
    series["moe_experts_hit_pct"] = [
        100.0 * v["moe_experts_hit"] / held for v in ticks]
    series["moe_load_imbalance"] = [v["moe_load_imbalance"] for v in ticks]
    series["moe_rows_elsewhere_pct"] = [
        100.0 * v["moe_rows_elsewhere"]
        / max(v["moe_rows"] + v["moe_rows_elsewhere"], 1) for v in ticks]
    if ticks:
        say(f"experts: {len(ticks)} decode ticks in the window, hit "
            f"{np.mean(series['moe_experts_hit_pct']):.2f} % of the "
            f"{held} held a tick, busiest over mean "
            f"{np.mean(series['moe_load_imbalance']):.2f}, "
            f"{np.mean(series['moe_rows_elsewhere_pct']):.2f} % of the "
            "assignments to experts held elsewhere")
    if len(traced) == 2 and not ctx.rehearse:
        a, b = traced
        item = jnp.dtype(ctx.cfg_file["dtype"]).itemsize
        in_trace = [(kind, v) for t, kind, v in calls if a <= t < b]
        moe = sum(fam.expert_kernel_bytes(
            v["moe_experts_hit"], v["moe_rows"], item) for _, v in in_trace)
        # a tick routes per_slot assignments for every active slot
        ssm = sum(fam.ssm_decode_bytes(round(
            (v["moe_rows"] + v["moe_rows_elsewhere"]) / per_slot)
            * m_layers) for kind, v in in_trace if kind == "decode")
        peak = yardstick.peak(jax.devices()[0].device_kind,
                              "hbm_bytes_per_s")
        series["moe_min_pct_of_traced_window"] = \
            100.0 * moe / peak / (b - a)
        series["ssm_min_pct_of_traced_window"] = \
            100.0 * ssm / peak / (b - a)
        say(f"traced {b - a:.3f} s: experts {moe / 1e9:.2f} GB, state "
            f"update {ssm / 1e9:.2f} GB to move, {moe / peak:.3f} s and "
            f"{ssm / peak:.3f} s at {peak / 1e9:.0f} GB/s")


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

        # warm both programs of the engine on the shapes the traffic uses
        for it in items[:2]:
            eng.submit(list(it.prompt), max_new_tokens=3)
        eng.run_until_idle()
        jax.block_until_ready(eng.cache)

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
        sound["streams"], control["streams"], positions = _streams(
            family, params, done, ref_len)
        stats = devices[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
    finally:
        eng.close()

    sound["state_arithmetic"] = max(ar["decode"], ar["chunked"])
    low_state = {"state_arithmetic": ar["bfloat16_state"]}
    n = serve_job.PROBE_REQUESTS
    say(f"probe: prefill + {serve_job.PROBE_TICKS} ticks of {n} requests "
        f"on the engine's own {eng.slots} slots vs the float32 reference: "
        f"max |logit diff| {sound['probe_logits']:.4f}, largest |logit| "
        f"{top:.2f}, tolerance {LOGIT_TOL} (control, the reference with "
        f"{jnp.dtype(CONTROL_ACT).name} activations: "
        f"{control['probe_logits']:.4f}); the first mixer's recurrent "
        f"state at the end, largest |diff| over largest |reference|: "
        f"{sound['probe_state']:.3e}, tolerance "
        f"{STATE_VS_REFERENCE_TOL:.1e} (control {control['probe_state']:.3e}"
        f"); the slots beside it untouched: {untouched}")
    say(f"probe, the recurrence alone on shared inputs, "
        f"{ARITHMETIC_TICKS} steps, largest |diff| over largest "
        f"|reference|: ds_ssm_decode {ar['decode']:.3e}, chunked scan "
        f"{ar['chunked']:.3e}, tolerance {STATE_TOL:.1e} (control, the "
        f"reference with a bfloat16 state: {ar['bfloat16_state']:.3e}); an "
        f"inactive slot bit for bit: {ar['idle_untouched']}")
    say(f"streams: {len(done)} finished requests replayed through the "
        f"float32 reference ({positions} positions): an emitted token sits "
        f"at most {sound['streams']:.4f} below the reference's top logit, "
        f"tolerance {STREAM_TOL} (control: {control['streams']:.4f})")
    checks = judge(sound)
    checks["streams_within_tolerance"] &= positions > 0
    checks["probe_left_other_slots_alone"] = bool(
        untouched and ar["idle_untouched"])
    checks["no_compile_in_window"] = series["compiles_in_window"] == 0
    if not ctx.rehearse:
        # the limits are of the published widths: only there must each
        # control come out as not correct, by the same judge
        checks["control_low_activations_not_correct"] = \
            not all(judge(control).values())
        checks["control_bfloat16_state_not_correct"] = \
            not all(judge(low_state).values())
    return {"series": series, "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": peak, "trace_dir": result["trace_dir"],
            "window_start": result["window_start"]}
