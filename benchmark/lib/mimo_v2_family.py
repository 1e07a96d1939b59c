"""Family ``mimo_v2`` (``configs/mimo-v2.5.json``: ``"family_module":
"lib.mimo_v2_family:MimoV2"``) and the job that serves it under
``serve_open_loop`` (``traffic/serve-agentic-saturated.json``:
``"job_module": "lib.mimo_v2_family:run"``).

The yardsticks of this configuration's kernels are here:
``expert_kernel_bytes`` (``moe_expert_roofline.saturated``),
``window_decode_bytes`` (``window_decode_roofline.saturated``) and
``full_decode_bytes`` (``full_decode_roofline.saturated``).  The attention
yardsticks count the PUBLISHED bytes (keys 192 wide): what the program
stores wider than that shows as lost roofline.

Notes for a reader of the metric files this cell shares (they are not
edited): ``moe_experts_hit`` is of the experts HELD here (16 x 6 layers),
not of the 256 the router ranges over; ``moe_rows_elsewhere`` is about
15/16 here (16 of 256 held).

The job is its own ``run``, made of ``serve_job``'s parts (its open loop,
its constants) as ``lib/nemotron_h_family.py::run`` is and for the same
reason: the probe must hand the model its ``state`` and ``slot``, and the
limits and controls are this configuration's.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from . import mimo_v2_reference, serve_job, traffic, yardstick
from .nemotron_h_family import _on_the_engines_cache, _trace_times
from .olmoe_family import _StallWatch
from .yardstick import say

# The limits of this cell's checks (``judge``).  Each lies between two
# readings taken on the chip at the published widths (PERF.md section 6,
# PR 36): the largest the program gave over its seeds, and what a control
# gives.  The controls are read in EVERY run through the same ``judge`` in
# the program's place, and the run is not correct unless each comes out as
# not correct (``run``):
#
# * A, CONTROL_ACT: the reference with its residual stream rounded to
#   float8 (e5m2) from the embedding on and after every layer, one precision
#   below the bfloat16 the configuration states;
# * B: the reference WITHOUT the window layers' sink;
# * C: the reference with a window of CONTROL_WINDOW keys for 128.
CONTROL_ACT = jnp.float8_e5m2
CONTROL_WINDOW = 256
#: the members of one reference call, in order: (round the residual
#: stream, the sink is on, the window's keys or 0 for the configuration's)
MEMBERS = {"reference": (False, True, 0), "A": (True, True, 0),
           "B": (False, False, 0), "C": (False, True, CONTROL_WINDOW)}
# Probe logits, max |program - reference| over prefill + a page and more of
# decode ticks of PROBE_REQUESTS requests on the engine's own pool and
# rings.  Activations and logits are bfloat16 and logits of random weights
# reach |6.5|, where a bfloat16 step is 0.031; the router's 8th and 9th of
# 256 sigmoid scores lie close, so bfloat16 swaps a choice here and there
# where the float32 reference did not.  Program 0.31-0.44 over 7 seeds (my
# chip runs, PR 36); A 1.57-1.80, B 1.02-1.15, C 1.39-1.60; the program is
# 0.92-1.07 from B, its nearest control.
LOGIT_TOL = 0.7
# Streams: how far below the reference's top logit a token sits that the
# timed engine emitted (the only reading drawn from the window itself).
# Program 0.10-0.31 over 7 seeds; A 1.42-1.65, B 0.74-1.21, C 1.08-1.63.
STREAM_TOL = 0.55
#: decode ticks of a probe: past a page boundary whatever the prompt's
#: length (``page_len`` + a few), forced tokens
PROBE_MARGIN = 4


def judge(readings: dict) -> dict:
    """The cell's limits on whatever readings are handed in, the program's
    or a control's in its place: check -> within its limit."""
    limits = {"probe_logits": LOGIT_TOL, "streams": STREAM_TOL}
    return {f"{k}_within_tolerance":
            bool(np.isfinite(v) and v <= limits[k])
            for k, v in readings.items()}


class MimoV2:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.mimo_v2 import MimoV2Config, MimoV2Model
        fields = {f.name for f in dataclasses.fields(MimoV2Config)}
        m = {k: v for k, v in cfg_file.items() if k in fields}
        # in the file n_routed_experts counts the experts HELD here; the
        # router's width is the published count
        m["n_routed_experts"] = cfg_file["published"]["n_routed_experts"]
        held = tuple(cfg_file["experts_held"])
        if rehearse:
            sizes = dict(cfg_file["rehearse"]["sizes"])
            held = tuple(sizes.pop("experts_held"))
            m.update(sizes)
        self.m = m = {**m, "experts_held": held}
        self.model = MimoV2Model(MimoV2Config(
            **m, param_dtype=cfg_file["dtype"]))
        self.vocab = m["vocab_size"]
        self.layers = self.model.serving_cache_layers()
        self.moe_layers = sum(m["moe_layer_freq"])
        # one program for every member and every call: the switches are
        # traced.  A member a call, not a loop over members inside one
        # program: XLA hoists the float32 copies of every weight out of
        # such a loop, 6.4 GB at the published widths
        self._reference = jax.jit(
            lambda p, t, s: mimo_v2_reference.mimo_v2_logits(
                p, t, self.m, act_dtype=CONTROL_ACT, round_acts=s[0] > 0,
                sink_on=s[1] > 0, block=128,
                window=jnp.where(s[2] > 0, s[2], m["sliding_window"]))[0])

    def make_params(self, seed: int, dtype):
        return serve_job._make_params(self.model, seed, dtype)

    def reference(self, params, tokens, pad_to: int) -> dict:
        """One sequence padded to ``pad_to`` (causal layers keep the
        padding out of the rows before it) through the reference and its
        controls, one program for every call: member -> float32 logits
        [T, V] for T = ``len(tokens)``."""
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            return {name: np.asarray(self._reference(
                params, padded, np.asarray(switches, np.int32)))[:len(tokens)]
                for name, switches in MEMBERS.items()}

    def expert_kernel_bytes(self, experts_hit: int, rows: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_moe_gate_up`` + ``ds_moe_down`` must move for
        ``rows`` (token, held expert) assignments over ``experts_hit``
        held experts (both summed over layers): each hit expert's three
        matrices once; per row, x in and h out (gate_up), h in and y out
        (down)."""
        d, f = self.m["hidden_size"], self.m["moe_intermediate_size"]
        return itemsize * (experts_hit * 3 * d * f + rows * 2 * (d + f))

    def _row_bytes(self) -> int:
        """A key and a value of one key head, as published."""
        return self.m["head_dim"] + self.m["v_head_dim"]

    def _query_bytes(self, slots: int) -> int:
        """The queries in and the outputs out, a layer call."""
        return slots * self.m["num_attention_heads"] * self._row_bytes()

    def window_decode_bytes(self, window_kv_rows: int, slots: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_window_decode_attn`` must move in a decode tick
        whose active slots hold ``window_kv_rows`` ring rows a window
        layer: every live row of every key head and window layer, + the
        queries and outputs."""
        heads = self.m["swa_num_key_value_heads"]
        return itemsize * self.layers["window"] * (
            window_kv_rows * heads * self._row_bytes()
            + self._query_bytes(slots))

    def full_decode_bytes(self, full_kv_tokens: int, slots: int,
                          itemsize: int) -> int:
        """The same for ``ds_paged_decode_attn``: ``full_kv_tokens`` keys
        a full layer over the active slots."""
        heads = self.m["num_key_value_heads"]
        return itemsize * self.layers["full"] * (
            full_kv_tokens * heads * self._row_bytes()
            + self._query_bytes(slots))


def _probe(family, eng, params, items, bucket: int, ref_len: int):
    """Prefill and a page and more of decode ticks of a few requests
    through the model's paged serving entry points, on the engine's own
    pool and window state (the cell's slots and pages; same kernels; one
    request live, in the middle slot) against the plain reference on the
    same context: logits of every step, and that the rings of the slots
    beside it keep what they held.  Every context is longer than the
    window and a page, the ticks cross a page boundary, and with a
    published prompt (512 or more) the ring has wrapped four times.
    Returns (member -> max |logit diff| to the reference, with the
    program's under ``"program"``; control -> the program's max |logit
    diff| to THAT control, smallest over the requests: a program that is
    one of the controls sits on it; largest |reference logit|; whether
    the slots beside it kept what they held)."""
    model = family.model
    slots, page_len, max_pages = eng.slots, eng.page_len, eng.max_pages
    slot, ticks = slots // 2, page_len + PROBE_MARGIN
    active = np.zeros((slots,), bool)
    active[slot] = True

    def run(params, cache, prompt, n, forced, row, table):
        # rings that are not zero where the request lands and beside it:
        # the prefill must overwrite its own and leave the others
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
                beside)

    run = _on_the_engines_cache(eng, run)
    rng = np.random.default_rng(12345)
    worst = dict.fromkeys(["program", "A", "B", "C"], 0.0)
    nearest = dict.fromkeys("ABC", np.inf)
    top, untouched = 0.0, True
    for it in items:
        prompt = list(it.prompt)[:min(bucket, ref_len - ticks)]
        forced = rng.integers(0, family.vocab, (ticks,)).astype(np.int32)
        n_pages = -(-(len(prompt) + ticks) // page_len)
        row = np.zeros((max_pages,), np.int32)
        row[:n_pages] = 1 + np.arange(n_pages)
        table = np.zeros((slots, max_pages), np.int32)
        table[slot] = row
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        got, beside = run(padded, np.int32(len(prompt)), forced, row, table)
        seq = prompt + [int(t) for t in forced]
        ref = family.reference(params, seq, ref_len)
        ref["program"] = np.asarray(got)
        want = ref["reference"][len(prompt) - 1:]
        top = max(top, float(np.abs(want).max()))
        untouched &= bool(beside)
        for name in worst:
            rows = ref[name] if name == "program" \
                else ref[name][len(prompt) - 1:]
            worst[name] = max(worst[name],
                              float(np.abs(rows - want).max()))
        for c in nearest:
            nearest[c] = min(nearest[c], float(np.abs(
                ref["program"] - ref[c][len(prompt) - 1:]).max()))
    return worst, nearest, top, untouched


def _streams(family, params, reqs, ref_len: int):
    """``serve_job._stream_slack`` with the controls beside it: how far
    below the reference's top logit a token sits, at most, over whole
    finished streams of the timed engine (teacher-forced on the engine's
    own tokens) for the tokens the engine emitted and, each control, for
    those it would have.  Returns (name -> slack, positions)."""
    slack = dict.fromkeys(["program", "A", "B", "C"], 0.0)
    positions = 0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        ref = family.reference(params, seq[:-1], ref_len)
        rows = ref["reference"][len(r.prompt) - 1:]
        at = np.arange(len(r.tokens))
        below = rows.max(axis=1)[:, None] - rows
        picks = {"program": np.asarray(r.tokens)}
        picks.update({c: ref[c][len(r.prompt) - 1:].argmax(axis=1)
                      for c in "ABC"})
        for name, tokens in picks.items():
            slack[name] = max(slack[name], float(below[at, tokens].max()))
        positions += len(r.tokens)
    return slack, positions


def _counters(ctx, eng, calls, res, traced, series) -> None:
    """What the program counted per call (``ServeEngine.aux_log``) into
    ``series``: the expert layers' per decode tick of the window
    (``moe_experts_hit_pct`` of the held experts x layers,
    ``moe_load_imbalance``, ``moe_rows_elsewhere_pct``) and, traced, the
    time the experts', the window kernel's and the paged kernel's bytes
    need at the chip's HBM peak as percentages of the traced window
    (``moe_`` / ``window_`` / ``full_min_pct_of_traced_window``)."""
    fam = ctx.family
    w0, w1 = res["window_start"], res["window_start"] + ctx.seconds
    held = fam.m["experts_held"][1] * fam.moe_layers
    per_slot = fam.m["num_experts_per_tok"] * fam.moe_layers
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
            "assignments to experts held elsewhere; caches: "
            f"{np.mean([v['full_kv_tokens'] for v in ticks]):.0f} keys a "
            "full layer and "
            f"{np.mean([v['window_kv_rows'] for v in ticks]):.0f} ring "
            "rows a window layer a tick")
    wanted = eng.prefill_tokens
    say(f"prefill bucket: {eng.prefill_pad_tokens} of "
        f"{wanted + eng.prefill_pad_tokens} tokens the prefills ran were "
        f"padding ({100.0 * eng.prefill_pad_tokens / max(wanted + eng.prefill_pad_tokens, 1):.1f} %)")
    if len(traced) == 2 and not ctx.rehearse:
        a, b = traced
        item = jnp.dtype(ctx.cfg_file["dtype"]).itemsize
        in_trace = [(kind, v) for t, kind, v in calls if a <= t < b]
        decode = [v for kind, v in in_trace if kind == "decode"]
        moe = sum(fam.expert_kernel_bytes(
            v["moe_experts_hit"], v["moe_rows"], item) for _, v in in_trace)

        def live(v):        # a tick routes per_slot assignments a slot
            return round((v["moe_rows"] + v["moe_rows_elsewhere"])
                         / per_slot)

        window = sum(fam.window_decode_bytes(
            v["window_kv_rows"], live(v), item) for v in decode)
        full = sum(fam.full_decode_bytes(
            v["full_kv_tokens"], live(v), item) for v in decode)
        peak = yardstick.peak(jax.devices()[0].device_kind,
                              "hbm_bytes_per_s")
        for name, nbytes in (("moe", moe), ("window", window),
                             ("full", full)):
            series[f"{name}_min_pct_of_traced_window"] = \
                100.0 * nbytes / peak / (b - a)
        say(f"traced {b - a:.3f} s, {len(decode)} decode ticks: experts "
            f"{moe / 1e9:.2f} GB, window rings {window / 1e9:.2f} GB, "
            f"full-layer keys {full / 1e9:.2f} GB to move (published "
            f"widths), at {peak / 1e9:.0f} GB/s {moe / peak:.3f}, "
            f"{window / peak:.3f} and {full / peak:.3f} s")


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
        probe, nearest, top, untouched = _probe(
            family, eng, params, items[:serve_job.PROBE_REQUESTS],
            serving["prefill_len"], ref_len)

        # warm both programs of the engine on the shapes the traffic uses
        for it in items[:2]:
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
        streams, positions = _streams(family, params, done, ref_len)
        stats = devices[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
    finally:
        eng.close()

    controls = ", ".join(f"{c} {probe[c]:.4f}" for c in "ABC")
    say(f"probe: prefill + {eng.page_len + PROBE_MARGIN} ticks of "
        f"{serve_job.PROBE_REQUESTS} requests on the engine's own "
        f"{eng.slots} slots vs the float32 reference: max |logit diff| "
        f"{probe['program']:.4f}, largest |logit| {top:.2f}, tolerance "
        f"{LOGIT_TOL} (controls, A the reference with "
        f"{jnp.dtype(CONTROL_ACT).name} activations, B without the sink, C "
        f"with a window of {CONTROL_WINDOW}: {controls}; the program's own "
        "distance to each control: "
        + ", ".join(f"{c} {nearest[c]:.4f}" for c in "ABC")
        + f"); the rings of the slots beside it untouched: {untouched}")
    controls = ", ".join(f"{c} {streams[c]:.4f}" for c in "ABC")
    say(f"streams: {len(done)} finished requests replayed through the "
        f"float32 reference ({positions} positions): an emitted token sits "
        f"at most {streams['program']:.4f} below the reference's top logit, "
        f"tolerance {STREAM_TOL} (controls: {controls})")
    checks = judge({"probe_logits": probe["program"],
                    "streams": streams["program"]})
    checks["streams_within_tolerance"] &= positions > 0
    # a program that left the sink out, or kept a wider window, would sit
    # on that control and not on the reference
    checks["probe_nearer_the_reference_than_a_control"] = bool(
        probe["program"] < min(nearest.values()))
    checks["probe_left_other_slots_alone"] = bool(untouched)
    checks["no_compile_in_window"] = series["compiles_in_window"] == 0
    if not ctx.rehearse:
        # the limits are of the published widths: only there must each
        # control come out as not correct, by the same judge
        for c, what in (("A", "low_activations"), ("B", "no_sink"),
                        ("C", "wide_window")):
            checks[f"control_{what}_not_correct"] = not all(judge(
                {"probe_logits": probe[c], "streams": streams[c]}).values())
    return {"series": series, "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": peak, "trace_dir": result["trace_dir"],
            "window_start": result["window_start"]}
