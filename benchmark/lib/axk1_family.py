"""Family ``axk1`` (``configs/a.x-k1.json``: ``"family_module":
"lib.axk1_family:AxK1"``) and the job that serves it under
``serve_open_loop`` (``traffic/serve-longreason-saturated.json``:
``"job_module": "lib.axk1_family:run"``).

The yardsticks of this configuration's kernels are here:
``expert_kernel_bytes`` (``moe_expert_roofline.saturated``: three matrices
an expert, whole or walked in blocks, the same bytes) and
``latent_decode_bytes`` / ``latent_decode_flops``
(``latent_decode_roofline.saturated``).  The latent yardstick counts the
PUBLISHED bytes (a row 576 wide = 1,152 B): what the program stores wider
than that (640) shows as lost roofline.  Absorbed latent attention is 121
FLOP a byte, half of the v5e's ridge of 240, so the yardstick is the LARGER
of the two times (bytes at the HBM peak, operations at the MXU peak): a
share of it cannot pass 100 %.

Notes for a reader of the metric files this cell shares (they are not
edited): ``moe_experts_hit`` is of the experts HELD here (12 x 5 layers),
not of the 192 the router ranges over; ``moe_rows_elsewhere`` is about
15/16 here (12 of 192 held); the shared expert is plain XLA matmuls and is
in no ``moe_*`` share.

The job is its own ``run``, made of ``serve_job``'s parts (its open loop,
its constants) as ``lib/mimo_v2_family.py::run`` is: the probe runs on the
engine's own ONE pool (``cache["k"]`` alone, no ``"v"``), and the limits
and controls are this configuration's.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from . import axk1_reference, serve_job, traffic, yardstick
from .nemotron_h_family import _on_the_engines_cache, _trace_times
from .olmoe_family import _StallWatch
from .yardstick import say

# The limits of this cell's checks (``judge``).  Each lies between two
# readings taken on the chip at the published widths (PERF.md section 6,
# PR 40; the program over 11 seeds, the means over 6): the largest the
# program gave, and what a control gives.  The controls are read in EVERY
# run through the same ``judge`` in the program's place, and the run is not
# correct unless controls A and B come out as not correct (``run``):
#
# * A, CONTROL_ACT: the reference with its residual stream rounded to
#   float8 (e5m2) from the embedding on and after every layer, one precision
#   below the bfloat16 the configuration states;
# * B: the reference WITHOUT the scores' ``q_rope . k_rope`` term (what an
#   absorbed kernel that read the rows' first 512 lanes only would compute);
# * C: the reference with the router, the softmax and the norms in
#   bfloat16 where the configuration says float32.  READ AND PRINTED, NOT
#   JUDGED: the program keeps those three in float32 but its activations in
#   bfloat16, as the configuration states, so against the float32 reference
#   it reads what C reads (probe 0.96-1.30 for C's 0.98-1.48, means 0.030-
#   0.034 for 0.034-0.040): both are a handful of experts swapped at the
#   router's 8th place, and no limit on logits lies between them.  That
#   those three are float32 in the program is held where it can be seen:
#   tests/test_axk1.py compares them at float32, where C fails by 1,000 x.
CONTROL_ACT = jnp.float8_e5m2
#: the members of one reference call, in order: (round the residual
#: stream, the rope term is on, router/softmax/norms in bfloat16)
MEMBERS = {"reference": (False, True, False), "A": (True, True, False),
           "B": (False, False, False), "C": (False, True, True)}
# Probe logits over prefill + a page and more of decode ticks of
# PROBE_REQUESTS requests on the engine's own pool, against the reference:
# the LARGEST |program - reference| and the MEAN over every position and
# token.  Activations and logits are bfloat16 and logits of random weights
# reach |9|, where a bfloat16 step is 0.0625.  The largest is a swapped
# expert: the router's 8th and 9th of 192 sigmoid scores lie close, a
# swapped expert weighs 2.5 / 8 of the routed sum, and in a stack of 6
# layers one swap in the last moves a logit by about 1; it is the limit
# that a fault at ONE position trips.  The mean is the level of the noise,
# and separates the precisions ten to one.  Largest: program 0.96-1.30,
# A 2.37-2.74, B 10.5-12.0; mean: program 0.030-0.034, A 0.348-0.352,
# B 1.66-1.67.
LOGIT_TOL = 2.0
LOGIT_MEAN_TOL = 0.1
# Streams: how far below the reference's top logit a token sits that the
# timed engine emitted (the only reading drawn from the window itself), on
# average over 5,000-10,000 positions of four finished requests.  Program
# 0.0022-0.0041, A 0.218-0.235, B 5.07-5.15.  The LARGEST such distance is
# printed and not judged: one position in ten thousand at which a swapped
# expert turned the argmax reads 0.44-1.47 for the program and 1.94-2.34
# for A, and no limit lies between those with room.
STREAM_MEAN_TOL = 0.03
#: decode ticks of a probe: past a page boundary whatever the prompt's
#: length (``page_len`` + a few), forced tokens
PROBE_MARGIN = 4


def judge(readings: dict) -> dict:
    """The cell's limits on whatever readings are handed in, the program's
    or a control's in its place: check -> within its limit."""
    limits = {"probe_logits": LOGIT_TOL, "probe_logits_mean": LOGIT_MEAN_TOL,
              "streams_mean": STREAM_MEAN_TOL}
    return {f"{k}_within_tolerance":
            bool(np.isfinite(v) and v <= limits[k])
            for k, v in readings.items()}


class AxK1:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.axk1 import AxK1Config, AxK1Model
        fields = {f.name for f in dataclasses.fields(AxK1Config)}
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
        self.model = AxK1Model(AxK1Config(
            **m, param_dtype=cfg_file["dtype"]))
        self.vocab = m["vocab_size"]
        self.layers = m["num_hidden_layers"]
        self.moe_layers = self.layers - m["first_k_dense_replace"]
        # one program for every member and every call: the switches are
        # traced.  A member a call, not a loop over members inside one
        # program: XLA hoists the float32 copies of every weight out of
        # such a loop
        self._reference = jax.jit(
            lambda p, t, s: axk1_reference.axk1_logits(
                p, t, self.m, act_dtype=CONTROL_ACT, round_acts=s[0] > 0,
                rope_term=s[1] > 0, low=s[2] > 0)[0])

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
        matrices once (in blocks or whole: the same bytes); per row, x in
        and h out (gate_up), h in and y out (down)."""
        d, f = self.m["hidden_size"], self.m["moe_intermediate_size"]
        return itemsize * (experts_hit * 3 * d * f + rows * 2 * (d + f))

    def _latent_row(self) -> int:
        """A cached row as PUBLISHED: ``[c_kv ; k_rope]``."""
        return self.m["kv_lora_rank"] + self.m["qk_rope_head_dim"]

    def latent_decode_bytes(self, latent_kv_tokens: int, slots: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_latent_decode_attn`` must move in a decode tick
        that read ``latent_kv_tokens`` live rows (summed over layers) for
        ``slots`` live slots: every live row once, + a layer call's
        queries in (a head ``[q_lat ; q_rope]``) and outputs out (a head
        ``kv_lora_rank`` wide)."""
        heads = self.m["num_attention_heads"]
        per_slot = heads * (self._latent_row() + self.m["kv_lora_rank"])
        return itemsize * (latent_kv_tokens * self._latent_row()
                           + self.layers * slots * per_slot)

    def latent_decode_flops(self, latent_kv_tokens: int) -> int:
        """Operations of the absorbed form for those rows: a head's score
        over the whole row and its sum over the row's value lanes, a
        multiply and an add each."""
        heads = self.m["num_attention_heads"]
        return 2 * heads * latent_kv_tokens * (
            self._latent_row() + self.m["kv_lora_rank"])


def _probe(family, eng, params, items, bucket: int, ref_len: int):
    """Prefill and a page and more of decode ticks of a few requests
    through the model's paged serving entry points, on the engine's own
    ONE pool (the cell's slots and pages; same kernels; one request live,
    in the middle slot) against the plain reference on the same context:
    logits of every step.  The prefill is the expanded form, the ticks the
    absorbed form over the rows the prefill wrote; the ticks cross a page
    boundary.  Returns (member -> max |logit diff| to the reference, with
    the program's under ``"program"``; control A, B -> the program's max
    |logit diff| to THAT control, smallest over the requests: a program
    that is one of them sits on it (not C: a program in bfloat16
    throughout is no farther from bfloat16 norms than from float32 ones);
    largest |reference logit|)."""
    model = family.model
    slots, page_len, max_pages = eng.slots, eng.page_len, eng.max_pages
    slot, ticks = slots // 2, page_len + PROBE_MARGIN
    active = np.zeros((slots,), bool)
    active[slot] = True

    def run(params, cache, prompt, n, forced, row, table):
        logits, k, _ = model.prefill_paged(
            params, prompt, n, np.int32(0), row, cache["k"])

        def tick(carry, token):
            k, lengths = carry
            tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(token)
            lg, k, _, lengths = model.decode_step_paged(
                params, tokens, k, None, table, lengths, active,
                impl=eng.decode_impl)
            return (k, lengths), lg[slot]

        lengths = jnp.zeros_like(cache["lengths"]).at[slot].set(n)
        (k, _), rest = jax.lax.scan(tick, (k, lengths), forced)
        first = jax.lax.dynamic_index_in_dim(logits[0], n - 1, 0, False)
        return (dict(cache, k=k),
                jnp.concatenate([first[None], rest]).astype(jnp.float32))

    run = _on_the_engines_cache(eng, run)
    rng = np.random.default_rng(12345)
    worst = dict.fromkeys(["program", "A", "B", "C"], 0.0)
    mean = dict.fromkeys(worst, 0.0)
    nearest = dict.fromkeys("AB", np.inf)
    top = 0.0
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
        got, = run(padded, np.int32(len(prompt)), forced, row, table)
        seq = prompt + [int(t) for t in forced]
        ref = family.reference(params, seq, ref_len)
        ref["program"] = np.asarray(got)
        want = ref["reference"][len(prompt) - 1:]
        top = max(top, float(np.abs(want).max()))
        for name in worst:
            rows = ref[name] if name == "program" \
                else ref[name][len(prompt) - 1:]
            worst[name] = max(worst[name],
                              float(np.abs(rows - want).max()))
            mean[name] += float(np.abs(rows - want).mean()) / len(items)
        for c in nearest:
            nearest[c] = min(nearest[c], float(np.abs(
                ref["program"] - ref[c][len(prompt) - 1:]).max()))
    return worst, mean, nearest, top


def _streams(family, params, reqs, ref_len: int):
    """``serve_job._stream_slack`` with the controls beside it and the
    mean beside the largest: how far below the reference's top logit a
    token sits over whole finished streams of the timed engine
    (teacher-forced on the engine's own tokens), for the tokens the engine
    emitted and, each control, for those it would have.  Returns (name ->
    largest slack, name -> mean slack over the positions, positions)."""
    names = ["program", "A", "B", "C"]
    slack, total = dict.fromkeys(names, 0.0), dict.fromkeys(names, 0.0)
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
            total[name] += float(below[at, tokens].sum())
        positions += len(r.tokens)
    return (slack, {k: v / max(positions, 1) for k, v in total.items()},
            positions)


def _counters(ctx, eng, calls, res, traced, series) -> None:
    """What the program counted per call (``ServeEngine.aux_log``) into
    ``series``: the expert layers' per decode tick of the window
    (``moe_experts_hit_pct`` of the held experts x layers,
    ``moe_load_imbalance``, ``moe_rows_elsewhere_pct``) and, traced, the
    time the experts' bytes and the latent kernel's yardstick need at the
    chip's peaks as percentages of the traced window (``moe_`` /
    ``latent_min_pct_of_traced_window``)."""
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
        rows = np.mean([v["latent_kv_tokens"] for v in ticks]) / fam.layers
        say(f"experts: {len(ticks)} decode ticks in the window, hit "
            f"{np.mean(series['moe_experts_hit_pct']):.2f} % of the "
            f"{held} held a tick, busiest over mean "
            f"{np.mean(series['moe_load_imbalance']):.2f}, "
            f"{np.mean(series['moe_rows_elsewhere_pct']):.2f} % of the "
            "assignments to experts held elsewhere; latent cache: "
            f"{rows:.0f} live rows a layer a tick")
    pad = eng.prefill_pad_tokens
    ran = eng.prefill_tokens + pad
    say(f"prefill bucket: {pad} of {ran} tokens the prefills ran were "
        f"padding ({100.0 * pad / max(ran, 1):.1f} %)")
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

        kind = jax.devices()[0].device_kind
        hbm = yardstick.peak(kind, "hbm_bytes_per_s")
        mxu = yardstick.peak(kind, "bf16_flops")
        nbytes = sum(fam.latent_decode_bytes(
            v["latent_kv_tokens"], live(v), item) for v in decode)
        flops = sum(fam.latent_decode_flops(v["latent_kv_tokens"])
                    for v in decode)
        latent_s = max(nbytes / hbm, flops / mxu)
        series["moe_min_pct_of_traced_window"] = \
            100.0 * moe / hbm / (b - a)
        series["latent_min_pct_of_traced_window"] = \
            100.0 * latent_s / (b - a)
        say(f"traced {b - a:.3f} s, {len(decode)} decode ticks: experts "
            f"{moe / 1e9:.2f} GB to move, {moe / hbm:.3f} s at "
            f"{hbm / 1e9:.0f} GB/s; latent rows {nbytes / 1e9:.2f} GB "
            f"(published width) = {nbytes / hbm:.3f} s, "
            f"{flops / 1e12:.2f} TFLOP of absorbed attention = "
            f"{flops / mxu:.3f} s at {mxu / 1e12:.0f} TFLOP/s: the larger "
            "is the yardstick")


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
        probe, probe_mean, nearest, top = _probe(
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
        streams, streams_mean, positions = _streams(family, params, done,
                                                    ref_len)
        stats = devices[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        say(f"memory: the pool {eng.kv_bytes / 1e9:.3f} GB "
            f"({sorted(k for k in eng.cache if k != 'lengths')}), weights "
            f"{eng.param_bytes / 1e9:.3f} GB, peak in use {peak / 1e9:.3f} GB")
    finally:
        eng.close()

    def both(big, mean, names="ABC"):
        return ", ".join(f"{c} {big[c]:.4f} / {mean[c]:.5f}" for c in names)

    controls = both(probe, probe_mean)
    say(f"probe: prefill (expanded) + {eng.page_len + PROBE_MARGIN} ticks "
        f"(absorbed) of {serve_job.PROBE_REQUESTS} requests on the "
        f"engine's own {eng.slots} slots vs the float32 reference: max "
        f"|logit diff| {probe['program']:.4f}, mean "
        f"{probe_mean['program']:.5f}, largest |logit| {top:.2f}, "
        f"tolerances {LOGIT_TOL} / {LOGIT_MEAN_TOL} (controls, largest / "
        "mean: A the reference with "
        f"{jnp.dtype(CONTROL_ACT).name} activations, B without the rope "
        f"term of the scores, C with router, softmax and norms in "
        f"bfloat16: {controls}; the program's own distance to each "
        "control: " + ", ".join(f"{c} {nearest[c]:.4f}" for c in "AB")
        + ")")
    controls = both(streams, streams_mean)
    say(f"streams: {len(done)} finished requests replayed through the "
        f"float32 reference ({positions} positions): an emitted token sits "
        f"at most {streams['program']:.4f} and on average "
        f"{streams_mean['program']:.5f} below the reference's top logit, "
        f"tolerance of the average {STREAM_MEAN_TOL} (controls: "
        f"{controls})")

    def readings(name):
        return {"probe_logits": probe[name],
                "probe_logits_mean": probe_mean[name],
                "streams_mean": streams_mean[name]}

    checks = judge(readings("program"))
    checks["streams_mean_within_tolerance"] &= positions > 0
    # a program that left the rope term out would sit on that control and
    # not on the reference
    checks["probe_nearer_the_reference_than_a_control"] = bool(
        probe["program"] < min(nearest.values()))
    checks["no_compile_in_window"] = series["compiles_in_window"] == 0
    if not ctx.rehearse:
        # the limits are of the published widths: only there must each
        # control come out as not correct, by the same judge
        for c, what in (("A", "low_activations"), ("B", "no_rope_term")):
            checks[f"control_{what}_not_correct"] = not all(
                judge(readings(c)).values())
    return {"series": series, "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": peak, "trace_dir": result["trace_dir"],
            "window_start": result["window_start"]}
