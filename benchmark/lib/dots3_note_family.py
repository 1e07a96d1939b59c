"""Family ``dots3_note`` (``configs/dots3-note-prev.json``: ``"family_module":
"lib.dots3_note_family:Dots3Note"``) and the job that serves it under
``serve_open_loop`` (``traffic/serve-mixedlen-saturated.json``:
``"job_module": "lib.dots3_note_family:run"``).

The yardsticks of this configuration's kernels are here, all at the PUBLISHED
widths: ``expert_kernel_bytes`` (``moe_expert_roofline.saturated``),
``index_score_bytes`` / ``index_score_flops``
(``index_score_roofline.saturated``: a scored key is 128 bfloat16 = 256 B and
64 heads x 128 multiply-adds, 62 FLOP a byte, a quarter of the v5e's ridge:
that metric's file says 32 heads, GLM-5.2's) and
``window_latent_decode_bytes`` / ``_flops``
(``window_latent_decode_roofline.saturated``: a live ring row is ``[c_kv
(1,024) ; k_rope (64)]`` = 2,176 B as PUBLISHED, read once for 64 heads x
(1,088 + 1,024) multiply-adds; 124 FLOP a byte, half the ridge, so bytes bound
it, and the yardstick is still the LARGER of the two times).  The full layers'
attention has no roofline here, as in ``lib/glm_dsa_family.py`` and for its
reason.

Notes for a reader of the metric files this cell shares (they are not
edited): ``moe_experts_hit`` is of the experts HELD here (16 x 8 layers), not
of the 256 the router ranges over; ``moe_rows_elsewhere`` is about 15/16; the
shared expert is plain XLA matmuls and is in no ``moe_*`` share;
``window_wrapped_share`` says a window of 4,096 and here it is 513;
``sparse_decode_share`` and ``latent_context_share`` say 64 heads and here
they are 128; ``index_topk_share`` matches XLA's sort, which no tick has held
since PR 50 (the picks are a mask), and reads 0; ``flash_fwd_share`` is the
SLIDING layers' prefill here (``ds_flash_fwd`` on a request's first chunk,
``ds_flash_fwd_ctx`` over the ring's last 512 rows + the chunk after it).

The job is its own ``run``, made of ``serve_job``'s parts as
``lib/glm_dsa_family.py::run`` is (its streams' replay and their limit's
arithmetic are that file's, imported): the probe runs on the engine's own
three arrays (rings, pool, indexer keys) at the mix's longest contexts,
prefills in the engine's chunks and ticks a program a call, the reference runs
while no engine holds the pages' memory, and the limits and controls are this
configuration's.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from . import dots3_note_reference, serve_job, traffic, yardstick
from .glm_dsa_family import (LOGIT_ROWS, PROBE_MARGIN, REPLICA_TOL,
                             _pick_streams, _streams)
from .nemotron_h_family import _on_the_engines_cache, _trace_times
from .olmoe_family import _StallWatch
from .yardstick import say

# The limits of this cell's checks (``judge``).  Each lies between two
# readings taken on the chip at the published widths (my chip runs, PR 56:
# the seeds are in PERF.md section 6): the largest the program gave over its
# seeds, and what the nearest control gives.  The controls are read in EVERY
# run through the same ``judge`` in the program's place, and the run is not
# correct unless each judged one comes out as not correct (``run``):
#
# * A, CONTROL_ACT: the reference with its residual stream rounded to float8
#   (e5m2) from the embedding on and after every layer, one precision below
#   the bfloat16 the configuration states;
# * B: the reference with the indexer's KEYS rounded to 8 bits (float8 e4m3)
#   before they score: one precision below the bfloat16 the index cache
#   states;
# * G: the reference WITHOUT the headwise gate (g = 1 on both kinds);
# * R: the reference WITHOUT the rescale of the normed latents (s_q = s_kv =
#   1 on both kinds): scores 5 to 10 times smaller, an all but flat softmax;
# * E, READ AND PRINTED, NOT JUDGED: the reference with the indexer's queries,
#   keys and head weights rounded to bfloat16, which is what the configuration
#   states.  It says what the program's distance IS on the full layers.
#
# WHERE they are read: as ``lib/glm_dsa_family.py`` reads its own.  The probe
# is two sequences cut from the schedule's own tokens: one as long as the
# mix's LONGEST prompt (16,379 tokens: eight chunks, the rings wrapped 31
# times, 12 % of a full layer's rows picked) read by every member, and one
# just past ``ds_index_score``'s first grid step of 8,192 keys (five chunks)
# read by the reference alone; each prefilled in the engine's chunks into a
# slot's rings and its pages, then decoded TOGETHER a page and more of ticks,
# every slot live (the long one in slot 0, the other in the middle slot, whose
# rings are then copied to every remaining slot: the same context 126 times,
# whose logits must be the middle slot's).  The streams are the timed engine's
# own (its compiled tick, 128 slots live).
CONTROL_ACT = jnp.float8_e5m2
#: the members of one reference call: (round the residual stream, 8-bit
#: indexer keys, no gate, no rescale, a bfloat16 indexer)
MEMBERS = {"reference": (0, 0, 0, 0, 0), "A": (1, 0, 0, 0, 0),
           "B": (0, 1, 0, 0, 0), "G": (0, 0, 1, 0, 0), "R": (0, 0, 0, 1, 0),
           "E": (0, 0, 0, 0, 1)}
#: the judged controls: each must come out as not correct
CONTROLS = {"A": "low_activations", "B": "index_keys_8bit", "G": "no_gate",
            "R": "no_rescale"}
READ = [name for name in MEMBERS if name != "reference"]
#: the controls whose logits lie far from the reference's
FAR_CONTROLS = ("A", "B", "G", "R")
# Probe logits over the LAST chunk of a chunked prefill + a page and more of
# decode ticks, against the reference: the LARGEST |program - reference| and
# the MEAN over every position and token, the worse of the two requests; the
# controls' on the long request.  Readings of the first seven runs at the
# published widths (my chip runs, PR 56, seeds 5600001 and 5600301-306;
# PERF.md section 6 has the later ones).  Mean: program 0.1824-0.1873, E
# 0.1395-0.1479 (most of the program's distance is picks a bfloat16 indexer
# flips: the rescaled latents make a head's softmax sharp, and which rows are
# in the set is most of its output), B 0.4098-0.4182, A 0.7587-0.7664, G
# 1.0383-1.0431, R 1.5235-1.5256: the limit refuses all four, B, the
# nearest, at 1.4 times it, the program at two thirds of it.  Largest (the
# extreme of 4,164 rows x 19,008 logits that reach |8|): program 4.18-5.86
# (4.18, 4.52, 4.54, 4.66, 4.71, 4.89 and one of 5.86), E 3.14-5.43, B
# 5.10-6.37, A 6.54-7.70, G 7.14-8.03, R 10.17-11.20: B's, A's and G's lie in
# or crowd the program's own tail, so THIS limit stands between the program's
# largest and R's smallest and refuses R alone; what it is for is a program
# that is wrong in a few rows and right on average.
LOGIT_TOL = 8.5
LOGIT_MEAN_TOL = 0.29
# Streams: ``lib/glm_dsa_family.py``'s reading (the mean of -log
# p_reference(draw) less p_reference's entropy, judged at 3 standard errors
# below) on what the timed engine DREW at temperature 1.0, ~6,630 draws of two
# requests to position 8,829: program -0.014 to 0.034 at a standard error of
# 0.0176, judged -0.066 to -0.019; control A, exact, 0.269-0.280.
STREAM_NLL_TOL = 0.1
# index_pick_agreement: of the rows the program's ticks picked on the LONG
# request (all three full layers, every probe tick), the share the float32
# reference also picks for that query; a control's is the share of ITS sets
# the reference's hold.  Program 0.8849-0.8932 (by full layer 0.996 / 0.851-
# 0.864 / 0.806-0.819: each behind more bfloat16 layers; the other request,
# printed: 0.997 / 0.900-0.910 / 0.868-0.880 at 8,414 rows), E 0.9124-0.9262,
# B 0.7431-0.7531, A 0.5547-0.5650, G 0.4769-0.4812, R 0.4167-0.4173: the
# floor lies midway between the program's lowest and B's highest and refuses
# all four.
PICK_AGREEMENT_FLOOR = 0.82
#: positions of the two reference programs (``lib/glm_dsa_family.py``'s)
REFERENCE_LONG = 16512
REFERENCE_MID = 8704


def judge(readings: dict) -> dict:
    """The cell's limits on whatever readings are handed in, the program's
    or a control's in its place: check -> within its limit."""
    limits = {"probe_logits": LOGIT_TOL, "probe_logits_mean": LOGIT_MEAN_TOL,
              "streams_nll": STREAM_NLL_TOL}
    out = {f"{k}_within_tolerance": bool(np.isfinite(v) and v <= limits[k])
           for k, v in readings.items() if k in limits}
    if "index_pick_agreement" in readings:
        out["index_pick_agreement_above_floor"] = bool(
            readings["index_pick_agreement"] >= PICK_AGREEMENT_FLOOR)
    return out


class Dots3Note:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.dots3_note import (Dots3NoteConfig,
                                                     Dots3NoteModel)
        fields = {f.name for f in dataclasses.fields(Dots3NoteConfig)}
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
        self.model = Dots3NoteModel(Dots3NoteConfig(
            **m, param_dtype=cfg_file["dtype"]))
        self.vocab = m["vocab_size"]
        kinds = list(m["layer_types"])
        self.full_layers = kinds.count("full_attention")
        self.window_layers = kinds.count("sliding_attention")
        self.moe_layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
        # one program a reference length for every member and every call:
        # the switches, the rows whose picks come back and the first row of
        # the logits are traced
        self._reference = jax.jit(
            lambda p, t, s, rows, first, count: dots3_note_reference.
            dots3_note_logits(
                p, t, self.m, act_dtype=CONTROL_ACT, round_acts=s[0] > 0,
                low_keys=s[1] > 0, no_gate=s[2] > 0, no_rescale=s[3] > 0,
                bf16_index=s[4] > 0, pick_rows=rows,
                logit_rows=(first, count)), static_argnums=5)

    def make_params(self, seed: int, dtype):
        return serve_job._make_params(self.model, seed, dtype)

    def reference(self, params, tokens, pad_to: int, pick_rows, first: int,
                  members) -> dict:
        """``lib/glm_dsa_family.py::GlmDsa.reference``'s contract: one
        sequence padded to ``pad_to`` through the reference and the controls
        ``members`` names: member -> float32 logits of the rows ``first`` ..
        ``len(tokens)`` (at most LOGIT_ROWS of them); and under ``"picks"``
        member -> its picked sets of the queries ``pick_rows``, bool [full
        layers, rows, T]."""
        count = min(LOGIT_ROWS, pad_to)
        assert first + count >= len(tokens), (first, count, len(tokens))
        start = min(first, pad_to - count)
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(tokens)] = tokens
        rows = np.asarray(pick_rows, np.int32)
        out = {"picks": {}}
        with jax.default_matmul_precision("highest"):
            for name in members:
                logits, picks = self._reference(
                    params, padded, np.asarray(MEMBERS[name], np.int32),
                    rows, np.int32(start), count)
                out[name] = np.asarray(
                    logits[0, first - start:len(tokens) - start])
                out["picks"][name] = np.asarray(picks[0])[:, :, :len(tokens)]
        return out

    def expert_kernel_bytes(self, experts_hit: int, rows: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_moe_gate_up`` + ``ds_moe_down`` must move for
        ``rows`` (token, held expert) assignments over ``experts_hit`` held
        experts (both summed over layers): each hit expert's three matrices
        once; per row, x in and h out (gate_up), h in and y out (down)."""
        d, f = self.m["hidden_size"], self.m["moe_intermediate_size"]
        return itemsize * (experts_hit * 3 * d * f + rows * 2 * (d + f))

    def index_score_bytes(self, scored_rows: int, slots: int,
                          itemsize: int) -> int:
        """HBM bytes ``ds_index_score`` must move in a decode tick that
        scored ``scored_rows`` keys (summed over the full layers) for
        ``slots`` live slots: every key once, a float32 score out for it, +
        a layer call's queries and head weights in."""
        J, D = self.m["index_n_heads"], self.m["index_head_dim"]
        return scored_rows * (D * itemsize + 4) \
            + self.full_layers * slots * J * (D * itemsize + 4)

    def index_score_flops(self, scored_rows: int) -> int:
        """A multiply and an add a head a key dim."""
        return 2 * self.m["index_n_heads"] * self.m["index_head_dim"] \
            * scored_rows

    def window_latent_decode_bytes(self, ring_rows: int, itemsize: int) -> int:
        """HBM bytes ``ds_window_latent_decode_attn`` must move in a decode
        tick that read ``ring_rows`` live ring rows (summed over slots and
        sliding layers): each row once, ``[c_kv ; k_rope]`` as PUBLISHED."""
        return ring_rows * itemsize * (self.m["swa_kv_lora_rank"]
                                       + self.m["swa_qk_rope_head_dim"])

    def window_latent_decode_flops(self, ring_rows: int) -> int:
        """The absorbed form: a head's score over the row's published width
        and its value over the latent's, a multiply and an add each."""
        C, rot = self.m["swa_kv_lora_rank"], self.m["swa_qk_rope_head_dim"]
        return 2 * self.m["swa_num_attention_heads"] * (C + rot + C) \
            * ring_rows


def _probe_plan(family, params, items, page_len: int, chunk: int,
                long_n: int, mid_n: int, long_len: int, mid_len: int):
    """The probe's two requests, cut from the schedule's own tokens: ``long_n``
    of them, read by every member, and the first ``mid_n``, read by the
    reference; each with the forced tokens of its ticks and the float32
    readings on prompt + ticks, taken NOW, while no engine holds the pages'
    memory."""
    ticks = page_len + PROBE_MARGIN
    rng = np.random.default_rng(12345)
    drawn = [t for it in items for t in it.prompt]
    drawn = (drawn * (long_n // len(drawn) + 1))[:long_n]
    plan = []
    for n, ref_len, members in ((long_n, long_len, list(MEMBERS)),
                                (mid_n, mid_len, ["reference"])):
        prompt = drawn[:n]
        forced = rng.integers(0, family.vocab, (ticks,)).astype(np.int32)
        first = (n - 1) // chunk * chunk    # the last chunk's first position
        ref = family.reference(params, prompt + forced.tolist(),
                               ref_len, n + np.arange(ticks), first, members)
        plan.append({"prompt": prompt, "forced": forced, "first": first,
                     "ref": ref})
    return plan


def _probe(family, eng, plan, bucket: int, chunk: int):
    """The plan's requests prefilled IN CHUNKS through the model's paged
    serving entry points on the engine's own rings, pool and indexer keys
    (the cell's slots and pages; same kernels), the long one into slot 0, the
    other into the middle slot, whose rings are then copied to every slot
    after the first; then a page and more of decode ticks of EVERY slot at
    once, a tick a program call as the engine calls its own, the slots after
    the first all reading the other request's pages (the same context and
    tokens, so the same logits).  Returns what
    ``lib/glm_dsa_family.py::_probe`` returns."""
    model = family.model
    slots, page_len, max_pages = eng.slots, eng.page_len, eng.max_pages
    ticks = page_len + PROBE_MARGIN
    at = [0, slots // 2]                        # the two requests' slots
    active = np.ones((slots,), bool)

    def prefill(params, cache, tokens, n, done, row, slot):
        logits, k, _, ik, state = model.prefill_paged(
            params, tokens, n, done, row, cache["k"], None,
            state=cache["state"], slot=slot, index_pool=cache["index_k"])
        return dict(cache, k=k, index_k=ik, state=state), logits[0]

    def spread(params, cache, lengths):
        """The middle slot's rings into every slot after the first."""
        state = {name: jnp.concatenate([leaf[:, :1], jnp.broadcast_to(
            leaf[:, at[1]:at[1] + 1], leaf[:, 1:].shape)], axis=1)
            for name, leaf in cache["state"].items()}
        return (dict(cache, state=state, lengths=lengths),)

    def tick(params, cache, tokens, table):
        lg, k, _, ik, state, lengths, aux = model.decode_step_paged(
            params, tokens, cache["k"], None, table, cache["lengths"],
            active, state=cache["state"], impl=eng.decode_impl,
            index_pool=cache["index_k"], aux=True)
        beside = jnp.max(jnp.abs(lg[1:] - lg[at[1]]).astype(jnp.float32))
        return (dict(cache, k=k, index_k=ik, state=state, lengths=lengths),
                lg[jnp.asarray(at)].astype(jnp.float32),
                aux["index_picks"][:, jnp.asarray(at)], beside)

    def clear(params, cache):
        return (dict(cache, lengths=jnp.zeros_like(cache["lengths"])),)

    prefill, spread, tick, clear = (_on_the_engines_cache(eng, fn)
                                    for fn in (prefill, spread, tick, clear))
    table = np.zeros((slots, max_pages), np.int32)
    lengths = np.zeros((slots,), np.int32)
    tokens = np.zeros((ticks, slots), np.int32)
    last, chunks, page0 = [], [], 1
    for i, req in enumerate(plan):
        n = len(req["prompt"])
        n_pages = -(-(n + ticks) // page_len)
        row = np.zeros((max_pages,), np.int32)
        row[:n_pages] = page0 + np.arange(n_pages)
        page0 += n_pages
        where = [at[0]] if i == 0 else list(range(1, slots))
        table[where], lengths[where] = row, n
        tokens[:, where] = req["forced"][:, None]
        starts = list(range(0, n, chunk))
        chunks.append(len(starts))
        for done in starts:
            part = req["prompt"][done:done + chunk]
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(part)] = part
            rows, = prefill(padded, np.int32(len(part)), np.int32(done), row,
                            np.int32(at[i]))
        last.append(np.asarray(rows[:n - req["first"]], np.float32))
    spread(lengths)
    rest, picks, beside = [], [], 0.0
    for t in range(ticks):
        lg, pk, off = tick(tokens[t], table)
        rest.append(np.asarray(lg))
        picks.append(np.asarray(pk))
        beside = max(beside, float(off))
    clear()
    rest, picks = np.stack(rest), np.stack(picks)
    names = ["program"] + READ
    worst = dict.fromkeys(names, 0.0)
    mean = dict.fromkeys(names, 0.0)
    nearest = dict.fromkeys(FAR_CONTROLS, np.inf)
    agree = {name: [] for name in READ}
    by_request, top = [], 0.0
    for i, req in enumerate(plan):
        ref, n = req["ref"], len(req["prompt"])
        got = np.concatenate([last[i], rest[:, i]])
        want = ref["reference"]
        top = max(top, float(np.abs(want).max()))
        for name in ["program"] + [c for c in READ if c in ref]:
            rows = got if name == "program" else ref[name]
            worst[name] = max(worst[name], float(np.abs(rows - want).max()))
            mean[name] = max(mean[name], float(np.abs(rows - want).mean()))
        for c in nearest:
            if c in ref:
                nearest[c] = min(nearest[c],
                                 float(np.abs(got - ref[c]).mean()))
        # tick t's query sits at position n + t and sees n + t + 1 keys
        own = ref["picks"]["reference"]         # [full layers, ticks, T]
        mine = picks[:, :, i]                   # [ticks, full layers, K]
        per_tick = [[float(np.mean(
            own[layer, t, mine[t, layer, :min(mine.shape[-1], n + t + 1)]]))
            for layer in range(mine.shape[1])] for t in range(ticks)]
        by_request.append([float(v) for v in np.mean(per_tick, axis=0)])
        for c in READ:
            if c in ref["picks"]:
                theirs = ref["picks"][c]
                agree[c].append(float((theirs & own).sum() / theirs.sum()))
    agreement = {k: float(np.mean(v)) for k, v in agree.items()}
    # judged where the controls are read: the long request's ticks
    agreement["program"] = float(np.mean(by_request[0]))
    return (worst, mean, nearest, agreement, by_request, chunks, top,
            float(beside))


def _counters(ctx, eng, calls, res, traced, series) -> None:
    """What the program counted per call (``ServeEngine.aux_log``) into
    ``series``: per decode tick of the window the expert layers'
    (``moe_experts_hit_pct`` of the held experts x layers,
    ``moe_load_imbalance``, ``moe_rows_elsewhere_pct``),
    ``index_selected_pct`` (the rows the full layers' attention attended over
    the live rows of the slots' contexts) and ``window_wrapped_pct`` (of the
    active slots, those whose context has passed the window);
    ``prefill_chunks_per_request``; and, traced, the time the experts' bytes,
    the indexer's yardstick and the ring kernel's need at the chip's peaks as
    percentages of the traced window (``moe_`` / ``index_score_`` /
    ``window_latent_min_pct_of_traced_window``)."""
    fam = ctx.family
    w0, w1 = res["window_start"], res["window_start"] + ctx.seconds
    held = fam.m["experts_held"][1] * fam.moe_layers
    per_slot = fam.m["num_experts_per_tok"] * fam.moe_layers

    def live(v):            # a tick routes per_slot assignments a slot
        return round((v["moe_rows"] + v["moe_rows_elsewhere"]) / per_slot)

    in_window = [(kind, v) for t, kind, v in calls if w0 <= t < w1]
    ticks = [v for kind, v in in_window if kind == "decode"]
    prefills = [v for kind, v in in_window if kind == "prefill"]
    series["moe_experts_hit_pct"] = [
        100.0 * v["moe_experts_hit"] / held for v in ticks]
    series["moe_load_imbalance"] = [v["moe_load_imbalance"] for v in ticks]
    series["moe_rows_elsewhere_pct"] = [
        100.0 * v["moe_rows_elsewhere"]
        / max(v["moe_rows"] + v["moe_rows_elsewhere"], 1) for v in ticks]
    series["index_selected_pct"] = [
        100.0 * v["index_selected_rows"] / max(v["latent_kv_tokens"], 1)
        for v in ticks]
    series["window_wrapped_pct"] = [
        100.0 * v["window_wrapped_slots"] / max(live(v), 1) for v in ticks]
    whole = sum(1 for v in prefills if v.get("final_chunk", True))
    if whole:
        series["prefill_chunks_per_request"] = len(prefills) / whole
    if ticks:
        rows = np.mean([v["latent_kv_tokens"] for v in ticks]) \
            / fam.full_layers
        ring = np.mean([v["window_latent_rows"] for v in ticks]) \
            / fam.window_layers
        say(f"experts: {len(ticks)} decode ticks in the window, hit "
            f"{np.mean(series['moe_experts_hit_pct']):.2f} % of the "
            f"{held} held a tick, busiest over mean "
            f"{np.mean(series['moe_load_imbalance']):.2f}, "
            f"{np.mean(series['moe_rows_elsewhere_pct']):.2f} % of the "
            f"assignments to experts held elsewhere; caches: {rows:.0f} live "
            "rows a full layer a tick, of which the attention attended "
            f"{np.mean(series['index_selected_pct']):.2f} %, {ring:.0f} ring "
            "rows a sliding layer, "
            f"{np.mean(series['window_wrapped_pct']):.2f} % of the active "
            "slots past the window")
    pad = eng.prefill_pad_tokens
    ran = eng.prefill_tokens + pad
    say(f"prefills: {len(prefills)} calls in the window for {whole} "
        f"requests; {pad} of {ran} tokens the prefills ran were padding "
        f"({100.0 * pad / max(ran, 1):.1f} %)")
    if len(traced) == 2 and not ctx.rehearse:
        a, b = traced
        item = jnp.dtype(ctx.cfg_file["dtype"]).itemsize
        in_trace = [(kind, v) for t, kind, v in calls if a <= t < b]
        decode = [v for kind, v in in_trace if kind == "decode"]
        moe = sum(fam.expert_kernel_bytes(
            v["moe_experts_hit"], v["moe_rows"], item) for _, v in in_trace)
        kind = jax.devices()[0].device_kind
        hbm = yardstick.peak(kind, "hbm_bytes_per_s")
        mxu = yardstick.peak(kind, "bf16_flops")
        score_s = max(
            sum(fam.index_score_bytes(v["index_scored_rows"], live(v), item)
                for v in decode) / hbm,
            sum(fam.index_score_flops(v["index_scored_rows"])
                for v in decode) / mxu)
        ring_rows = sum(v["window_latent_rows"] for v in decode)
        ring_s = max(fam.window_latent_decode_bytes(ring_rows, item) / hbm,
                     fam.window_latent_decode_flops(ring_rows) / mxu)
        for name, seconds in (("moe", moe / hbm), ("index_score", score_s),
                              ("window_latent", ring_s)):
            series[f"{name}_min_pct_of_traced_window"] = \
                100.0 * seconds / (b - a)
        say(f"traced {b - a:.3f} s, {len(decode)} decode ticks: experts "
            f"{moe / 1e9:.2f} GB to move, {moe / hbm:.3f} s at "
            f"{hbm / 1e9:.0f} GB/s; the indexer's yardstick {score_s:.3f} s, "
            f"the ring kernel's {ring_s:.3f} s for {ring_rows:.0f} live ring "
            "rows (each the larger of bytes at the HBM peak and operations "
            f"at {mxu / 1e12:.0f} TFLOP/s, published widths)")


def run(ctx) -> dict:
    """``lib/glm_dsa_family.py::run``'s order: parameters, the reference's
    readings for the probe (no engine yet: the reference has the memory the
    caches will take), the engine, the probe on its arrays, warm-up, the open
    loop, and, the engine closed and its caches given back, the replay of
    what it drew."""
    from deepspeed_tpu.inference import ServeEngine
    from deepspeed_tpu.parallel import build_mesh

    family, mix = ctx.family, ctx.mix
    serving = dict(ctx.cfg_file["serving"])
    if ctx.rehearse:
        serving.update(ctx.cfg_file["rehearse"]["serving"])
    lead_s = float(mix["lead_s"])
    grace_s = float(mix.get("first_token_grace_s", 0))
    temperature = float(serving["temperature"])
    devices = jax.devices()[:1]
    mesh = build_mesh(pp=1, dp=1, tp=1, devices=devices)
    params = family.make_params(ctx.seed, jnp.dtype(ctx.cfg_file["dtype"]))
    items = traffic.build_schedule(mix, ctx.seed, lead_s + ctx.seconds,
                                   family.vocab)
    if not items:
        raise ValueError("the traffic mix gave no request in the horizon")
    bucket, chunk = serving["prefill_len"], serving["prefill_chunk_len"]
    page_len = serving["page_len"]
    ticks = page_len + PROBE_MARGIN
    long_len = min(REFERENCE_LONG, serving["max_seq_len"])
    mid_len = min(REFERENCE_MID, serving["max_seq_len"])
    # ``ds_index_score`` streams 128 pages a grid step: a context is past
    # one when it holds more keys than that
    past = 128 * page_len if 128 * page_len < mid_len else mid_len // 2
    # the long probe is as long as the mix's longest prompt, its last chunk
    # not whole; the other lies midway between a grid step and its
    # reference's length
    long_n = min(int(mix["prompt_len"]["hi"]), long_len - ticks) - 5
    mid_n = min(past + (mid_len - ticks - past) // 2, long_n)

    def peak_gb():
        stats = devices[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)) / 1e9

    plan = _probe_plan(family, params, items[:16], page_len, chunk, long_n,
                       mid_n, long_len, mid_len)
    peaks = [("the reference before the engine", peak_gb())]
    eng = ServeEngine(family.model,
                      {"serving": serving, "telemetry": {"enabled": False}},
                      mesh=mesh, params=params, seed=ctx.seed % (2 ** 31 - 1))
    series, traced = {}, []
    try:
        (probe, probe_mean, nearest, agreement, by_request, chunks, top,
         beside) = _probe(family, eng, plan, bucket, chunk)
        peaks.append(("the engine built and the probe on its arrays",
                      peak_gb()))

        # warm the programs of the engine on the shapes the traffic uses:
        # both rungs, a chunked prompt, the tick
        for n in (chunk + chunk // 2, chunk // 2):
            tokens = [t for it in items for t in it.prompt][:n] or [1]
            eng.submit((tokens * (n // len(tokens) + 1))[:n],
                       max_new_tokens=3)
        eng.run_until_idle()
        jax.block_until_ready(eng.cache)
        eng.prefill_pad_tokens = eng.prefill_tokens = 0
        eng.prefill_chunk_calls = dict.fromkeys(eng.prefill_chunk_calls, 0)

        log0 = len(eng.aux_log)
        watch = _StallWatch(eng)
        try:
            with _trace_times(traced):
                result = serve_job._open_loop(ctx, eng, items, lead_s,
                                              grace_s, series)
        finally:
            watch.stop()
        peaks.append(("warm-up and the open loop", peak_gb()))
        say(watch.report(result["window_start"], ctx.seconds))
        _counters(ctx, eng, list(eng.aux_log)[log0:], result, traced,
                  series)
        say(f"memory: the arrays {eng.kv_bytes / 1e9:.3f} GB + the rings "
            f"({eng.state_bytes}), weights {eng.param_bytes / 1e9:.3f} GB")
        say("set-up by phase: " + ", ".join(
            f"{phase} {dt:.1f}" for phase, _, dt in eng.setup_log))
    finally:
        eng.close()
        # the caches' memory is the reference's from here on
        for leaf in jax.tree.leaves(eng.cache):
            leaf.delete()
    replayed = _pick_streams(result["all_reqs"], long_len, mid_len, past)
    excess, stderr, positions, deepest = _streams(
        family, params, replayed, temperature, ticks)
    stats = devices[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    say(f"memory: peak in use {peak / 1e9:.3f} GB of "
        f"{int(stats.get('bytes_limit', 0)) / 1e9:.3f}; as far as each "
        "phase: " + ", ".join(f"{what} {gb:.3f}" for what, gb in peaks)
        + " (the reference runs before the engine is built and after its "
        "caches are given back)")

    def both(big, mean):
        return ", ".join(f"{c} {big[c]:.4f} / {mean[c]:.5f}" for c in READ)

    say(f"probe: prefills of {' and '.join(map(str, chunks))} chunks "
        f"({' and '.join(str(len(r['prompt'])) for r in plan)} tokens) + "
        f"{ticks} ticks of all {eng.slots} slots on the engine's own rings, "
        "pool and indexer keys vs the float32 reference: max |logit diff| "
        f"{probe['program']:.4f}, mean {probe_mean['program']:.5f} (the "
        f"worse request's), largest |logit| {top:.2f}, tolerances "
        f"{LOGIT_TOL} / {LOGIT_MEAN_TOL}; a slot reading the middle slot's "
        f"pages and a copy of its rings sits at most {beside:.5f} from it; "
        f"index_pick_agreement {agreement['program']:.5f} (by request and "
        "full layer "
        + " ; ".join(" / ".join(f"{v:.5f}" for v in r) for r in by_request)
        + f"), floor {PICK_AGREEMENT_FLOOR} (controls: "
        + ", ".join(f"{c} {agreement[c]:.5f}" for c in READ)
        + "); (controls on the first request, largest / mean: A the "
        f"reference with {jnp.dtype(CONTROL_ACT).name} activations, B with "
        "8-bit indexer keys, G without the headwise gate, R without the "
        "latents' rescale, E (read, not judged) with a bfloat16 indexer: "
        f"{both(probe, probe_mean)}; the program's own mean distance to "
        "each control: "
        + ", ".join(f"{c} {nearest[c]:.5f}" for c in nearest) + ")")
    say(f"streams: {len(replayed)} requests of the timed engine replayed "
        f"through the float32 reference to position {deepest} ({positions} "
        f"draws at temperature {temperature}): -log p of a draw sits "
        f"{excess['program']:.5f} above the reference's entropy on average, "
        f"standard error {stderr:.5f}, judged at 3 below: "
        f"{excess['program'] - 3 * stderr:.5f}, tolerance {STREAM_NLL_TOL} "
        "(controls, exact: "
        + ", ".join(f"{c} {v:.5f}" for c, v in excess.items()
                    if c != "program") + ")")
    series["index_pick_agreement"] = agreement["program"]

    def readings(name):
        out = {"probe_logits": probe[name],
               "probe_logits_mean": probe_mean[name],
               "index_pick_agreement": agreement[name]}
        if name == "program":
            out["streams_nll"] = excess[name] - 3 * stderr
        elif name in excess:
            out["streams_nll"] = excess[name]
        return out

    checks = judge(readings("program"))
    checks["streams_nll_within_tolerance"] &= positions > 0
    # a program that left out its gate or its rescale, or kept its indexer's
    # keys in 8 bits, would sit on that control and not on the reference
    checks["probe_nearer_the_reference_than_a_control"] = bool(
        probe_mean["program"] < min(nearest.values()))
    checks["slots_on_one_context_agree"] = beside <= REPLICA_TOL
    checks["no_compile_in_window"] = series["compiles_in_window"] == 0
    if ctx.rehearse:
        # a flipped pick of 16 is six points of agreement: the floor is of
        # 2,048 picks at the published widths
        checks["index_pick_agreement_above_floor"] = \
            agreement["program"] >= 0.8
    else:
        # the limits are of the published widths: only there must each
        # control come out as not correct, by the same judge; and only
        # there are the contexts the cell's own
        window = family.m["sliding_window_size"]
        checks["probe_prefilled_in_three_chunks"] = min(chunks) >= 3
        checks["probe_past_a_grid_step_of_keys"] = min(
            len(r["prompt"]) for r in plan) > past
        checks["probe_past_the_window"] = min(
            len(r["prompt"]) for r in plan) > window
        checks["streams_past_the_window_and_the_picks"] = deepest > max(
            window, family.m["index_topk"])
        for c, what in CONTROLS.items():
            checks[f"control_{what}_not_correct"] = not all(
                judge(readings(c)).values())
    return {"series": series, "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": peak, "trace_dir": result["trace_dir"],
            "window_start": result["window_start"]}
