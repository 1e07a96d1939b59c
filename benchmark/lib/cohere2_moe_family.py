"""Family ``cohere2_moe`` (``configs/command-a-plus-05-2026.json``:
``"family_module": "lib.cohere2_moe_family:Cohere2Moe"``) and the job that
serves it under ``serve_open_loop`` (``traffic/serve-longctx-saturated.json``:
``"job_module": "lib.cohere2_moe_family:run"``).

The yardsticks of this configuration's kernels are here:
``expert_kernel_bytes`` (``moe_expert_roofline.saturated``: three matrices an
expert, walked in blocks or whole, the same bytes), ``window_decode_bytes``
(``window_decode_roofline.saturated``), ``full_decode_bytes``
(``full_decode_roofline.saturated``) and ``flash_fwd_flops``
(``flash_fwd_roofline.saturated``).  All at the PUBLISHED widths (keys and
values 128 wide on 8 key heads), which are the widths the program stores.

Notes for a reader of the metric files this cell shares (they are not
edited): ``moe_experts_hit`` is of the experts HELD here (8 x 4 layers), not
of the 128 the router ranges over; ``moe_rows_elsewhere`` is about 15/16 here
(8 of 128 held); the shared experts are plain XLA matmuls and are in no
``moe_*`` share; the window and full decode metric files speak of MiMo's
widths (192 / 128, a sink): here read 128 / 128 on 8 key heads, no sink, a
ring of 4,096 walked by the slot body in blocks of 512 rows.

The job is its own ``run``, made of ``serve_job``'s parts (its open loop, its
constants) as ``lib/mimo_v2_family.py::run`` is: the probe hands the model
its ``state`` and ``slot`` and prefills in CHUNKS, as the engine does for a
prompt over ``prefill_chunk_len``, and the limits and controls are this
configuration's.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from . import cohere2_moe_reference, serve_job, traffic, yardstick
from .nemotron_h_family import _on_the_engines_cache, _trace_times
from .olmoe_family import _StallWatch
from .yardstick import say

# The limits of this cell's checks (``judge``).  Each lies between two
# readings taken on the chip at the published widths (PERF.md section 6,
# PR 45): the largest the program gave over its seeds, and what a control
# gives.  The controls are read in EVERY run through the same ``judge`` in
# the program's place, and the run is not correct unless each of them
# comes out as not correct (``run``):
#
# * A, CONTROL_ACT: the reference with its residual stream rounded to
#   float8 (e5m2) from the embedding on and after every layer, one precision
#   below the bfloat16 the configuration states;
# * B: the reference with the window layers read as FULL layers (every key
#   ``j <= t``, still rotated): what rings that kept more than the window,
#   or a band mask lost between chunks, would compute past 4,096;
# * C: the reference with the FULL layers rotated as the window layers are;
# * D: the reference with the router, the softmax and the LayerNorm at
#   bfloat16's precision where the configuration says float32.  Against
#   the float32 reference its logits lie about as far off as the program's
#   (the program's activations are bfloat16, as the configuration states),
#   so no distance tells them apart; the DIRECTION does: a variance
#   rounded to bfloat16 scales a whole row of logits by up to 2**-9, and
#   the program's rounding is not of that kind (LOGIT_SCALE_TOL).
CONTROL_ACT = jnp.float8_e5m2
CONTROL_WINDOW = 1 << 30
#: the members of one reference call, in order: (round the residual
#: stream, the window layers' window or 0 for the configuration's, rotate
#: the full layers, router/softmax/LayerNorm in bfloat16)
MEMBERS = {"reference": (0, 0, 0, 0), "A": (1, 0, 0, 0),
           "B": (0, CONTROL_WINDOW, 0, 0), "C": (0, 0, 1, 0),
           "D": (0, 0, 0, 1)}
JUDGED = "ABCD"
#: the controls whose distance to the program is compared with the
#: reference's (a program that computed one of them would sit on it)
NEAREST = "ABC"
#: the control a finished stream is replayed through beside the reference
#: (the one that differs past the window only; a replay is 2 of the 5
#: members, the others are judged by the probe)
STREAM_CONTROLS = "B"
# Probe logits, |program - reference| over the rows of both probes on the
# engine's own pool and rings (512 rows of the last chunk of a chunked
# prefill and its last, and a page and more of decode ticks: 1,162 rows of
# 32,768 logits): their MEAN is the level of the noise and separates the
# coarser precision and the mechanisms.  Over 10 runs at 1,162 rows, a seed
# each (PERF.md section 6, PR 45): program 0.0077-0.0086; B 0.0379-0.0420
# (the nearest: it differs from the reference past the window only), A
# 0.121-0.128, C 0.117-0.133; D 0.0133-0.0160, which this limit does not
# separate.  The LARGEST difference is printed and not judged: it is one
# swapped expert (the router's 8th and 9th of 128 sigmoid scores lie
# close, and bfloat16 activations swap a choice here and there where the
# float32 reference did not: 2-3 % of the program's rows).
LOGIT_MEAN_TOL = 0.02
# The same rows' SCALE error: the part of a row's difference that is the
# reference's own row times a number (``row_scale_error``), averaged over
# the rows.  It holds the program to LayerNorm statistics in float32: a
# mean and a variance rounded to bfloat16 (control D) scale every logit of
# a row alike by up to 2**-9, while the program's roundings (bfloat16
# activations into float32 sums) have no such part.  Over the same runs:
# program 0.00009-0.00015, D 0.00090-0.00104 (B 0.0015-0.0019, A 0.0071-
# 0.0082, C 0.0065-0.0089): the limit lies twice over the one and three
# times under the other.
LOGIT_SCALE_TOL = 0.0003
# Streams: how far below the reference's top logit a token sits that the
# timed engine emitted (the only reading drawn from the window itself), on
# average over the positions of four finished requests, those whose PROMPT
# passed the window first (under this mix all four, 1,511 positions: each
# of their tokens was decoded from wrapped rings with every other slot
# live, after a prefill whose second chunk read the ring the first left).
# One position where bfloat16 swapped the top two is 0.1-0.3, so the sum
# goes by how many a run holds, and A TRANSITION THAT RECURS COUNTS ONCE
# (``_streams``): a greedy stream of random weights falls into a loop, and
# a loop replays its near-tie every period (seed 4501104, run three times:
# one request of the four held 41 such positions of 453, 2 of them first
# seen, and read 0.0136 alone and 0.0041 over the four with every position
# counted, 0.00022 with this counting).  Program, every position counted:
# 0.00002-0.00049 over 24 runs of an earlier round (few positions past the
# window), 0.00002-0.00034 in 14 of this round's 16 such runs and 0.0041 in
# the two of that seed; this counting can only read lower.  B, the control
# replayed beside the reference: 0.0052-0.053 with every position counted,
# 0.0049 in the one run made with this counting; where a seed's loops
# bring it under the limit, B still fails by the probe's mean.  The
# largest such distance is printed and not judged (program 0.01-0.38).
STREAM_MEAN_TOL = 0.0015
#: decode ticks of a probe: past a page boundary whatever the prompt's
#: length (``page_len`` + a few), forced tokens
PROBE_MARGIN = 4
#: rows of a probe's last prefill chunk that are compared, evenly spaced,
#: and its last
PROBE_ROWS = 512


def row_scale_error(rows, want):
    """[R, V] logits and the reference's: per row, the size of the part of
    the difference that is the reference's own row times a number,
    ``|<rows - want, want>| / <want, want>`` (``LOGIT_SCALE_TOL``)."""
    return np.abs(((rows - want) * want).sum(axis=1)
                  / (want * want).sum(axis=1))


def judge(readings: dict) -> dict:
    """The cell's limits on whatever readings are handed in, the program's
    or a control's in its place: check -> within its limit."""
    limits = {"probe_logits_mean": LOGIT_MEAN_TOL,
              "probe_logits_scale": LOGIT_SCALE_TOL,
              "streams_mean": STREAM_MEAN_TOL}
    return {f"{k}_within_tolerance":
            bool(np.isfinite(v) and v <= limits[k])
            for k, v in readings.items()}


class Cohere2Moe:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                                      Cohere2MoeModel)
        fields = {f.name for f in dataclasses.fields(Cohere2MoeConfig)}
        m = {k: v for k, v in cfg_file.items() if k in fields}
        # in the file num_experts counts the experts HELD here; the
        # router's width is the published count
        m["num_experts"] = cfg_file["published"]["num_experts"]
        held = tuple(cfg_file["experts_held"])
        if rehearse:
            sizes = dict(cfg_file["rehearse"]["sizes"])
            held = tuple(sizes.pop("experts_held"))
            m.update(sizes)
        self.m = m = {**m, "experts_held": held}
        self.model = Cohere2MoeModel(Cohere2MoeConfig(
            **m, param_dtype=cfg_file["dtype"]))
        self.vocab = m["vocab_size"]
        self.layers = self.model.serving_cache_layers()
        # one program for every member and every call: the switches are
        # traced.  A member a call, not a loop over members inside one
        # program: XLA hoists the float32 copies of every weight out of
        # such a loop
        self._reference = jax.jit(
            lambda p, t, s: cohere2_moe_reference.cohere2_moe_logits(
                p, t, self.m, act_dtype=CONTROL_ACT, round_acts=s[0] > 0,
                window=jnp.where(s[1] > 0, s[1], m["sliding_window"]),
                rotate_full=s[2] > 0, low=s[3] > 0, block=128)[0])

    def make_params(self, seed: int, dtype):
        return serve_job._make_params(self.model, seed, dtype)

    def reference(self, params, tokens, pad_to: int, rows,
                  controls: str = JUDGED) -> dict:
        """One sequence padded to ``pad_to`` (causal layers keep the
        padding out of the rows before it) through the reference and
        ``controls``, one program for every call: member -> float32 logits
        [len(rows), V] at positions ``rows``."""
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            return {name: np.asarray(self._reference(
                params, padded,
                np.asarray(MEMBERS[name], np.int32)))[rows]
                for name in ("reference", *controls)}

    def expert_kernel_bytes(self, experts_hit: int, rows: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_moe_gate_up`` + ``ds_moe_down`` must move for
        ``rows`` (token, held expert) assignments over ``experts_hit``
        held experts (both summed over layers): each hit expert's three
        matrices once (in blocks or whole: the same bytes); per row, x in
        and h out (gate_up), h in and y out (down)."""
        d, f = self.m["hidden_size"], self.m["intermediate_size"]
        return itemsize * (experts_hit * 3 * d * f + rows * 2 * (d + f))

    def _query_bytes(self, slots: int) -> int:
        """The queries in and the outputs out, a layer call."""
        return slots * self.m["num_attention_heads"] * 2 * self.m["head_dim"]

    def _kv_bytes(self, rows: int) -> int:
        """A key and a value of every key head for ``rows`` tokens."""
        return rows * self.m["num_key_value_heads"] * 2 * self.m["head_dim"]

    def window_decode_bytes(self, window_kv_rows: int, slots: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_window_decode_attn`` must move in a decode tick
        whose active slots hold ``window_kv_rows`` ring rows a window
        layer: every live row of every key head and window layer, + the
        queries and outputs."""
        return itemsize * self.layers["window"] * (
            self._kv_bytes(window_kv_rows) + self._query_bytes(slots))

    def full_decode_bytes(self, full_kv_tokens: int, slots: int,
                          itemsize: int) -> int:
        """The same for ``ds_paged_decode_attn``: ``full_kv_tokens`` keys
        a full layer over the active slots."""
        return itemsize * self.layers["full"] * (
            self._kv_bytes(full_kv_tokens) + self._query_bytes(slots))

    def flash_fwd_flops(self, live_pairs: float) -> float:
        """Operations ``ds_flash_fwd*`` must do for ``live_pairs`` (query,
        key) pairs the masks let through a head (summed over layers, the
        program's counter ``flash_live_keys`` x 1,024): for each of the
        query heads a score over the head's width and a weighted sum of a
        value as wide, a multiply and an add each."""
        return live_pairs * self.m["num_attention_heads"] \
            * 4 * self.m["head_dim"]


def _probe_plans(items, window: int, ticks: int):
    """The probes: (what, prompt).  ``by_prompt``: a prompt of the window
    and an eighth more, prefilled in the engine's chunks, so that every
    chunk after the first reads the window layers' rings and the full
    layer's pages and the context passes the window before the first
    tick; ``while_decoding``: a prompt that ends half the ticks short of
    the window, so that the rings wrap under the decode kernel.  Tokens
    are the schedule's own."""
    tokens = [t for it in items for t in it.prompt]
    need = window + window // 8
    tokens = (tokens * (need // len(tokens) + 1))[:need]
    short = max(window - ticks // 2, 1)
    return [("by_prompt", tokens), ("while_decoding", tokens[-short:])]


def _probe(family, eng, params, items, ref_len: int):
    """A prefill in chunks of ``serving.prefill_chunk_len``, as the engine
    cuts a prompt, and a page and more of decode ticks of each of
    ``_probe_plans``' requests through the model's paged serving entry
    points, on the engine's own pool and window state (the cell's slots
    and pages; same kernels; one request live, in the middle slot)
    against the plain reference on the same context: logits of
    ``PROBE_ROWS`` rows and the last of the last chunk and of every tick,
    and that the rings of the slots beside it keep what they held.
    Returns (member -> (max, mean, scale) of the logit differences to the
    reference, the program's under ``"program"``: the largest and the mean
    |difference| and the mean over rows of the rows' |scale error|
    (``LOGIT_SCALE_TOL``); nearest control -> the program's MEAN
    |logit diff| to THAT control over the same rows (the mean and not
    the largest: where a control differs from the reference by a few keys
    only, as B does just past the window, the largest is one swapped
    expert on either side); largest |reference logit|; whether the slots
    beside it kept what they held)."""
    model = family.model
    slots, page_len, max_pages = eng.slots, eng.page_len, eng.max_pages
    slot, ticks = slots // 2, page_len + PROBE_MARGIN
    active = np.zeros((slots,), bool)
    active[slot] = True

    # three programs on the engine's own cache, called from the host as the
    # engine calls its own (a chunk a call, a tick a call): inside ONE
    # program with the chunks and the ticks as loops the compiler is free
    # to keep the loop-carried rings in another layout, and copies them
    # (2.25 GB a leaf at the published widths)
    def mark(params, cache):
        # rings that are not zero where the request lands and beside it:
        # the prefill must write its own and leave the others
        state = {name: jax.lax.dynamic_update_slice_in_dim(
            leaf, jnp.full(leaf.shape[:1] + (3,) + leaf.shape[2:], 0.5,
                           leaf.dtype), slot - 1, axis=1)
            for name, leaf in cache["state"].items()}
        return dict(cache, state=state), jnp.int32(0)

    def chunk(params, cache, toks, n, prefix, row):
        logits, k, v, state = model.prefill_paged(
            params, toks[None], n, prefix, row, cache["k"], cache["v"],
            state=cache["state"], slot=np.int32(slot))
        at = jnp.concatenate([jnp.arange(PROBE_ROWS) * n // PROBE_ROWS,
                              jnp.maximum(n - 1, 0)[None]])
        lengths = cache["lengths"].at[slot].set(prefix + n)
        return (dict(cache, k=k, v=v, state=state, lengths=lengths),
                logits[0][at].astype(jnp.float32))

    def tick(params, cache, token, table):
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(token)
        lg, k, v, state, lengths = model.decode_step_paged(
            params, tokens, cache["k"], cache["v"], table, cache["lengths"],
            active, state=cache["state"], impl=eng.decode_impl)
        beside = jnp.all(jnp.stack([
            jnp.all(leaf[:, s] == 0.5)
            for leaf in state.values() for s in (slot - 1, slot + 1)]))
        return (dict(cache, k=k, v=v, state=state, lengths=lengths),
                lg[slot].astype(jnp.float32), beside)

    mark, chunk, tick = (_on_the_engines_cache(eng, f)
                         for f in (mark, chunk, tick))

    def run(chunks, lens, forced, row, table):
        mark()
        sampled, prefix = [], 0
        for toks, n in zip(chunks, lens):
            sampled.append(np.asarray(chunk(toks, n, np.int32(prefix),
                                            row)[0]))
            prefix += int(n)
        rest = [tick(token, table) for token in forced]
        return (sampled, np.stack([np.asarray(lg) for lg, _ in rest]),
                bool(rest[-1][1]))

    rng = np.random.default_rng(12345)
    names = ["program", *JUDGED]
    worst = dict.fromkeys(names, 0.0)
    total, scale = dict.fromkeys(names, 0.0), dict.fromkeys(names, 0.0)
    count = rows_seen = 0
    nearest = dict.fromkeys(NEAREST, 0.0)
    top, untouched = 0.0, True
    step = eng.prefill_chunk_len
    plans = _probe_plans(items, family.m["sliding_window"], ticks)
    for what, prompt in plans:
        forced = rng.integers(0, family.vocab, (ticks,)).astype(np.int32)
        # as many chunks for every probe, the last ones empty for the
        # shorter: one program for every probe
        starts = np.arange(0, max(len(p) for _, p in plans), step)
        lens = np.clip(len(prompt) - starts, 0, step).astype(np.int32)
        chunks = np.zeros((len(starts), step), np.int32)
        for i, (a, n) in enumerate(zip(starts, lens)):
            chunks[i, :n] = prompt[a:a + n]
        n_pages = -(-(len(prompt) + ticks) // page_len)
        row = np.zeros((max_pages,), np.int32)
        row[:n_pages] = 1 + np.arange(n_pages)
        table = np.zeros((slots, max_pages), np.int32)
        table[slot] = row
        sampled, rest, beside = run(chunks, lens, forced, row, table)
        last = int(np.flatnonzero(lens)[-1])    # the last chunk that ran
        start, n = int(lens[:last].sum()), int(lens[last])
        # the sampled rows of the last chunk and its last, then tick i's
        # logits at position len(prompt) + i: every forced token is fed
        at = np.concatenate([start + np.arange(PROBE_ROWS) * n // PROBE_ROWS,
                             [len(prompt) - 1],
                             len(prompt) + np.arange(ticks)])
        got = np.concatenate([np.asarray(sampled)[last], np.asarray(rest)])
        seq = list(prompt) + [int(t) for t in forced]
        ref = family.reference(params, seq, ref_len, at)
        ref["program"] = got
        want = ref["reference"]
        top = max(top, float(np.abs(want).max()))
        untouched &= bool(beside)
        count += want.size
        rows_seen += len(want)
        for name in names:
            diff = ref[name] - want
            worst[name] = max(worst[name], float(np.abs(diff).max()))
            total[name] += float(np.abs(diff).sum())
            scale[name] += float(row_scale_error(diff + want, want).sum())
        for c in nearest:
            nearest[c] += float(np.abs(got - ref[c]).sum())
        say(f"probe {what}: prompt {len(prompt)} in chunks of {lens.tolist()}"
            f", {ticks} ticks to {len(seq)}: max |logit diff| "
            f"{float(np.abs(got - want).max()):.4f}")
    return ({n: (worst[n], total[n] / max(count, 1),
                 scale[n] / max(rows_seen, 1)) for n in names},
            {c: v / max(count, 1) for c, v in nearest.items()}, top,
            untouched)


def _streams(family, params, reqs, ref_len: int):
    """``serve_job._stream_slack`` with the controls beside it: how far
    below the reference's top logit a token sits, over whole finished
    streams of the timed engine (teacher-forced on the engine's own
    tokens; a transition that recurs counted once, over all positions),
    for the tokens the engine emitted and, each of
    ``STREAM_CONTROLS``, for those it would have.  Returns (name ->
    (largest, mean) slack, positions, the program's mean by request as
    text)."""
    names = ["program", *STREAM_CONTROLS]
    worst, total = dict.fromkeys(names, 0.0), dict.fromkeys(names, 0.0)
    positions, by_request = 0, []
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        at = np.arange(len(r.tokens))
        ref = family.reference(params, seq[:-1], ref_len,
                               len(r.prompt) - 1 + at, STREAM_CONTROLS)
        rows = ref["reference"]
        below = rows.max(axis=1)[:, None] - rows
        picks = {"program": np.asarray(r.tokens)}
        picks.update({c: ref[c].argmax(axis=1) for c in names[1:]})
        fed = np.asarray(seq[len(r.prompt) - 1:-1])
        for name, tokens in picks.items():
            slack = below[at, tokens]
            # a greedy stream that has fallen into a loop replays the same
            # near-tie once a period: a transition (token fed, token
            # picked) counts where it is first seen
            once = np.zeros(len(tokens), bool)
            once[np.unique(np.stack([fed, tokens]), axis=1,
                           return_index=True)[1]] = True
            worst[name] = max(worst[name], float(slack.max()))
            total[name] += float(slack[once].sum())
            if name == "program":
                by_request.append(
                    f"{len(r.prompt)} + {len(r.tokens)}: "
                    f"{float(slack[once].sum()) / len(tokens):.5f} "
                    f"({int((slack > 0.02).sum())} over 0.02, "
                    f"{int((slack[once] > 0.02).sum())} of them first "
                    f"seen; all counted {float(slack.mean()):.5f})")
        positions += len(r.tokens)
    return ({n: (worst[n], total[n] / max(positions, 1)) for n in names},
            positions, "; ".join(by_request))


def _counters(ctx, eng, calls, res, traced, series) -> None:
    """What the program counted per call (``ServeEngine.aux_log``) into
    ``series``: per decode tick of the window the expert layers'
    (``moe_experts_hit_pct`` of the held experts x layers,
    ``moe_load_imbalance``, ``moe_rows_elsewhere_pct``) and
    ``window_wrapped_pct`` (of the active slots, those whose context has
    passed the window); ``prefill_chunks_per_request`` (the window's
    prefill calls over the requests they prefilled); and, traced, the time
    the experts', the window kernel's and the paged kernel's bytes need at
    the chip's HBM peak and the prefills' attention operations at its MXU
    peak, as percentages of the traced window
    (``moe_`` / ``window_`` / ``full_`` / ``flash_min_pct_of_traced_window``)."""
    fam = ctx.family
    w0, w1 = res["window_start"], res["window_start"] + ctx.seconds
    layers = fam.m["num_hidden_layers"]
    held = fam.m["experts_held"][1] * layers
    per_slot = fam.m["num_experts_per_tok"] * layers

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
    series["window_wrapped_pct"] = [
        100.0 * v["window_wrapped_slots"] / max(live(v), 1) for v in ticks]
    whole = sum(1 for v in prefills if v.get("final_chunk", True))
    if whole:
        series["prefill_chunks_per_request"] = len(prefills) / whole
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
            "rows a window layer a tick, "
            f"{np.mean(series['window_wrapped_pct']):.2f} % of the active "
            "slots past the window")
    wanted = eng.prefill_tokens
    say(f"prefills: {len(prefills)} calls in the window for {whole} "
        f"requests ({sum(eng.prefill_chunk_calls.values())} chunk calls in "
        f"all); {eng.prefill_pad_tokens} of "
        f"{wanted + eng.prefill_pad_tokens} tokens the prefills ran were "
        f"padding ({100.0 * eng.prefill_pad_tokens / max(wanted + eng.prefill_pad_tokens, 1):.1f} %)")
    if len(traced) == 2 and not ctx.rehearse:
        a, b = traced
        item = jnp.dtype(ctx.cfg_file["dtype"]).itemsize
        in_trace = [(kind, v) for t, kind, v in calls if a <= t < b]
        decode = [v for kind, v in in_trace if kind == "decode"]
        moe = sum(fam.expert_kernel_bytes(
            v["moe_experts_hit"], v["moe_rows"], item) for _, v in in_trace)
        window = sum(fam.window_decode_bytes(
            v["window_kv_rows"], live(v), item) for v in decode)
        full = sum(fam.full_decode_bytes(
            v["full_kv_tokens"], live(v), item) for v in decode)
        flash = sum(fam.flash_fwd_flops(1024.0 * v["flash_live_keys"])
                    for kind, v in in_trace if kind == "prefill")
        kind = jax.devices()[0].device_kind
        hbm = yardstick.peak(kind, "hbm_bytes_per_s")
        mxu = yardstick.peak(kind, "bf16_flops")
        for name, seconds in (("moe", moe / hbm), ("window", window / hbm),
                              ("full", full / hbm), ("flash", flash / mxu)):
            series[f"{name}_min_pct_of_traced_window"] = \
                100.0 * seconds / (b - a)
        say(f"traced {b - a:.3f} s, {len(decode)} decode ticks: experts "
            f"{moe / 1e9:.2f} GB, window rings {window / 1e9:.2f} GB, "
            f"full-layer keys {full / 1e9:.2f} GB to move, at "
            f"{hbm / 1e9:.0f} GB/s {moe / hbm:.3f}, {window / hbm:.3f} and "
            f"{full / hbm:.3f} s; the prefills' attention "
            f"{flash / 1e12:.2f} TFLOP, at {mxu / 1e12:.0f} TFLOP/s "
            f"{flash / mxu:.3f} s")


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
    # one reference program for every replay: the longer probe (the window
    # and an eighth, its ticks) with room.  No longer: at 5,760 and 6,272
    # positions the program no longer fits beside the engine's and every
    # call took three times as long (PERF.md section 6, PR 45)
    bucket, window = serving["prefill_len"], family.m["sliding_window"]
    ref_len = min(bucket + bucket // 4 + 2 * serving["page_len"],
                  serving["max_seq_len"])
    try:
        probe, nearest, top, untouched = _probe(
            family, eng, params, items[:serve_job.PROBE_REQUESTS], ref_len)

        # warm the programs of the engine on the shapes the traffic uses:
        # both rungs, a chunked prompt, the tick
        for n in (bucket + bucket // 2, bucket // 2):
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
        say(watch.report(result["window_start"], ctx.seconds))
        _counters(ctx, eng, list(eng.aux_log)[log0:], result, traced,
                  series)
        # those whose prompt passed the window first: every token of
        # theirs was decoded from wrapped rings beside the other slots',
        # after a prefill whose second chunk read the ring the first left
        done = sorted((r for r in result["all_reqs"]
                       if r.done.is_set() and r.error is None
                       and len(r.prompt) + len(r.tokens) <= ref_len),
                      key=lambda r: len(r.prompt) <= window)
        done = done[:serve_job.STREAM_REQUESTS]
        streams, positions, by_request = _streams(family, params, done,
                                                  ref_len)
        stats = devices[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
    finally:
        eng.close()

    def both(readings):
        return ", ".join(
            f"{c} " + " / ".join(f"{v:.5f}" for v in readings[c])
            for c in readings if c != "program")

    say(f"probe: a chunked prefill + {eng.page_len + PROBE_MARGIN} ticks of "
        f"{len(MEMBERS) - 3} requests on the engine's own {eng.slots} slots "
        f"vs the float32 reference: |logit diff| largest / mean / the "
        f"rows' mean |scale error| {probe['program'][0]:.4f} / "
        f"{probe['program'][1]:.5f} / {probe['program'][2]:.6f}, largest "
        f"|logit| {top:.2f}, tolerance on the mean {LOGIT_MEAN_TOL}, on "
        f"the scale error {LOGIT_SCALE_TOL} "
        f"(controls, A the reference with {jnp.dtype(CONTROL_ACT).name} "
        "activations, B the window layers read as full, C the full layers "
        "rotated, D router, softmax and LayerNorm in bfloat16: "
        f"{both(probe)}; the program's own mean distance to a "
        "control: "
        + ", ".join(f"{c} {nearest[c]:.5f}" for c in nearest)
        + f"); the rings of the slots beside it untouched: {untouched}")
    say(f"streams: {len(done)} finished requests replayed through the "
        f"float32 reference ({positions} positions, "
        f"{sum(len(r.tokens) for r in done if len(r.prompt) > window)} "
        f"of them past the window by the prompt): an emitted token sits "
        f"at most {streams['program'][0]:.4f} and on average "
        f"{streams['program'][1]:.5f} below the reference's top logit, "
        f"tolerance on the mean {STREAM_MEAN_TOL} (by request, prompt + "
        f"tokens: mean: {by_request}; controls, largest / mean: "
        f"{both(streams)})")

    def readings(name):
        # a control that no stream was replayed through is judged by the
        # probe's readings
        return {"probe_logits_mean": probe[name][1],
                "probe_logits_scale": probe[name][2],
                **({"streams_mean": streams[name][1]}
                   if name in streams else {})}

    checks = judge(readings("program"))
    checks["streams_mean_within_tolerance"] &= positions > 0
    # a program that read its window layers as full, or rotated its full
    # layers, would sit on that control and not on the reference
    checks["probe_nearer_the_reference_than_a_control"] = bool(
        probe["program"][1] < min(nearest.values()))
    checks["probe_left_other_slots_alone"] = bool(untouched)
    checks["no_compile_in_window"] = series["compiles_in_window"] == 0
    if ctx.rehearse:
        # the random part of a row's scale error goes as 1 / sqrt(hidden
        # size): at the rehearsal's 64 it stands where the limit does
        del checks["probe_logits_scale_within_tolerance"]
    else:
        # the limits are of the published widths: only there must each
        # control come out as not correct, by the same judge
        for c, what in (("A", "low_activations"), ("B", "window_as_full"),
                        ("C", "full_rotated"),
                        ("D", "low_router_softmax_norm")):
            checks[f"control_{what}_not_correct"] = not all(
                judge(readings(c)).values())
    return {"series": series, "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": peak, "trace_dir": result["trace_dir"],
            "window_start": result["window_start"]}
