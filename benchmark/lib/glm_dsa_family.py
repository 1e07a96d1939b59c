"""Family ``glm_dsa`` (``configs/glm-5.2.json``: ``"family_module":
"lib.glm_dsa_family:GlmDsa"``) and the job that serves it under
``serve_open_loop`` (``traffic/serve-docreason-saturated.json``:
``"job_module": "lib.glm_dsa_family:run"``).

The yardsticks of this configuration's kernels are here, all at the
PUBLISHED widths: ``expert_kernel_bytes`` (``moe_expert_roofline.saturated``),
``index_score_bytes`` / ``index_score_flops``
(``index_score_roofline.saturated``: a scored key is 128 bfloat16 = 256 B and
32 heads x 128 multiply-adds; 31 FLOP a byte, an eighth of the v5e's ridge,
so bytes bound it, and the yardstick is still the LARGER of the two times).
The attention over the picked rows has NO roofline here:
``ds_sparse_latent_decode_attn`` reads rows that XLA's gather has just
written, and reads them faster than the HBM peak a yardstick would divide by
(610 MB a tick in 0.66 ms = 920 GB/s against 819; a share of that peak read
102.9 %: my chip run, PR 49, seed 4901001), so only its share of busy time is
reported (``sparse_decode_share.saturated``).  The gather itself, which
fetches the picked rows from the pool and is the mechanism's largest cost, is
in NO metric: its fusions have no name of their own on the op line, and it
stays unmeasured until the harness keeps scopes there (PERF.md sections 5 and
7).

Notes for a reader of the metric files this cell shares (they are not
edited): ``moe_experts_hit`` is of the experts HELD here (16 x 6 layers), not
of the 256 the router ranges over; ``moe_rows_elsewhere`` is about 15/16; the
shared expert is plain XLA matmuls and is in no ``moe_*`` share; the prefill
holds no ``ds_flash_fwd`` (its attention is XLA's, under a mask), so
``flash_fwd_share.saturated`` does not list this cell.

The job is its own ``run``, made of ``serve_job``'s parts as
``lib/axk1_family.py::run`` is: the probe runs on the engine's own two paged
arrays at the mix's longest contexts, prefills in the engine's chunks, the
reference runs while no engine holds the pages' memory, and the limits and
controls are this configuration's.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from . import glm_dsa_reference, serve_job, traffic, yardstick
from .nemotron_h_family import _on_the_engines_cache, _trace_times
from .olmoe_family import _StallWatch
from .yardstick import say

# The limits of this cell's checks (``judge``).  Each lies between two
# readings taken on the chip at the published widths (my chip runs, PR 49,
# second round: the seeds are in PERF.md section 6): the largest the program
# gave over its seeds, and what a control gives.  The controls are read in
# EVERY run through the same ``judge`` in the program's place, and the run is
# not correct unless each judged one comes out as not correct (``run``):
#
# * A, CONTROL_ACT: the reference with its residual stream rounded to float8
#   (e5m2) from the embedding on and after every layer, one precision below
#   the bfloat16 the configuration states;
# * B: the reference with the indexer's KEYS rounded to 8 bits (float8 e4m3)
#   before they score: one precision below the bfloat16 the index cache
#   states;
# * C: the reference WITHOUT an indexer (a query takes the 2,048 positions
#   nearest before it: what a program that skipped the selection, or read a
#   window, would compute);
# * D: the reference whose later layers all take the FIRST full layer's picks
#   (picks taken from the wrong layer);
# * E, READ AND PRINTED, NOT JUDGED: the reference with the indexer's queries,
#   keys and head weights rounded to bfloat16, which is what the configuration
#   states.  It says what the program's distance IS.
#
# WHERE they are read.  The reference runs while no engine holds the pages'
# memory (before it is built, and after it is closed), so its length is the
# cell's own: REFERENCE_LONG holds the longest prompt of the mix and a
# probe's ticks, REFERENCE_MID a prompt past 8,192 keys (``ds_index_score``'s
# first grid step).  The probe is two requests of the schedule's head: the
# LONGEST prompt (16,384 tokens, eight chunks, 12 % of its rows picked) and
# the shortest of those over 8,192 (five chunks, 24 %), prefilled in the
# engine's chunks on the engine's own arrays and then decoded TOGETHER, every
# slot live (the long one in slot 0, the other in the middle slot, and the
# remaining slots reading the other's pages: the same context thirty times,
# whose logits must be the middle slot's).  Every member is read on the
# LONG one, where the program lies farthest from the reference (more scores
# crowd the 2,048th place the longer the context); the other is compared with
# the reference alone.  The streams are the timed engine's own (its compiled
# tick, 32 slots live): the request with the most draws that fits
# REFERENCE_LONG and ends past 8,192 positions, and the one with the most
# that fits REFERENCE_MID, with A beside it.
#
# The reference picks ITS OWN sets, and that sets the level of every reading.
# With weights drawn from a seed a head's softmax over 2,048 picked rows is
# nearly flat (scores of size 0.8), so its output is a mean of ~1,000 random
# value rows and is as large as ONE row's share of it: which rows are in the
# set is all the output is.  Replacing a share f of the rows moves it by
# sqrt(2 f) of its own size, and a bfloat16 score does flip picks at the
# 2,048th place (E).  So the logit limits below are three to five times the
# A.X-K1 cell's, and stand where the 8-bit keys of B are still refused.
CONTROL_ACT = jnp.float8_e5m2
#: the members of one reference call: (round the residual stream, 8-bit
#: indexer keys, no indexer, stale picks, a bfloat16 indexer)
MEMBERS = {"reference": (0, 0, 0, 0, 0), "A": (1, 0, 0, 0, 0),
           "B": (0, 1, 0, 0, 0), "C": (0, 0, 1, 0, 0), "D": (0, 0, 0, 1, 0),
           "E": (0, 0, 0, 0, 1)}
#: the judged controls: each must come out as not correct
CONTROLS = {"A": "low_activations", "B": "index_keys_8bit",
            "C": "no_indexer", "D": "picks_of_the_first_layer"}
#: every member but the reference itself is read and printed on the probe's
#: second request
READ = [name for name in MEMBERS if name != "reference"]
#: the controls whose logits lie far from the reference's
FAR_CONTROLS = ("A", "B", "C")
#: the controls read beside the stream of each reference length
STREAM_CONTROLS = {"long": (), "mid": ("A",)}
# Probe logits over the LAST chunk of a chunked prefill + a page and more of
# decode ticks, against the reference: the LARGEST |program - reference| and
# the MEAN over every position and token, the worse of the two requests (the
# long one's, always); the controls' on the long request.  Readings of the
# sixteen runs that read every member there (seeds 4902001-006, 4903001-006,
# 4903101 and three more; PERF.md section 6).  Largest: program 1.97-2.49, A
# 4.95-5.62, B 2.91-3.31, C 10.9-12.2, D 1.04-1.24, E 1.84-2.85: the limit
# refuses A and C.  Mean: program 0.1754-0.1808, A 0.673-0.677, B
# 0.372-0.376, C 1.647-1.657, D 0.034-0.035, E 0.137-0.143: the limit
# refuses A, B and C.  (At 4,400 positions, the first round's probe, the
# program's mean was 0.140-0.147 and B's 0.294-0.297: both grow with the
# context, as the scores crowd the 2,048th place.)
LOGIT_TOL = 3.6
LOGIT_MEAN_TOL = 0.27
# Streams.  The engine SAMPLES at the configuration's temperature (1.0, what
# the source's line is served at), so an emitted token is a draw and not an
# argmax, and what a replay can say is how likely the draws are: the mean of
# -log p_reference(token) over the replayed positions less the mean entropy of
# p_reference there, which is 0 for draws from the reference itself.  It
# stands far from 0 for draws from OTHER logits (C, no indexer: 2.1), from a
# degraded forward (A, float8 residual: 0.32) or at another temperature, and
# it is BLIND to noise in the logits that is independent of them (such noise
# adds as much cross-entropy as it takes entropy away:
# tests/test_glm_dsa.py): it is the check of the engine's OWN compiled tick,
# 32 slots live at the mix's contexts, for errors of kind (a wrong row, a
# skipped selection, a wrong slot), and the probe's logit distances remain
# the check of degree.  The draws' noise is known (the variance of -log p
# under p, summed): the program's reading is judged at its lower bound,
# excess - 3 standard errors (program -0.027 to 0.054, 0.02 on average over
# eighteen runs, at a standard error of 0.025-0.036); a control's is exact (its
# distribution's cross-entropy, no draw: A 0.286-0.327, C 2.14-2.15).
STREAM_NLL_TOL = 0.1
# index_pick_agreement: of the rows the program's ticks picked on the LONG
# request (both full layers, every probe tick: 16,384 rows and more to pick
# 2,048 from), the share the float32 reference also picks for that query; a
# control's is the share of ITS sets the reference's hold, on the same
# request.  Program 0.9252-0.9302 (first full layer 0.996, second 0.854-0.864,
# behind four layers of bfloat16 residual; the other request, printed: 0.998
# / 0.89-0.90 at 8,289 rows), A 0.702-0.711, B 0.838-0.847, C 0.123-0.127, D
# 0.562-0.563, E 0.939-0.947 (a bfloat16 indexer ALONE flips 5-6 % of the
# picks at this length, 2 % at 4,400).  It is the one limit that refuses D,
# whose logits lie nearer the reference than bfloat16's do (late layers weigh
# little in the logits of random weights).
PICK_AGREEMENT_FLOOR = 0.885
#: positions of the two reference programs, and the rows whose logits a call
#: hands back (a chunk and a probe's ticks; the deepest of a stream's draws)
REFERENCE_LONG = 16512
REFERENCE_MID = 8704
LOGIT_ROWS = 4096
#: decode ticks of a probe: past a page boundary whatever the prompt's
#: length (``page_len`` + a few), forced tokens
PROBE_MARGIN = 4
#: draws a stream's replay needs to be worth its time
STREAM_MIN_TOKENS = 64
#: how far a slot that reads the middle slot's pages may sit from it
REPLICA_TOL = 0.05


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


class GlmDsa:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaModel
        fields = {f.name for f in dataclasses.fields(GlmDsaConfig)}
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
        self.model = GlmDsaModel(GlmDsaConfig(
            **m, param_dtype=cfg_file["dtype"]))
        self.vocab = m["vocab_size"]
        self.layers = m["num_hidden_layers"]
        self.full_layers = list(m["indexer_types"]).count("full")
        self.moe_layers = list(m["mlp_layer_types"]).count("sparse")
        # one program a reference length for every member and every call:
        # the switches, the rows whose picks come back and the first row of
        # the logits are traced
        self._reference = jax.jit(
            lambda p, t, s, rows, first, count: glm_dsa_reference.
            glm_dsa_logits(
                p, t, self.m, act_dtype=CONTROL_ACT, round_acts=s[0] > 0,
                low_keys=s[1] > 0, skip_indexer=s[2] > 0,
                stale_picks=s[3] > 0, bf16_index=s[4] > 0, pick_rows=rows,
                logit_rows=(first, count)), static_argnums=5)

    def make_params(self, seed: int, dtype):
        return serve_job._make_params(self.model, seed, dtype)

    def reference(self, params, tokens, pad_to: int, pick_rows, first: int,
                  members) -> dict:
        """One sequence padded to ``pad_to`` (causal layers keep the padding
        out of the rows before it) through the reference and the controls
        ``members`` names, one program for every call of a length: member ->
        float32 logits of the rows ``first`` .. ``len(tokens)`` (at most
        LOGIT_ROWS of them); and under ``"picks"`` member -> its picked sets
        of the queries ``pick_rows``, bool [full layers, rows, T]."""
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


def _probe_plan(family, params, items, page_len: int, chunk: int,
                long_len: int, mid_len: int, past: int):
    """The probe's two requests, of the schedule's head: the LONGEST prompt
    (cut to what ``long_len`` holds with the ticks), read by every member,
    and the shortest of those over ``past`` tokens (cut to ``mid_len``
    likewise), read by the reference; each with the forced tokens of its
    ticks and the float32 readings on prompt + ticks, taken NOW, while no
    engine holds the pages' memory."""
    ticks = page_len + PROBE_MARGIN
    rng = np.random.default_rng(12345)
    by_len = sorted(items, key=lambda it: len(it.prompt))
    over = [it for it in by_len if len(it.prompt) > past] or by_len[-1:]
    plan = []
    for it, ref_len, members in ((by_len[-1], long_len, list(MEMBERS)),
                                 (over[0], mid_len, ["reference"])):
        prompt = list(it.prompt)[:ref_len - ticks]
        n = len(prompt)
        forced = rng.integers(0, family.vocab, (ticks,)).astype(np.int32)
        first = (n - 1) // chunk * chunk    # the last chunk's first position
        ref = family.reference(params, prompt + forced.tolist(),
                               ref_len, n + np.arange(ticks), first, members)
        plan.append({"prompt": prompt, "forced": forced, "first": first,
                     "ref": ref})
    return plan


def _probe(family, eng, plan, bucket: int, chunk: int):
    """The plan's requests prefilled IN CHUNKS through the model's paged
    serving entry points on the engine's own two arrays (the cell's slots
    and pages; same kernels), then a page and more of decode ticks of EVERY
    slot at once: the long request in slot 0, the other in the middle slot,
    and every remaining slot reading the other's pages (the same context and
    tokens, so the same logits).  Against the plan's reference readings:
    logits of every row of the LAST chunk and of every tick, and the ticks'
    picked sets.  Returns (name -> max |logit diff| to the reference, the
    program's under ``"program"``; name -> mean; control -> the program's
    mean |logit diff| to THAT control; name -> the share of its picks that
    the reference's own sets hold, the program's on the FIRST request, where
    the controls are read; the program's by request and full layer;
    the chunks of each prefill; largest |reference logit|; how far a slot
    that read the middle slot's pages sat from it)."""
    model = family.model
    slots, page_len, max_pages = eng.slots, eng.page_len, eng.max_pages
    ticks = page_len + PROBE_MARGIN
    at = [0, slots // 2]                        # the two requests' slots
    active = np.ones((slots,), bool)

    def prefill(params, cache, tokens, n, done, row):
        logits, k, _, ik = model.prefill_paged(
            params, tokens, n, done, row, cache["k"],
            index_pool=cache["index_k"])
        return dict(cache, k=k, index_k=ik), logits[0]

    def decode(params, cache, lengths, forced, table):
        def tick(carry, tokens):
            k, ik, lengths = carry
            lg, k, _, ik, lengths, aux = model.decode_step_paged(
                params, tokens, k, None, table, lengths, active,
                impl=eng.decode_impl, index_pool=ik, aux=True)
            beside = jnp.max(jnp.abs(lg[1:] - lg[at[1]]).astype(jnp.float32))
            return (k, ik, lengths), (lg[jnp.asarray(at)],
                                      aux["index_picks"][:, jnp.asarray(at)],
                                      beside)

        (k, ik, _), (rest, picks, beside) = jax.lax.scan(
            tick, (cache["k"], cache["index_k"], lengths), forced)
        return (dict(cache, k=k, index_k=ik), rest.astype(jnp.float32),
                picks, jnp.max(beside))

    prefill = _on_the_engines_cache(eng, prefill)
    decode = _on_the_engines_cache(eng, decode)
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
            rows, = prefill(padded, np.int32(len(part)), np.int32(done), row)
        last.append(np.asarray(rows[:n - req["first"]], np.float32))
    rest, picks, beside = decode(lengths, tokens, table)
    rest, picks = np.asarray(rest), np.asarray(picks)
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


def _streams(family, params, reqs, temperature: float, ticks: int):
    """How likely the reference finds what the timed engine DREW: each of
    ``reqs`` (a request of the window, the reference length it fits, the
    controls read beside it) teacher-forced on the engine's own tokens
    through the float32 reference; over the deepest LOGIT_ROWS draws of each,
    -log p_reference(draw) less the entropy of p_reference at that position
    (p = softmax(logits / temperature)), and for a control the cross-entropy
    of ITS distribution under the reference's, less the same entropy.
    Returns (name -> mean excess, the program's under ``"program"``; the
    standard error of the program's; its positions; the deepest position)."""
    total = {"program": 0.0}
    count = {"program": 0}
    var, deepest = 0.0, 0
    for r, ref_len, controls in reqs:
        tokens = list(r.tokens)[:ref_len - len(r.prompt)]
        seq = list(r.prompt) + tokens
        n = min(len(tokens), LOGIT_ROWS)
        first = len(seq) - 1 - n               # the row that drew tokens[-n]
        ref = family.reference(params, seq[:-1], ref_len, np.zeros(ticks),
                               first, ["reference", *controls])
        logp, entropy, spread = _surprise(ref["reference"], temperature)
        total["program"] += float(np.sum(
            -logp[np.arange(n), np.asarray(tokens[-n:])] - entropy))
        var += float(np.sum(spread))
        for c in controls:
            theirs = np.exp(_log_softmax(ref[c] / temperature))
            total[c] = total.get(c, 0.0) + float(np.sum(
                -np.sum(theirs * logp, axis=1, dtype=np.float64) - entropy))
            count[c] = count.get(c, 0) + n
        count["program"] += n
        deepest = max(deepest, len(seq) - 1)
    return ({k: v / max(count[k], 1) for k, v in total.items()},
            float(np.sqrt(var)) / max(count["program"], 1),
            count["program"], deepest)


def _log_softmax(x):
    x = x - x.max(axis=1, keepdims=True)
    return x - np.log(np.sum(np.exp(x), axis=1, keepdims=True))


def _surprise(logits, temperature: float):
    """logits [N, V] -> (log p [N, V] for p = softmax(logits / temperature),
    p's entropy [N], the variance of -log p under p [N]): what -log p(draw)
    is on average for a draw from p, and how far one draw strays from it."""
    logp = _log_softmax(logits / temperature)
    p = np.exp(logp)
    entropy = -np.sum(p * logp, axis=1, dtype=np.float64)
    spread = np.sum(p * np.square(logp), axis=1, dtype=np.float64) \
        - np.square(entropy)
    return logp, entropy, spread


def _pick_streams(reqs, long_len: int, mid_len: int, past: int):
    """Of the timed engine's requests, for each reference length the one
    with the most draws that fit it (LOGIT_ROWS at most are replayed); the
    long one's draws must end over ``past`` positions deep."""
    def draws(r, ref_len):
        return min(len(r.tokens), ref_len - len(r.prompt), LOGIT_ROWS)

    out = []
    for kind, ref_len, deep in (("long", long_len, past), ("mid", mid_len, 0)):
        left = [r for r in reqs if r.error is None
                and draws(r, ref_len) >= min(STREAM_MIN_TOKENS, ref_len // 16)
                and len(r.prompt) + draws(r, ref_len) > deep
                and all(r is not o for o, _, _ in out)]
        if left:
            best = max(left, key=lambda r: (draws(r, ref_len),
                                            len(r.prompt)))
            out.append((best, ref_len, STREAM_CONTROLS[kind]))
    return out


def _counters(ctx, eng, calls, res, traced, series) -> None:
    """What the program counted per call (``ServeEngine.aux_log``) into
    ``series``: per decode tick of the window the expert layers'
    (``moe_experts_hit_pct`` of the held experts x layers,
    ``moe_load_imbalance``, ``moe_rows_elsewhere_pct``) and
    ``index_selected_pct`` (the rows the attention read over the live rows of
    the slots' contexts); ``prefill_chunks_per_request``; and, traced, the
    time the experts' bytes and the indexer's yardstick need at the chip's
    peaks as percentages of the traced window (``moe_`` /
    ``index_score_min_pct_of_traced_window``)."""
    fam = ctx.family
    w0, w1 = res["window_start"], res["window_start"] + ctx.seconds
    held = fam.m["experts_held"][1] * fam.moe_layers
    per_slot = fam.m["num_experts_per_tok"] * fam.moe_layers
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
    whole = sum(1 for v in prefills if v.get("final_chunk", True))
    if whole:
        series["prefill_chunks_per_request"] = len(prefills) / whole
    if ticks:
        live = np.mean([v["latent_kv_tokens"] for v in ticks]) / fam.layers
        say(f"experts: {len(ticks)} decode ticks in the window, hit "
            f"{np.mean(series['moe_experts_hit_pct']):.2f} % of the "
            f"{held} held a tick, busiest over mean "
            f"{np.mean(series['moe_load_imbalance']):.2f}, "
            f"{np.mean(series['moe_rows_elsewhere_pct']):.2f} % of the "
            f"assignments to experts held elsewhere; caches: {live:.0f} live "
            "rows a layer a tick, of which the attention read "
            f"{np.mean(series['index_selected_pct']):.2f} %")
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

        def live(v):        # a tick routes per_slot assignments a slot
            return round((v["moe_rows"] + v["moe_rows_elsewhere"])
                         / per_slot)

        kind = jax.devices()[0].device_kind
        hbm = yardstick.peak(kind, "hbm_bytes_per_s")
        mxu = yardstick.peak(kind, "bf16_flops")
        score_s = max(
            sum(fam.index_score_bytes(v["index_scored_rows"], live(v), item)
                for v in decode) / hbm,
            sum(fam.index_score_flops(v["index_scored_rows"])
                for v in decode) / mxu)
        series["moe_min_pct_of_traced_window"] = \
            100.0 * moe / hbm / (b - a)
        series["index_score_min_pct_of_traced_window"] = \
            100.0 * score_s / (b - a)
        say(f"traced {b - a:.3f} s, {len(decode)} decode ticks: experts "
            f"{moe / 1e9:.2f} GB to move, {moe / hbm:.3f} s at "
            f"{hbm / 1e9:.0f} GB/s; the indexer's yardstick {score_s:.3f} s "
            "(the larger of bytes at the HBM peak and operations at "
            f"{mxu / 1e12:.0f} TFLOP/s, published widths)")


def run(ctx) -> dict:
    """``serve_job.run``'s parts in this cell's order: parameters, the
    reference's readings for the probe (no engine yet: the reference has the
    memory the pages will take), the engine, the probe on its arrays, warm-up,
    the open loop, and, the engine closed and its pages given back, the replay
    of what it drew."""
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
    long_len = min(REFERENCE_LONG, serving["max_seq_len"])
    mid_len = min(REFERENCE_MID, serving["max_seq_len"])
    # ``ds_index_score`` streams 128 pages a grid step: a context is past
    # one when it holds more keys than that
    past = 128 * page_len if 128 * page_len < mid_len else mid_len // 2

    def peak_gb():
        stats = devices[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)) / 1e9

    plan = _probe_plan(family, params, items[:max(16, int(mix["lead_burst"]))],
                       page_len, chunk, long_len, mid_len, past)
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
        say(f"memory: the arrays {eng.kv_bytes / 1e9:.3f} GB "
            f"({eng.state_bytes}), weights {eng.param_bytes / 1e9:.3f} GB")
        say("set-up by phase: " + ", ".join(
            f"{phase} {dt:.1f}" for phase, _, dt in eng.setup_log))
    finally:
        eng.close()
        # the pages' memory is the reference's from here on
        for leaf in jax.tree.leaves(eng.cache):
            leaf.delete()
    replayed = _pick_streams(result["all_reqs"], long_len, mid_len, past)
    excess, stderr, positions, deepest = _streams(
        family, params, replayed, temperature, page_len + PROBE_MARGIN)
    stats = devices[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    say(f"memory: peak in use {peak / 1e9:.3f} GB of "
        f"{int(stats.get('bytes_limit', 0)) / 1e9:.3f}; as far as each "
        "phase: " + ", ".join(f"{what} {gb:.3f}" for what, gb in peaks)
        + " (the reference runs before the engine is built and after its "
        "pages are given back)")

    def both(big, mean):
        return ", ".join(f"{c} {big[c]:.4f} / {mean[c]:.5f}" for c in READ)

    say(f"probe: prefills of {' and '.join(map(str, chunks))} chunks "
        f"({' and '.join(str(len(r['prompt'])) for r in plan)} tokens) + "
        f"{eng.page_len + PROBE_MARGIN} ticks of all {eng.slots} slots on "
        "the engine's own arrays vs the float32 reference: max |logit diff| "
        f"{probe['program']:.4f}, mean {probe_mean['program']:.5f} (the "
        f"worse request's), largest |logit| {top:.2f}, tolerances "
        f"{LOGIT_TOL} / {LOGIT_MEAN_TOL}; a slot reading the middle slot's "
        f"pages sits at most {beside:.5f} from it; index_pick_agreement "
        f"{agreement['program']:.5f} (by request and full layer "
        + " ; ".join(" / ".join(f"{v:.5f}" for v in r) for r in by_request)
        + f"), floor {PICK_AGREEMENT_FLOOR} (controls: "
        + ", ".join(f"{c} {agreement[c]:.5f}" for c in READ)
        + "); (controls on the first request, largest / mean: A the "
        f"reference with {jnp.dtype(CONTROL_ACT).name} activations, B with "
        "8-bit indexer keys, C without an indexer, D with the first full "
        "layer's picks everywhere, E (read, not judged) with a bfloat16 "
        f"indexer: {both(probe, probe_mean)}; the program's own mean "
        "distance to each control: "
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
    # a program that skipped its indexer, or kept its keys in 8 bits, would
    # sit on that control and not on the reference (not D: it lies nearer
    # the reference than bfloat16 does, and the picks' agreement holds it)
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
        checks["probe_prefilled_in_three_chunks"] = min(chunks) >= 3
        checks["probe_past_a_grid_step_of_keys"] = min(
            len(r["prompt"]) for r in plan) > past
        checks["streams_past_a_grid_step_of_keys"] = deepest > past
        for c, what in CONTROLS.items():
            checks[f"control_{what}_not_correct"] = not all(
                judge(readings(c)).values())
    return {"series": series, "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": peak, "trace_dir": result["trace_dir"],
            "window_start": result["window_start"]}
