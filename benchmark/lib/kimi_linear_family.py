"""Family ``kimi_linear`` (``configs/kimi-linear-48b-a3b.json``:
``"family_module": "lib.kimi_linear_family:KimiLinear"``) and the job that
serves it under ``serve_open_loop`` (``traffic/serve-longgen-saturated
.json``: ``"job_module": "lib.kimi_linear_family:run"``).

The yardsticks of this configuration's kernels are here:
``expert_kernel_bytes`` (``moe_expert_roofline.saturated``: three matrices
an expert), ``kda_decode_bytes`` (``kda_decode_roofline.saturated``: the
float32 state of each live slot and KDA layer in and out, + the kernel's
small operands; bandwidth bounds it) and ``latent_decode_bytes`` /
``latent_decode_flops`` (``latent_decode_roofline.saturated``, under A.X-K1's
counter ``latent_kv_tokens``: the PUBLISHED bytes of a row, 1,152 B, and the
LARGER of the two times, as ``lib/axk1_family.py`` says why).

Notes for a reader of the metric files this cell shares (they are not
edited): ``moe_experts_hit`` is of the experts HELD here (32 x 7 layers),
not of the 256 the router ranges over; ``moe_rows_elsewhere`` is about 7/8;
the shared expert is plain XLA matmuls and is in no ``moe_*`` share;
``latent_decode_roofline`` says 64 heads, and here they are 32.

The job is its own ``run``, made of ``serve_job``'s parts (its open loop,
its constants) as ``lib/nemotron_h_family.py::run`` is: the probe hands the
model its ``state`` and ``slot`` and runs one request's prompt in chunks,
and the limits and controls are this configuration's.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from . import kimi_linear_reference, serve_job, traffic, yardstick
from .nemotron_h_family import (_on_the_engines_cache, _relative,
                                _trace_times)
from .olmoe_family import _StallWatch
from .yardstick import say

# The limits of this cell's checks (``judge``).  Each lies between two
# readings taken on the chip at the published widths (PERF.md section 6,
# PR 52): the largest the program gave over its seeds, and what a control
# gives.  The controls are read in EVERY run through the same ``judge`` in
# the program's place, and the run is not correct unless each comes out as
# not correct (``run``):
#
# * CONTROL_ACT: the reference with its residual stream rounded to float8
#   (e5m2) from the embedding on and after every layer, one precision below
#   the bfloat16 the configuration states, against itself in float32: its
#   logits and first KDA layer's state on the probe's sequences, and the
#   tokens it would have emitted on the streams'.  (A reference whose
#   matmuls run in bfloat16 is no control: that is the precision the
#   program itself runs in, and it reads what the program reads, as
#   ``lib/axk1_family.py`` found of its control C.)
# * the reference's recurrence with a bfloat16 state, in the place of
#   ``ds_kda_decode`` and the chunked form.
CONTROL_ACT = jnp.float8_e5m2
# Probe logits over prefill (one request's in chunks) + a page and more of
# decode ticks of PROBE_REQUESTS requests on the engine's own pool and
# state, a tick a program call, against the reference: the LARGEST |program - reference| and the MEAN
# over every position and token.  Activations and logits are bfloat16 and
# logits of random weights reach |5|, where a bfloat16 step is 0.031.  The
# largest is a swapped expert at the router's 8th place (the 8th and 9th
# of 256 sigmoid scores lie close; a swapped expert weighs 2.446 / 8 of
# the routed sum); the mean is the level of the noise.  Over 34 runs, a
# seed each (my chip runs, PR 52): largest, program 0.46-0.75, control
# 1.51-1.87; mean, program 0.027-0.040, control 0.235-0.245.
LOGIT_TOL = 1.0
LOGIT_MEAN_TOL = 0.06
# Streams: how far below the reference's top logit a token sits that the
# timed engine emitted (the only reading drawn from the window itself), on
# average over the 2,000-3,100 positions of the four finished requests
# replayed.  Program 0.0034-0.0050, control 0.180-0.200.  (The largest such
# distance, 0.23-0.47 for the control's 1.4-1.8, is printed and not
# judged, as in ``lib/axk1_family.py``.)  A tick that lost its
# convolution's tail read 3.1 here while the probe's ticks, then a scan in
# one program, read right: the streams are what caught it.
STREAM_MEAN_TOL = 0.03
# The first KDA layer's float32 state after the prompt and the ticks
# against the reference's, largest |diff| over largest |reference|.  The
# first layer, because its input (the embedding) is the same on both sides;
# what differs is bfloat16 matmuls' rounding of q, k, v and the gates.  It
# catches a state that is stale, started from what the slot held, taken in
# past the prompt's true length or written to another slot (all of order
# 1), not the state's own arithmetic.  Program 2.5e-3 to 5.8e-3, control
# 6.3e-2 to 9.3e-2.
STATE_VS_REFERENCE_TOL = 3e-2
# The state's own arithmetic, on identical inputs: ARITHMETIC_TICKS decode
# updates through ds_kda_decode on the engine's own state, and the chunked
# form from a state that is not zero, against the reference's
# token-by-token recurrence at full precision; largest |diff| over largest
# |reference|.  float32 on both sides: they differ by summation order
# (1.7e-7 and 2.1e-5 read); the same recurrence with a bfloat16 state by
# 2**-9 a step (1.2e-2).
STATE_TOL = 1e-4
ARITHMETIC_TICKS = 256
#: the sequence the router's bias is balanced on, [1, tokens]
BALANCE_TOKENS = (1, 1024)
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


class KimiLinear:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.kimi_linear import (KimiLinearConfig,
                                                      KimiLinearModel)
        fields = {f.name for f in dataclasses.fields(KimiLinearConfig)}
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
        self.model = KimiLinearModel(KimiLinearConfig(
            **m, param_dtype=cfg_file["dtype"]))
        cfg = self.model.config
        self.vocab = m["vocab_size"]
        self.kda_layers, self.mla_layers = cfg.count("kda"), cfg.count("mla")
        self.moe_layers = cfg.count("moe")
        # one program for every call: the switch is traced
        self._reference = jax.jit(
            lambda p, t, n, low: kimi_linear_reference.kimi_linear_logits(
                p, t, self.m, act_dtype=CONTROL_ACT, round_acts=low > 0,
                length=n))

    def make_params(self, seed: int, dtype):
        """``serve_job._make_params``, then the selection bias a trained
        router has, for weights drawn from a seed: the source's own load
        balancing, run by the REFERENCE on tokens drawn from the seed
        (``kimi_linear_reference.balance_router_bias``; the
        configuration's ``assumed.e_score_correction_bias``)."""
        params = serve_job._make_params(self.model, seed, dtype)
        tokens = np.random.default_rng([int(seed), 3]).integers(
            0, self.vocab, BALANCE_TOKENS).astype(np.int32)
        with jax.default_matmul_precision("highest"):
            bias = jax.jit(
                lambda p, t: kimi_linear_reference.balance_router_bias(
                    p, t, self.m))(params, tokens)
        return dict(params, moe=dict(
            params["moe"], router_bias=tuple(bias.astype(dtype))))

    def reference(self, params, tokens, pad_to: int):
        """One sequence padded to ``pad_to`` (causal layers keep the
        padding out of the rows before it; the recurrence stops at the
        true length) through the reference and its control, one program
        for both: (logits [2, T, V], the KDA layers' states after the
        sequence [2, layers, H, dk, dv]) for T = ``len(tokens)``; member 0
        is the float32 reference, member 1 the control with CONTROL_ACT
        activations."""
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            got = [self._reference(params, padded, np.int32(len(tokens)),
                                   np.int32(low)) for low in (0, 1)]
        return (np.stack([np.asarray(g[0][0, :len(tokens)]) for g in got]),
                np.stack([np.asarray(g[1][0]) for g in got]))

    def expert_kernel_bytes(self, experts_hit: int, rows: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_moe_gate_up`` + ``ds_moe_down`` must move for
        ``rows`` (token, held expert) assignments over ``experts_hit``
        held experts (both summed over layers): each hit expert's three
        matrices once; per row, x in and h out (gate_up), h in and y out
        (down)."""
        d, f = self.m["hidden_size"], self.m["moe_intermediate_size"]
        return itemsize * (experts_hit * 3 * d * f + rows * 2 * (d + f))

    def kda_decode_bytes(self, slot_layers: int) -> int:
        """HBM bytes ``ds_kda_decode`` must move for ``slot_layers`` (live
        slot, KDA layer) pairs: the float32 state in and out, and the
        kernel's small operands as it takes them (``a``, ``k``, ``b k``
        and ``q`` a column a head, ``b v`` in and ``o`` out a row a head).
        The convolution, the projections and the gates are XLA's, not this
        kernel's."""
        lin = self.m["linear_attn_config"]
        H, dk = lin["num_heads"], lin["head_dim"]
        return slot_layers * 4 * (2 * H * dk * dk + 4 * H * dk + 2 * H * dk)

    def _latent_row(self) -> int:
        """A cached row as PUBLISHED: ``[c_kv ; k_pe]``."""
        return self.m["kv_lora_rank"] + self.m["qk_rope_head_dim"]

    def latent_decode_bytes(self, latent_kv_tokens: int, slots: int,
                            itemsize: int) -> int:
        """HBM bytes ``ds_latent_decode_attn`` must move in a decode tick
        that read ``latent_kv_tokens`` live rows (summed over the latent
        layers) for ``slots`` live slots: every live row once, + a layer
        call's queries in (a head ``[q_lat ; q_pe]``) and outputs out (a
        head ``kv_lora_rank`` wide)."""
        heads = self.m["num_attention_heads"]
        per_slot = heads * (self._latent_row() + self.m["kv_lora_rank"])
        return itemsize * (latent_kv_tokens * self._latent_row()
                           + self.mla_layers * slots * per_slot)

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
    pool and request state (the cell's slots and pages; same kernels; one
    request live, in the middle slot) against the plain reference on the
    same context: logits of every step, the first KDA layer's state at the
    end, and that the slots beside it keep what they held.  The LAST
    request's prompt runs in two chunks of half the bucket, the second from
    the slot's state and the request's pages.  Returns (the program's
    readings, the control's, largest |reference logit|, whether the slots
    beside it kept what they held)."""
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
        logits, k, _, state = model.prefill_paged(
            params, tokens, n, done, row, cache["k"], None,
            state=cache["state"], slot=np.int32(slot))
        return (dict(cache, k=k, state=state),
                jax.lax.dynamic_index_in_dim(logits[0], n - 1, 0, False))

    def tick(params, cache, token, table):
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(token)
        lg, k, _, state, lengths = model.decode_step_paged(
            params, tokens, cache["k"], None, table, cache["lengths"],
            active, state=cache["state"], impl=eng.decode_impl)
        return (dict(cache, k=k, state=state, lengths=lengths),
                lg[slot].astype(jnp.float32))

    def after(params, cache, n):
        """Sets the slot's length (``n`` before the ticks, 0 after them)
        and reads the first KDA layer's state of the slot and whether the
        slots beside it hold what they held."""
        state = cache["state"]
        beside = jnp.all(jnp.stack([
            jnp.all(leaf[:, s] == 0.5)
            for leaf in state.values() for s in (slot - 1, slot + 1)]))
        lengths = jnp.zeros_like(cache["lengths"]).at[slot].set(n)
        return dict(cache, lengths=lengths), state["kda"][0, slot], beside

    # a chunk and a tick a program each, called from the host as the
    # engine calls its own (in ONE program a loop's carries are laid out
    # anew, and the compiler's choices for a tick alone go unseen: an
    # in-place update of the tails that ran twice on the chip did)
    poison, chunk, tick, after = (_on_the_engines_cache(eng, fn)
                                  for fn in (poison, chunk, tick, after))
    longest = min(bucket, ref_len - n_ticks)
    rng = np.random.default_rng(12345)
    keys = ("probe_logits", "probe_logits_mean", "probe_state")
    sound, control = dict.fromkeys(keys, 0.0), dict.fromkeys(keys, 0.0)
    top, untouched = 0.0, True
    for i, it in enumerate(items):
        prompt = list(it.prompt)[:longest]
        width = bucket
        if i == len(items) - 1:
            # in two chunks of the lower rung, the second with rows of
            # its own whatever the prompt's length
            width = bucket // 2
            prompt = (prompt * (1 + longest // len(prompt)))[:longest - 7]
        forced = rng.integers(0, family.vocab, (n_ticks,)).astype(np.int32)
        n_pages = -(-(len(prompt) + n_ticks) // page_len)
        row = np.zeros((max_pages,), np.int32)
        row[:n_pages] = 1 + np.arange(n_pages)
        table = np.zeros((slots, max_pages), np.int32)
        table[slot] = row
        poison()
        for done in range(0, len(prompt), width):
            part = prompt[done:done + width]
            padded = np.zeros((1, width), np.int32)
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
                (sound, np.asarray(got), np.asarray(got_state)),
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
    inactive) of the LAST KDA layer through ``kda_decode`` on the engine's
    own state (the timed kernel at the cell's slots, every other slot
    inactive) and one sequence through ``kda_chunked`` from a state that
    is not zero (the prefill's form), against the reference's
    token-by-token ``recurrence`` in float32 and, the control, with a
    bfloat16 state.  Largest |diff| over largest |reference| of the final
    states."""
    from deepspeed_tpu.ops.pallas.kda import kda_chunked, kda_decode
    lin = family.m["linear_attn_config"]
    H, dk = lin["num_heads"], lin["head_dim"]
    T = ARITHMETIC_TICKS
    slots, layer = eng.slots, family.kda_layers - 1
    where = np.array([0, 1, slots // 2, slots - 1])
    live = np.array([True, True, False, True])
    active = np.zeros((slots,), bool)
    active[where[live]] = True
    rng = np.random.default_rng(2052)
    f32 = np.float32

    def unit(x):
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(f32)

    # as the model makes them: unit keys, queries scaled, A in [1, 16]
    # times a step log-uniform in [0.001, 0.1]
    q = unit(rng.normal(size=(4, T, H, dk))) * f32(dk ** -0.5)
    k = unit(rng.normal(size=(4, T, H, dk)))
    v = rng.normal(0, 0.5, (4, T, H, dk)).astype(f32)
    g = (-rng.uniform(1.0, 16.0, (1, 1, H, 1)) * np.exp(rng.uniform(
        np.log(1e-3), np.log(0.1), (4, T, H, dk)))).astype(f32)
    b = rng.uniform(0.0, 1.0, (4, T, H)).astype(f32)
    h0 = rng.normal(0, 0.1, (4, H, dk, dk)).astype(f32)

    def decode(params, cache, h0, q, k, v, g, b):
        kda = cache["state"]["kda"].at[layer, where].set(h0)
        flat = kda.reshape((-1,) + kda.shape[2:])

        def spread(t):
            return jnp.zeros((slots,) + t.shape[1:], t.dtype).at[
                where].set(t)

        def tick(flat, step):
            q_t, k_t, v_t, g_t, b_t = step
            flat, _ = kda_decode(
                flat, spread(jnp.exp(g_t)), spread(k_t), spread(v_t),
                spread(q_t), spread(b_t), active, base=layer * slots)
            return flat, None

        flat, _ = jax.lax.scan(tick, flat, tuple(
            jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, b)))
        kda = flat.reshape(kda.shape)
        return (dict(cache, state=dict(cache["state"], kda=kda)),
                kda[layer, where])

    got, = _on_the_engines_cache(eng, decode)(h0, q, k, v, g, b)
    got = np.asarray(got)
    with eng._pallas_scope():
        _, chunked = jax.jit(kda_chunked)(q[0], k[0], v[0], g[0], b[0],
                                          h0[0])
    with jax.default_matmul_precision("highest"):
        def reference(state_dtype):
            fn = jax.jit(jax.vmap(
                lambda h, *t: kimi_linear_reference.recurrence(
                    *t, state_dtype=state_dtype, h0=h)[0]))
            return np.asarray(fn(h0, q, k, v, g, b))
        want, want_low = reference(jnp.float32), reference(jnp.bfloat16)
    return {"decode": _relative(got[live], want[live]),
            "chunked": _relative(chunked, want[0]),
            "bfloat16_state": _relative(want_low[live], want[live]),
            "idle_untouched": bool(np.array_equal(got[~live], h0[~live]))}


def _streams(family, params, reqs, ref_len: int):
    """``serve_job._stream_slack`` with the control beside it and the mean
    beside the largest: how far below the reference's top logit a token
    sits over whole finished streams of the timed engine (teacher-forced
    on the engine's own tokens), for the tokens the engine emitted and,
    the control, for those the CONTROL_ACT reference would have.  Returns
    ((largest, mean) of the program, of the control, positions)."""
    big, total, positions = [0.0, 0.0], [0.0, 0.0], 0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        ref = family.reference(params, seq[:-1], ref_len)[0]
        rows, low = ref[:, len(r.prompt) - 1:]
        at = np.arange(len(r.tokens))
        below = rows.max(axis=1)[:, None] - rows
        for i, tokens in enumerate((np.asarray(r.tokens),
                                    low.argmax(axis=1))):
            big[i] = max(big[i], float(below[at, tokens].max()))
            total[i] += float(below[at, tokens].sum())
        positions += len(r.tokens)
    n = max(positions, 1)
    return (big[0], total[0] / n), (big[1], total[1] / n), positions


def _counters(ctx, eng, calls, res, traced, series) -> None:
    """What the program counted per call (``ServeEngine.aux_log``) into
    ``series``: the expert layers' per decode tick of the window
    (``moe_experts_hit_pct`` of the held experts x layers,
    ``moe_load_imbalance``, ``moe_rows_elsewhere_pct``),
    ``prefill_chunks_per_request`` (the window's prefill calls over those
    that finished a prompt) and, traced, the time the experts' bytes, the
    state update's bytes and the latent kernel's yardstick need at the
    chip's peaks as percentages of the traced window (``moe_`` / ``kda_`` /
    ``latent_min_pct_of_traced_window``)."""
    fam = ctx.family
    w0, w1 = res["window_start"], res["window_start"] + ctx.seconds
    held = fam.m["experts_held"][1] * fam.moe_layers
    ticks = [v for t, kind, v in calls if kind == "decode" and w0 <= t < w1]
    prefills = [v for t, kind, v in calls
                if kind == "prefill" and w0 <= t < w1]
    series["moe_experts_hit_pct"] = [
        100.0 * v["moe_experts_hit"] / held for v in ticks]
    series["moe_load_imbalance"] = [v["moe_load_imbalance"] for v in ticks]
    series["moe_rows_elsewhere_pct"] = [
        100.0 * v["moe_rows_elsewhere"]
        / max(v["moe_rows"] + v["moe_rows_elsewhere"], 1) for v in ticks]
    whole = sum(1 for v in prefills if v.get("final_chunk", True))
    if whole:
        series["prefill_chunks_per_request"] = len(prefills) / whole
    if ticks:
        rows = np.mean([v["latent_kv_tokens"] for v in ticks]) \
            / fam.mla_layers
        say(f"experts: {len(ticks)} decode ticks in the window, hit "
            f"{np.mean(series['moe_experts_hit_pct']):.2f} % of the "
            f"{held} held a tick, busiest over mean "
            f"{np.mean(series['moe_load_imbalance']):.2f}, "
            f"{np.mean(series['moe_rows_elsewhere_pct']):.2f} % of the "
            "assignments to experts held elsewhere; "
            f"{np.mean([v['kda_slot_layers'] for v in ticks]):.0f} states "
            f"rewritten a tick, {rows:.0f} live latent rows a layer a tick; "
            f"{len(prefills)} prefill calls for {whole} prompts")
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
        kda = sum(fam.kda_decode_bytes(round(v["kda_slot_layers"]))
                  for v in decode)
        kind = jax.devices()[0].device_kind
        hbm = yardstick.peak(kind, "hbm_bytes_per_s")
        mxu = yardstick.peak(kind, "bf16_flops")
        nbytes = sum(fam.latent_decode_bytes(
            v["latent_kv_tokens"],
            round(v["kda_slot_layers"] / fam.kda_layers), item)
            for v in decode)
        flops = sum(fam.latent_decode_flops(v["latent_kv_tokens"])
                    for v in decode)
        latent_s = max(nbytes / hbm, flops / mxu)
        series["moe_min_pct_of_traced_window"] = \
            100.0 * moe / hbm / (b - a)
        series["kda_min_pct_of_traced_window"] = \
            100.0 * kda / hbm / (b - a)
        series["latent_min_pct_of_traced_window"] = \
            100.0 * latent_s / (b - a)
        say(f"traced {b - a:.3f} s, {len(decode)} decode ticks: experts "
            f"{moe / 1e9:.2f} GB to move, {moe / hbm:.3f} s at "
            f"{hbm / 1e9:.0f} GB/s; KDA states {kda / 1e9:.2f} GB = "
            f"{kda / hbm:.3f} s; latent rows {nbytes / 1e9:.2f} GB "
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
        sound, control, top, untouched = _probe(
            family, eng, params, items[:serve_job.PROBE_REQUESTS],
            serving["prefill_len"], ref_len)
        ar = _state_arithmetic(family, eng)

        # warm the engine's programs on the shapes the traffic uses: both
        # rungs, a prompt in chunks, the tick
        longest = max(items, key=lambda it: len(it.prompt))
        for it in (*items[:2], longest):
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
    say(f"probe: prefill (one of {n} in two chunks) + "
        f"{eng.page_len + PROBE_MARGIN} ticks of {n} requests on the "
        f"engine's own {eng.slots} slots vs the float32 reference: max "
        f"|logit diff| {sound['probe_logits']:.4f}, mean "
        f"{sound['probe_logits_mean']:.5f}, largest |logit| {top:.2f}, "
        f"tolerances {LOGIT_TOL} / {LOGIT_MEAN_TOL} (control, the reference "
        f"with {jnp.dtype(CONTROL_ACT).name} activations: "
        f"{control['probe_logits']:.4f} / "
        f"{control['probe_logits_mean']:.5f}); the first KDA layer's state "
        f"at the end, largest |diff| over largest |reference|: "
        f"{sound['probe_state']:.3e}, tolerance "
        f"{STATE_VS_REFERENCE_TOL:.1e} (control "
        f"{control['probe_state']:.3e}); the slots beside it untouched: "
        f"{untouched}")
    say(f"probe, the recurrence alone on shared inputs, "
        f"{ARITHMETIC_TICKS} steps from a state that is not zero, largest "
        f"|diff| over largest |reference|: ds_kda_decode "
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
        checks["control_low_activations_not_correct"] = \
            not all(judge(control).values())
        checks["control_bfloat16_state_not_correct"] = \
            not all(judge(low_state).values())
    return {"series": series, "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": peak, "trace_dir": result["trace_dir"],
            "window_start": result["window_start"]}
