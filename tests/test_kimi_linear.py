"""Kimi Linear (``deepspeed_tpu/models/kimi_linear.py``): the two delta-rule
decode kernels of ``ops/pallas/kda.py`` (a decay a key channel, KDA's; one
decay a head, Olmo Hybrid's) in interpret mode against their plain form
with dead slots untouched, both chunked (WY) forms against the
token-by-token recurrence from a non-zero state (a padded tail; decays
under which a naive ``e^-G`` overflows) and against each other, the model against the benchmark's plain float32 reference,
prefill then decode through the one pool and the state by slot, a prompt
prefilled whole against the same prompt in chunks with other slots' ticks
between them in a slot that held a state, the shares of the experts with
the shared expert counted once against the uncut layer, the engine's
streams, its books and the refusals.  CPU, tiny widths, seeded weights.
(Its cell's rehearsal: tests/test_benchmark_cells.py.)"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import drawn_once

from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.inference.kv_cache import (PagedKVCacheSpec,
                                              init_paged_cache)
from deepspeed_tpu.models import kimi_linear
from deepspeed_tpu.models.kimi_linear import (KimiLinearConfig,
                                              KimiLinearModel)
from deepspeed_tpu.ops.pallas.kda import (gdn_chunked, gdn_decode,
                                          gdn_decode_reference, gdn_heads,
                                          gdn_rest, kda_chunked, kda_decode,
                                          kda_decode_reference)
from deepspeed_tpu.ops.pallas.runtime import interpret_scope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import kimi_linear_reference, olmo_hybrid_reference  # noqa: E402

LIN = {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "head_dim": 16,
       "num_heads": 4, "short_conv_kernel_size": 4}
TINY = KimiLinearConfig(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5, num_attention_heads=4,
    num_key_value_heads=4, linear_attn_config=LIN, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_experts=16,
    num_experts_per_token=3, experts_held=(4, 8), model_max_length=256,
    attn_impl="dense",
    # scores and states of size 1, so that a mixer left out shows
    initializer_range=0.1)
SERVING = {"slots": 3, "page_len": 8, "max_seq_len": 96, "prefill_len": 32,
           "prefix_cache": False}
# float32 on the CPU: the model and the reference differ by summation
# order, and the chunked form reassociates the recurrence (the inverse of
# a chunk's triangular system as a product of matmuls): measured 1e-5 on
# logits of size 3.  A mixer, a state or a chunk's start left out moves
# the logits by 0.1 and more
F32_TOL = 1e-4


def _params(cfg=TINY, seed=0):
    return drawn_once(KimiLinearModel, cfg, seed)


def _reference(params, tokens, cfg=TINY, **switches):
    """(logits [B, T, V], the KDA layers' states [B, layers, H, dk, dv])."""
    with jax.default_matmul_precision("highest"):
        return tuple(np.asarray(t) for t in
                     kimi_linear_reference.kimi_linear_logits(
                         params, tokens, dataclasses.asdict(cfg), **switches))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, shape).astype(np.int32)


# -- the recurrence -------------------------------------------------------
# The two forms of ``ops/pallas/kda.py`` under one set of tests: "channel"
# (KDA: a decay a key channel, ``b`` in (0, 1)) and "head" (Gated DeltaNet,
# Olmo Hybrid: ONE decay a head, ``b`` in (0, 2), the state at rest ``[dk,
# H dv]``).  A form: (decode on states BY HEAD, the chunked form, the
# benchmark's token-by-token recurrence).

def _gdn_decode_by_head(state, a, k, v, q, b, active, **kw):
    new, o = gdn_decode(gdn_rest(jnp.asarray(state)), a, k, v, q, b, active,
                        **kw)
    return gdn_heads(new, state.shape[1]), o


FORMS = {
    "channel": (kda_decode, kda_decode_reference, kda_chunked,
                kimi_linear_reference.recurrence),
    "head": (_gdn_decode_by_head, gdn_decode_reference, gdn_chunked,
             olmo_hybrid_reference.recurrence),
}
forms = pytest.mark.parametrize("form", sorted(FORMS))


def recurrence(form, q, k, v, g, b, state0):
    """The benchmark's token-by-token delta rule (the oracle of the
    chunked form): -> (o, final state)."""
    with jax.default_matmul_precision("highest"):
        final, o = FORMS[form][3](q, k, v, g, b, h0=state0)
    return o, final


def _recurrence_inputs(form, T, H=3, dk=16, dv=8, decay=0.1, seed=0):
    """q, k [T, H, dk], v [T, H, dv], the log-decay (a channel or a head),
    the step (below 1, or below 2 with every other position above 1) and a
    drawn state [H, dk, dv]; dk != dv and H no multiple of 8."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    f32 = np.float32
    q, k, v = (unit(rng.normal(size=(T, H, dk))).astype(f32) * dk ** -0.5,
               unit(rng.normal(size=(T, H, dk))).astype(f32),
               rng.normal(size=(T, H, dv)).astype(f32))
    g = -rng.uniform(0.0, decay, size=(T, H, dk)).astype(f32)
    b = rng.uniform(0, 1, size=(T, H)).astype(f32)
    if form == "head":
        g, b = g[..., 0], b + (np.arange(T) % 2)[:, None].astype(f32)
    return q, k, v, g, b, rng.normal(size=(H, dk, dv)).astype(f32)


@forms
def test_kda_decode_kernel_rewrites_the_live_slots_and_no_other(form):
    """Two layers' slots in one row, the second layer's updated: the live
    slots are the plain form's, the dead ones and the other layer bit for
    bit what they were, a dead slot's output 0."""
    S = 5
    decode, plain = FORMS[form][:2]
    q, k, v, g, b, _ = _recurrence_inputs(form, S, seed=1)
    state = np.random.default_rng(2).normal(
        size=(2 * S, 3, 16, 8)).astype(np.float32)
    active = np.array([True, False, True, True, False])
    new, o = decode(jnp.asarray(state), np.exp(g), k, v, q, b, active,
                    base=S, interpret=True)
    want, want_o = plain(state[S:], np.exp(g), k, v, q, b, active)
    new = np.asarray(new)
    np.testing.assert_allclose(new[S:], want, atol=1e-6)
    np.testing.assert_allclose(o, want_o, atol=1e-6)
    np.testing.assert_array_equal(new[:S], state[:S])
    np.testing.assert_array_equal(new[S:][~active], state[S:][~active])
    assert not np.asarray(o)[~active].any()
    # the kernel's plain form is the recurrence's one step
    o_t, s_t = recurrence(form, q[:1], k[:1], v[:1], g[:1], b[:1], state[S])
    np.testing.assert_allclose(want[0], s_t, atol=1e-6)
    np.testing.assert_allclose(want_o[0], o_t[0], atol=1e-6)


@forms
def test_kda_decode_kernel_with_no_live_slot_moves_nothing(form):
    S = 3
    q, k, v, g, b, _ = _recurrence_inputs(form, S, seed=3)
    state = np.random.default_rng(4).normal(
        size=(S, 3, 16, 8)).astype(np.float32)
    new, o = FORMS[form][0](jnp.asarray(state), np.exp(g), k, v, q, b,
                            np.zeros((S,), bool), interpret=True)
    np.testing.assert_array_equal(new, state)
    assert not np.asarray(o).any()


@pytest.mark.parametrize("heads,dk,dv", [(4, 8, 96), (5, 16, 128)],
                         ids=["heads_from_mid_tile", "a_head_a_tile"])
def test_gdn_decode_kernel_walks_lane_tiles_across_heads(heads, dk, dv):
    """Widths whose lanes are whole tiles (4 x 96 = 3 tiles: heads 1 and 3
    start in the middle of one): a tile's columns of ``k`` and ``q`` change
    at the lane where the next head starts."""
    S = 4
    q, k, v, g, b, _ = _recurrence_inputs("head", S, H=heads, dk=dk, dv=dv,
                                          decay=1.0, seed=6)
    state = np.random.default_rng(7).normal(
        size=(S, heads, dk, dv)).astype(np.float32)
    active = np.array([True, True, False, True])
    new, o = _gdn_decode_by_head(state, np.exp(g), k, v, q, b, active,
                                 interpret=True)
    want, want_o = gdn_decode_reference(state, np.exp(g), k, v, q, b, active)
    np.testing.assert_allclose(new, want, atol=2e-6)
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(new)[2], state[2])


@forms
@pytest.mark.parametrize("decay", [0.1, 5.0], ids=["mild", "strong"])
def test_chunked_form_is_the_recurrence_from_a_state_that_is_not_zero(
        form, decay):
    """150 positions (two chunks and 22 rows of a third) from a drawn
    state.  At the strong decay a channel's log-decay over a chunk passes
    -300: ``e^-G`` is inf in float32, and the form that divides by it
    gives nothing finite."""
    chunked = FORMS[form][2]
    q, k, v, g, b, s0 = _recurrence_inputs(form, 150, decay=decay)
    o, final = jax.jit(chunked)(q, k, v, g, b, s0)
    want_o, want = recurrence(form, q, k, v, g, b, s0)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, atol=2e-5 * np.abs(want_o).max())
    np.testing.assert_allclose(final, want, atol=2e-5 * np.abs(want).max())
    worst = -np.cumsum(g[:64], axis=0).min()
    with np.errstate(over="ignore"):
        assert (decay < 1) == bool(np.isfinite(np.exp(np.float32(worst))))
    # from zeros the first outputs are others: the start is read
    other, _ = jax.jit(chunked)(q, k, v, g, b, np.zeros_like(s0))
    assert np.abs(np.asarray(other)[:4] - want_o[:4]).max() > 1e-2


@forms
def test_a_padded_tail_leaves_the_state_at_the_true_length(form):
    """Positions with ``g = 0`` and ``b = 0`` (a padded rung's) neither
    decay the state nor feed it."""
    q, k, v, g, b, s0 = _recurrence_inputs(form, 128, seed=5)
    n = 77
    live = np.arange(128) < n
    g_pad = g * live.reshape((-1,) + (1,) * (g.ndim - 1))
    b_pad = b * live[:, None]
    o, final = jax.jit(FORMS[form][2])(q, k, v, g_pad, b_pad, s0)
    want_o, want = recurrence(form, q[:n], k[:n], v[:n], g[:n], b[:n], s0)
    np.testing.assert_allclose(final, want, atol=2e-5 * np.abs(want).max())
    np.testing.assert_allclose(o[:n], want_o,
                               atol=2e-5 * np.abs(want_o).max())


@pytest.mark.parametrize("common", [1.0, 1e3], ids=["alike", "one_token"])
def test_one_decay_a_head_survives_keys_that_resemble_each_other(common):
    """Keys with a common part (cosine ~0.5 between any two), and a run of
    ONE key (a prompt that repeats a token: every convolution window the
    same), 150 of them, at steps up to 2.  The inverse of a chunk's
    triangular system as the product ``(I + N)(I + N^2)...`` (KDA's, and
    this form's as first written) read 3e7 times the recurrence's largest
    output on the first and ``inf`` on the second: its powers ``N^k`` grow
    like ``C(64, k)`` and cancel; by halves it is the recurrence's to
    1e-5.  (KDA's form keeps the product, and its weakness on a run of one
    token, until its cell is read with the other: PERF.md section 7.)"""
    form = "head"
    q, k, v, g, b, s0 = _recurrence_inputs(form, 150, decay=0.05, seed=9)
    shared = np.random.default_rng(10).normal(size=(1,) + k.shape[1:])
    k = k + common * shared.astype(np.float32)
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype(np.float32)
    assert (k[0] * k[1]).sum(-1).min() > (0.99 if common > 1 else 0.3)
    o, final = jax.jit(FORMS[form][2])(q, k, v, g, b, s0)
    want_o, want = recurrence(form, q, k, v, g, b, s0)
    np.testing.assert_allclose(o, want_o, atol=2e-5 * np.abs(want_o).max())
    np.testing.assert_allclose(final, want, atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("decay", [0.1, 5.0], ids=["mild", "strong"])
def test_one_decay_a_head_is_kda_with_the_decay_spread_over_the_channels(
        decay):
    """The tie that keeps the two chunked forms one mathematics: the
    scalar-decay form against ``kda_chunked`` fed the same decay broadcast
    over a head's channels, steps up to 2, from a drawn state."""
    q, k, v, g, b, s0 = _recurrence_inputs("head", 150, decay=decay, seed=8)
    o, final = jax.jit(gdn_chunked)(q, k, v, g, b, s0)
    spread = np.broadcast_to(g[..., None], k.shape)
    want_o, want = jax.jit(kda_chunked)(q, k, v, spread, b, s0)
    np.testing.assert_allclose(o, want_o, atol=2e-5 * np.abs(want_o).max())
    np.testing.assert_allclose(final, want, atol=2e-5 * np.abs(want).max())


# -- the model against the reference --------------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_apply_matches_the_reference_in_float32(attn_impl):
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    params, tokens = _params(cfg), _tokens((2, 37))
    with jax.default_matmul_precision("highest"), interpret_scope(True):
        got, aux = KimiLinearModel(cfg).apply(params, tokens, aux=True)
    want, _ = _reference(params, tokens, cfg)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    # 3 of 16 a token over 4 expert layers, half the experts held
    assert int(aux["moe_rows"]) + int(aux["moe_rows_elsewhere"]) \
        == 2 * 37 * 3 * 4


def test_the_float32_tolerance_fails_a_rounded_residual_stream():
    params, tokens = _params(), _tokens((1, 37))
    want, _ = _reference(params, tokens)
    low, _ = _reference(params, tokens, act_dtype=jnp.bfloat16,
                        round_acts=True)
    assert np.abs(low - want).max() > 100 * F32_TOL


def test_the_eight_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """The routed parts of all shares (each computes the shared expert
    too: counted once) add up to the uncut reference's layer."""
    cfg = dataclasses.replace(TINY, experts_held=None, num_experts=32)
    params = _params(cfg, 2)
    x = jnp.asarray(np.random.RandomState(8).randn(12, 64), jnp.float32)
    bias = jnp.asarray(np.random.RandomState(9).randn(32) * 0.1, jnp.float32)
    params["moe"]["router_bias"] = (bias,) * cfg.count("moe")

    def layer(c, p):
        ep = kimi_linear.at(p["moe"], 0)
        out, st = kimi_linear._experts(
            c, ep, kimi_linear.stacked_experts(p), 0, x, None)
        return out, kimi_linear.shared_expert(ep, x), st

    _, shared, stats = layer(cfg, params)
    assert int(stats.rows) == 12 * 3 and float(jnp.abs(shared).max()) > 0
    routed, rows, elsewhere = 0.0, 0, 0
    for first in range(0, 32, 4):                   # eight shares of four
        share = dataclasses.replace(cfg, experts_held=(first, 4))
        held = dict(params, moe={
            k: (v[:, first:first + 4] if k in ("gate_w", "up_w", "down_w")
                else v) for k, v in params["moe"].items()})
        part, same, st = layer(share, held)
        np.testing.assert_array_equal(same, shared)
        routed = routed + (part - same)
        rows += int(st.rows)
        elsewhere += int(st.rows_elsewhere)
    with jax.default_matmul_precision("highest"):
        uncut = kimi_linear_reference.expert_layer(
            kimi_linear.at(params["moe"], 0),
            kimi_linear.stacked_experts(params), 0, x,
            dataclasses.asdict(cfg))
    np.testing.assert_allclose(routed + shared, uncut, atol=1e-5)
    assert rows == 12 * 3 and elsewhere == 7 * 12 * 3


def test_a_balanced_bias_evens_the_load_and_both_sides_read_it():
    """The source's balancing rule, run by the reference on the seed's
    weights, lowers the busiest held expert's share; the program selects
    on score + bias as the reference does."""
    cfg = dataclasses.replace(TINY, experts_held=None)
    params, tokens = _params(cfg, 3), _tokens((1, 160), 11)
    with jax.default_matmul_precision("highest"):
        bias = kimi_linear_reference.balance_router_bias(
            params, tokens, dataclasses.asdict(cfg), steps=64, rate=0.05)
    assert bias.shape == (cfg.count("moe"), cfg.num_experts)
    assert float(jnp.abs(bias).max()) > 0.01
    balanced = dict(params, moe=dict(params["moe"], router_bias=tuple(bias)))
    model = KimiLinearModel(cfg)
    with jax.default_matmul_precision("highest"):
        _, before = model.apply(params, tokens, aux=True)
        got, after = model.apply(balanced, tokens, aux=True)
    assert float(after["moe_load_imbalance"]) \
        < 0.8 * float(before["moe_load_imbalance"])
    want, _ = _reference(balanced, tokens, cfg)
    np.testing.assert_allclose(got, want, atol=F32_TOL)


# -- the paged steps ------------------------------------------------------

def _serve(model, params, prompt, forced, chunks, impl, page_len=8, slots=3,
           max_pages=12, bucket=32):
    """Prefill ``prompt`` in ``chunks`` (lengths) into the LAST slot,
    whose last occupant left a state behind (0.5 everywhere), with a
    decode tick of the first slot, which lives on pages and a state of its
    own, between the chunks; then one tick a forced token of the last
    slot alone.  Returns (the logits of every prompt position and of every
    tick, the last slot's state, the request's cached rows, the first
    slot's state after its ticks and before them)."""
    cfg = model.config
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, slots=slots, heads=cfg.n_kv_head,
        pages=1 + 2 * max_pages, page_len=page_len, head_dim=cfg.d_head,
        max_pages=max_pages, dtype=jnp.float32, v_head_dim=cfg.d_head_v,
        values_in_keys=cfg.values_in_keys)
    pool = init_paged_cache(spec)["k"]
    state = {name: jnp.full(s.shape, 0.5, s.dtype)
             for name, s in model.serving_state(slots).items()}
    n_pages = -(-(len(prompt) + len(forced)) // page_len)
    row = np.zeros((max_pages,), np.int32)
    row[:n_pages] = 1 + np.arange(n_pages)
    other = np.zeros((max_pages,), np.int32)
    other[:2] = 1 + max_pages + np.arange(2)
    slot = slots - 1
    prefill = jax.jit(lambda p, t, n, pre, row, k, st, s: model.prefill_paged(
        p, t, n, pre, row, k, None, state=st, slot=s))
    decode = jax.jit(lambda p, t, k, tab, ln, act, st:
                     model.decode_step_paged(p, t, k, None, tab, ln, act,
                                             state=st, impl=impl))
    # the first slot's own request: three tokens in
    first = np.zeros((1, bucket), np.int32)
    first[0, :3] = [5, 6, 7]
    _, pool, _, state = prefill(params, first, np.int32(3), np.int32(0),
                                other, pool, state, np.int32(0))
    before = jax.tree.map(lambda a: np.asarray(a[:, 0]), state)
    table = np.zeros((slots, max_pages), np.int32)
    table[0], table[slot] = other, row
    lengths = jnp.zeros((slots,), jnp.int32).at[0].set(3)
    only_first = np.array([True] + [False] * (slots - 1))
    done, rows = 0, []
    for n in chunks:
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt[done:done + n]
        logits, pool, none, state = prefill(
            params, padded, np.int32(n), np.int32(done), row, pool, state,
            np.int32(slot))
        assert none is None
        rows.append(np.asarray(logits[0, :n]))
        done += n
        if done < len(prompt):      # a tick of the other slot in between
            _, pool, _, state, lengths = decode(
                params, jnp.full((slots,), 9, jnp.int32), pool, table,
                lengths, only_first, state)
    after = jax.tree.map(lambda a: np.asarray(a[:, 0]), state)
    active = np.zeros((slots,), bool)
    active[slot] = True
    lengths = lengths.at[slot].set(done)
    mine = jax.tree.map(lambda a: np.asarray(a[:, slot]), state)
    for token in forced:
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(int(token))
        logits, pool, none, state, lengths = decode(
            params, tokens, pool, table, lengths, active, state)
        rows.append(np.asarray(logits[slot])[None])
    cached = np.asarray(pool)[:, row[:n_pages]]
    return (np.concatenate(rows), mine, cached,
            jax.tree.map(lambda a: np.asarray(a[:, slot]), state),
            (before, after))


@pytest.mark.parametrize("chunks", [(27,), (16, 11), (8, 8, 11)],
                         ids=["whole", "two_chunks", "three_chunks"])
@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_paged_steps_against_the_reference(impl, chunks):
    """The prefill (whole, or in chunks that start from the slot's state
    and the request's pages) then ticks through the pool and the state:
    every logit is the reference's full forward's, and the slot's KDA
    states after the prompt and after the ticks are the reference's."""
    cfg = dataclasses.replace(
        TINY, attn_impl="flash" if impl == "pallas" else "dense")
    model, params = KimiLinearModel(cfg), _params(cfg)
    prompt, forced = _tokens((27,), 4), _tokens((9,), 5)
    with interpret_scope(True), jax.default_matmul_precision("highest"):
        got, at_prompt, _, at_end, _ = _serve(model, params, prompt, forced,
                                              chunks, impl)
    seq = np.concatenate([prompt, forced])[None]
    want, end_states = _reference(params, seq, cfg)
    _, prompt_states = _reference(params, seq, cfg, length=27)
    np.testing.assert_allclose(got, want[0], atol=F32_TOL)
    np.testing.assert_allclose(at_prompt["kda"], prompt_states[0],
                               atol=F32_TOL)
    np.testing.assert_allclose(at_end["kda"], end_states[0], atol=F32_TOL)


def test_a_prompt_in_chunks_ends_where_the_whole_prompt_does():
    """The same prompt whole and in three chunks with another slot's
    ticks between them, in a slot that held a state: the same state, the
    same convolution tail, the same cached rows, the same logits; the
    other slot's ticks moved its own state and the chunks left it alone."""
    model, params = KimiLinearModel(TINY), _params()
    prompt, forced = _tokens((27,), 4), _tokens((4,), 5)
    with jax.default_matmul_precision("highest"):
        whole = _serve(model, params, prompt, forced, (27,), "dense")
        parts = _serve(model, params, prompt, forced, (8, 8, 11), "dense")
    np.testing.assert_allclose(parts[0], whole[0], atol=F32_TOL)
    for name in ("kda", "kda_conv"):
        np.testing.assert_allclose(parts[1][name], whole[1][name],
                                   atol=F32_TOL)
        np.testing.assert_allclose(parts[3][name], whole[3][name],
                                   atol=F32_TOL)
    np.testing.assert_allclose(parts[2], whole[2], atol=F32_TOL)
    assert np.abs(whole[1]["kda"]).max() > 0.05
    # a state left behind did not leak in: it was 0.5 everywhere
    assert np.abs(whole[1]["kda"] - 0.5).min() > 1e-3
    before, after = parts[4]
    assert np.abs(after["kda"] - before["kda"]).max() > 1e-3   # two ticks
    np.testing.assert_array_equal(whole[4][0]["kda"], whole[4][1]["kda"])


# -- through the engine ---------------------------------------------------

@pytest.mark.parametrize("serving", [{}, {"prefill_chunk_len": 16}],
                         ids=["plain", "chunked"])
def test_engine_streams_sit_on_the_reference_logits(serving):
    """Through ``ServeEngine``: more requests than slots (a slot is taken
    again after its last occupant), one prompt over the chunk length;
    every emitted token is the reference's argmax."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    model, params = KimiLinearModel(cfg), _params(cfg)
    eng = ServeEngine(model, {"serving": {**SERVING, **serving}},
                      params=params)
    chunked = bool(serving)
    try:
        lens = (5, 29, 3, 45 if chunked else 30, 12)
        prompts = [[int(t) for t in _tokens((n,), 7 + n)] for n in lens]
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run_until_idle()
        assert eng._decode_fn._cache_size() == 1
        assert sorted(eng.cache) == ["k", "lengths", "state"]
        assert sorted(eng.state_bytes) == ["kda", "kda_conv", "latent"]
        assert eng.state_bytes["kda"] == 4 * 3 * 4 * 16 * 16 * 4
        assert model.serving_cache_layers() == {"latent": 1, "kda": 4}
        prefills = [v for _, kind, v in eng.aux_log if kind == "prefill"]
        ticks = [v for _, kind, v in eng.aux_log if kind == "decode"]
        assert all(v["kda_slot_layers"] == 0 and v["kda_chunk_tokens"] > 0
                   and v["kda_chunk_tokens"] % 4 == 0 for v in prefills)
        assert ticks and all(
            v["kda_slot_layers"] in (4, 8, 12) and v["kda_chunk_tokens"] == 0
            and v["latent_kv_tokens"] > 0 for v in ticks)
        if chunked:
            # 45 tokens in three chunks of the one program, 29 in two
            assert eng.prefill_chunk_calls == {32: 5}
            assert sum("chunk_pos" in v for v in prefills) == 5
    finally:
        eng.close()
    for prompt, r in zip(prompts, reqs):
        seq = np.asarray(prompt + list(r.tokens))[None]
        rows = _reference(params, seq[:, :-1], cfg)[0][0][len(prompt) - 1:]
        assert len(r.tokens) == 10
        slack = rows.max(axis=1) - rows[np.arange(10), r.tokens]
        assert slack.max() < F32_TOL, slack


@pytest.mark.parametrize("serving,named", [
    ({"page_len": 0}, "page_len"),
    ({"prefix_cache": True}, "prefix_cache"),
    ({"speculate_k": 2, "draft": {"d_model": 32, "n_layer": 1,
                                  "n_head": 2}}, "speculate_k"),
    ({"quantization": {"kv": "int8"}}, "quantization"),
])
def test_engine_refuses_the_arms_these_steps_lack(serving, named):
    with pytest.raises(ValueError,
                       match=f"KimiLinearModel cannot be served.*{named}"):
        ServeEngine(KimiLinearModel(TINY),
                    {"serving": {**SERVING, **serving}}, params=_params())


@pytest.mark.parametrize("field,value,named", [
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("q_lora_rank", 24, "q_lora_rank"),
    ("mla_use_nope", False, "mla_use_nope"),
    ("num_nextn_predict_layers", 1, "num_nextn_predict_layers"),
    ("num_expert_group", 4, "group-limited"),
    ("moe_router_activation_func", "softmax", "moe_router_activation_func"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("linear_attn_config", {**LIN, "kda_layers": [1, 2, 3]},
     "in neither"),
    ("linear_attn_config", {**LIN, "full_attn_layers": [4, 5]},
     "or in both"),
    ("experts_held", (12, 8), "experts_held"),
])
def test_config_refuses_what_is_not_built(field, value, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(TINY, **{field: value})


def test_config_reads_the_published_row():
    """The catalog's own keys build the configuration as published: 27
    layers, 20 KDA mixers to 7 latent ones, one dense FFN, rows 640 wide at
    rest over values of 512, a pool of one key head over the latent layers
    alone, 13 MB of state a slot."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        file = json.load(f)
    fields = {f.name for f in dataclasses.fields(KimiLinearConfig)}
    keys = {k: v for k, v in file.items()
            if k in fields and k != "experts_held"}
    keys.update(file["published"])
    cfg = KimiLinearConfig(**keys)
    assert (cfg.num_hidden_layers, cfg.count("kda"), cfg.count("mla"),
            cfg.count("dense"), cfg.count("moe")) == (27, 20, 7, 1, 26)
    assert cfg.mixers[:8] == ("kda",) * 3 + ("mla",) + ("kda",) * 3 + ("mla",)
    assert (cfg.n_layer, cfg.n_kv_head, cfg.d_head, cfg.d_head_v,
            cfg.values_in_keys) == (7, 1, 640, 512, True)
    assert (cfg.hidden_size, cfg.kda_width, cfg.kda_head_dim,
            cfg.conv_kernel, cfg.num_experts, cfg.vocab_size) == (
        2304, 4096, 128, 4, 256, 163840)
    state = KimiLinearModel(cfg).serving_state(1)
    per_layer = sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                    for s in state.values()) // 20
    assert per_layer == 32 * 128 * 128 * 4 + 3 * 12288 * 4   # float32 tail
