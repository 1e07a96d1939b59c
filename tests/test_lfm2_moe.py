"""LFM2 MoE (``deepspeed_tpu/models/lfm2_moe.py``): the model against the
benchmark's plain float32 reference, prefill then decode through the paired
pools and the convolution rows by slot over a mixed batch with an inactive
slot, a prompt prefilled whole against the same prompt in chunks, a slot
taken again after a longer request, the paired-head layout against the
dense decode on unpaired keys, the router against the reference's with a
bias that is not zero, the engine's streams, its books and the refusals.
CPU, toy widths (4 query heads on 2 key heads of 8, which rest as ONE row
of 16), seeded weights.  (The cell's rehearsal:
tests/test_benchmark_cells.py; the published widths compiled:
tests/test_chip_lfm2_moe.py.)"""
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
from deepspeed_tpu.models import walked
from deepspeed_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel
from deepspeed_tpu.moe.dropless import route_sigmoid_topk
from deepspeed_tpu.ops.pallas.decode_attention import decode_attention_paged
from deepspeed_tpu.ops.pallas.runtime import interpret_scope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import lfm2_moe_reference  # noqa: E402

TYPES = ("conv", "conv", "full_attention", "conv", "conv", "conv", "conv")
TINY = Lfm2MoeConfig(
    vocab_size=128, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=7, num_dense_layers=2,
    layer_types=TYPES, num_attention_heads=4, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    max_position_embeddings=256, attn_impl="dense", initializer_range=0.1)
SERVING = {"slots": 3, "page_len": 8, "max_seq_len": 96, "prefill_len": 32,
           "prefix_cache": False}
# float32 on the CPU: the program and the reference differ by summation
# order (1e-5 of logits of order 3, measured); a tap, a gate, the bias, a
# norm or a chunk's start left out moves the logits by 0.1 and more.
F32_TOL = 1e-3


def _params(cfg=TINY, seed=0, bias=0.3):
    """Seeded weights with an ``expert_bias`` that is NOT zero (the source
    starts it at zero, where a router that ignored it would pass)."""
    params = drawn_once(Lfm2MoeModel, cfg, seed)
    rng = np.random.default_rng(seed + 100)
    params["moe"] = dict(params["moe"], router_bias=tuple(
        jnp.asarray(rng.normal(0, bias, b.shape), jnp.float32)
        for b in params["moe"]["router_bias"]))
    return params


def _reference(params, tokens, cfg=TINY, **switches):
    """(logits [B, T, V], the conv layers' z [B, layers, T, d])."""
    with jax.default_matmul_precision("highest"):
        return tuple(np.asarray(t) for t in
                     lfm2_moe_reference.lfm2_moe_logits(
                         params, tokens, dataclasses.asdict(cfg), **switches))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, shape).astype(np.int32)


def _kept(z, length):
    """The reference's ``z`` [layers, T, d] -> what a slot keeps after
    ``length`` positions [layers, 2 d]: the last two rows side by side."""
    return z[:, length - 2:length].reshape(z.shape[0], -1)


# -- the model against the reference --------------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_apply_matches_the_reference_in_float32(attn_impl):
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    params, tokens = _params(cfg), _tokens((2, 70))
    with jax.default_matmul_precision("highest"), interpret_scope(True):
        got, aux = Lfm2MoeModel(cfg).apply(params, tokens, aux=True)
    want, _ = _reference(params, tokens, cfg)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    assert sorted(aux) == sorted(Lfm2MoeModel.serving_aux)
    assert int(aux["moe_rows"]) == 2 * 70 * 2 * 5


@pytest.mark.parametrize("switch", [
    {"reverse_taps": True}, {"use_bias": False}, {"round_acts": True}],
    ids=["taps_reversed", "no_expert_bias", "low_activations"])
def test_the_float32_tolerance_fails_the_benchmarks_controls(switch):
    params, tokens = _params(), _tokens((1, 70))
    want, _ = _reference(params, tokens)
    low, _ = _reference(params, tokens, **switch)
    assert np.abs(low - want).max() > 40 * F32_TOL


@pytest.mark.parametrize("field,value", [
    ("use_expert_bias", False), ("norm_topk_prob", False),
    ("routed_scaling_factor", 2.5), ("conv_L_cache", 4)])
def test_the_keys_that_are_read_are_read_on_both_sides(field, value):
    """``use_expert_bias``, ``norm_topk_prob``, ``routed_scaling_factor``
    and ``conv_L_cache`` are the source's to set: another value gives
    another model, the reference's alike."""
    cfg = dataclasses.replace(TINY, **{field: value})
    params, tokens = _params(cfg), _tokens((1, 40))
    got = Lfm2MoeModel(cfg).apply(params, tokens)
    np.testing.assert_allclose(got, _reference(params, tokens, cfg)[0],
                               atol=F32_TOL)
    if field != "conv_L_cache":         # the same leaves: another reading
        as_published = Lfm2MoeModel(TINY).apply(params, tokens)
        assert np.abs(np.asarray(got) - np.asarray(as_published)).max() \
            > 0.05


def test_the_router_is_the_references_with_a_bias_that_is_not_zero():
    """``route_sigmoid_topk(eps=1e-6)`` against the reference's dense
    gates: the same experts (the bias steers the choice) at the same
    weights (the scores alone, over their sum + 1e-6); without the bias
    another choice."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(50, 32)), jnp.float32)
    p = {"router_w": jnp.asarray(rng.normal(0, 0.3, (32, 8)), jnp.float32),
         "router_bias": jnp.asarray(rng.normal(0, 0.3, (8,)), jnp.float32)}
    m = dataclasses.asdict(TINY)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(lfm2_moe_reference.gates(p, x, m))
        no_bias = np.asarray(lfm2_moe_reference.gates(p, x, m,
                                                      use_bias=False))
        weights, experts = route_sigmoid_topk(
            x, p["router_w"], p["router_bias"], 2, eps=1e-6)
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), axis=1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert ((want > 0) != (no_bias > 0)).any()
    # the guard under the sum shows: the weights add up to just under 1
    assert np.all(got.sum(axis=1) < 1.0)
    exact, _ = route_sigmoid_topk(x, p["router_w"], p["router_bias"], 2)
    np.testing.assert_allclose(np.asarray(exact).sum(axis=1), 1.0, atol=1e-6)


# -- the paired pool ------------------------------------------------------

@pytest.mark.parametrize("kv_heads,head_dim,pairs", [
    (8, 64, 2), (2, 8, 2), (8, 16, 8), (3, 64, 1), (4, 128, 1),
    (4, 192, 1)])
def test_lane_pairs(kv_heads, head_dim, pairs):
    assert walked.lane_pairs(kv_heads, head_dim) == pairs


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_the_paired_pool_attends_as_the_dense_decode_on_unpaired_keys(impl):
    """8 query heads on 4 key heads of 16 through ``PairedPagePool`` (the
    pool rests as 2 heads of 32) against ``decode_attention_paged(impl=
    "dense")`` on a pool of the model's own 4 heads of 16 that holds the
    same keys: the same outputs, and the two pools hold the same bytes."""
    rng = np.random.default_rng(0)
    S, Hq, Hkv, D, page_len, pages, layers = 3, 8, 4, 16, 8, 7, 2
    lengths = np.array([13, 0, 20], np.int32)
    table = np.array([[1, 2, 0], [0, 0, 0], [3, 4, 5]], np.int32)
    plain = [jnp.zeros((layers, pages, Hkv, page_len, D), jnp.float32)] * 2
    paired = [jnp.zeros((layers, pages, Hkv // 2, page_len, 2 * D),
                        jnp.float32)] * 2
    layer = 1
    # fill the live positions a token at a time, as a prefill's write does
    for s in (0, 2):
        n = int(lengths[s])
        k, v = (jnp.asarray(rng.normal(size=(n, Hkv, D)), jnp.float32)
                for _ in "kv")
        at = np.arange(n)
        ids, offs = table[s][at // page_len], at % page_len
        keep = np.ones((n,), bool)
        a = walked.PagePool(plain, ids, offs, keep)
        a.write(layer, k, v)
        plain = a.arrays()
        b = walked.PairedPagePool(paired, ids, offs, keep, kv_heads=Hkv)
        b.write(layer, k, v)
        paired = b.arrays()
    for a, b in zip(plain, paired):
        np.testing.assert_array_equal(
            np.asarray(a).transpose(0, 1, 3, 2, 4).reshape(-1),
            np.asarray(b).transpose(0, 1, 3, 2, 4).reshape(-1))
    q = jnp.asarray(rng.normal(size=(S, Hq, D)), jnp.float32)
    pool = walked.PairedPagePool(paired, np.zeros((S,), np.int32),
                                 np.zeros((S,), np.int32),
                                 np.zeros((S,), bool), kv_heads=Hkv)
    assert (pool.heads, pool.pairs) == (2, 2)
    with interpret_scope(True), jax.default_matmul_precision("highest"):
        got = pool.attend(layer, q, table, lengths, impl=impl,
                          sm_scale=D ** -0.5)
        flat = [t.reshape((-1,) + t.shape[2:]) for t in plain]
        want = decode_attention_paged(
            q, *flat, table + layer * pages, lengths, sm_scale=D ** -0.5,
            impl="dense")
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert not np.asarray(got[1]).any()         # the idle slot: zeros
    # a context gathered out of the paired pool, as a chunk reads it
    ctx = pool.unpaired(walked.prefix_keys(
        pool.flat()[0], layer * pages + table[2], 20))
    want_ctx = walked.prefix_keys(flat[0], layer * pages + table[2], 20)
    np.testing.assert_array_equal(ctx, want_ctx)


# -- the paged steps ------------------------------------------------------

def _serve(model, params, prompt, forced, chunks, impl, page_len=8, slots=3,
           max_pages=12, bucket=32, first_len=3):
    """Prefill ``prompt`` in ``chunks`` (lengths) into the LAST slot, whose
    last occupant left rows behind (0.5 everywhere), with a decode tick of
    the first slot, which lives on pages and rows of its own, between the
    chunks, the middle slot inactive throughout; then one tick a forced
    token of the last slot WITH the first slot (a mixed batch).  Returns
    (the logits of every prompt position and of every tick, the last slot's
    rows after the prompt, the request's cached keys and values, its rows
    after the ticks, the middle slot's rows at the end)."""
    cfg = model.config
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, slots=slots, heads=cfg.n_kv_head,
        pages=1 + 2 * max_pages, page_len=page_len, head_dim=cfg.d_head,
        max_pages=max_pages, dtype=jnp.float32)
    cache = init_paged_cache(spec)
    k_pool, v_pool = cache["k"], cache["v"]
    state = {name: jnp.full(s.shape, 0.5, s.dtype)
             for name, s in model.serving_state(slots).items()}
    n_pages = -(-(len(prompt) + len(forced)) // page_len)
    row = np.zeros((max_pages,), np.int32)
    row[:n_pages] = 1 + np.arange(n_pages)
    other = np.zeros((max_pages,), np.int32)
    other[:4] = 1 + max_pages + np.arange(4)
    slot = slots - 1
    prefill = jax.jit(lambda p, t, n, pre, row, k, v, st, s:
                      model.prefill_paged(p, t, n, pre, row, k, v, state=st,
                                          slot=s))
    decode = jax.jit(lambda p, t, k, v, tab, ln, act, st:
                     model.decode_step_paged(p, t, k, v, tab, ln, act,
                                             state=st, impl=impl))
    # the first slot's own request
    first = np.zeros((1, bucket), np.int32)
    first[0, :first_len] = 5 + np.arange(first_len)
    _, k_pool, v_pool, state = prefill(
        params, first, np.int32(first_len), np.int32(0), other, k_pool,
        v_pool, state, np.int32(0))
    table = np.zeros((slots, max_pages), np.int32)
    table[0], table[slot] = other, row
    lengths = jnp.zeros((slots,), jnp.int32).at[0].set(first_len)
    only_first = np.array([True] + [False] * (slots - 1))
    done, rows = 0, []
    for n in chunks:
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt[done:done + n]
        logits, k_pool, v_pool, state = prefill(
            params, padded, np.int32(n), np.int32(done), row, k_pool, v_pool,
            state, np.int32(slot))
        rows.append(np.asarray(logits[0, :n]))
        done += n
        if done < len(prompt):      # a tick of the other slot in between
            _, k_pool, v_pool, state, lengths = decode(
                params, jnp.full((slots,), 9, jnp.int32), k_pool, v_pool,
                table, lengths, only_first, state)
    active = np.array([True] + [False] * (slots - 2) + [True])
    lengths = lengths.at[slot].set(done)
    mine = np.asarray(state["conv"][:, slot])
    for token in forced:
        tokens = jnp.full((slots,), 9, jnp.int32).at[slot].set(int(token))
        logits, k_pool, v_pool, state, lengths = decode(
            params, tokens, k_pool, v_pool, table, lengths, active, state)
        rows.append(np.asarray(logits[slot])[None])
    cached = np.stack([np.asarray(k_pool)[:, row[:n_pages]],
                       np.asarray(v_pool)[:, row[:n_pages]]])
    return (np.concatenate(rows), mine, cached,
            np.asarray(state["conv"][:, slot]),
            np.asarray(state["conv"][:, 1]))


@pytest.mark.parametrize("chunks", [(27,), (16, 11), (8, 8, 11)],
                         ids=["whole", "two_chunks", "three_chunks"])
@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_paged_steps_against_the_reference(impl, chunks):
    """The prefill (whole, or in chunks that start from the slot's rows and
    the request's pages) then ticks of a mixed batch through the paired
    pools and the rows: every logit is the reference's full forward's, the
    slot's rows after the prompt and after the ticks are the reference's
    ``z`` at the last two positions, and the inactive slot between the two
    live ones holds what it held."""
    cfg = dataclasses.replace(
        TINY, attn_impl="flash" if impl == "pallas" else "dense")
    model, params = Lfm2MoeModel(cfg), _params(cfg)
    prompt, forced = _tokens((27,), 4), _tokens((9,), 5)
    with interpret_scope(True), jax.default_matmul_precision("highest"):
        got, at_prompt, _, at_end, idle = _serve(model, params, prompt,
                                                 forced, chunks, impl)
    seq = np.concatenate([prompt, forced])[None]
    want, z = _reference(params, seq, cfg)
    np.testing.assert_allclose(got, want[0], atol=F32_TOL)
    np.testing.assert_allclose(at_prompt, _kept(z[0], 27), atol=F32_TOL)
    np.testing.assert_allclose(at_end, _kept(z[0], 36), atol=F32_TOL)
    np.testing.assert_array_equal(idle, np.full_like(idle, 0.5))


def test_a_prompt_in_chunks_ends_where_the_whole_prompt_does():
    """The same prompt whole and in three chunks with another slot's ticks
    between them, in a slot that held rows: the same rows, the same cached
    keys and values, the same logits."""
    model, params = Lfm2MoeModel(TINY), _params()
    prompt, forced = _tokens((27,), 4), _tokens((4,), 5)
    with jax.default_matmul_precision("highest"):
        whole = _serve(model, params, prompt, forced, (27,), "dense")
        parts = _serve(model, params, prompt, forced, (8, 8, 11), "dense")
    for a, b in zip(parts[:4], whole[:4]):
        np.testing.assert_allclose(a, b, atol=F32_TOL)
    # rows left behind did not leak in: they were 0.5 everywhere
    assert np.abs(whole[1] - 0.5).min() > 1e-4


def test_a_slot_taken_again_after_a_longer_request_starts_from_nothing():
    """The slot's request (20 tokens, three pages) ends; a request of 5
    tokens is prefilled into the SAME slot on the same pages: its prefill
    overwrites the rows, nothing is cleared, its convolutions start from
    zeros, and its logits and rows are those of the short prompt alone."""
    model, params = Lfm2MoeModel(TINY), _params()
    cfg = TINY
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, slots=2, heads=cfg.n_kv_head, pages=9,
        page_len=8, head_dim=cfg.d_head, max_pages=4, dtype=jnp.float32)
    cache = init_paged_cache(spec)
    state = {name: jnp.zeros(s.shape, s.dtype)
             for name, s in model.serving_state(2).items()}
    prefill = jax.jit(lambda t, n, row, k, v, st: model.prefill_paged(
        params, t, n, np.int32(0), row, k, v, state=st, slot=np.int32(1)))
    row = np.array([1, 2, 3, 0], np.int32)
    k_pool, v_pool = cache["k"], cache["v"]
    logits = {}
    for n, seed in ((20, 1), (5, 2)):
        prompt = _tokens((n,), seed)
        padded = np.zeros((1, 32), np.int32)
        padded[0, :n] = prompt
        out, k_pool, v_pool, state = prefill(padded, np.int32(n), row,
                                             k_pool, v_pool, state)
        logits[n] = (prompt, np.asarray(out[0, :n]))
    prompt, got = logits[5]
    want, z = _reference(params, prompt[None])
    np.testing.assert_allclose(got, want[0], atol=F32_TOL)
    np.testing.assert_allclose(state["conv"][:, 1], _kept(z[0], 5),
                               atol=F32_TOL)
    assert not np.asarray(state["conv"][:, 0]).any()


# -- through the engine ---------------------------------------------------

@pytest.mark.parametrize("serving", [{}, {"prefill_chunk_len": 16}],
                         ids=["plain", "chunked"])
def test_engine_streams_sit_on_the_reference_logits(serving):
    """Through ``ServeEngine``: more requests than slots (a slot is taken
    again after its last occupant), one prompt over the chunk length; every
    emitted token is the reference's argmax."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    model, params = Lfm2MoeModel(cfg), _params(cfg)
    eng = ServeEngine(model, {"serving": {**SERVING, **serving}},
                      params=params)
    chunked = bool(serving)
    try:
        lens = (5, 29, 3, 45 if chunked else 30, 12)
        prompts = [[int(t) for t in _tokens((n,), 7 + n)] for n in lens]
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run_until_idle()
        assert eng._decode_fn._cache_size() == 1
        assert sorted(eng.cache) == ["k", "lengths", "state", "v"]
        # the pool rests paired: ONE row of 16 for 2 key heads of 8
        assert eng.cache["k"].shape[2:] == (1, 8, 16)
        assert eng.paged_decode_arm == "direct"
        assert sorted(eng.state_bytes) == ["conv", "kv"]
        assert eng.state_bytes["conv"] == 6 * 3 * 2 * 32 * 4
        assert model.serving_cache_layers() == {"full": 1, "conv": 6}
        # the query projection rests output-major inside the engine
        assert isinstance(eng.params["full"]["q_w"][0], walked.OutputMajor)
        prefills = [v for _, kind, v in eng.aux_log if kind == "prefill"]
        ticks = [v for _, kind, v in eng.aux_log if kind == "decode"]
        assert all(v["conv_slot_layers"] == 0 and v["full_kv_tokens"] == 0
                   and v["moe_rows"] > 0 for v in prefills)
        assert ticks and all(
            v["conv_slot_layers"] in (6, 12, 18) and v["full_kv_tokens"] > 0
            and v["moe_rows"] == v["conv_slot_layers"] // 6 * 2 * 5
            for v in ticks)
        if chunked:
            # 45 tokens in three chunks of the one program, 29 in two
            assert eng.prefill_chunk_calls == {32: 5}
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
                       match=f"Lfm2MoeModel cannot be served.*{named}"):
        ServeEngine(Lfm2MoeModel(TINY),
                    {"serving": {**SERVING, **serving}}, params=_params())


@pytest.mark.parametrize("field,value,named", [
    ("conv_bias", True, "conv_bias"),
    ("layer_types", TYPES[:6] + ("sliding_attention",), "layer_types"),
    ("layer_types", TYPES[:6], "one a layer"),
    ("tie_word_embeddings", False, "tie_word_embeddings"),
    ("num_dense_layers", 8, "dense layer after an expert layer"),
    ("num_key_value_heads", 3, "multiple of num_key_value_heads"),
    ("conv_L_cache", 1, "conv_L_cache"),
    ("num_experts_per_tok", 9, "num_experts_per_tok"),
    ("attn_impl", "triton", "attn_impl"),
])
def test_config_refuses_what_is_not_built(field, value, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(TINY, **{field: value})


def test_config_reads_the_published_row():
    """The catalog's own keys build the configuration as published: 40
    layers, 30 conv mixers to 10 full ones, two dense layers then 38 expert
    layers of 64, 32 query heads on 8 key heads of 64 that rest as 4 of
    128, 8,192 B of rows a conv layer a slot, 23.8 B parameters."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b.json")) as f:
        file = json.load(f)
    fields = {f.name for f in dataclasses.fields(Lfm2MoeConfig)}
    keys = {k: v for k, v in file.items() if k in fields}
    assert file["model_type"] == "lfm2_moe" and "head_dim" not in keys
    cut = Lfm2MoeConfig(**keys)
    assert [k for k, _ in cut.kinds] == ["conv", "conv"] + [
        "full", "conv", "conv", "conv"] * 2
    assert [f for _, f in cut.kinds] == ["dense"] * 2 + ["moe"] * 8
    keys.update(file["published"])
    cfg = Lfm2MoeConfig(**keys)
    assert (cfg.num_hidden_layers, cfg.count("conv"), cfg.count("full"),
            cfg.count("dense"), cfg.count("moe")) == (40, 30, 10, 2, 38)
    assert cfg.kinds[:10] == cut.kinds
    assert (cfg.n_layer, cfg.n_head, cfg.num_key_value_heads, cfg.key_dim,
            cfg.pairs, cfg.n_kv_head, cfg.d_head, cfg.n_positions,
            cfg.rope_theta) == (10, 32, 8, 64, 2, 4, 128, 128000, 1e6)
    state = Lfm2MoeModel(cut).serving_state(512)["conv"]
    assert state.shape == (8, 512, 4096)
    params = jax.eval_shape(Lfm2MoeModel(cfg).init, jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert round(count / 1e8) == 238            # 23.8 B: the name's 24B
    assert "lm_head" not in params
    held = jax.eval_shape(Lfm2MoeModel(cut).init, jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(held))
    assert round(count / 1e6) == 5267           # the cut: 10.53 GB
