"""Olmo Hybrid (``deepspeed_tpu/models/olmo_hybrid.py``): the model against
the benchmark's plain float32 reference, prefill then decode through the
two pools and the state by slot over a mixed batch with an inactive slot, a
prompt prefilled whole against the same prompt in chunks with another
slot's ticks between them, a slot taken again after a longer request, the
engine's streams, its books and the refusals.  CPU, toy widths with keys
and values of different widths and a head count that is no multiple of 8,
seeded weights.  (The two kernels of ``ops/pallas/kda.py`` under one set of
tests: tests/test_kimi_linear.py; the cell's rehearsal:
tests/test_benchmark_cells.py.)"""
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
from deepspeed_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                              OlmoHybridModel)
from deepspeed_tpu.ops.pallas.kda import gdn_heads
from deepspeed_tpu.ops.pallas.runtime import interpret_scope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import olmo_hybrid_reference  # noqa: E402

TYPES = ("linear_attention",) * 3 + ("full_attention", "linear_attention")
TINY = OlmoHybridConfig(
    vocab_size=128, hidden_size=48, intermediate_size=96,
    num_hidden_layers=5, layer_types=TYPES, num_attention_heads=3,
    num_key_value_heads=3, linear_num_key_heads=3, linear_num_value_heads=3,
    linear_key_head_dim=8, linear_value_head_dim=16,
    max_position_embeddings=256, attn_impl="dense", initializer_range=0.1)
SERVING = {"slots": 3, "page_len": 8, "max_seq_len": 96, "prefill_len": 32,
           "prefix_cache": False}
# float32 on the CPU.  Every sublayer's output is scaled to unit RMS before
# it joins the stream, so a sublayer's RELATIVE error is the stream's
# absolute one: the chunked form reassociates the recurrence (1e-6 of an
# output of order 1, 1e-4 of the first positions' outputs, which are
# small), and that reaches the logits as 2e-3 to 6e-3 of 3 (measured; with the
# reference's own recurrence in the model's place, 1e-5).  A mixer, a state,
# a norm or a chunk's start left out moves the logits by 0.1 and more.
F32_TOL = 1e-2


def _params(cfg=TINY, seed=0):
    return drawn_once(OlmoHybridModel, cfg, seed)


def _reference(params, tokens, cfg=TINY, **switches):
    """(logits [B, T, V], the linear layers' states [B, layers, H, dk,
    dv])."""
    with jax.default_matmul_precision("highest"):
        return tuple(np.asarray(t) for t in
                     olmo_hybrid_reference.olmo_hybrid_logits(
                         params, tokens, dataclasses.asdict(cfg), **switches))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, shape).astype(np.int32)


# -- the model against the reference --------------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_apply_matches_the_reference_in_float32(attn_impl):
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    params, tokens = _params(cfg), _tokens((2, 70))
    with jax.default_matmul_precision("highest"), interpret_scope(True):
        got, aux = OlmoHybridModel(cfg).apply(params, tokens, aux=True)
    want, _ = _reference(params, tokens, cfg)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    assert sorted(aux) == sorted(OlmoHybridModel.serving_aux)


@pytest.mark.parametrize("switch", [
    {"step_factor": 1.0}, {"state_dtype": jnp.bfloat16}],
    ids=["step_without_its_factor_2", "bfloat16_state"])
def test_the_float32_tolerance_fails_the_benchmarks_controls(switch):
    params, tokens = _params(), _tokens((1, 70))
    want, _ = _reference(params, tokens)
    low, _ = _reference(params, tokens, **switch)
    assert np.abs(low - want).max() > 4 * F32_TOL


def test_a_step_that_stays_below_one_is_the_source_without_the_flag():
    """``linear_allow_neg_eigval`` false: ``b = sigmoid(.)``, on both
    sides."""
    cfg = dataclasses.replace(TINY, linear_allow_neg_eigval=False)
    params, tokens = _params(cfg), _tokens((1, 40))
    got = OlmoHybridModel(cfg).apply(params, tokens)
    np.testing.assert_allclose(got, _reference(params, tokens, cfg)[0],
                               atol=F32_TOL)
    with_flag = OlmoHybridModel(TINY).apply(params, tokens)
    assert np.abs(np.asarray(got) - np.asarray(with_flag)).max() > 0.05


# -- the paged steps ------------------------------------------------------

def _serve(model, params, prompt, forced, chunks, impl, page_len=8, slots=3,
           max_pages=12, bucket=32, first_len=3):
    """Prefill ``prompt`` in ``chunks`` (lengths) into the LAST slot,
    whose last occupant left a state behind (0.5 everywhere), with a
    decode tick of the first slot, which lives on pages and a state of its
    own, between the chunks, the middle slot inactive throughout; then one
    tick a forced token of the last slot WITH the first slot (a mixed
    batch).  Returns (the logits of every prompt position and of every
    tick, the last slot's state after the prompt, the request's cached
    keys and values, its state after the ticks, the middle slot's state at
    the end)."""
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
    mine = jax.tree.map(lambda a: np.asarray(a[:, slot]), state)
    for token in forced:
        tokens = jnp.full((slots,), 9, jnp.int32).at[slot].set(int(token))
        logits, k_pool, v_pool, state, lengths = decode(
            params, tokens, k_pool, v_pool, table, lengths, active, state)
        rows.append(np.asarray(logits[slot])[None])
    cached = np.stack([np.asarray(k_pool)[:, row[:n_pages]],
                       np.asarray(v_pool)[:, row[:n_pages]]])
    return (np.concatenate(rows), mine, cached,
            jax.tree.map(lambda a: np.asarray(a[:, slot]), state),
            jax.tree.map(lambda a: np.asarray(a[:, 1]), state))


def _by_head(rest):
    return np.asarray(gdn_heads(rest, TINY.gdn_heads))


@pytest.mark.parametrize("chunks", [(27,), (16, 11), (8, 8, 11)],
                         ids=["whole", "two_chunks", "three_chunks"])
@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_paged_steps_against_the_reference(impl, chunks):
    """The prefill (whole, or in chunks that start from the slot's state
    and the request's pages) then ticks of a mixed batch through the pools
    and the state: every logit is the reference's full forward's, the
    slot's states after the prompt and after the ticks are the reference's,
    and the inactive slot between the two live ones holds what it held."""
    cfg = dataclasses.replace(
        TINY, attn_impl="flash" if impl == "pallas" else "dense")
    model, params = OlmoHybridModel(cfg), _params(cfg)
    prompt, forced = _tokens((27,), 4), _tokens((9,), 5)
    with interpret_scope(True), jax.default_matmul_precision("highest"):
        got, at_prompt, _, at_end, idle = _serve(model, params, prompt,
                                                 forced, chunks, impl)
    seq = np.concatenate([prompt, forced])[None]
    want, end_states = _reference(params, seq, cfg)
    _, prompt_states = _reference(params, seq, cfg, length=27)
    np.testing.assert_allclose(got, want[0], atol=F32_TOL)
    np.testing.assert_allclose(_by_head(at_prompt["gdn"]), prompt_states[0],
                               atol=F32_TOL)
    np.testing.assert_allclose(_by_head(at_end["gdn"]), end_states[0],
                               atol=F32_TOL)
    for leaf in idle.values():
        np.testing.assert_array_equal(leaf, np.full_like(leaf, 0.5))


def test_a_prompt_in_chunks_ends_where_the_whole_prompt_does():
    """The same prompt whole and in three chunks with another slot's
    ticks between them, in a slot that held a state: the same state, the
    same convolution tail, the same cached keys and values, the same
    logits."""
    model, params = OlmoHybridModel(TINY), _params()
    prompt, forced = _tokens((27,), 4), _tokens((4,), 5)
    with jax.default_matmul_precision("highest"):
        whole = _serve(model, params, prompt, forced, (27,), "dense")
        parts = _serve(model, params, prompt, forced, (8, 8, 11), "dense")
    np.testing.assert_allclose(parts[0], whole[0], atol=F32_TOL)
    for name in ("gdn", "gdn_conv"):
        np.testing.assert_allclose(parts[1][name], whole[1][name],
                                   atol=F32_TOL)
        np.testing.assert_allclose(parts[3][name], whole[3][name],
                                   atol=F32_TOL)
    np.testing.assert_allclose(parts[2], whole[2], atol=F32_TOL)
    assert np.abs(whole[1]["gdn"]).max() > 0.05
    # a state left behind did not leak in: it was 0.5 everywhere
    assert np.abs(whole[1]["gdn"] - 0.5).min() > 1e-3


def test_a_slot_taken_again_after_a_longer_request_starts_from_nothing():
    """The first slot's request (20 tokens, three pages) ends; a request of
    5 tokens is prefilled into the SAME slot on the same pages: its
    prefill overwrites the state and the tail, nothing is cleared, and its
    logits and state are those of the short prompt alone."""
    model, params = OlmoHybridModel(TINY), _params()
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
    want, states = _reference(params, prompt[None])
    np.testing.assert_allclose(got, want[0], atol=F32_TOL)
    np.testing.assert_allclose(_by_head(state["gdn"][:, 1]), states[0],
                               atol=F32_TOL)
    assert not np.asarray(state["gdn"][:, 0]).any()


# -- through the engine ---------------------------------------------------

@pytest.mark.parametrize("serving", [{}, {"prefill_chunk_len": 16}],
                         ids=["plain", "chunked"])
def test_engine_streams_sit_on_the_reference_logits(serving):
    """Through ``ServeEngine``: more requests than slots (a slot is taken
    again after its last occupant), one prompt over the chunk length;
    every emitted token is the reference's argmax."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    model, params = OlmoHybridModel(cfg), _params(cfg)
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
        assert sorted(eng.state_bytes) == ["gdn", "gdn_conv", "kv"]
        assert eng.state_bytes["gdn"] == 4 * 3 * 3 * 8 * 16 * 4
        assert model.serving_cache_layers() == {"full": 1, "gdn": 4}
        prefills = [v for _, kind, v in eng.aux_log if kind == "prefill"]
        ticks = [v for _, kind, v in eng.aux_log if kind == "decode"]
        # a rung of 32 runs the chunked form over one chunk of 64
        assert all(v["gdn_slot_layers"] == 0 and v["full_kv_tokens"] == 0
                   and v["gdn_chunk_tokens"] == 64 * 4 for v in prefills)
        assert ticks and all(
            v["gdn_slot_layers"] in (4, 8, 12) and v["gdn_chunk_tokens"] == 0
            and v["full_kv_tokens"] > 0 for v in ticks)
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
                       match=f"OlmoHybridModel cannot be served.*{named}"):
        ServeEngine(OlmoHybridModel(TINY),
                    {"serving": {**SERVING, **serving}}, params=_params())


@pytest.mark.parametrize("field,value,named", [
    ("num_key_value_heads", 1, "grouped keys"),
    ("rope_parameters", {"rope_theta": 500000.0}, "rope_theta"),
    ("layer_types", TYPES[:4] + ("sliding_attention",), "layer_types"),
    ("layer_types", TYPES[:4], "one a layer"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("linear_num_value_heads", 6, "linear_num_value_heads"),
    ("hidden_act", "gelu", "hidden_act"),
    ("attn_impl", "triton", "attn_impl"),
])
def test_config_refuses_what_is_not_built(field, value, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(TINY, **{field: value})


def test_config_reads_the_published_row():
    """The catalog's own keys build the configuration as published: 32
    layers, 24 linear mixers to 8 full ones, heads of 128 on 30 key heads
    in two pools, 2,211,840 B of state a layer at rest with no lane of
    padding, 13.69 MB a slot over six layers with the tails."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        file = json.load(f)
    fields = {f.name for f in dataclasses.fields(OlmoHybridConfig)}
    keys = {k: v for k, v in file.items() if k in fields}
    assert (keys["rope_parameters"], file["model_type"]) == (
        {"rope_theta": None}, "olmo_hybrid")
    cut = OlmoHybridConfig(**keys)
    assert cut.kinds == ("gdn",) * 3 + ("full",) + ("gdn",) * 3 + ("full",)
    keys.update(file["published"])
    cfg = OlmoHybridConfig(**keys)
    assert (cfg.num_hidden_layers, cfg.count("gdn"), cfg.count("full")) \
        == (32, 24, 8)
    assert cfg.kinds == cut.kinds * 4
    # the default list is the published one
    assert OlmoHybridConfig().layer_types == cfg.layer_types
    assert (cfg.n_layer, cfg.n_head, cfg.n_kv_head, cfg.d_head,
            cfg.n_positions) == (8, 30, 30, 128, 65536)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
            cfg.key_width, cfg.value_width, cfg.conv_width) == (
        3840, 11008, 100352, 2880, 5760, 11520)
    state = OlmoHybridModel(cut).serving_state(1)
    assert state["gdn"].shape == (6, 1, 96, 5760)
    assert state["gdn"].shape[-1] % 128 == 0 and 96 % 8 == 0
    nbytes = {k: int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
              for k, s in state.items()}
    assert nbytes["gdn"] == 6 * 2211840
    # the tail in the type the weights are served in (bfloat16: 2 B)
    assert nbytes["gdn_conv"] // 2 + nbytes["gdn"] == 6 * (
        2211840 + 3 * 11520 * 2)
    params = jax.eval_shape(OlmoHybridModel(cfg).init, jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert round(count / 1e6) == 7431      # 7.43 B: the name's 7B
