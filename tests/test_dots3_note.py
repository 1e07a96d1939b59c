"""dots3-note-prev (latent attention inside a window on three layers in four,
over an indexer's picks on the fourth, a gate a head on both): the ring
kernel in interpret mode against a plain reference over rings that have and
have not wrapped and an inactive slot, the model against the benchmark's
plain float32 reference, prefill in chunks then decode through rings, pool
AND indexer keys past the window's wrap and past ``index_topk``, a prompt in
three chunks against the same prompt whole, that gate, rescale and window edge
each move the logits, the shares of the experts with the shared expert counted
once against the uncut layer, the engine's three caches and the refusals.
CPU, the configuration file's ``rehearse`` sizes, seeded weights.  (Its
cell's rehearsal: tests/test_benchmark_cells.py.)"""
import dataclasses
import importlib
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
from deepspeed_tpu.models import dots3_note
from deepspeed_tpu.models.dots3_note import Dots3NoteConfig, Dots3NoteModel
from deepspeed_tpu.ops.pallas.runtime import interpret_scope

# the package exports a function of the module's own name
da = importlib.import_module("deepspeed_tpu.ops.pallas.decode_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import dots3_note_reference  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "dots3-note-prev.json")) as _f:
    FILE = json.load(_f)
_SIZES = dict(FILE["rehearse"]["sizes"])
# 0.1 and not the rehearsal's 0.06: the gate, the rescale and the window's
# edge must each show well over the float32 tolerance
TINY = Dots3NoteConfig(
    **{**_SIZES, "experts_held": tuple(_SIZES["experts_held"]),
       "initializer_range": 0.1}, attn_impl="dense")
WINDOW, TOPK = TINY.sliding_window_size, TINY.index_topk      # 9 and 16
SERVING = {"slots": 3, "page_len": 8, "max_seq_len": 96, "prefill_len": 16,
           "prefill_chunk_len": 16, "prefix_cache": False}
# float32 on the CPU: the model and the reference differ by summation order
# (measured 4e-6 on logits of size 3); a pick flipped by that order, or a
# ring row off by one, would move a logit by 1e-2, and none is
F32_TOL = 2e-4


def _params(cfg=TINY, seed=0):
    return drawn_once(Dots3NoteModel, cfg, seed)


def _m(cfg):
    return dataclasses.asdict(cfg)


def _reference(params, tokens, cfg=TINY, **kw):
    with jax.default_matmul_precision("highest"):
        out = dots3_note_reference.dots3_note_logits(params, tokens, _m(cfg),
                                                     block=32, **kw)
    return jax.tree.map(np.asarray, out)


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, shape).astype(np.int32)


# -- the kernel -----------------------------------------------------------

@pytest.mark.parametrize("rows,window", [(16, 9), (576, 513), (128, 128)],
                         ids=["toy", "published_ring", "whole_granules"])
def test_ring_kernel_reads_wrapped_unwrapped_and_no_inactive_ring(rows,
                                                                  window):
    """``ds_window_latent_decode_attn`` over stacked rings ``[layers x
    slots, R, W]``: a slot whose ring has wrapped is live in its ``window``
    rows, one that has not in its first ``length``, an inactive one (length
    0) gives exact zeros whatever its ring holds.  The granules past a
    slot's length are never copied (they hold NaN here); the dead rows of
    a live granule are copied and weigh 0 (finite: the engine's rings start
    as zeros and the rows past the window are never written).  Against the
    softmax written out in numpy, and the dense arm."""
    rng = np.random.default_rng(0)
    S, H, W, C, layers = 4, 4, 56, 48, 2
    rings = rng.standard_normal((layers * S, rows, W)).astype(np.float32)
    rings[:, window:] = 7.0
    lengths = np.asarray([window, 5, 0, 1], np.int32)
    g = da.ring_granule(rows)
    for s, n in enumerate(lengths):
        rings[S + s, -(-n // g) * g:] = np.nan
    q = rng.standard_normal((S, H, W)).astype(np.float32)
    with interpret_scope(True):
        got = da.window_latent_decode_attention(
            jnp.asarray(q), jnp.asarray(rings), jnp.asarray(lengths), C,
            base=S, sm_scale=0.25, impl="pallas")
    dense = da.window_latent_decode_attention(
        jnp.asarray(q), jnp.asarray(np.nan_to_num(rings)),
        jnp.asarray(lengths), C, base=S, sm_scale=0.25, impl="dense")
    want = np.zeros((S, H, C), np.float32)
    for s, n in enumerate(lengths):
        if n:
            live = rings[S + s, :n]
            sc = q[s] @ live.T * 0.25
            p = np.exp(sc - sc.max(-1, keepdims=True))
            want[s] = (p / p.sum(-1, keepdims=True)) @ live[:, :C]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dense), want, atol=2e-5)
    assert not np.asarray(got)[2].any()


def test_a_ring_at_rest_is_whole_granules():
    assert da.ring_granule(576) == 64 and da.ring_granule(16) == 16
    with pytest.raises(ValueError, match="granules"):
        da.ring_granule(513)
    assert TINY.ring_rows == 16
    assert Dots3NoteConfig(layer_types=("full_attention",) * 46
                           ).ring_rows == 576


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_apply_matches_the_reference_in_float32(attn_impl):
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    tokens = _tokens((2, 40))
    got = np.asarray(Dots3NoteModel(cfg).apply(_params(cfg), tokens))
    np.testing.assert_allclose(got, _reference(_params(cfg), tokens, cfg),
                               atol=F32_TOL)


@pytest.mark.parametrize("switch", [{"no_gate": True}, {"no_rescale": True},
                                    {"window": WINDOW + 1},
                                    {"window": WINDOW - 1},
                                    {"low_keys": True},
                                    {"round_acts": True,
                                     "act_dtype": jnp.bfloat16}],
                         ids=["gate", "rescale", "window_edge_over",
                              "window_edge_under", "index_keys_8bit",
                              "bfloat16_residual"])
def test_gate_rescale_and_window_edge_each_move_the_logits(switch):
    """None of them can be dropped inside the tolerance: a reference without
    the gate, without the rescale, with a window one key wider or narrower
    (or with 8-bit indexer keys, or a bfloat16 residual stream) lies a
    hundred tolerances and more from the program."""
    tokens = _tokens((2, 40), 3)
    got = np.asarray(Dots3NoteModel(TINY).apply(_params(), tokens))
    off = _reference(_params(), tokens, **switch)
    assert np.abs(off - got).max() > 100 * F32_TOL


def _paged(model, params, prompt, forced, chunks, impl, page_len=8, slots=3,
           max_pages=12, bucket=32):
    """Prefill ``prompt`` in ``chunks`` into the middle slot's rings and
    pages, then one decode tick a forced token, the other slots inactive
    and holding NaN rings.  Returns the logits of every prompt position and
    tick, the ticks' picked positions [ticks, full layers, K], the caches."""
    cfg = model.config
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, slots=slots, heads=1, pages=1 + max_pages,
        page_len=page_len, head_dim=cfg.d_head, max_pages=max_pages,
        dtype=jnp.float32, v_head_dim=cfg.d_head_v, values_in_keys=True,
        index_layers=cfg.n_index_layer, index_dim=cfg.d_index)
    cache = init_paged_cache(spec)
    pool, keys = cache["k"], cache["index_k"]
    slot = slots // 2
    # what a slot held before must not be read: not by the request's first
    # chunk (its own rings hold 100.0: finite, as whatever an engine's slot
    # held before is; a dead row of a live granule is copied and weighs 0),
    # not by a tick of the slots beside it (NaN: never copied)
    state = {k: jnp.full(v.shape, jnp.nan, jnp.float32).at[:, slot].set(100.0)
             for k, v in model.serving_state(slots).items()}
    row = np.zeros((max_pages,), np.int32)
    n_pages = -(-(len(prompt) + len(forced)) // page_len)
    row[:n_pages] = 1 + np.arange(n_pages)
    done, rows, picked = 0, [], []
    prefill = jax.jit(lambda p, t, n, pre, r, k, ik, st: model.prefill_paged(
        p, t, n, pre, r, k, None, state=st, slot=np.int32(slot),
        index_pool=ik, aux=True))
    decode = jax.jit(lambda p, t, k, ik, st, tab, ln, act: model.
                     decode_step_paged(p, t, k, None, tab, ln, act, state=st,
                                       impl=impl, index_pool=ik, aux=True))
    for n in chunks:
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt[done:done + n]
        logits, pool, none, keys, state, aux = prefill(
            params, padded, np.int32(n), np.int32(done), row, pool, keys,
            state)
        assert none is None
        assert int(aux["latent_context_rows"]) \
            == cfg.count("full") * (done + n)
        rows.append(np.asarray(logits[0, :n]))
        done += n
    table = np.zeros((slots, max_pages), np.int32)
    table[slot] = row
    active = np.zeros((slots,), bool)
    active[slot] = True
    lengths = jnp.zeros((slots,), jnp.int32).at[slot].set(done)
    for token in forced:
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(int(token))
        logits, pool, none, keys, state, lengths, aux = decode(
            params, tokens, pool, keys, state, table, lengths, active)
        rows.append(np.asarray(logits[slot])[None])
        picked.append(np.asarray(aux["index_picks"][:, slot]))
        n = int(lengths[slot])
        assert int(aux["window_latent_rows"]) \
            == cfg.count("window") * min(n, WINDOW)
        assert int(aux["window_wrapped_slots"]) == int(n > WINDOW)
        assert int(aux["latent_kv_tokens"]) == cfg.count("full") * n
        assert int(aux["index_scored_rows"]) == cfg.count("full") * n
        assert int(aux["index_selected_rows"]) \
            == cfg.count("full") * min(cfg.index_topk, n)
    return (np.concatenate(rows), np.stack(picked) if picked else None,
            {"k": pool, "index_k": keys, "state": state})


@pytest.mark.parametrize("chunks", [(27,), (16, 11), (8, 8, 11), (5,)],
                         ids=["whole", "two_chunks", "three_chunks",
                              "under_the_window"])
@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_paged_steps_through_rings_pool_and_index_keys_against_the_reference(
        attn_impl, chunks):
    """Prefill (whole; in chunks that read the slot's rings and the
    request's pages) writes all three caches; the ticks attend the ring on a
    sliding layer and the pages under the picks on a full one: every logit
    is the reference's full forward's and every picked set the reference's
    own, past the window's wrap (9) and past ``index_topk`` (16).
    ``under_the_window``: the ticks start at a context of 5, wrap the ring at
    10 and fill the picks at 16."""
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    model, params = Dots3NoteModel(cfg), _params(cfg)
    n, ticks = sum(chunks), 14
    prompt, forced = _tokens((n,), 4), _tokens((ticks,), 5)
    with interpret_scope(True):
        got, picked, cache = _paged(
            model, params, prompt, forced, chunks,
            "pallas" if attn_impl == "flash" else "dense")
    seq = np.concatenate([prompt, forced])[None]
    want, sets = _reference(params, seq, cfg, pick_rows=n + np.arange(ticks))
    np.testing.assert_allclose(got, want[0], atol=F32_TOL)
    assert picked.shape == (ticks, 2, TOPK)
    for i in range(ticks):
        count = min(TOPK, n + i + 1)
        for layer in range(2):
            mine = np.zeros(n + ticks, bool)
            mine[picked[i, layer, :count]] = True
            assert mine.sum() == count
            np.testing.assert_array_equal(mine, sets[0, layer, i])
    # the slots beside the request's hold what they held
    ring = np.asarray(cache["state"]["window_latent"])
    assert np.isnan(ring[:, 0]).all() and np.isnan(ring[:, 2]).all()
    assert np.isfinite(ring[:, 1, 0, :min(n + ticks, WINDOW)]).all()


def test_a_prompt_in_three_chunks_is_the_same_prompt_whole():
    """The same 40 tokens as one prefill call and as chunks of 16, 16 and 8
    of the same program: the logits of every position, the slot's rings and
    the request's pages and indexer keys agree (float32: the chunks' flash
    blocks and the whole prompt's sum in another order)."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    model, params = Dots3NoteModel(cfg), _params(cfg)
    prompt = _tokens((40,), 6)
    with interpret_scope(True):
        whole, _, a = _paged(model, params, prompt, [], (40,), "pallas",
                             bucket=48)
        parts, _, b = _paged(model, params, prompt, [], (16, 16, 8), "pallas",
                             bucket=16)
    np.testing.assert_allclose(parts, whole, atol=F32_TOL)
    ring_a, ring_b = (np.asarray(c["state"]["window_latent"])[:, 1, 0, :WINDOW]
                      for c in (a, b))
    np.testing.assert_allclose(ring_b, ring_a, atol=F32_TOL)
    for name in ("k", "index_k"):
        np.testing.assert_allclose(np.asarray(b[name])[:, 1:6],
                                   np.asarray(a[name])[:, 1:6], atol=F32_TOL)


def test_the_rings_hold_the_rescaled_latent_of_the_last_window_positions():
    """Position ``p`` lies at row ``p % window``: after a prefill of 27 the
    first sliding layer's ring holds positions 18-26, each ``[s_kv
    RMSNorm(c) ; RoPE(k_r) ; 0]`` as the whole-sequence forward computes
    them."""
    model, params = Dots3NoteModel(TINY), _params()
    prompt = _tokens((27,), 4)
    _, _, cache = _paged(model, params, prompt, [], (16, 11), "dense")
    w = TINY.window
    positions = jnp.arange(27, dtype=jnp.int32)[None]
    rows = {}

    def spy(kind, i, ap, ip, h, lat):
        if kind == "window" and i == 0:
            rows["first"] = np.asarray(
                dots3_note.latent_rows(lat[3][0], lat[4][0], w.row))
        return dots3_note._dense_attention(TINY, kind, ap, ip, h, lat,
                                           positions)

    dots3_note._layers(TINY, params, prompt[None], positions, None, spy)
    ring = np.asarray(cache["state"]["window_latent"])[0, 1, 0]
    for p in range(18, 27):
        np.testing.assert_allclose(ring[p % WINDOW], rows["first"][p],
                                   atol=F32_TOL)
    assert not ring[:WINDOW, w.kv_rank + w.rot:].any()


def test_the_sixteen_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """The routed parts of all shares (each computes the shared expert too:
    counted once) add up to the uncut layer; the reference's share is the
    program's."""
    cfg = dataclasses.replace(TINY, experts_held=None, n_routed_experts=32)
    params = _params(cfg, 2)
    x = jnp.asarray(np.random.RandomState(8).randn(12, 64), jnp.float32)

    def layer(c, p):
        ep = dots3_note.at(p["moe"], 0)
        out, st = dots3_note._experts(c, ep, dots3_note.stacked_experts(p), 0,
                                      x, None)
        return out, dots3_note.shared_expert(ep, x), st

    full, shared, stats = layer(cfg, params)
    assert int(stats.rows) == 12 * 3 and float(jnp.abs(shared).max()) > 0
    routed, rows, elsewhere = 0.0, 0, 0
    for first in range(0, 32, 2):                   # sixteen shares of two
        share = dataclasses.replace(cfg, experts_held=(first, 2))
        held = dict(params, moe={
            k: (v[:, first:first + 2] if k in ("gate_w", "up_w", "down_w")
                else v) for k, v in params["moe"].items()})
        part, same, st = layer(share, held)
        np.testing.assert_array_equal(same, shared)
        routed = routed + (part - same)
        rows += int(st.rows)
        elsewhere += int(st.rows_elsewhere)
    np.testing.assert_allclose(routed + shared, full, atol=1e-6)
    assert rows == 12 * 3 and elsewhere == 15 * 12 * 3
    held = dataclasses.replace(cfg, experts_held=(4, 8))
    cut = dict(params, moe={
        k: (v[:, 4:12] if k in ("gate_w", "up_w", "down_w") else v)
        for k, v in params["moe"].items()})
    tokens = _tokens((1, 24))
    got = np.asarray(Dots3NoteModel(held).apply(cut, tokens))
    assert np.abs(got - _reference(cut, tokens, held)).max() < F32_TOL


# -- through the engine -------------------------------------------------------

@pytest.mark.parametrize("serving", [{}, {"prefill_chunk_len": 8},
                                     {"prefill_len": 64,
                                      "prefill_chunk_len": 0}],
                         ids=["chunks_of_16", "chunks_of_8", "whole_prompts"])
def test_engine_streams_sit_on_the_reference_logits(serving):
    """Prompts under and over the window, ``index_topk`` and the chunk, four
    requests through three slots (one waits, and lands in a slot whose rings
    another request filled): every greedy token is the reference's argmax
    at its position, wherever the reference's top two lie clear of each
    other."""
    params = _params()
    eng = ServeEngine(Dots3NoteModel(TINY),
                      {"serving": {**SERVING, **serving}}, params=params)
    prompts = [_tokens((n,), 10 + n).tolist() for n in (37, 5, 20, 50)]
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    assert eng._decode_fn._cache_size() == 1
    for prompt, r in zip(prompts, reqs):
        tokens = r.result()
        assert r.finish_reason == "length" and len(tokens) == 12
        seq = np.asarray([prompt + list(tokens)], np.int32)
        rows = _reference(params, seq)[0, len(prompt) - 1:-1]
        top = np.sort(rows, axis=-1)
        clear = top[:, -1] - top[:, -2] > 10 * F32_TOL
        assert clear.sum() >= 10
        np.testing.assert_array_equal(rows.argmax(-1)[clear],
                                      np.asarray(tokens)[clear])
    if serving.get("prefill_chunk_len", 16):
        assert sum(eng.prefill_chunk_calls.values()) >= 3 + 2 + 4
    decode = [v for _, kind, v in eng.aux_log if kind == "decode"]
    assert decode and all(
        name in decode[-1] for name in Dots3NoteModel.serving_aux)
    assert max(v["window_wrapped_slots"] for v in decode) >= 2
    eng.close()


def test_engine_holds_rings_pool_and_index_keys_at_once(tmp_path):
    """The first model to ask for all three: request state by slot beside
    one pool of latent rows and the indexer's keys under its page ids; the
    gauges name each by kind."""
    eng = ServeEngine(Dots3NoteModel(TINY), {
        "serving": SERVING,
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}},
        params=_params())
    assert sorted(eng.cache) == ["index_k", "k", "lengths", "state"]
    assert eng.cache["k"].shape[0] == eng.cache["index_k"].shape[0] == 2
    ring = eng.cache["state"]["window_latent"]
    assert ring.shape == (3, 3, 1, 16, 56)
    assert eng.cache_spec.pool_names == ("k", "index_k")
    assert sorted(eng.state_bytes) == ["index_k", "latent", "window_latent"]
    assert eng.state_bytes["window_latent"] == ring.size * 4
    assert eng.state_bytes["latent"] + eng.state_bytes["index_k"] \
        == eng.kv_bytes
    assert eng.model.serving_cache_layers() == {
        "latent": 2, "window_latent": 3, "index": 2}
    eng.submit(_tokens((20,)).tolist(), max_new_tokens=4)
    eng.run_until_idle()
    reg = eng.telemetry.registry
    for kind, nbytes in eng.state_bytes.items():
        assert reg.gauge("serve_state_bytes", "").value(kind=kind) == nbytes
    assert reg.gauge("serve_cache_layers", "").value(
        kind="window_latent") == 3
    eng.close()


@pytest.mark.parametrize("serving,named", [
    ({"page_len": 0, "prefill_chunk_len": 0}, "page_len"),
    ({"speculate_k": 2, "draft": {"d_model": 32, "n_layer": 1, "n_head": 2}},
     "speculate_k"),
    ({"quantization": {"weights": "int8"}}, "quantization"),
    ({"lora": {"rank": 2, "alpha": 4.0, "max_adapters": 2,
               "hbm_adapter_slots": 1, "targets": ["qkv_w"]}}, "lora"),
    ({"prefix_cache": True}, "prefix"),
    ({"prefix_cache": True, "kv_tier": {"idle_park_ticks": 3,
                                        "host_budget_pages": 8}}, "state")])
def test_engine_refuses_the_arms_these_steps_lack(serving, named):
    with pytest.raises(ValueError, match=named):
        ServeEngine(Dots3NoteModel(TINY),
                    {"serving": {**SERVING, **serving}}, params=_params())


@pytest.mark.parametrize("field,value,named", [
    ("attention_gate_type", "elementwise", "headwise"),
    ("swa_attention_gate_type", "none", "headwise"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("scoring_func", "softmax", "sigmoid"),
    ("topk_method", "greedy", "noaux_tc"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("swa_num_key_value_heads", 2, "num_key_value_heads"),
    ("experts_held", (12, 8), "experts_held"),
    ("layer_types", ("full_attention",) * 4, "layer_types"),
    ("sliding_window_size", 1, "sliding_window_size")])
def test_config_refuses_what_is_not_built(field, value, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(TINY, **{field: value})


def test_config_reads_the_published_row():
    """The configuration file's keys are the source's, the widths as
    published: two kinds of latent attention, the deployment's share."""
    fields = {f.name for f in dataclasses.fields(Dots3NoteConfig)}
    keys = {k: v for k, v in FILE.items() if k in fields}
    keys["n_routed_experts"] = FILE["published"]["n_routed_experts"]
    keys["experts_held"] = tuple(FILE["experts_held"])
    cfg = Dots3NoteConfig(**keys)
    assert cfg.full == (128, 1024, 512, 128, 64, 128, 8e7)
    assert cfg.window == (64, 1024, 1024, 192, 64, 128, 5e4)
    assert (cfg.full.row, cfg.window.row, cfg.ring_rows) == (640, 1152, 576)
    assert cfg.kinds == ("full",) + ("window",) * 3 + ("full",) \
        + ("window",) * 3 + ("full",)
    assert (cfg.n_layer, cfg.n_index_layer, cfg.d_head, cfg.d_head_v,
            cfg.d_index) == (3, 3, 640, 512, 128)
    assert cfg.rescale(1024) == 5 ** 0.5 and cfg.rescale(512) == 10 ** 0.5
    assert cfg.held == (0, 16) and cfg.count("moe") == 8
    assert FILE["reduced"] == ["num_hidden_layers", "layer_types",
                               "n_routed_experts", "vocab_size"]
    for key in ("indexer", "rescale", "gate", "window_edge", "rope",
                "router", "sampling", "row_width_at_rest", "weights"):
        assert FILE["assumed"][key]
