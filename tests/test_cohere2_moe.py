"""Command A+ (``cohere2_moe``): each new piece against a line of jax.numpy
written by hand (the parallel block, the LayerNorm without a bias, the
interleaved rotation, the tied head over a slice), the model against the
benchmark's plain float32 reference, prefill then decode through the page
pool and rings that wrap, a prompt in chunks against the whole prompt, the
shares of the experts against the uncut layer, and the refusals.  CPU, tiny
widths, seeded weights.  (Its cell's rehearsal: tests/test_benchmark_cells.py.)
"""
import dataclasses
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
from deepspeed_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                              Cohere2MoeModel, _live_pairs,
                                              layer_norm, rope_interleaved)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import cohere2_moe_reference  # noqa: E402

TYPES = ("sliding_attention",) * 3 + ("full_attention",)
# initializer_range 0.2: at hidden 64 the scores of weights drawn at 0.02
# are ~0.03 and neither the rotation nor the window would show
TINY = Cohere2MoeConfig(
    vocab_size=128, hidden_size=64, intermediate_size=32,
    num_hidden_layers=4, layer_types=TYPES, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, sliding_window=8, num_experts=16,
    num_experts_per_tok=3, num_shared_experts=2, experts_held=(4, 8),
    max_position_embeddings=256, initializer_range=0.2, attn_impl="dense")
SERVING = {"slots": 3, "page_len": 8, "max_seq_len": 96, "prefill_len": 16,
           "prefill_chunk_len": 16, "prefix_cache": False}
# float32 on the CPU: the model and the reference differ by summation
# order (measured 2e-6 on logits of size 6); every control below moves
# them by 0.1 or more
F32_TOL = 5e-5


def _params(cfg=TINY, seed=0):
    return drawn_once(Cohere2MoeModel, cfg, seed)


def _reference(params, tokens, cfg=TINY, **switches):
    with jax.default_matmul_precision("highest"):
        return np.asarray(cohere2_moe_reference.cohere2_moe_logits(
            params, tokens, dataclasses.asdict(cfg), **switches))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, shape).astype(np.int32)


def test_a_bfloat16_layer_norm_scales_whole_rows_of_logits():
    """The reading by which the cell tells LayerNorm statistics in
    bfloat16 from bfloat16 activations (``row_scale_error``, its limit
    ``LOGIT_SCALE_TOL``): a rounded variance scales every logit of a row
    alike.  In float32 the model has no such part; the reference with
    router, softmax and LayerNorm in bfloat16 has one a thousand times
    larger, and one above the limit the cell sets at the published
    widths."""
    from lib.cohere2_moe_family import LOGIT_SCALE_TOL, row_scale_error
    params, tokens = _params(), _tokens((1, 40))
    want = _reference(params, tokens)[0]
    got = np.asarray(Cohere2MoeModel(TINY).apply(params, tokens))[0]
    low = _reference(params, tokens, low=True)[0]
    assert row_scale_error(got, want).mean() < 1e-6
    assert row_scale_error(low, want).mean() > LOGIT_SCALE_TOL


# -- the pieces, each against a line by hand -----------------------------

def test_layer_norm_takes_the_mean_out_and_has_no_bias():
    x = jnp.asarray(np.random.RandomState(0).randn(5, 64) + 3.0, jnp.float32)
    w = jnp.asarray(np.random.RandomState(1).rand(64) + 0.5, jnp.float32)
    xn = np.asarray(x)
    want = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
        xn.var(-1, keepdims=True) + 1e-5) * np.asarray(w)
    np.testing.assert_allclose(layer_norm(x, w, 1e-5), want, atol=1e-5)
    # an RMSNorm would keep the mean of 3 in
    rms = xn / np.sqrt((xn ** 2).mean(-1, keepdims=True) + 1e-5) * np.asarray(w)
    assert np.abs(rms - want).max() > 0.5


@pytest.mark.parametrize("theta", [50000.0, 100.0])
def test_interleaved_rotation_against_the_closed_form(theta):
    """Pair ``i`` is ``(x[2i], x[2i + 1])`` at ``pos * theta**(-2i/D)``:
    by hand with complex numbers, and against the reference's own (0/1
    matrices); rotate-half (``models/walked.py::rope``) is another
    function."""
    from deepspeed_tpu.models.walked import rope
    rng = np.random.RandomState(0)
    x = rng.randn(1, 3, 7, 16).astype(np.float32)       # [B, H, T, D]
    pos = np.asarray([[0, 1, 2, 5, 9, 100, 4095]], np.int32)
    got = np.asarray(rope_interleaved(jnp.asarray(x), jnp.asarray(pos),
                                      theta))
    z = x[..., 0::2] + 1j * x[..., 1::2]
    ang = pos[0][:, None] * theta ** (-np.arange(8) / 8.0)
    turned = z * np.exp(1j * ang)
    want = np.stack([turned.real, turned.imag], -1).reshape(x.shape)
    # the program's angles are float32: at position 4,095 an ulp of the
    # angle is 2.4e-4, and the values reach 3
    np.testing.assert_allclose(got, want, atol=1e-3)
    np.testing.assert_allclose(got[:, :, :5], want[:, :, :5], atol=2e-5)
    # the reference's, positions = rows
    t = jnp.asarray(rng.randn(6, 3, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(cohere2_moe_reference.rope_interleaved(t, theta))
    mine = np.asarray(rope_interleaved(
        t.transpose(1, 0, 2)[None], jnp.arange(6)[None], theta))[0]
    np.testing.assert_allclose(mine.transpose(1, 0, 2), ref, atol=2e-5)
    half = np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), theta))
    assert np.abs(half - want).max() > 0.1


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_apply_is_the_reference(impl):
    """The whole-sequence forward against the float32 reference, on a
    sequence five windows long; and the readings that must NOT pass: the
    sequential block on the same norm, the full layer rotated, the window
    layers read as full, router / softmax / LayerNorm in bfloat16."""
    cfg = dataclasses.replace(TINY, attn_impl=impl)
    params, tokens = _params(cfg), _tokens((2, 40))
    got = np.asarray(Cohere2MoeModel(cfg).apply(params, tokens))
    want = _reference(params, tokens, cfg)
    assert np.abs(want).max() > 3.0
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    for switches in ({"parallel": False}, {"rotate_full": True},
                     {"window": 16}, {"low": True}):
        other = _reference(params, tokens, cfg, **switches)
        assert np.abs(other - want).max() > 1000 * F32_TOL, switches


def test_the_head_is_the_embedding_over_the_slice():
    """Tied: logits = LN_f(x) wte^T, so the tree has no head of its own,
    and a model over a slice of the vocabulary gives the logits of those
    rows and no others."""
    params = _params()
    assert "lm_head" not in params
    tokens = _tokens((1, 12)) % 64
    whole = np.asarray(Cohere2MoeModel(TINY).apply(params, tokens))
    cut = dataclasses.replace(TINY, vocab_size=64)
    sliced = dict(params, wte=params["wte"][:64])
    got = np.asarray(Cohere2MoeModel(cut).apply(sliced, tokens))
    np.testing.assert_allclose(got, whole[..., :64], atol=1e-6)
    scaled = dataclasses.replace(TINY, logit_scale=0.25)
    np.testing.assert_allclose(
        Cohere2MoeModel(scaled).apply(params, tokens), 0.25 * whole,
        atol=1e-6)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's test of the cut: sixteen... here four shares of four
    experts each.  Every share computes attention and the shared experts
    alike; its routed part differs.  The routed parts of all shares, with
    attention and the shared experts counted once, give the uncut layer."""
    one = dataclasses.replace(TINY, num_hidden_layers=1,
                              layer_types=("sliding_attention",),
                              experts_held=None)
    whole = _params(one)
    tokens = _tokens((1, 20))
    full = _reference(whole, tokens, one)
    model_full = np.asarray(Cohere2MoeModel(one).apply(whole, tokens))
    np.testing.assert_allclose(model_full, full, atol=F32_TOL)

    def hidden(cfg, params):
        """The layer's output before the head: the head is linear in it
        after a LayerNorm, so compare what the layer ADDS instead: run
        with the embedding as head through a hook-free route, logits of a
        model whose final norm and head are taken out by hand."""
        from deepspeed_tpu.models import cohere2_moe as M
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        x = params["wte"][tokens]
        lp = M.at(params["window"], 0)
        h = M.layer_norm(x, lp["ln"], cfg.layer_norm_eps)
        q, k, v = M._qkv(cfg, "window", lp, h, positions)
        attn = M._self_attention(cfg, "window", q, k, v)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, T, -1) @ lp["o_w"]
        stats = []
        ffn = M._ffn(cfg, params, lp, 0, h.reshape(B * T, -1), None, stats)
        shared = M._shared_experts(cfg, lp, h.reshape(B * T, -1))
        return (np.asarray(attn[0]), np.asarray(ffn - shared),
                np.asarray(shared))

    attn, routed_all, shared = hidden(one, whole)
    parts = []
    for first in range(0, 16, 4):
        cfg = dataclasses.replace(one, experts_held=(first, 4))
        share = dict(whole, experts={
            k: v[:, first:first + 4] for k, v in whole["experts"].items()})
        a, routed, s = hidden(cfg, share)
        np.testing.assert_allclose(a, attn, atol=1e-6)
        np.testing.assert_allclose(s, shared, atol=1e-6)
        parts.append(routed)
        # and the share's own reference is the share's own model
        got = np.asarray(Cohere2MoeModel(cfg).apply(share, tokens))
        np.testing.assert_allclose(got, _reference(share, tokens, cfg),
                                   atol=F32_TOL)
    np.testing.assert_allclose(sum(parts), routed_all, atol=2e-5)
    assert np.abs(parts[0]).max() > 1e-3


@pytest.mark.parametrize("name,d,f,matrices,up,down", [
    ("olmoe-1b-7b", 2048, 1024, 2, (1, 48), (1, 48)),
    ("nemotron-3-super (latent 1,024, relu2)", 1024, 2688, 1, (1, 48),
     (1, 48)),
    ("mimo-v2.5", 4096, 2048, 2, (1, 80), (1, 48)),
    ("a.x-k1", 7168, 2048, 2, (2, 72), (1, 72)),
    ("command-a-plus", 4096, 4096, 2, (2, 80), (1, 80))])
def test_expert_kernels_fetch_what_they_fetched(name, d, f, matrices, up,
                                                down):
    """``moe/dropless.py`` is as it was: at each benchmark configuration's
    widths, in bfloat16, the up-projections' and the down-projection's
    (blocks of the output width, VMEM limit in MiB).  Command A+'s 4,096 x
    4,096 walks its two up-projections in halves and its down-projection
    whole, inside the budgets the others set."""
    from deepspeed_tpu.moe import dropless

    def blocks(rows, width, n):
        w = [jax.ShapeDtypeStruct((8, rows, width), jnp.bfloat16)] * n
        nb = dropless.weight_blocks(w, width)
        return nb, dropless._vmem_limit(w, nb) >> 20

    assert blocks(d, f, matrices) == up
    assert blocks(f, d, 1) == down


# -- through the pool and the rings --------------------------------------

def _paged(cfg, slots=3, page_len=8, max_seq=96):
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, pages=1 + slots * (max_seq // page_len),
        heads=cfg.n_kv_head, page_len=page_len, head_dim=cfg.d_head,
        slots=slots, max_pages=max_seq // page_len, dtype=jnp.float32)
    cache = init_paged_cache(spec)
    model = Cohere2MoeModel(cfg)
    state = {k: jnp.full(v.shape, 0.5, v.dtype)
             for k, v in model.serving_state(slots).items()}
    return model, cache, state


def _steps(model):
    """The two paged steps jitted (eager, a call is an XLA dispatch an
    operation and minutes a test)."""
    def prefill(params, tokens, n, prefix, row, k, v, state, slot):
        return model.prefill_paged(params, tokens, n, prefix, row, k, v,
                                   state=state, slot=slot)

    def decode(params, tokens, k, v, table, lengths, active, state, impl):
        return model.decode_step_paged(params, tokens, k, v, table, lengths,
                                       active, state=state, impl=impl)

    return jax.jit(prefill), jax.jit(decode, static_argnums=(8,))


def _prefill_in_chunks(prefill, params, cache, state, prompt, chunk, bucket,
                       row, slot):
    """The engine's order: chunks of ``chunk`` tokens, each padded to
    ``bucket``, into ``slot``.  Returns (the last chunk's logits at its
    live rows, k, v, state)."""
    k, v = cache["k"], cache["v"]
    for start in range(0, len(prompt), chunk):
        part = prompt[start:start + chunk]
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(part)] = part
        logits, k, v, state = prefill(
            params, padded, np.int32(len(part)), np.int32(start), row, k, v,
            state, np.int32(slot))
    return np.asarray(logits[0, :len(part)]), k, v, state


@pytest.mark.parametrize("impl,prompt_len,chunk", [
    ("dense", 37, 16), ("flash", 30, 12), ("dense", 5, 16),
    ("flash", 27, 4)],
    ids=["dense_3_chunks", "flash_unaligned_chunks", "one_short_chunk",
         "flash_chunks_of_half_the_window"])
def test_prefill_in_chunks_then_decode_past_a_wrapped_ring(impl, prompt_len,
                                                           chunk):
    """A prompt in chunks (each reads the ring and the pages the one
    before left; a chunk that is no multiple of the window too), then 12
    decode ticks, the ring of 8 wrapping again: every logit against the
    reference's full forward on the same tokens; the rings and pages
    against the whole prompt's in ONE prefill; the slots beside it
    untouched."""
    cfg = dataclasses.replace(TINY, attn_impl=impl)
    params = _params(cfg)
    model, cache, state = _paged(cfg)
    prefill, decode = _steps(model)
    slot, page_len, ticks, bucket = 1, 8, 12, 16
    prompt = [int(t) for t in _tokens((prompt_len,), seed=3)]
    forced = _tokens((ticks,), seed=4)
    max_pages = 96 // page_len
    row = np.zeros((max_pages,), np.int32)
    row[:8] = 1 + np.arange(8)
    table = np.zeros((3, max_pages), np.int32)
    table[slot] = row
    last, k, v, state = _prefill_in_chunks(
        prefill, params, cache, state, prompt, chunk, bucket, row, slot)
    ref = _reference(params, np.asarray([prompt + list(forced)]), cfg)[0]
    tail = len(prompt) - (len(prompt) - 1) // chunk * chunk
    np.testing.assert_allclose(
        last, ref[len(prompt) - tail:len(prompt)], atol=F32_TOL)

    # the whole prompt in one prefill leaves the same rings and pages
    padded = np.zeros((1, 48), np.int32)
    padded[0, :len(prompt)] = prompt
    _, cache1, state1 = _paged(cfg)
    _, k1, v1, s1 = prefill(
        params, padded, np.int32(len(prompt)), np.int32(0), row,
        cache1["k"], cache1["v"], state1, np.int32(slot))
    W = cfg.sliding_window
    live = np.sort((len(prompt) - 1 - np.arange(min(W, len(prompt)))) % W)
    for name in ("window_k", "window_v"):
        np.testing.assert_allclose(
            np.asarray(state[name])[:, slot][:, :, live],
            np.asarray(s1[name])[:, slot][:, :, live], atol=1e-5)
        for beside in (0, 2):
            assert np.all(np.asarray(state[name])[:, beside] == 0.5)
    pages = row[:-(-len(prompt) // page_len)]
    got_k = np.asarray(k)[0, pages].transpose(1, 0, 2, 3).reshape(
        2, -1, 16)[:, :len(prompt)]
    want_k = np.asarray(k1)[0, pages].transpose(1, 0, 2, 3).reshape(
        2, -1, 16)[:, :len(prompt)]
    np.testing.assert_allclose(got_k, want_k, atol=1e-5)

    active = np.asarray([False, True, False])
    lengths = jnp.zeros((3,), jnp.int32).at[slot].set(len(prompt))
    for i, token in enumerate(forced):
        tokens = np.zeros((3,), np.int32)
        tokens[slot] = token
        logits, k, v, state, lengths = decode(
            params, tokens, k, v, table, lengths, active, state,
            "pallas" if impl == "flash" else "dense")
        np.testing.assert_allclose(logits[slot], ref[len(prompt) + i],
                                   atol=F32_TOL)
    for name in ("window_k", "window_v"):
        for beside in (0, 2):
            assert np.all(np.asarray(state[name])[:, beside] == 0.5)


def test_a_zero_length_chunk_writes_nothing():
    """The benchmark's probe runs two chunks for every prompt: the second,
    empty for a short prompt, leaves rings, pages and lengths as they
    were."""
    params = _params()
    model, cache, state = _paged(TINY)
    prefill, _ = _steps(model)
    row = np.zeros((12,), np.int32)
    row[:4] = 1 + np.arange(4)
    prompt = [int(t) for t in _tokens((11,), seed=5)]
    _, k, v, state = _prefill_in_chunks(prefill, params, cache, state,
                                        prompt, 16, 16, row, 1)
    _, k2, v2, state2 = prefill(
        params, np.zeros((1, 16), np.int32), np.int32(0), np.int32(11), row,
        k, v, state, np.int32(1))
    for a, b in ((k, k2), (v, v2), (state["window_k"], state2["window_k"]),
                 (state["window_v"], state2["window_v"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n,p,w", [(5, 0, None), (5, 7, None), (5, 0, 3),
                                   (5, 7, 3), (4, 2, 8), (6, 6, 8),
                                   (4096, 8192, None), (4096, 4096, 4096)])
def test_live_pairs_counts_what_the_masks_let_through(n, p, w):
    want = sum(min(t + 1, w or t + 1) for t in range(p, p + n))
    got = float(_live_pairs(jnp.int32(n), jnp.int32(p), w))
    assert abs(got - want) <= 1e-6 * want, (got, want)


# -- through the engine ---------------------------------------------------

@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_engine_serves_prompts_longer_than_any_prefill_program(impl,
                                                               tmp_path):
    """``ServeEngine`` with ``prefill_chunk_len`` 16 and ``prefill_len``
    16: prompts of 37 and 33 tokens are served in three chunks each, into
    slots whose neighbours decode meanwhile; every stream is the
    reference's teacher-forced argmax chain; one compiled tick and one
    prefill program; the counters say what happened."""
    cfg = dataclasses.replace(TINY, attn_impl=impl)
    params = _params(cfg)
    eng = ServeEngine(Cohere2MoeModel(cfg), {
        "serving": SERVING,
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}},
        params=params)
    try:
        assert eng.max_prompt_len == SERVING["max_seq_len"] - 1
        tokens = _tokens((2, 40), seed=7)
        prompts = [list(tokens[0, :37]), list(tokens[1, :5]),
                   list(tokens[1, :16]), list(tokens[0, :33])]
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run_until_idle()
        for p, r in zip(prompts, reqs):
            seq = np.asarray([p + r.tokens[:-1]], np.int32)
            chain = _reference(params, seq, cfg)[0][len(p) - 1:].argmax(-1)
            assert r.finish_reason == "length"
            assert list(chain) == r.tokens, (len(p), r.tokens, chain)
        assert eng._decode_fn._cache_size() == 1
        assert eng._prefill_fn._cache_size() == 1
        assert eng.prefill_calls == {16: 8}
        assert eng.prefill_chunk_calls == {16: 6}
        reg = eng.telemetry.registry
        assert reg.counter("serve_prefill_chunks_total", "").value(
            bucket="16") == 6
        gauge = reg.gauge("serve_cache_layers", "")
        assert (gauge.value(kind="full"), gauge.value(kind="window")) == (1, 3)
        state = reg.gauge("serve_state_bytes", "")
        assert state.value(kind="window_k") == eng.state_bytes["window_k"] \
            == 3 * 3 * 2 * 8 * 16 * 4
        prefills = [v for _, kind, v in eng.aux_log if kind == "prefill"]
        assert [v.get("final_chunk") for v in prefills] == [
            None, None, False, False, True, False, False, True]
        assert [v.get("chunk_pos") for v in prefills[2:5]] == [0, 16, 32]
        # 5 live rows x 4 layers in the third chunk of the 37
        assert prefills[4]["flash_q_rows"] == 20
        ticks = [v for _, kind, v in eng.aux_log if kind == "decode"]
        assert max(v["window_wrapped_slots"] for v in ticks) >= 3
        assert all(v["window_kv_rows"] <= 3 * 8 for v in ticks)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit([1] * 96, max_new_tokens=1)
    finally:
        eng.close()


def test_a_prefilling_slot_rides_the_decode_ticks_masked():
    """While a long prompt prefills in chunks, the ticks of the other
    slots go on, and they leave the prefilling slot's rings alone: its
    stream is what it is alone on the engine.  The order stays
    synchronous while a slot prefills (``_run_ahead`` stands down): no
    tick is sent ahead of, or behind, a chunk."""
    params = _params()
    tokens = _tokens((2, 60), seed=9)
    long_prompt, short = list(tokens[0, :50]), list(tokens[1, :6])

    def serve(prompts):
        eng = ServeEngine(Cohere2MoeModel(TINY), {"serving": SERVING},
                          params=params)
        try:
            reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
            eng.run_until_idle()
            return [r.tokens for r in reqs], dict(eng.ahead_stats)
        finally:
            eng.close()

    (alone,), _ = serve([long_prompt])
    (first, second, third), sent = serve([short, long_prompt, short])
    assert second == alone
    assert first == third
    # 50 tokens in chunks of 16: a tick after each chunk, each waited for
    assert sent["sync"] >= 3 and not sent["behind"], sent


@pytest.mark.parametrize("serving,named", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"page_len": 0}, "page_len"),
    ({"speculate_k": 2, "draft": {"d_model": 32, "n_layer": 1,
                                  "n_head": 2}}, "speculate_k"),
    ({"quantization": {"kv": "int8"}}, "quantization"),
    ({"lora": {"rank": 4}}, "lora"),
    ({"prefix_cache": True,
      "kv_tier": {"idle_park_ticks": 4, "host_budget_pages": 8}}, "kv_tier"),
])
def test_engine_refuses_what_the_model_cannot_hold(serving, named):
    serving = {**SERVING, **serving}
    if not serving["page_len"]:
        del serving["prefill_chunk_len"]    # the config asks pages of it
    with pytest.raises(ValueError, match=named):
        ServeEngine(Cohere2MoeModel(TINY), {"serving": serving},
                    params=_params())


@pytest.mark.parametrize("field,value,named", [
    ("use_parallel_block", False, "sequential"),
    ("rms_norm_eps", 1e-6, "RMSNorm"),
    ("use_qk_norm", True, "use_qk_norm"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", False, "untied"),
    ("first_k_dense_replace", 1, "dense prefix"),
    ("expert_selection_fn", "softmax", "expert_selection_fn"),
    ("shared_expert_combination_strategy", "sum", "average"),
    ("position_embedding_type", "rope", "rope_gptj"),
    ("rotary_pct", 0.5, "partial"),
    ("layer_types", ("full_attention",) * 3, "layer_types"),
    ("experts_held", (12, 8), "held"),
])
def test_config_refuses_what_is_not_built(field, value, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(TINY, **{field: value})


def test_config_reads_the_published_row():
    """The catalog's own keys build the configuration as published: 24
    window and 8 full layers, 128 heads on 8, a window of 4,096."""
    import json
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else []
    row = next((r for r in rows if r["name"] == "command-a-plus-05-2026"),
               None)
    if row is None:
        pytest.skip("the catalog is not here")
    fields = {f.name for f in dataclasses.fields(Cohere2MoeConfig)}
    cfg = Cohere2MoeConfig(**{k: v for k, v in row["config"].items()
                              if k in fields})
    assert (cfg.count("window"), cfg.count("full")) == (24, 8)
    assert (cfg.n_head, cfg.n_kv_head, cfg.d_head) == (128, 8, 128)
    assert cfg.sliding_window == 4096 and cfg.n_layer == 8
    file = json.load(open(os.path.join(
        ROOT, "benchmark/configs/command-a-plus-05-2026.json")))
    for key, value in row["config"].items():
        if key not in file["reduced"]:
            assert file[key] == value, key
