"""A.X-K1 (latent attention, MLA): the absorbed form against the expanded
form on the same latents, the latent decode kernel in interpret mode
against its dense arm, the model against the benchmark's plain float32
reference, prefill then decode through the ONE pool, a prefix hit and a
chunked prefill against the whole prompt's logits, the shares of the
experts with the shared expert counted once against the uncut layer, YaRN's
frequencies against the closed form, ``rope``'s old callers bit for bit,
the blocked expert kernels against the whole-block ones, the one-pool spec
and the refusals.  CPU, tiny widths, seeded weights.  (Its cell's
rehearsal: tests/test_benchmark_cells.py.)"""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import drawn_once

from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.inference.kv_cache import (PagedKVCacheSpec,
                                              init_paged_cache,
                                              paged_partition_specs)
from deepspeed_tpu.models import axk1
from deepspeed_tpu.models.axk1 import (AxK1Config, AxK1Model, softmax_scale,
                                       yarn_inv_freq)
from deepspeed_tpu.models.walked import rope
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas.decode_attention import (
    latent_decode_attention, latent_pages_per_block)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import axk1_reference  # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
PUBLISHED_YARN = {**YARN, "factor": 32,
                  "original_max_position_embeddings": 4096}
TINY = AxK1Config(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=3, rope_scaling=YARN,
    max_position_embeddings=256, experts_held=(4, 8), attn_impl="dense",
    # scores of size 1, so that the rope term and the scale show
    initializer_range=0.06)
SERVING = {"slots": 3, "page_len": 8, "max_seq_len": 96, "prefill_len": 32,
           "prefix_cache": False}
# float32 on the CPU: the model and the reference differ by summation
# order (measured 4e-7 on logits of size 1.7); the absorbed form
# reassociates two matmuls a head (measured 1e-6); leaving the rope term
# out moves the logits by 0.3
F32_TOL = 1e-5


def _params(cfg=TINY, seed=0):
    return drawn_once(AxK1Model, cfg, seed)


def _reference(params, tokens, cfg=TINY, **switches):
    with jax.default_matmul_precision("highest"):
        return np.asarray(axk1_reference.axk1_logits(
            params, tokens, dataclasses.asdict(cfg), **switches))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, shape).astype(np.int32)


# -- the pieces -----------------------------------------------------------

def test_yarn_frequencies_are_the_closed_form_at_the_published_sizes():
    """theta 10,000, 64 rotated dims, factor 32 over 4,096: pairs 0-9 turn
    more than 32 times in 4,096 positions and keep their frequency, pairs
    23-31 turn less than once and have 1/32 of it, pairs 10-23 ramp; the
    angle at a position past 4,096 follows."""
    cfg = AxK1Config(rope_scaling=PUBLISHED_YARN)
    got = yarn_inv_freq(cfg)
    own = 10000.0 ** (-np.arange(32) / 32.0)
    turns = 4096 * own / (2 * math.pi)
    assert (turns[:10] > 32).all() and (turns[11:] < 32).all()
    assert (turns[23:] < 1).all() and (turns[:22] > 1).all()
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0, 1)
    want = own * (1 - ramp) + own / 32 * ramp
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_array_equal(got[:11], own[:11].astype(np.float32))
    np.testing.assert_allclose(got[23:], own[23:] / 32, rtol=1e-6)
    np.testing.assert_allclose(
        got, axk1_reference.yarn_inv_freq(dataclasses.asdict(cfg)), rtol=1e-6)
    # rotate at position 9,000: pair i by 9000 * want[i]
    x = jnp.asarray(np.random.RandomState(0).randn(1, 1, 1, 64), jnp.float32)
    out = np.asarray(rope(x, jnp.asarray([[9000]]), cfg.rope_theta,
                          inv_freq=got))[0, 0, 0]
    ang = 9000 * want
    a, b = np.asarray(x)[0, 0, 0, :32], np.asarray(x)[0, 0, 0, 32:]
    np.testing.assert_allclose(out[:32], a * np.cos(ang) - b * np.sin(ang),
                               atol=2e-3)
    np.testing.assert_allclose(out[32:], b * np.cos(ang) + a * np.sin(ang),
                               atol=2e-3)
    assert abs(softmax_scale(cfg) - 192 ** -0.5
               * (0.1 * math.log(32) + 1) ** 2) < 1e-7


@pytest.mark.parametrize("rotary_dim", [None, 8])
def test_rope_without_frequencies_is_what_it_was_bit_for_bit(rotary_dim):
    """The callers that hand ``theta`` alone (OLMoE, MiMo) get the line
    ``rope`` had before it took frequencies."""
    x = jnp.asarray(np.random.RandomState(1).randn(2, 3, 10, 24), jnp.float32)
    pos = jnp.asarray(np.random.RandomState(2).randint(0, 500, (2, 10)))
    rot = 24 if rotary_dim is None else rotary_dim
    half = rot // 2
    inv = 1e4 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, :, None] * inv
    x1, x2 = x[..., :half], x[..., half:rot]
    was = jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                           x2 * jnp.cos(ang) + x1 * jnp.sin(ang),
                           x[..., rot:]], axis=-1)
    np.testing.assert_array_equal(rope(x, pos, 1e4, rotary_dim=rotary_dim),
                                  was)


def _latents_of(cfg, seed=3, S=3, T=20):
    """A layer's parameters and what ``_latents`` gives for S sequences."""
    params = _params(cfg, seed)
    ap = axk1.at(params["attn"], 1)
    h = jnp.asarray(np.random.RandomState(seed).randn(S, T, cfg.hidden_size),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (S, T))
    return ap, axk1._latents(cfg, ap, h, pos)


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_absorbed_attention_equals_expanded_on_the_same_latents(impl):
    """The last query of each sequence, absorbed over a page pool that
    holds the latent rows, against the expanded form's last row."""
    cfg, S, T, page_len = TINY, 3, 20, 8
    ap, (q_nope, q_rope, c_kv, k_rope) = _latents_of(cfg, S=S, T=T)
    want = np.asarray(axk1._self_attention(
        cfg, ap, q_nope, q_rope, c_kv, k_rope))            # [S, H, T, dv]
    lengths = jnp.asarray([T, 0, 13], jnp.int32)            # one free slot
    pages = -(-T // page_len)
    pool = np.zeros((1 + S * pages, page_len, cfg.latent_width), np.float32)
    table = np.zeros((S, pages + 1), np.int32)
    rows = np.asarray(axk1._cached_rows(cfg, c_kv, k_rope))
    for s in range(S):
        for p in range(pages):
            table[s, p] = 1 + s * pages + p
            chunk = rows[s, p * page_len:(p + 1) * page_len]
            pool[table[s, p], :len(chunk)] = chunk
    at = np.maximum(np.asarray(lengths) - 1, 0)
    pick = np.arange(S)
    q_lat = jnp.einsum("shn,hnc->shc", q_nope[pick, :, at], ap["k_b_w"])
    o_lat = latent_decode_attention(
        axk1._cached_rows(cfg, q_lat, q_rope[pick, :, at]),
        jnp.asarray(pool), jnp.asarray(table), lengths, cfg.kv_lora_rank,
        sm_scale=softmax_scale(cfg), impl=impl, interpret=True)
    assert o_lat.shape == (S, cfg.n_head, cfg.kv_lora_rank)
    got = np.asarray(jnp.einsum("shc,hcv->shv", o_lat, ap["v_b_w"]))
    for s in (0, 2):
        np.testing.assert_allclose(got[s], want[s, :, at[s]], atol=F32_TOL)
    np.testing.assert_array_equal(got[1], 0.0)              # the free slot


def test_latent_kernel_walks_blocks_of_pages_and_skips_dead_ones(monkeypatch):
    """Two pages a block where the budget holds two: slots whose lengths
    end inside a page, at a page's end and inside a later block agree with
    the dense arm."""
    import importlib
    da = importlib.import_module(
        "deepspeed_tpu.ops.pallas.decode_attention")
    page_len, W, C, H, S, max_pages = 8, 40, 32, 4, 4, 6
    monkeypatch.setattr(da, "PAGED_KV_VMEM_BUDGET", 2 * 2 * page_len * W * 4)
    assert latent_pages_per_block(page_len, W, 4, max_pages) == 2
    rng = np.random.RandomState(5)
    pool = jnp.asarray(rng.randn(1 + S * max_pages, page_len, W), jnp.float32)
    q = jnp.asarray(rng.randn(S, H, W), jnp.float32)
    table = jnp.asarray(rng.permutation(S * max_pages).reshape(S, max_pages)
                        + 1, jnp.int32)
    lengths = jnp.asarray([5, 16, 33, 48], jnp.int32)
    want = latent_decode_attention(q, pool, table, lengths, C, sm_scale=0.2,
                                   impl="dense")
    got = latent_decode_attention(q, pool, table, lengths, C, sm_scale=0.2,
                                  impl="pallas", interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)


SHAPES = {"olmoe": (64, 32, 8, 8), "mimo": (128, 64, 16, 8),
          "axk1": (224, 64, 12, 8)}       # d, f, experts, top-k (toy-scaled)


@pytest.mark.parametrize("family", sorted(SHAPES))
@pytest.mark.parametrize("tokens", [6, 40])
def test_blocked_expert_kernels_equal_the_whole_block_ones(family, tokens,
                                                           monkeypatch):
    """An expert's matrices walked in blocks of the output width give what
    the whole matrices give (a column of the result reads the same
    products; the CPU's matmul sums them in an order of the shape's, 1e-7
    apart)."""
    d, f, e, k = SHAPES[family]
    f, d = 2 * 128 * (f // 32), 2 * 128 * (d // 64)   # whole lanes a block
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(tokens, d) * 0.5, jnp.float32)
    router = jnp.asarray(rng.randn(d, e), jnp.float32)
    gate, up = (jnp.asarray(rng.randn(e, d, f) * 0.05, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.randn(e, f, d) * 0.05, jnp.float32)

    def run():
        return dropless.dropless_moe(x, router, gate, up, down, k,
                                     interpret=True)

    assert dropless.weight_blocks((gate, up), f) == 1
    whole, stats = run()
    monkeypatch.setattr(dropless, "MOE_WEIGHT_VMEM_BUDGET", 2 * d * f * 4)
    assert dropless.weight_blocks((gate, up), f) == 2       # gate_up halves
    assert dropless.weight_blocks((down,), d) == 1
    blocked, stats_b = run()
    np.testing.assert_allclose(blocked, whole, atol=1e-6)
    monkeypatch.setattr(dropless, "MOE_WEIGHT_VMEM_BUDGET", d * f * 4)
    assert dropless.weight_blocks((down,), d) == 2          # down too
    np.testing.assert_allclose(run()[0], whole, atol=1e-6)
    assert int(stats_b.rows) == int(stats.rows) == tokens * k


def test_the_published_widths_block_the_up_projections_only():
    """d 7,168, f 2,048 in bfloat16: both up-projections in two blocks of
    1,024 columns (56 MiB in flight), the down-projection whole; OLMoE's,
    Nemotron's (latent 1,024) and MiMo's widths whole, as they were."""
    def blocks(d, f, n=2):
        w = [jax.ShapeDtypeStruct((12, d, f), jnp.bfloat16)] * n
        return dropless.weight_blocks(w, f), dropless._vmem_limit(
            w, dropless.weight_blocks(w, f))

    assert blocks(7168, 2048) == (2, (56 + 16) << 20)
    assert blocks(2048, 7168, n=1) == (1, (56 + 16) << 20)
    assert blocks(4096, 2048) == (1, (64 + 16) << 20)
    assert blocks(2048, 4096, n=1)[0] == 1
    assert blocks(2048, 1024) == (1, dropless.MOE_VMEM_LIMIT)
    assert blocks(1024, 2688, n=1)[0] == 1


# -- the model against the reference --------------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_apply_matches_the_reference_in_float32(attn_impl):
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    params, tokens = _params(cfg), _tokens((2, 24))
    from deepspeed_tpu.ops.pallas.runtime import interpret_scope
    with interpret_scope(True):
        got = np.asarray(AxK1Model(cfg).apply(params, tokens))
    want = _reference(params, tokens, cfg)
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < F32_TOL


@pytest.mark.parametrize("switch", [
    {"rope_term": False}, {"low": True},
    {"round_acts": True, "act_dtype": jnp.bfloat16}],
    ids=["no_rope_term", "bfloat16_router_softmax_norms",
         "bfloat16_activations"])
def test_the_float32_tolerance_fails_each_control(switch):
    params, tokens = _params(), _tokens((2, 24))
    want = _reference(params, tokens)
    assert np.abs(_reference(params, tokens, **switch) - want).max() \
        > 100 * F32_TOL


def test_the_sixteen_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """The routed parts of all shares (each computes the shared expert
    too: counted once) add up to the uncut layer; and the reference's
    share is the program's."""
    cfg = dataclasses.replace(TINY, experts_held=None, n_routed_experts=32)
    params = _params(cfg, 2)
    x = jnp.asarray(np.random.RandomState(8).randn(12, 64), jnp.float32)

    def layer(c, p):
        ep = axk1.at(p["moe"], 0)
        out, st = axk1._experts(c, ep, axk1.stacked_experts(p), 0, x, None)
        return out, axk1.shared_expert(ep, x), st

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
    tokens = _tokens((1, 20))
    got = np.asarray(AxK1Model(held).apply(cut, tokens))
    assert np.abs(got - _reference(cut, tokens, held)).max() < F32_TOL


def _paged_logits(model, params, prompt, forced, chunks, impl, page_len=8,
                  slots=3, max_pages=12):
    """Prefill ``prompt`` in ``chunks`` (lengths; each after the first
    reads its prefix from the pages), then one decode tick a forced token,
    in the middle slot of a pool of its own.  Returns the logits of every
    prompt position and of every tick."""
    cfg = model.config
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, slots=slots, heads=cfg.n_kv_head,
        pages=1 + max_pages, page_len=page_len, head_dim=cfg.d_head,
        max_pages=max_pages, dtype=jnp.float32, v_head_dim=cfg.d_head_v,
        values_in_keys=cfg.values_in_keys)
    pool = init_paged_cache(spec)["k"]
    row = np.zeros((max_pages,), np.int32)
    n_pages = -(-(len(prompt) + len(forced)) // page_len)
    row[:n_pages] = 1 + np.arange(n_pages)
    bucket, done, rows = 32, 0, []
    prefill = jax.jit(lambda *a: model.prefill_paged(*a, None))
    decode = jax.jit(lambda p, t, k, tab, ln, act: model.decode_step_paged(
        p, t, k, None, tab, ln, act, impl=impl))
    for n in chunks:
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt[done:done + n]
        logits, pool, none = prefill(
            params, padded, np.int32(n), np.int32(done), row, pool)
        assert none is None
        rows.append(np.asarray(logits[0, :n]))
        done += n
    slot = slots // 2
    table = np.zeros((slots, max_pages), np.int32)
    table[slot] = row
    active = np.zeros((slots,), bool)
    active[slot] = True
    lengths = jnp.zeros((slots,), jnp.int32).at[slot].set(done)
    for token in forced:
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(int(token))
        logits, pool, none, lengths = decode(
            params, tokens, pool, table, lengths, active)
        rows.append(np.asarray(logits[slot])[None])
    return np.concatenate(rows)


@pytest.mark.parametrize("chunks", [(27,), (16, 11), (8, 8, 11)],
                         ids=["whole", "prefix_hit", "chunked"])
@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_paged_steps_against_the_reference_logits(attn_impl, chunks):
    """Expanded prefill (whole; after a prefix of two pages; in chunks of
    a page) writes latent rows; the absorbed ticks read them across a page
    boundary: every logit is the reference's full forward's."""
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    model, params = AxK1Model(cfg), _params(cfg)
    prompt, forced = _tokens((27,), 4), _tokens((9,), 5)
    from deepspeed_tpu.ops.pallas.runtime import interpret_scope
    with interpret_scope(True):
        got = _paged_logits(model, params, prompt, forced, chunks,
                            "pallas" if attn_impl == "flash" else "dense")
    seq = np.concatenate([prompt, forced])[None]
    want = _reference(params, seq, cfg)[0]
    np.testing.assert_allclose(got[:27], want[:27], atol=F32_TOL)
    np.testing.assert_allclose(got[27:], want[27:], atol=F32_TOL)
    # the control: the ticks without the rope term are far from these
    off = _reference(params, seq, cfg, rope_term=False)[0]
    assert np.abs(off[27:] - want[27:]).max() > 100 * F32_TOL


@pytest.mark.parametrize("chunks", [(27,), (16, 11), (8, 8, 11)],
                         ids=["whole", "prefix_hit", "chunked"])
def test_a_prefill_counts_the_rows_its_kernel_walked_and_the_pairs_it_let(
        chunks):
    """A prefill's counters against counts made in numpy: from nothing
    (``ds_flash_fwd`` over the prompt's own keys) the context kernel does
    not run and both read 0; after a prefix the context's rows
    ``ds_latent_context_attn`` walked, a layer each, and the (query, key)
    pairs the causal rule let through a head (a query at position p sees p
    + 1 keys, a padding row of the bucket none), a layer each, float32;
    ``latent_kv_tokens`` stays the tick's."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    model, params = AxK1Model(cfg), _params(cfg)
    prompt = _tokens((27,), 4)
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, slots=1, heads=cfg.n_kv_head, pages=13,
        page_len=8, head_dim=cfg.d_head, max_pages=12, dtype=jnp.float32,
        v_head_dim=cfg.d_head_v, values_in_keys=cfg.values_in_keys)
    pool = init_paged_cache(spec)["k"]
    row = np.zeros((12,), np.int32)
    row[:4] = 1 + np.arange(4)
    prefill = jax.jit(lambda *a: model.prefill_paged(*a, None, aux=True))
    done = 0
    from deepspeed_tpu.ops.pallas.runtime import interpret_scope
    with interpret_scope(True):
        for n in chunks:
            padded = np.zeros((1, 32), np.int32)
            padded[0, :n] = prompt[done:done + n]
            _, pool, _, aux = prefill(
                params, padded, np.int32(n), np.int32(done), row, pool)
            paged = cfg.n_layer * (done > 0)
            done += n
            assert aux["latent_context_rows"].dtype == jnp.int32
            assert int(aux["latent_context_rows"]) == paged * done
            assert aux["latent_context_pairs"].dtype == jnp.float32
            assert float(aux["latent_context_pairs"]) \
                == paged * (1 + np.arange(done - n, done)).sum()
            assert int(aux["latent_kv_tokens"]) == 0


@pytest.mark.parametrize("serving", [
    {}, {"prefix_cache": True}, {"prefill_chunk_len": 8}],
    ids=["plain", "prefix_cache", "chunked"])
def test_engine_streams_sit_on_the_reference_logits(serving):
    """Through ``ServeEngine``: prompts that share two pages, one over the
    chunk length; every emitted token is the reference's argmax."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    model, params = AxK1Model(cfg), _params(cfg)
    eng = ServeEngine(model, {"serving": {**SERVING, **serving}},
                      params=params)
    try:
        base = list(_tokens((20,), 6))
        prompts = [base + list(_tokens((n,), 7 + n)) for n in (5, 9)] \
            + [list(_tokens((3,), 9))]
        reqs = [eng.submit([int(t) for t in prompts[0]], max_new_tokens=10)]
        eng.run_until_idle()
        reqs += [eng.submit([int(t) for t in p], max_new_tokens=10)
                 for p in prompts[1:]]
        eng.run_until_idle()
        assert eng._decode_fn._cache_size() == 1
        if serving.get("prefix_cache"):
            assert eng.prefix.hits >= 1 and reqs[1].shared_len == 16
        kinds = {v.get("latent_kv_tokens") for _, kind, v in eng.aux_log
                 if kind == "prefill"}
        assert kinds == {0.0}
        ticks = [v["latent_kv_tokens"] for _, kind, v in eng.aux_log
                 if kind == "decode"]
        assert ticks and all(t % cfg.n_layer == 0 for t in ticks)
    finally:
        eng.close()
    for prompt, r in zip(prompts, reqs):
        seq = np.asarray([int(t) for t in prompt] + list(r.tokens))[None]
        rows = _reference(params, seq[:, :-1], cfg)[0][len(prompt) - 1:]
        assert len(r.tokens) == 10
        slack = rows.max(axis=1) - rows[np.arange(10), r.tokens]
        assert slack.max() < F32_TOL, slack


def test_engine_holds_one_pool_and_says_its_bytes(tmp_path):
    model = AxK1Model(TINY)
    eng = ServeEngine(model, {
        "serving": SERVING,
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}},
        params=_params())
    try:
        # 96 / 8 = 12 pages a slot + the scratch page; 32 + 8 = 40 lanes
        assert sorted(eng.cache) == ["k", "lengths"]
        assert eng.cache["k"].shape == (3, 37, 1, 8, 40)
        spec = eng.cache_spec
        assert spec.values_in_keys and spec.pool_names == ("k",)
        assert spec.value_dim == 32 and spec.row_width == 40
        assert spec.bytes == eng.cache["k"].nbytes == eng.kv_bytes
        assert spec.page_bytes == 3 * 8 * 40 * 4
        assert eng.state_bytes == {"latent": spec.bytes}
        assert eng.page_leaf_nbytes() == [spec.page_bytes]
        reg = eng.telemetry.registry
        assert reg.gauge("serve_cache_layers", "").value(kind="latent") == 3
        assert reg.gauge("serve_state_bytes", "").value(kind="latent") \
            == spec.bytes
        assert reg.gauge("serve_kv_bytes", "").value() == spec.bytes
    finally:
        eng.close()


def test_a_copied_page_and_an_exported_one_carry_the_one_pool():
    """Copy-on-write of a shared page and the export / adoption of a
    request's pages walk ``pool_names``: one leaf, the same stream."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    params = _params(cfg)
    serving = {**SERVING, "prefix_cache": True}
    prompt = [int(t) for t in _tokens((20,), 11)]
    engines = [ServeEngine(AxK1Model(cfg), {"serving": serving},
                           params=params) for _ in range(2)]
    try:
        a, b = engines
        first = a.submit(prompt, max_new_tokens=6)
        a.run_until_idle()
        again = a.submit(prompt, max_new_tokens=6)      # identical: a COW
        a.run_until_idle()
        assert a.prefix.cow >= 1 and again.tokens == first.tokens
        moved = a.submit(prompt[:12], max_new_tokens=1, detach_kv=True)
        a.run_until_idle()
        pages = a.export_pages(moved)
        assert [len(p) for p in pages] == [a.cache_spec.page_bytes] * 2
        adopted = b.adopt_request(prompt[:12], moved.tokens[0], 5, None,
                                  pages)
        b.run_until_idle()
        whole = b.submit(prompt[:12], max_new_tokens=5)
        b.run_until_idle()
        assert len(adopted.tokens) == 5 and adopted.tokens == whole.tokens
    finally:
        for e in engines:
            e.close()


def test_the_two_pool_spec_is_what_it_was():
    spec = PagedKVCacheSpec(layers=2, slots=3, heads=4, pages=5, page_len=8,
                            head_dim=24, max_pages=4, v_head_dim=16)
    assert spec.pool_names == ("k", "v") and spec.row_width == 40
    assert spec.page_bytes == 2 * 4 * 8 * (24 + 16) * 4
    assert sorted(init_paged_cache(spec)) == ["k", "lengths", "v"]
    assert sorted(paged_partition_specs()) == ["k", "lengths", "v"]
    assert sorted(paged_partition_specs(values_in_keys=True)) \
        == ["k", "lengths"]
    quant = dataclasses.replace(spec, quant=True, dtype=jnp.int8,
                                v_head_dim=None)
    assert quant.pool_names == ("k", "v", "k_scale", "v_scale")
    with pytest.raises(ValueError, match="values_in_keys"):
        dataclasses.replace(spec, values_in_keys=True, v_head_dim=32)


# -- the refusals ---------------------------------------------------------

@pytest.mark.parametrize("serving,named", [
    ({"page_len": 0}, "page_len"),
    ({"speculate_k": 2, "draft": {"d_model": 32, "n_layer": 1,
                                  "n_head": 2}}, "speculate_k"),
    ({"quantization": {"kv": "int8"}}, "quantization"),
    ({"lora": {"rank": 4, "alpha": 8.0, "max_adapters": 4,
               "hbm_adapter_slots": 2, "targets": ["qkv_w"]}}, "lora"),
])
def test_engine_refuses_the_arms_these_steps_lack(serving, named):
    with pytest.raises(ValueError,
                       match=f"AxK1Model cannot be served.*{named}"):
        ServeEngine(AxK1Model(TINY), {"serving": {**SERVING, **serving}},
                    params=_params())


@pytest.mark.parametrize("field,value,named", [
    ("topk_method", "noaux_tc", "group-limited"),
    ("scoring_func", "softmax", "scoring_func"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("rope_scaling", {"type": "linear", "factor": 2}, "rope_scaling type"),
    ("rope_scaling", {**YARN, "mscale": 0.7}, "mscale"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
])
def test_config_refuses_what_is_not_built(field, value, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(TINY, **{field: value})


def test_config_reads_the_published_row():
    """The catalog's own keys build the configuration as published: 61
    latent layers, one dense FFN, rows 640 wide at rest over values of
    512, a pool of one key head."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "a.x-k1.json")) as f:
        file = json.load(f)
    fields = {f.name for f in dataclasses.fields(AxK1Config)}
    keys = {k: v for k, v in file.items()
            if k in fields and k != "experts_held"}
    keys.update(file["published"])
    cfg = AxK1Config(**keys)
    assert (cfg.count("dense"), cfg.count("moe")) == (1, 60)
    assert (cfg.n_layer, cfg.n_head, cfg.n_kv_head) == (61, 64, 1)
    assert (cfg.d_head, cfg.d_head_v, cfg.qk_head_dim) == (640, 512, 192)
    assert cfg.values_in_keys and cfg.held == (0, 192)
    assert abs(softmax_scale(cfg) - 0.13087) < 1e-4
