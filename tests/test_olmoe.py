"""OLMoE: the block's pieces against hand-written cases, the dropless
expert layer against every-expert-masked, the model against the benchmark's
plain float32 reference, the paged serving path through ``ServeEngine``
against the reference's full forward, and the refusals.  CPU, tiny widths,
seeded weights.  (Its cell's rehearsal: tests/test_benchmark_cells.py.)"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import drawn_once

from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.inference.quantize import (NotGPT2ParamsError,
                                              quantize_gpt2_params)
from deepspeed_tpu.models.olmoe import OlmoeConfig, OlmoeModel, qkv_heads
from deepspeed_tpu.models.walked import rms_norm, rope
from deepspeed_tpu.moe.dropless import dropless_moe, route_topk, row_tile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import olmoe_reference  # noqa: E402

TINY = OlmoeConfig(vocab_size=256, hidden_size=64, intermediate_size=32,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=4, num_experts=8,
                   num_experts_per_tok=2, max_position_embeddings=128,
                   attn_impl="dense")
SOURCE_KEYS = {k: getattr(TINY, k) for k in (
    "num_attention_heads", "rms_norm_eps", "num_experts",
    "num_experts_per_tok", "rope_theta", "norm_topk_prob")}

# float32 on the CPU: the model and the reference differ by summation
# order only (measured 2e-7 on logits of size 0.5).  A bf16 matmul anywhere
# (8 bits of mantissa) shows as 1e-3 or more, so this fails it by 20 x.
F32_TOL = 5e-5
# the paged steps run the flash and paged-decode kernels (online softmax:
# other partial sums, an exp of a running maximum) over 17 positions:
# measured 1e-4 on logits of size 2; still a third of what bf16 shows
PAGED_TOL = 3e-4


def _params(cfg=TINY, seed=0, scale=8.0):
    """Seeded weights; larger than init so that routing is decisive and
    logits are of size 1."""
    return jax.tree.map(
        lambda a: a * scale if a.ndim > 1 and a.shape[-1] != 1 else a,
        drawn_once(OlmoeModel, cfg, seed))


def _reference(params, tokens, keys=SOURCE_KEYS):
    with jax.default_matmul_precision("highest"):
        return olmoe_reference.olmoe_logits(params, jnp.asarray(tokens),
                                            keys)


# -- the block's pieces ---------------------------------------------------

def test_rms_norm_by_hand():
    x = jnp.asarray([[3.0, 4.0, 0.0, 0.0]])
    got = rms_norm(x, jnp.asarray([1.0, 2.0, 1.0, 1.0]), 0.0)
    rms = np.sqrt((9 + 16) / 4)
    np.testing.assert_allclose(got, [[3 / rms, 8 / rms, 0, 0]], rtol=1e-6)


def test_rope_by_hand():
    """Dh 4, theta 100: pair 0 is (x0, x2) at angle p, pair 1 is (x1, x3)
    at angle p / 10."""
    x = jnp.asarray([1.0, 2.0, 3.0, 4.0]).reshape(1, 1, 1, 4)
    x = jnp.broadcast_to(x, (1, 1, 3, 4))
    got = np.asarray(rope(x, jnp.asarray([[0, 1, 5]]), 100.0))[0, 0]
    np.testing.assert_allclose(got[0], [1, 2, 3, 4], atol=1e-6)
    for row, p in ((1, 1.0), (2, 5.0)):
        a, b = p, p / 10.0
        want = [np.cos(a) - 3 * np.sin(a), 2 * np.cos(b) - 4 * np.sin(b),
                3 * np.cos(a) + np.sin(a), 4 * np.cos(b) + 2 * np.sin(b)]
        np.testing.assert_allclose(got[row], want, rtol=1e-5)


def test_rope_scores_depend_on_distance_only():
    k = jax.random.split(jax.random.PRNGKey(1))
    q = jax.random.normal(k[0], (1, 1, 1, 16))
    key = jax.random.normal(k[1], (1, 1, 1, 16))

    def score(m, n):
        return float(jnp.sum(rope(q, jnp.asarray([[m]]), 1e4)
                             * rope(key, jnp.asarray([[n]]), 1e4)))

    assert abs(score(7, 3) - score(104, 100)) < 1e-4
    assert abs(score(7, 3) - score(7, 4)) > 1e-3


def test_qk_norm_spans_the_whole_projection_before_the_heads():
    """q_proj = 2 * identity on d = 8, two heads: the norm divides by the
    RMS of all 8 numbers, not of a head's 4; position 0 leaves RoPE out."""
    cfg = dataclasses.replace(TINY, hidden_size=8, num_attention_heads=2,
                              num_key_value_heads=2)
    eye = jnp.eye(8)
    bp = {"q_w": 2 * eye, "k_w": eye, "v_w": eye,
          "q_norm": jnp.full((8,), 3.0), "k_norm": jnp.ones((8,))}
    h = jnp.asarray([[[1.0, 1, 1, 1, 5, 5, 5, 5]]])
    q, k, v = qkv_heads(cfg, bp, h, jnp.zeros((1, 1), jnp.int32))
    rms = np.sqrt((4 * 4 + 4 * 100) / 8)
    np.testing.assert_allclose(q[0, :, 0], [[6 / rms] * 4, [30 / rms] * 4],
                               rtol=1e-5)
    np.testing.assert_allclose(v[0, :, 0], [[1] * 4, [5] * 4])
    assert q.shape == (1, 2, 1, 4)


# -- the dropless expert layer ---------------------------------------------

def dense_moe_reference(x, router_w, gate_w, up_w, down_w, top_k: int,
                        renormalize: bool = False):
    """Every expert on every token, masked by the top-k: what
    ``dropless_moe`` must equal (one layer's own weights)."""
    weights, experts = route_topk(x, router_w, top_k, renormalize)
    e = router_w.shape[-1]
    gates = jnp.sum(jax.nn.one_hot(experts, e, dtype=jnp.float32)
                    * weights[..., None], axis=1)           # [N, E]
    xf = x.astype(jnp.float32)
    hp = jax.lax.Precision.HIGHEST
    g = jnp.einsum("nd,edf->enf", xf, gate_w.astype(jnp.float32),
                   precision=hp)
    u = jnp.einsum("nd,edf->enf", xf, up_w.astype(jnp.float32), precision=hp)
    out = jnp.einsum("enf,efd->end", jax.nn.silu(g) * u,
                     down_w.astype(jnp.float32), precision=hp)
    return jnp.einsum("end,ne->nd", out, gates, precision=hp).astype(x.dtype)


def _layer(n, d=32, e=64, f=48, seed=0, skew=None):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (n, d))
    router = jax.random.normal(k[1], (d, e)) * 0.3
    if skew is not None:
        # one expert takes most tokens, the last 16 take none
        router = router.at[:, skew].set(jnp.mean(x, 0) * 4 + 1.0)
        router = router.at[:, -16:].set(0.0)
        x = x + 2.0 * jnp.mean(x, 0)
    ws = [jax.random.normal(k[2 + i], s) * 0.2
          for i, s in enumerate(((e, d, f), (e, d, f), (e, f, d)))]
    return x, router, ws


@pytest.mark.parametrize("n", [1, 37, 64], ids=["one_token", "ragged", "tick"])
def test_dropless_equals_every_expert_masked_top8_of_64(n):
    x, router, ws = _layer(n, skew=3)
    if n > 1:
        x = x.at[:, 0].add(3.0)
        router = router.at[0, -16:].set(-20.0)      # never chosen
    y, stats = jax.jit(lambda *a: dropless_moe(*a, 8))(x, router, *ws)
    want = dense_moe_reference(x, router, *ws, 8)
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=1e-5)
    _, experts = route_topk(x, router, 8)
    counts = np.bincount(np.asarray(experts).ravel(), minlength=64)
    assert int(stats.rows) == n * 8
    assert int(stats.experts_hit) == int((counts > 0).sum())
    assert int(stats.max_rows) == counts.max()
    if n > 1:
        assert counts.max() >= n // 2 and (counts == 0).sum() >= 16


def test_dropless_leaves_padding_rows_out():
    x, router, ws = _layer(24, e=8)
    valid = jnp.arange(24) < 10
    y, stats = dropless_moe(x, router, *ws, 2, valid=valid)
    want = dense_moe_reference(x, router, *ws, 2)
    np.testing.assert_allclose(y[:10], want[:10], atol=2e-5)
    assert float(jnp.abs(y[10:]).max()) == 0.0
    assert int(stats.rows) == 20


def test_dropless_reads_a_layer_of_the_stacked_experts():
    x, router, ws = _layer(9, e=8)
    stacked = [jnp.concatenate([jnp.full_like(w, jnp.nan), w]) for w in ws]
    y, _ = jax.jit(lambda x, r, *w: dropless_moe(
        x, r, *w, 2, expert_offset=jnp.int32(8)))(x, router, *stacked)
    np.testing.assert_allclose(y, dense_moe_reference(x, router, *ws, 2),
                               atol=2e-5)


def test_dropless_renormalises_only_when_asked():
    x, router, ws = _layer(5, e=8)
    w, _ = route_topk(x, router, 2)
    wn, _ = route_topk(x, router, 2, renormalize=True)
    assert float(jnp.max(jnp.sum(w, -1))) < 0.999
    np.testing.assert_allclose(jnp.sum(wn, -1), 1.0, rtol=1e-6)
    y, _ = dropless_moe(x, router, *ws, 2, renormalize=True)
    np.testing.assert_allclose(
        y, dense_moe_reference(x, router, *ws, 2, renormalize=True),
        atol=2e-5)


@pytest.mark.parametrize("rows,experts,tile", [
    (512, 64, 16), (8192, 64, 128), (2048, 64, 32), (16, 8, 16),
    (1 << 20, 64, 128)])
def test_row_tile_follows_rows_an_expert(rows, experts, tile):
    assert row_tile(rows, experts) == tile


def test_moe_config_refuses_top8_on_the_capacity_paths_only():
    """``top_k not in (1, 2)`` is a check of moe/layer.py's capacity
    dispatch; the dropless layer routes any k."""
    from deepspeed_tpu.moe import MoEConfig
    with pytest.raises(ValueError, match="top_k"):
        MoEConfig(n_experts=64, d_model=8, d_ff=8, top_k=8)
    x, router, ws = _layer(3, e=8)
    assert dropless_moe(x, router, *ws, 5)[0].shape == x.shape


# -- the model against the plain reference ----------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_apply_matches_the_reference_in_float32(attn_impl):
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    params = _params(cfg)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 24))
    got = OlmoeModel(cfg).apply(params, jnp.asarray(tokens))
    want = _reference(params, tokens)
    assert float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(got, want, atol=F32_TOL)


def test_the_float32_tolerance_fails_bf16_weights():
    params = _params()
    tokens = np.random.default_rng(0).integers(0, 256, (1, 24))
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got = OlmoeModel(TINY).apply(low, jnp.asarray(tokens))
    diff = float(jnp.abs(got.astype(jnp.float32)
                         - _reference(params, tokens)).max())
    assert diff > 20 * F32_TOL


def test_apply_reports_the_expert_counters():
    params = _params()
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 256, (1, 16)))
    _, aux = OlmoeModel(TINY).apply(params, tokens, aux=True)
    assert int(aux["moe_rows"]) == 16 * 2 * 2
    assert 1 <= int(aux["moe_experts_hit"]) <= 16
    assert 1.0 <= float(aux["moe_load_imbalance"]) <= 8.0


def test_init_draws_in_param_dtype_and_counts_its_parameters():
    cfg = dataclasses.replace(TINY, param_dtype="bfloat16")
    params = jax.eval_shape(OlmoeModel(cfg).init, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(params)
    assert all(a.dtype == jnp.bfloat16 for a in leaves)
    assert sum(int(np.prod(a.shape)) for a in leaves) == cfg.num_params
    full = OlmoeConfig()
    assert round(full.num_params / 1e9, 2) == 6.92


# -- the paged serving path against the reference's full forward -----------

SERVING = {"slots": 4, "page_len": 8, "max_seq_len": 96, "prefill_len": 32}


def _slack(params, req, keys=SOURCE_KEYS):
    """How far below the reference's top logit each emitted token sits
    (teacher-forced on the engine's own tokens): logits, not tokens."""
    seq = list(req.prompt) + list(req.tokens)
    ref = np.asarray(_reference(params, [seq[:-1]], keys))[0]
    rows = ref[len(req.prompt) - 1:]
    at = np.arange(len(req.tokens))
    return float((rows.max(axis=1) - rows[at, req.tokens]).max())


@pytest.mark.parametrize("extra", [
    {}, {"prefill_chunk_len": 8}, {"decode_impl": "dense"},
    {"prefix_cache": False}],
    ids=["paged", "chunked_prefill", "dense_arm", "no_prefix_cache"])
def test_engine_streams_sit_on_the_reference_logits(extra):
    """Prompts of 3, 11 (crosses a page of 8), 19 (crosses two, and a
    prefill chunk of 8 three times) and 32 (the whole bucket); 20 tokens
    out cross two more pages."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    params = _params(cfg)
    eng = ServeEngine(OlmoeModel(cfg), {"serving": {**SERVING, **extra}},
                      params=params)
    rng = np.random.default_rng(5)
    reqs = [eng.submit([int(t) for t in rng.integers(0, 256, (n,))],
                       max_new_tokens=20) for n in (3, 11, 19, 32)]
    eng.run_until_idle()
    try:
        for r in reqs:
            assert r.finish_reason == "length" and len(r.tokens) == 20
            assert _slack(params, r) <= 1e-4
        assert eng._decode_fn._cache_size() == 1
        assert eng._prefill_fn._cache_size() == 1
        kinds = [k for _, k, _ in eng.aux_log]
        assert kinds.count("decode") == eng._ticks or extra
        assert kinds.count("prefill") >= 4
    finally:
        eng.close()


def test_engine_streams_at_head_128_run_the_direct_arm():
    """16 heads of 128, as published: the engine's pool chooses the
    direct arm of the paged decode kernel, and the streams still sit on
    the reference's logits across page edges (prompts of 3 and 19, 14
    tokens out: pages of 8 fill and open under the hand-written
    copies)."""
    cfg = dataclasses.replace(
        TINY, hidden_size=2048, num_attention_heads=16,
        num_key_value_heads=16, num_hidden_layers=1, num_experts=2,
        intermediate_size=8, attn_impl="flash")
    keys = {**SOURCE_KEYS, "num_attention_heads": 16, "num_experts": 2}
    params = _params(cfg, scale=2.0)         # 2,048 wide: logits of size 1
    eng = ServeEngine(OlmoeModel(cfg), {"serving": SERVING}, params=params)
    rng = np.random.default_rng(7)
    reqs = [eng.submit([int(t) for t in rng.integers(0, 256, (n,))],
                       max_new_tokens=14) for n in (3, 19)]
    eng.run_until_idle()
    try:
        assert eng.paged_decode_arm == "direct"
        for r in reqs:
            assert r.finish_reason == "length" and len(r.tokens) == 14
            assert _slack(params, r, keys) <= 1e-4
        assert eng._decode_fn._cache_size() == 1
    finally:
        eng.close()


def test_engine_reuses_a_cached_prefix():
    """The second request shares 16 tokens (two pages) with the first:
    its prefill attends the cached pages (the ``prefix_len > 0`` arm)."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    params = _params(cfg)
    eng = ServeEngine(OlmoeModel(cfg), {"serving": SERVING}, params=params)
    head = [int(t) for t in np.random.default_rng(7).integers(0, 256, (16,))]
    first = eng.submit(head + [1, 2, 3], max_new_tokens=6)
    eng.run_until_idle()
    second = eng.submit(head + [9, 8, 7, 6], max_new_tokens=6)
    eng.run_until_idle()
    try:
        assert second.shared_len == 16 and eng.prefix.hits == 1
        assert max(_slack(params, first), _slack(params, second)) <= 1e-4
    finally:
        eng.close()


def test_paged_steps_against_the_reference_logits():
    """Prefill of 11 tokens then 6 forced decode ticks through the model's
    paged entry points (a pool of one request, slot 0 of 4): every row of
    logits against the reference's full forward."""
    from deepspeed_tpu.inference.kv_cache import (PagedKVCacheSpec,
                                                  init_paged_cache)
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    model, params = OlmoeModel(cfg), _params(cfg)
    spec = PagedKVCacheSpec(layers=2, slots=4, heads=4, pages=13, page_len=8,
                            head_dim=16, max_pages=12, dtype=jnp.float32)
    cache = init_paged_cache(spec)
    rng = np.random.default_rng(3)
    prompt, forced = rng.integers(0, 256, (11,)), rng.integers(0, 256, (6,))
    row = np.zeros((12,), np.int32)
    row[:3] = [5, 2, 9]                      # pages in no particular order
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :11] = prompt
    logits, k, v = model.prefill_paged(params, tokens, 11, 0, row,
                                       cache["k"], cache["v"])
    got = [logits[0, 10]]
    table = np.zeros((4, 12), np.int32)
    table[0] = row
    lengths = jnp.zeros((4,), jnp.int32).at[0].set(11)
    active = np.array([True, False, False, False])
    for t in forced:
        step = jnp.zeros((4,), jnp.int32).at[0].set(int(t))
        lg, k, v, lengths = model.decode_step_paged(
            params, step, k, v, table, lengths, active)
        got.append(lg[0])
    want = _reference(params, [list(prompt) + list(forced)])[0][10:]
    np.testing.assert_allclose(np.stack(got), want, atol=PAGED_TOL)
    assert int(lengths[0]) == 17 and int(lengths[1]) == 0


def test_engine_counters_reach_the_registry():
    params = _params()
    eng = ServeEngine(OlmoeModel(TINY), {
        "serving": SERVING,
        "telemetry": {"enabled": True, "output_path": os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "olmoe_tel")}}, params=params)
    eng.submit([1, 2, 3, 4], max_new_tokens=4)
    eng.run_until_idle()
    try:
        t, kind, last = eng.aux_log[-1]
        assert kind == "decode" and last["moe_rows"] == 1 * 2 * 2
        names = {m.name for m in eng.telemetry.registry.metrics()}
        assert {"serve_moe_experts_hit", "serve_moe_load_imbalance"} <= names
        assert eng._moe_hit_gauge.value() == last["moe_experts_hit"]
        assert eng._moe_imbalance_gauge.value() \
            == pytest.approx(last["moe_load_imbalance"])
    finally:
        eng.close()


@pytest.mark.parametrize("heads,head_dim,extra,arm", [
    (16, 128, {}, "direct"), (4, 16, {}, "packed"),
    (16, 128, {"decode_impl": "dense"}, None)],
    ids=["head_128", "head_16", "dense_arm"])
def test_engine_says_which_decode_arm_its_pool_chose(heads, head_dim, extra,
                                                     arm):
    """``paged_decode_arm{arm=}`` is set once at construction from the
    pool's shape, 1 on the arm ``ds_paged_decode_attn`` runs: 16 heads of
    128 (the published widths) rest as the matmul operand; where another
    arm decodes the gauge does not exist."""
    cfg = dataclasses.replace(
        TINY, hidden_size=heads * head_dim, num_attention_heads=heads,
        num_key_value_heads=heads, num_hidden_layers=1, num_experts=2,
        intermediate_size=8, attn_impl="flash")
    shapes = jax.eval_shape(OlmoeModel(cfg).init, jax.random.PRNGKey(0))
    eng = ServeEngine(OlmoeModel(cfg), {
        "serving": {**SERVING, "page_len": 16, **extra},
        "telemetry": {"enabled": True, "output_path": os.path.join(
            os.environ.get("TMPDIR", "/tmp"), f"olmoe_arm_{heads}")}},
        params=jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes))
    try:
        assert eng.paged_decode_arm == arm
        names = {m.name for m in eng.telemetry.registry.metrics()}
        assert ("paged_decode_arm" in names) == (arm is not None)
        if arm is not None:
            gauge = eng.telemetry.registry.gauge("paged_decode_arm")
            assert {a: gauge.value(arm=a) for a in ("direct", "packed")} \
                == {a: int(a == arm) for a in ("direct", "packed")}
    finally:
        eng.close()


# -- refusals ---------------------------------------------------------------

@pytest.mark.parametrize("serving,named", [
    ({"page_len": 0}, "page_len"),
    ({"speculate_k": 2, "draft": {"d_model": 32, "n_layer": 1,
                                  "n_head": 2}}, "speculate_k"),
    ({"quantization": {"weights": "int8"}}, "quantization"),
    ({"quantization": {"kv": "int8"}}, "quantization"),
    ({"lora": {"rank": 4}}, "lora")])
def test_engine_refuses_what_the_model_lacks_at_construction(serving, named):
    with pytest.raises(ValueError, match="OlmoeModel cannot be served.*"
                       + named):
        ServeEngine(OlmoeModel(TINY), {"serving": {**SERVING, **serving}})


@pytest.mark.parametrize("field,value", [
    ("num_key_value_heads", 2), ("clip_qkv", 8.0), ("attention_bias", True),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("attn_impl", "ring")])
def test_config_refuses_what_is_not_built(field, value):
    with pytest.raises(ValueError, match=field.split("_")[0]):
        dataclasses.replace(TINY, **{field: value})


@pytest.mark.parametrize("kwarg", ["k_scale", "lora"])
def test_paged_steps_refuse_gpt2s_arms(kwarg):
    with pytest.raises(NotImplementedError, match=kwarg):
        OlmoeModel(TINY).decode_step_paged(
            None, None, None, None, None, None, None, **{kwarg: object()})


class _ClaimsEverything(OlmoeModel):
    serving_unsupported = ()


@pytest.mark.parametrize("serving", [
    {"quantization": {"weights": "int8"}},
    {"speculate_k": 2, "draft": {"d_model": 32, "n_layer": 1, "n_head": 2}}],
    ids=["int8_weights", "draft"])
def test_gpt2_only_arms_name_their_error(serving):
    """A model of another family that does not declare what it lacks gets
    a named error from the GPT-2-only arms, not a KeyError."""
    with pytest.raises(NotGPT2ParamsError, match="GPT"):
        ServeEngine(_ClaimsEverything(TINY),
                    {"serving": {**SERVING, **serving}})
    with pytest.raises(NotGPT2ParamsError):
        quantize_gpt2_params({"blocks": {"q_w": jnp.zeros((1, 2, 2))}})


# -- nothing new on the BERT path; the new cells rehearse -------------------

def test_bert_initialize_imports_nothing_of_olmoe():
    code = """
import sys, numpy as np
import deepspeed_tpu
from deepspeed_tpu.models.bert import BertConfig, BertModel
before = set(sys.modules)
cfg = {"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
       "bf16": {"enabled": True}, "zero_optimization": {"stage": 0},
       "optimizer": {"type": "Adam", "params": {"lr": 1e-4}}}
model = BertModel(BertConfig(vocab_size=64, hidden_size=16,
    num_hidden_layers=1, num_attention_heads=2, intermediate_size=32,
    max_position_embeddings=16))
engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
new = [m for m in sys.modules if m.startswith("deepspeed_tpu")]
assert not [m for m in new if "olmoe" in m or "dropless" in m], new
print("OK", len(set(sys.modules) - before))
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu",
                              "XLA_FLAGS": ""})     # one device
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]


def test_paged_decode_kernel_at_head_128_equals_the_dense_arm():
    """16 heads of 128, pages of 16 (``_paged_block_layout``: ``fold`` 1,
    a key fills the lanes alone): lengths of 1, a full page, a page and
    one, several blocks, and a free slot; pages in shuffled order."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        decode_attention_paged
    S, H, PL, Dh, MAXP = 6, 16, 16, 128, 12
    P = 1 + S * MAXP
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    kp = jax.random.normal(k[0], (P, H, PL, Dh))
    vp = jax.random.normal(k[1], (P, H, PL, Dh))
    q = jax.random.normal(k[2], (S, H, Dh))
    lens = np.array([1, 16, 17, 100, 192, 0], np.int32)
    table = np.zeros((S, MAXP), np.int32)
    perm, c = np.random.default_rng(0).permutation(np.arange(1, P)), 0
    for s in range(S):
        n = -(-int(lens[s]) // PL)
        table[s, :n] = perm[c:c + n]
        c += n
    got = decode_attention_paged(q, kp, vp, jnp.asarray(table),
                                 jnp.asarray(lens), impl="pallas")
    want = decode_attention_paged(q, kp, vp, jnp.asarray(table),
                                  jnp.asarray(lens), impl="dense")
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(got[5]).max()) == 0.0
