"""Quantized serving plane (docs/serving.md "quantized serving"):

* numeric-bounds per-op tests — ``|q(x) - x| <= scale/2`` for the KV
  row quantizer and the per-channel weight quantizer (the scale-
  derived bound of inference/quantize.py),
* int8-domain parity — the dense paged arms are BITWISE the reference
  over the dequantized gathered view (the semantics anchor), the
  pallas fused-dequant arms match dense at the established kernel
  tolerance, single- and multi-query,
* default-off is bitwise-unchanged: the explicit fp16 arm emits the
  same streams as no quantization block at all, no scale leaves, no
  dtype changes,
* engine tolerance tier — kv-int8 first tokens are EXACT (prefill
  computes fp; only storage quantizes), full greedy streams' agreement
  reported against a pinned floor,
* zero-recompile + COW/eviction under quantized pages (scale sidecars
  ride the copy_page program; pool accounting stays clean),
* quantized-draft speculation: spec stream == non-spec stream at
  k in {1, 4} under weights+kv int8 (and the unpaged weights arm),
* config validation, the serve_param_bytes/serve_kv_bytes memory
  plane -> summarize row (params >= 1.8x, KV >= 2x smaller), and
  admitted requests at one KV-byte budget (>= 2x, nothing cut short).
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.config.config import DeepSpeedServingConfig
from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.inference.quantize import (
    dequantize_channels, dequantize_rows, param_nbytes,
    quantize_channels, quantize_gpt2_params, quantize_rows,
    quantized_partition_specs)
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model, gpt2_prefill
from deepspeed_tpu.ops.pallas.decode_attention import (
    decode_attention_paged, decode_attention_paged_multi,
    decode_attention_reference, dequantize_paged)
from deepspeed_tpu.runtime.stages import reset_fault_injection

TINY = GPT2Config(vocab_size=128, n_positions=64, d_model=32, n_layer=2,
                  n_head=4, remat=None, attn_impl="dense")
TINY_FLASH = GPT2Config(**{**TINY.__dict__, "attn_impl": "flash"})

_CHAOS_ENVS = ("DS_STAGE_FAULT", "DS_STAGE_DELAY_S")


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    for env in _CHAOS_ENVS:
        monkeypatch.delenv(env, raising=False)
    reset_fault_injection()
    yield
    reset_fault_injection()


def _tokens(n, vocab=128, seed=0):
    return np.random.default_rng(seed).integers(
        0, vocab, (n,)).astype(np.int32)


def _serve_cfg(slots=4, max_seq=32, prefill=24, telemetry_path=None,
               **serving_extra):
    cfg = {"serving": {"slots": slots, "max_seq_len": max_seq,
                       "prefill_len": prefill, **serving_extra}}
    if telemetry_path is not None:
        cfg["telemetry"] = {"enabled": True,
                            "output_path": str(telemetry_path)}
    return cfg


def _streams(model, params, serving_extra, prompts, gen=6,
             draft_params=None):
    eng = ServeEngine(model, _serve_cfg(**serving_extra), params=params,
                      draft_params=draft_params)
    rs = [eng.submit(p, max_new_tokens=gen) for p in prompts]
    eng.run_until_idle()
    assert all(r.error is None for r in rs), [r.error for r in rs]
    out = [r.tokens for r in rs]
    eng.close()
    return out


def _agreement(a, b):
    total = same = 0
    for ta, tb in zip(a, b):
        for x, y in zip(ta, tb):
            total += 1
            same += x == y
    return same / max(total, 1)


# ---------------------------------------------------------------------------
# numeric bounds: the scale-derived error contract, per op
# ---------------------------------------------------------------------------


def test_quantize_rows_numeric_bounds():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(5, 4, 16) * rng.lognormal(0, 2, (5, 4, 1)),
                    jnp.float32)
    q, s = quantize_rows(x)
    assert q.dtype == jnp.int8 and s.shape == (5, 4)
    err = jnp.abs(dequantize_rows(q, s) - x)
    # round-to-nearest within the symmetric range: |q*s - x| <= s/2,
    # and the absmax element itself is EXACT (maps to +-127)
    assert (err <= s[..., None] / 2 + 1e-6).all()
    flat = np.asarray(jnp.abs(x)).reshape(-1, 16)
    deq = np.asarray(jnp.abs(dequantize_rows(q, s))).reshape(-1, 16)
    idx = flat.argmax(axis=1)
    np.testing.assert_allclose(deq[np.arange(len(idx)), idx],
                               flat[np.arange(len(idx)), idx], rtol=1e-6)
    # all-zero rows: scale 1.0, exact-zero round trip
    qz, sz = quantize_rows(jnp.zeros((2, 3, 8)))
    assert (np.asarray(sz) == 1.0).all()
    assert (np.asarray(dequantize_rows(qz, sz)) == 0).all()


def test_quantize_channels_numeric_bounds():
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.randn(2, 32, 3, 32), jnp.float32)  # qkv shape
    q, s = quantize_channels(w)
    assert q.dtype == jnp.int8 and s.shape == (2, 1, 3, 32)
    err = jnp.abs(dequantize_channels(q, s) - w)
    assert (err <= s / 2 + 1e-6).all()
    # the fused matmul's error obeys the per-channel bound too:
    # |x·w8·s - x·w| <= sum|x| * s/2 per output channel
    x = jnp.asarray(rng.randn(4, 32), jnp.float32)
    got = jnp.einsum("bd,dke->bke", x, q[0].astype(jnp.float32)) * s[0]
    ref = jnp.einsum("bd,dke->bke", x, w[0])
    bound = jnp.sum(jnp.abs(x), axis=1)[:, None, None] * (s[0] / 2)
    assert (jnp.abs(got - ref) <= bound + 1e-5).all()


def test_quantized_param_tree_and_specs():
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    qp = quantize_gpt2_params(params)
    for name in ("qkv_w", "out_w", "fc_w", "proj_w"):
        assert qp["blocks"][name].dtype == jnp.int8
        assert qp["blocks"][name + "_scale"].dtype == jnp.float32
    # the input tree is never mutated; non-covered leaves untouched
    assert params["blocks"]["qkv_w"].dtype == jnp.float32
    assert qp["wte"] is params["wte"]
    assert qp["blocks"]["ln1_scale"] is params["blocks"]["ln1_scale"]
    # int8 + scales beat the fp32 master by > 2x on this config
    assert param_nbytes(params) / param_nbytes(qp) > 2.0
    specs = quantized_partition_specs(model.param_partition_specs(params))
    # column-parallel scales keep the output-channel shard; the
    # contracted (size-1) axis is never sharded
    assert specs["blocks"]["qkv_w_scale"] == P(None, None, None, "model")
    assert specs["blocks"]["fc_w_scale"] == P(None, None, "model")
    assert specs["blocks"]["out_w_scale"] == P(None, None, None)
    assert specs["blocks"]["proj_w_scale"] == P(None, None, None)


def test_quant_weights_prefill_logits_close():
    """The whole-model weights-arm bound: tiny logits drift, greedy
    argmax preserved on this seed (reported tier, pinned loose)."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(_tokens(12, seed=3)[None])
    ref, _, _ = gpt2_prefill(TINY, params, toks)
    got, _, _ = gpt2_prefill(TINY, quantize_gpt2_params(params), toks)
    assert float(jnp.max(jnp.abs(got - ref))) < 0.05


# ---------------------------------------------------------------------------
# kernel parity: int8 domain, dense defines the semantics
# ---------------------------------------------------------------------------


def _quant_pool(S, H, page_len, max_pages, Dh, seed=0):
    rng = np.random.RandomState(seed)
    P_ = 1 + S * max_pages
    k8, ks = quantize_rows(jnp.asarray(rng.randn(P_, H, page_len, Dh),
                                       jnp.float32))
    v8, vs = quantize_rows(jnp.asarray(rng.randn(P_, H, page_len, Dh),
                                       jnp.float32))
    pt = jnp.asarray(np.arange(1, P_).reshape(S, max_pages), jnp.int32)
    return k8, ks, v8, vs, pt


def test_quant_kernel_parity_single_query():
    S, H, page_len, M, Dh = 4, 3, 16, 3, 32
    k8, ks, v8, vs, pt = _quant_pool(S, H, page_len, M, Dh)
    q = jnp.asarray(np.random.RandomState(1).randn(S, H, Dh),
                    jnp.float32)
    lengths = jnp.asarray([0, 7, 16, 2 * 16 + 5], jnp.int32)
    out_d = decode_attention_paged(q, k8, v8, pt, lengths, impl="dense",
                                   k_scale=ks, v_scale=vs)
    out_p = decode_attention_paged(q, k8, v8, pt, lengths,
                                   impl="pallas", interpret=True,
                                   k_scale=ks, v_scale=vs)
    # int8-domain semantics anchor: dense == reference over the
    # dequantized gathered view, BITWISE
    ref = decode_attention_reference(q, dequantize_paged(k8, ks, pt),
                                     dequantize_paged(v8, vs, pt),
                                     lengths)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(ref))
    # fused kernel vs dense: the established kernel tolerance
    np.testing.assert_allclose(out_p, out_d, atol=2e-6, rtol=2e-6)
    # free slot -> exact zeros on both arms
    assert (np.asarray(out_d[0]) == 0).all()
    assert (np.asarray(out_p[0]) == 0).all()


def test_quant_kernel_parity_multi_query():
    S, H, page_len, M, Dh, W = 3, 2, 8, 4, 16, 5
    k8, ks, v8, vs, pt = _quant_pool(S, H, page_len, M, Dh, seed=2)
    q = jnp.asarray(np.random.RandomState(3).randn(S, H, W, Dh),
                    jnp.float32)
    base = np.asarray([0, 6, 2 * 8 + 3])
    lens = np.where(base[:, None] > 0,
                    base[:, None] + np.arange(W)[None] + 1, 0)
    lens = jnp.asarray(np.minimum(lens, M * page_len), jnp.int32)
    md = decode_attention_paged_multi(q, k8, v8, pt, lens, impl="dense",
                                      k_scale=ks, v_scale=vs)
    mp = decode_attention_paged_multi(q, k8, v8, pt, lens,
                                      impl="pallas", interpret=True,
                                      k_scale=ks, v_scale=vs)
    # the multi dense arm is DEFINED as W stacked single-query dense
    # calls over the same int8 domain — bitwise by construction
    for i in range(W):
        one = decode_attention_paged(q[:, :, i], k8, v8, pt, lens[:, i],
                                     impl="dense", k_scale=ks,
                                     v_scale=vs)
        np.testing.assert_array_equal(np.asarray(md[:, :, i]),
                                      np.asarray(one))
    np.testing.assert_allclose(mp, md, atol=2e-6, rtol=2e-6)
    # masked rows (slot 0, every row) -> exact zeros
    assert (np.asarray(mp[0]) == 0).all()


def test_quant_kernel_arg_validation():
    S, H, page_len, M, Dh = 2, 2, 8, 2, 16
    k8, ks, v8, vs, pt = _quant_pool(S, H, page_len, M, Dh)
    lengths = jnp.asarray([3, 5], jnp.int32)
    q = jnp.zeros((S, H, Dh), jnp.float32)
    with pytest.raises(ValueError, match="together"):
        decode_attention_paged(q, k8, v8, pt, lengths, impl="dense",
                               k_scale=ks)
    fp = jnp.zeros((1 + S * M, H, page_len, Dh), jnp.float32)
    with pytest.raises(ValueError, match="int8"):
        decode_attention_paged_multi(
            jnp.zeros((S, H, 2, Dh), jnp.float32), fp, fp, pt,
            jnp.zeros((S, 2), jnp.int32), impl="dense", k_scale=ks,
            v_scale=vs)


# ---------------------------------------------------------------------------
# engine: default-off bitwise, tolerance tiers, zero recompiles
# ---------------------------------------------------------------------------


BOUNDARY_PROMPTS = [1, 3, 8, 17, 20]


def test_quant_default_off_is_bitwise_unchanged():
    """The acceptance bar: no quantization block, the explicit fp16
    arm, and an empty dict all emit the SAME streams (they are the
    same compiled programs), with no scale leaves and no dtype
    changes anywhere."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(n, seed=10 + n)) for n in BOUNDARY_PROMPTS]
    absent = _streams(model, params, dict(page_len=8), prompts)
    explicit = _streams(
        model, params,
        dict(page_len=8,
             quantization={"weights": "fp16", "kv": "fp16"}), prompts)
    empty = _streams(model, params, dict(page_len=8, quantization={}),
                     prompts)
    assert absent == explicit == empty
    eng = ServeEngine(model, _serve_cfg(
        page_len=8, quantization={"weights": "fp16", "kv": "fp16"}),
        params=params)
    assert set(eng.cache) == {"k", "v", "lengths"}
    assert eng.cache["k"].dtype == jnp.float32
    assert eng.params["blocks"]["qkv_w"].dtype == jnp.float32
    assert "qkv_w_scale" not in eng.params["blocks"]
    assert not eng.cache_spec.quant
    eng.close()


@pytest.mark.parametrize("cfg", [TINY, TINY_FLASH],
                         ids=["dense", "flash"])
def test_quant_engine_tolerance_tier(cfg):
    """The documented tolerance tier (docs/serving.md): kv-int8 FIRST
    tokens are exact (prefill attends fp; only storage quantizes),
    and full greedy streams agree with the fp engine above the pinned
    floor on fixed seeds (a tolerance tier, never asserted equal;
    pinned here so a numerics regression is loud)."""
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(n, seed=20 + n)) for n in BOUNDARY_PROMPTS]
    fp = _streams(model, params, dict(page_len=8), prompts)
    for quant in ({"kv": "int8"}, {"weights": "int8", "kv": "int8"}):
        qs = _streams(model, params,
                      dict(page_len=8, quantization=quant), prompts)
        if "weights" not in quant:
            # prefill computes full-precision K/V -> exact first token
            assert [t[0] for t in qs] == [t[0] for t in fp]
        assert _agreement(fp, qs) >= 0.9, (quant, fp, qs)
    # engine shape checks for the quantized cache
    eng = ServeEngine(model, _serve_cfg(
        page_len=8, quantization={"weights": "int8", "kv": "int8"}),
        params=params)
    assert eng.cache["k"].dtype == jnp.int8
    assert eng.cache["k_scale"].shape == eng.cache["k"].shape[:-1]
    assert eng.params["blocks"]["qkv_w"].dtype == jnp.int8
    assert eng.cache_spec.quant and eng.cache_spec.bytes == eng.kv_bytes
    eng.close()


def test_quant_weights_unpaged_engine():
    """The weights arm is independent of paging: the slot-cache engine
    serves int8 weights with the same tolerance tier."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(n, seed=30 + n)) for n in (2, 9, 15)]
    fp = _streams(model, params, {}, prompts)
    w8 = _streams(model, params,
                  dict(quantization={"weights": "int8"}), prompts)
    assert _agreement(fp, w8) >= 0.9
    with pytest.raises(DeepSpeedConfigError, match="page_len"):
        # kv int8 without pages must fail loudly at config parse
        ServeEngine(model, {"serving": {
            "slots": 2, "quantization": {"kv": "int8"}}}, params=params)


def test_quant_zero_recompiles_mixed_waves(tmp_path):
    """Acceptance bar: the quantized programs compile ONCE across
    waves of mixed page counts / lengths — recompiles_total == 0 and
    jit cache size 1 for serve_decode, serve_prefill and
    serve_copy_page."""
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(
        slots=3, page_len=8, telemetry_path=tmp_path,
        quantization={"weights": "int8", "kv": "int8"}))
    rng = np.random.default_rng(7)
    reqs = []
    for wave in range(3):
        for i in range(5):
            n = int(rng.integers(1, 24))
            reqs.append(eng.submit(
                list(_tokens(n, seed=100 * wave + i)),
                max_new_tokens=int(rng.integers(1, 9))))
        eng.run_until_idle()
    assert all(r.error is None for r in reqs)
    eng.telemetry.compile_monitor.sample()
    reg = eng.telemetry.registry
    for prog in ("serve_decode", "serve_prefill", "serve_copy_page"):
        assert reg.counter("recompiles_total").value(program=prog) == 0
    assert eng._decode_fn._cache_size() == 1
    assert eng._prefill_fn._cache_size() == 1
    eng.close()


# ---------------------------------------------------------------------------
# COW + prefix eviction over quantized pages
# ---------------------------------------------------------------------------


def test_quant_cow_copies_scale_sidecars():
    """copy_page must move the scale rows WITH the int8 rows, or the
    copied page dequantizes with the wrong scales."""
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(
        page_len=8, quantization={"kv": "int8"}))
    r = eng.submit(list(_tokens(10, seed=40)), max_new_tokens=2)
    eng.run_until_idle()
    assert r.error is None
    # snapshot before the call: copy_fn DONATES the cache
    before = {k: np.asarray(v) for k, v in eng.cache.items()}
    src, dst = 1, eng.cache_spec.pages - 1
    eng.cache = eng._copy_fn(eng.cache, np.int32(src), np.int32(dst))
    for key in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(eng.cache[key][:, dst]), before[key][:, src])
    np.testing.assert_array_equal(np.asarray(eng.cache["lengths"]),
                                  before["lengths"])
    eng.close()


def test_quant_prefix_cow_eviction_accounting():
    """Prefix sharing + divergent-append COW + leaf eviction under the
    quantized pool: streams match the no-prefix quantized run token
    for token (the COW'd page carries its scales), and the pool's
    refcounts drain clean."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    # IDENTICAL prompts (the existing COW test's shape): sharing runs
    # down INTO the partial tail page, so each later admission COWs it
    # before its divergent append
    prompt = list(_tokens(13, seed=50))         # 1 full + 4-token tail
    prompts = [prompt] * 3
    quant = {"weights": "int8", "kv": "int8"}

    def run(prefix_cache):
        eng = ServeEngine(model, _serve_cfg(
            page_len=8, prefix_cache=prefix_cache, quantization=quant),
            params=params)
        rs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
        assert all(r.error is None for r in rs)
        out = [r.tokens for r in rs]
        cow = eng.prefix.cow if eng.prefix else 0
        hits = eng.prefix.hits if eng.prefix else 0
        eng.prefix and eng.prefix.clear()
        assert eng.pool.refs == {}, eng.pool.refs
        eng.close()
        return out, cow, hits

    on, cow, hits = run(True)
    off, _, _ = run(False)
    # the COW'd shared page dequantizes identically to the original:
    # prefix on/off stay token-identical on the quantized engine too
    assert on == off
    assert hits == 2 and cow >= 1
    # eviction under pool pressure: a pool too small to hold the
    # prefix cache + live slots still serves (leaf-LRU eviction frees
    # quantized pages), accounting clean
    eng = ServeEngine(model, _serve_cfg(
        slots=2, page_len=8, pages=8, quantization=quant),
        params=params)
    rs = [eng.submit(list(_tokens(12, seed=60 + i)), max_new_tokens=3)
          for i in range(5)]
    eng.run_until_idle()
    assert all(r.error is None for r in rs)
    eng.prefix.clear()
    assert eng.pool.refs == {}
    eng.close()


# ---------------------------------------------------------------------------
# quantized-draft speculation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 4])
def test_quant_spec_draft_stream_parity(k):
    """Speculation under full quantization (int8 target weights, int8
    KV pages, int8 DRAFT weights — the 'quantized draft is nearly
    free' composition): the speculative greedy stream equals the
    non-speculative stream of the SAME quantized engine at k in
    {1, 4}."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(n, seed=70 + n)) for n in (2, 7, 12)]
    quant = {"weights": "int8", "kv": "int8"}
    base = _streams(model, params,
                    dict(page_len=8, quantization=quant), prompts,
                    gen=2 * (k + 1) + 1)
    spec = _streams(
        model, params,
        dict(page_len=8, quantization=quant, speculate_k=k,
             draft={"d_model": 32, "n_layer": 2, "n_head": 4}),
        prompts, gen=2 * (k + 1) + 1, draft_params=params)
    assert spec == base
    # unpaged weights-only arm composes with speculation too
    b2 = _streams(model, params,
                  dict(quantization={"weights": "int8"}), prompts,
                  gen=2 * (k + 1) + 1)
    s2 = _streams(
        model, params,
        dict(quantization={"weights": "int8"}, speculate_k=k,
             draft={"d_model": 32, "n_layer": 2, "n_head": 4}),
        prompts, gen=2 * (k + 1) + 1, draft_params=params)
    assert s2 == b2


def test_quant_spec_draft_params_are_quantized():
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(
        quantization={"weights": "int8"}, speculate_k=2,
        draft={"d_model": 32, "n_layer": 2, "n_head": 4}))
    assert eng.draft_params["blocks"]["qkv_w"].dtype == jnp.int8
    # the draft cache keeps the master dtype (slot layout, fp rollback)
    assert eng._draft_cache["k"].dtype == jnp.float32
    # param-bytes plane counts target + draft (both quantized)
    assert eng.param_bytes == param_nbytes(eng.params) + \
        param_nbytes(eng.draft_params)
    eng.close()


def test_quant_tp_dp_sharded_matches_single_device():
    """The sharding story survives quantization: int8 weights' scale
    rows keep the Megatron column split, int8 pages + sidecars keep
    the DP-pages/TP-heads split — dp2×tp2 streams == single device."""
    from deepspeed_tpu.parallel import build_mesh
    model = GPT2Model(TINY_FLASH)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(5, seed=i)) for i in range(4)]
    quant = {"weights": "int8", "kv": "int8"}

    def run(mesh):
        eng = ServeEngine(model, _serve_cfg(
            page_len=8, quantization=quant), mesh=mesh, params=params)
        rs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_idle()
        assert all(r.error is None for r in rs)
        toks = [r.tokens for r in rs]
        eng.close()
        return toks

    base = run(None)
    sharded = run(build_mesh(dp=2, tp=2, devices=jax.devices()[:4]))
    assert base == sharded


# ---------------------------------------------------------------------------
# config validation + memory plane + tooling
# ---------------------------------------------------------------------------


def test_quant_config_validation():
    ok = DeepSpeedServingConfig({"serving": {
        "page_len": 8, "quantization": {"weights": "int8",
                                        "kv": "int8"}}})
    assert ok.quantization == {"weights": "int8", "kv": "int8"}
    dflt = DeepSpeedServingConfig({"serving": {}})
    assert dflt.quantization == {"weights": "fp16", "kv": "fp16"}
    with pytest.raises(DeepSpeedConfigError, match="unknown key"):
        DeepSpeedServingConfig({"serving": {
            "quantization": {"wieghts": "int8"}}})
    with pytest.raises(DeepSpeedConfigError, match="fp16"):
        DeepSpeedServingConfig({"serving": {
            "quantization": {"weights": "int4"}}})
    with pytest.raises(DeepSpeedConfigError, match="page_len"):
        DeepSpeedServingConfig({"serving": {
            "quantization": {"kv": "int8"}}})
    # page_len beyond the kernels' one-scale-lane-per-row limit must
    # fail at config parse, not on the first decode tick (the fp pool
    # keeps accepting any page_len)
    with pytest.raises(DeepSpeedConfigError, match="128"):
        DeepSpeedServingConfig({"serving": {
            "page_len": 256, "quantization": {"kv": "int8"}}})
    DeepSpeedServingConfig({"serving": {"page_len": 256}})
    with pytest.raises(DeepSpeedConfigError, match="dict"):
        DeepSpeedServingConfig({"serving": {"quantization": "int8"}})


def test_quant_memory_gauges_flow_to_summarize(tmp_path, capsys):
    from deepspeed_tpu.telemetry.cli import summarize
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))

    def run(tel, quant):
        eng = ServeEngine(model, _serve_cfg(
            page_len=8, telemetry_path=tel, flush_interval_ticks=2,
            quantization=quant), params=params)
        eng.submit(list(_tokens(6, seed=80)), max_new_tokens=4)
        eng.run_until_idle()
        reg = eng.telemetry.registry
        pb = reg.gauge("serve_param_bytes").value()
        kb = reg.gauge("serve_kv_bytes").value()
        assert pb == eng.param_bytes and kb == eng.kv_bytes
        assert kb == eng.cache_spec.bytes
        eng.close()
        return pb, kb

    fp_dir, q_dir = tmp_path / "fp", tmp_path / "q"
    pb_fp, kb_fp = run(fp_dir, None)
    pb_q, kb_q = run(q_dir, {"weights": "int8", "kv": "int8"})
    # the whole point, measured on the exported plane
    assert pb_fp / pb_q >= 1.8
    assert kb_fp / kb_q >= 2.0
    report = summarize(os.path.join(str(q_dir), "events.jsonl"))
    out = capsys.readouterr().out
    assert report["serve_param_bytes"] == pb_q
    assert report["serve_kv_bytes"] == kb_q
    assert "serving memory" in out


def test_int8_pages_admit_twice_the_fp_pool_at_one_kv_byte_budget():
    """The capacity claim as counts: one byte budget buys fp32 pages
    or int8 pages with their scale sidecars
    (``PagedKVCacheSpec.page_bytes``, ``quant=True``).  The work is
    page-exact (a short request lives in one page, a long one in
    three, no decode crosses a boundary), so no stream is cut short in
    either pool; the int8 pool admits at least twice as many requests
    together, and the greedy streams' agreement is reported against
    the tolerance tier's floor, never asserted equal."""
    from deepspeed_tpu.inference import PagedKVCacheSpec
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    page_len, budget_pages = 8, 8
    shape = dict(layers=TINY.n_layer, slots=1, heads=TINY.n_head,
                 pages=1, page_len=page_len, head_dim=TINY.d_head,
                 max_pages=1)
    fp_page = PagedKVCacheSpec(dtype=jnp.float32, **shape).page_bytes
    q_page = PagedKVCacheSpec(dtype=jnp.int8, quant=True,
                              **shape).page_bytes
    budget = budget_pages * fp_page
    work = [list(_tokens(3 * page_len - 4 if i % 4 == 3
                         else page_len - 4, seed=500 + i))
            for i in range(48)]

    def leg(pages, quant):
        extra = {"quantization": quant} if quant else {}
        eng = ServeEngine(model, _serve_cfg(
            slots=32, prefill=3 * page_len - 4, queue_capacity=256,
            page_len=page_len, pages=pages, prefix_cache=False,
            **extra), params=params)
        # the spec's accounting is the arrays' own
        assert sum(int(eng.cache[k].nbytes) for k in eng.cache
                   if k != "lengths") == eng.cache_spec.bytes
        assert eng.cache_spec.bytes == eng.kv_bytes
        rs = [eng.submit(p, max_new_tokens=4) for p in work]
        peak = 0
        while eng.scheduler.active or eng._pending or eng.queue.qsize():
            eng.step()
            peak = max(peak, len(eng.scheduler.active))
        kv_bytes = eng.kv_bytes
        eng.close()
        assert all(r.error is None for r in rs), [r.error for r in rs]
        assert all(r.finish_reason == "length" for r in rs)
        return [r.tokens for r in rs], peak, kv_bytes

    # + the scratch page, which holds no request
    tok_fp, peak_fp, bytes_fp = leg(budget // fp_page + 1, None)
    tok_q, peak_q, bytes_q = leg(budget // q_page + 1, {"kv": "int8"})
    assert bytes_q <= bytes_fp, (bytes_q, bytes_fp)
    assert peak_q >= 2 * peak_fp, (peak_q, peak_fp)
    assert _agreement(tok_fp, tok_q) >= 0.9
