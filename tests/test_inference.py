"""KV-cached decode engine: kernel differentials, prefill==decode logit
parity against the training forward, the one-compiled-decode-program
(zero recompile) contract, slot lifecycle, chaos, and the telemetry
plumbing (docs/serving.md).
"""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.inference import (KVCacheSpec, ServeEngine, init_cache,
                                     shard_cache)
from deepspeed_tpu.inference.kv_cache import validate_cache_mesh
from deepspeed_tpu.inference.scheduler import Request, SlotScheduler
from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2Model,
                                       gpt2_decode_step, gpt2_prefill)
from deepspeed_tpu.ops.pallas.decode_attention import (
    decode_attention, decode_attention_reference)
from deepspeed_tpu.parallel import build_mesh
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


# ---------------------------------------------------------------------------
# decode kernel differentials
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_k", [32, 64, 256])
def test_decode_kernel_matches_dense(block_k):
    rng = np.random.RandomState(0)
    S, H, T, Dh = 5, 3, 130, 32
    q = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    k = jnp.asarray(rng.randn(S, H, T, Dh), jnp.float32)
    v = jnp.asarray(rng.randn(S, H, T, Dh), jnp.float32)
    lengths = jnp.asarray([0, 1, 33, 77, 130], jnp.int32)
    out_p = decode_attention(q, k, v, lengths, impl="pallas",
                             block_k=block_k)
    out_d = decode_attention(q, k, v, lengths, impl="dense")
    np.testing.assert_allclose(out_p, out_d, atol=2e-6, rtol=2e-6)
    # free slot (length 0) outputs exact zeros on BOTH paths
    assert (np.asarray(out_p[0]) == 0).all()
    assert (np.asarray(out_d[0]) == 0).all()


def test_decode_kernel_masks_garbage_tail():
    """Positions beyond a slot's live length hold garbage (evicted
    request, uninitialized cache) and must never be attended."""
    rng = np.random.RandomState(1)
    S, H, T, Dh = 2, 2, 64, 16
    q = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    k = jnp.asarray(rng.randn(S, H, T, Dh), jnp.float32)
    v = jnp.asarray(rng.randn(S, H, T, Dh), jnp.float32)
    poisoned_k = k.at[:, :, 20:].set(1e4)
    poisoned_v = v.at[:, :, 20:].set(1e4)
    lengths = jnp.asarray([20, 7], jnp.int32)
    for impl in ("pallas", "dense"):
        clean = decode_attention(q, k, v, lengths, impl=impl)
        poisoned = decode_attention(q, poisoned_k, poisoned_v, lengths,
                                    impl=impl)
        np.testing.assert_array_equal(np.asarray(clean),
                                      np.asarray(poisoned))


def test_decode_kernel_single_compile_across_lengths():
    """Traced lengths: one jit cache entry no matter the mix."""
    rng = np.random.RandomState(2)
    S, H, T, Dh = 4, 2, 64, 16
    q = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    k = jnp.asarray(rng.randn(S, H, T, Dh), jnp.float32)
    v = jnp.asarray(rng.randn(S, H, T, Dh), jnp.float32)
    f = jax.jit(lambda q, k, v, l: decode_attention(q, k, v, l,
                                                    impl="pallas"))
    for lens in ([0, 0, 0, 0], [1, 5, 64, 0], [64, 64, 64, 64]):
        f(q, k, v, jnp.asarray(lens, jnp.int32)).block_until_ready()
    assert f._cache_size() == 1


def test_decode_kernel_bf16():
    rng = np.random.RandomState(3)
    S, H, T, Dh = 2, 2, 32, 16
    mk = lambda *s: jnp.asarray(rng.randn(*s), jnp.bfloat16)
    q, k, v = mk(S, H, Dh), mk(S, H, T, Dh), mk(S, H, T, Dh)
    lengths = jnp.asarray([9, 32], jnp.int32)
    out = decode_attention(q, k, v, lengths, impl="pallas")
    ref = decode_attention_reference(q, k, v, lengths)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


# ---------------------------------------------------------------------------
# prefill == decode logit parity vs the training forward
# ---------------------------------------------------------------------------


def _decode_chain(cfg, params, toks, t_prompt, t_max, impl):
    """Teacher-forced prefill + step-decode; returns (prefill_logits,
    [decode logits per position t_prompt..T-1]) for slot 1 of a 3-slot
    cache (free slots ride along masked)."""
    model_dtype = params["wte"].dtype
    L, H, Dh = cfg.n_layer, cfg.n_head, cfg.d_head
    logits_p, ks, vs = gpt2_prefill(cfg, params,
                                    jnp.asarray(toks[:, :t_prompt]))
    S = 3
    kc = jnp.zeros((L, S, H, t_max, Dh), model_dtype)
    vc = jnp.zeros((L, S, H, t_max, Dh), model_dtype)
    kc = kc.at[:, 1, :, :t_prompt].set(ks[:, 0])
    vc = vc.at[:, 1, :, :t_prompt].set(vs[:, 0])
    lens = jnp.asarray([0, t_prompt, 0], jnp.int32)
    active = jnp.asarray([False, True, False])
    out = []
    for t in range(t_prompt, toks.shape[1]):
        tok_t = jnp.asarray([0, toks[0, t], 0], jnp.int32)
        lg, kc, vc, lens = gpt2_decode_step(cfg, params, tok_t, kc, vc,
                                            lens, active, impl=impl)
        out.append(lg[1])
    return logits_p, out


@pytest.mark.parametrize("cfg,impl", [(TINY, "dense"),
                                      (TINY_FLASH, "pallas")],
                         ids=["dense", "pallas"])
def test_prefill_decode_parity_fp32(cfg, impl):
    """fp32 parity bar.  Pallas arm (the production serving path): the
    prefill runs the training forward's own ops and is BITWISE against
    it; a decode tick is a different program (single-query kernel, one
    row of every matmul), so it is held to a few float32 ulps at the
    logits' scale.  The dense arm is ulp-bounded throughout (XLA lowers
    the single-query score einsum to a different matmul shape than the
    batched training one)."""
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = _tokens(24, seed=0)[None]
    full = model.apply(params, jnp.asarray(toks), jax.random.PRNGKey(1),
                       train=False)
    logits_p, decs = _decode_chain(cfg, params, toks, 8, 32, impl)
    if impl == "pallas":
        np.testing.assert_array_equal(np.asarray(logits_p),
                                      np.asarray(full[:, :8]))
        tol = 4 * np.finfo(np.float32).eps * max(
            1.0, float(np.abs(np.asarray(full)).max()))
        for i, lg in enumerate(decs):
            np.testing.assert_allclose(lg, full[0, 8 + i], rtol=0,
                                       atol=tol)
    else:
        np.testing.assert_allclose(logits_p, full[:, :8], atol=1e-6)
        for i, lg in enumerate(decs):
            np.testing.assert_allclose(lg, full[0, 8 + i], atol=1e-5)


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_prefill_decode_parity_fp16(impl):
    cfg = TINY if impl == "dense" else TINY_FLASH
    model = GPT2Model(cfg)
    p16 = jax.tree.map(lambda a: a.astype(jnp.float16),
                       model.init(jax.random.PRNGKey(0)))
    toks = _tokens(20, seed=1)[None]
    full = model.apply(p16, jnp.asarray(toks), jax.random.PRNGKey(1),
                       train=False)
    logits_p, decs = _decode_chain(cfg, p16, toks, 6, 32, impl)
    scale = float(np.abs(np.asarray(full, np.float32)).max())
    tol = max(1e-2 * scale, 1e-2)
    np.testing.assert_allclose(np.asarray(logits_p, np.float32),
                               np.asarray(full[:, :6], np.float32),
                               atol=tol)
    for i, lg in enumerate(decs):
        np.testing.assert_allclose(np.asarray(lg, np.float32),
                                   np.asarray(full[0, 6 + i], np.float32),
                                   atol=tol)


def test_decode_parity_interpret_explicit():
    """The kernel's interpret path (forced, not auto-detected) matches
    the dense reference — the interpretable CPU fallback contract."""
    rng = np.random.RandomState(5)
    S, H, T, Dh = 3, 2, 48, 16
    q = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    k = jnp.asarray(rng.randn(S, H, T, Dh), jnp.float32)
    v = jnp.asarray(rng.randn(S, H, T, Dh), jnp.float32)
    lengths = jnp.asarray([0, 17, 48], jnp.int32)
    out = decode_attention(q, k, v, lengths, impl="pallas",
                           interpret=True)
    ref = decode_attention(q, k, v, lengths, impl="dense")
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)


# ---------------------------------------------------------------------------
# ServeEngine: greedy correctness, lifecycle, zero recompiles
# ---------------------------------------------------------------------------


def _serve_cfg(slots=4, max_seq=32, prefill=8, telemetry_path=None,
               **serving_extra):
    cfg = {"serving": {"slots": slots, "max_seq_len": max_seq,
                       "prefill_len": prefill, **serving_extra}}
    if telemetry_path is not None:
        cfg["telemetry"] = {"enabled": True,
                            "output_path": str(telemetry_path)}
    return cfg


def _greedy_reference(model, params, prompt, n):
    """Teacher-forced argmax chain through the TRAINING forward."""
    seq = list(prompt)
    out = []
    for _ in range(n):
        lg = model.apply(params, jnp.asarray([seq]),
                         jax.random.PRNGKey(0), train=False)
        tok = int(jnp.argmax(lg[0, -1]))
        out.append(tok)
        seq.append(tok)
    return out


@pytest.mark.parametrize("cfg", [TINY, TINY_FLASH], ids=["dense", "flash"])
def test_serve_greedy_matches_training_forward(cfg):
    model = GPT2Model(cfg)
    eng = ServeEngine(model, _serve_cfg())
    prompts = [list(_tokens(int(n), seed=i))
               for i, n in enumerate([3, 7, 1, 5, 8, 2])]
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert r.error is None
        assert r.finish_reason == "length"
        assert r.tokens == _greedy_reference(model, eng.params, p, 5)
    eng.close()


def test_serve_mixed_load_zero_recompiles(tmp_path):
    """THE acceptance bar: one compiled decode program survives an
    arbitrary request mix — varying prompt lengths, generation lengths,
    admissions and evictions interleaved — with zero recompiles,
    asserted via recompiles_total{program=serve_decode}."""
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(
        slots=3, telemetry_path=tmp_path))
    rng = np.random.default_rng(7)
    reqs = []
    for wave in range(3):
        for i in range(5):
            reqs.append(eng.submit(
                list(_tokens(int(rng.integers(1, 8)), seed=100 * wave + i)),
                max_new_tokens=int(rng.integers(1, 9))))
        eng.run_until_idle()
    assert all(r.error is None for r in reqs)
    eng.telemetry.compile_monitor.sample()
    reg = eng.telemetry.registry
    assert reg.counter("recompiles_total").value(program="serve_decode") == 0
    assert reg.counter("recompiles_total").value(program="serve_prefill") == 0
    assert eng._decode_fn._cache_size() == 1
    assert reg.counter("serve_requests_total").value() == len(reqs)
    eng.close()


def test_serve_slot_lifecycle_reasons():
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(slots=2, max_seq=16, prefill=8))
    # length: budget exhausts
    r_len = eng.submit([1, 2, 3], max_new_tokens=2)
    # eos: pick the greedy chain's 2nd token as the eos id
    chain = _greedy_reference(model, eng.params, [5, 6], 4)
    r_eos = eng.submit([5, 6], max_new_tokens=10, eos_id=chain[1])
    # kv_capacity: prompt 8 + decode hits max_seq_len=16 before the
    # 100-token budget
    r_cap = eng.submit(list(_tokens(8, seed=3)), max_new_tokens=100)
    eng.run_until_idle()
    assert r_len.finish_reason == "length" and len(r_len.tokens) == 2
    assert r_eos.finish_reason == "eos"
    # truncated at the FIRST greedy occurrence of the eos id
    stop = chain.index(chain[1]) + 1
    assert r_eos.tokens == chain[:stop]
    assert r_cap.finish_reason == "kv_capacity"
    # prompt(8) fills 8 rows; decode ticks append until the slot is full
    assert len(r_cap.tokens) == 16 - 8 + 1
    eng.close()


def test_serve_slot_reuse_is_isolated():
    """A slot's stale KV rows from an evicted request must not leak
    into the next request served from that slot (masked by length)."""
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(slots=1))
    p1, p2 = list(_tokens(7, seed=11)), list(_tokens(4, seed=12))
    r1 = eng.submit(p1, max_new_tokens=6)
    r2 = eng.submit(p2, max_new_tokens=6)
    eng.run_until_idle()
    assert r1.tokens == _greedy_reference(model, eng.params, p1, 6)
    assert r2.tokens == _greedy_reference(model, eng.params, p2, 6)
    eng.close()


def test_serve_continuous_admission_mid_flight():
    """Continuous batching: a request submitted while others are
    mid-decode is admitted into a free slot on the next tick without
    waiting for the batch to drain."""
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(slots=2))
    r1 = eng.submit(list(_tokens(3, seed=21)), max_new_tokens=8)
    r2 = eng.submit(list(_tokens(5, seed=22)), max_new_tokens=8)
    for _ in range(3):
        eng.step()
    r3 = eng.submit(list(_tokens(2, seed=23)), max_new_tokens=3)
    # both slots busy: r3 waits queued until one finishes, then decodes
    eng.run_until_idle()
    for r in (r1, r2, r3):
        assert r.error is None
        assert r.tokens == _greedy_reference(
            model, eng.params, r.prompt, len(r.tokens))
    eng.close()


def test_serve_submit_validation():
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(prefill=4))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([])
    with pytest.raises(ValueError, match="prefill_len"):
        eng.submit([1, 2, 3, 4, 5])
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1], max_new_tokens=0)
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit([1])


def test_serve_close_fails_queued_requests():
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(slots=1))
    reqs = [eng.submit([1, 2], max_new_tokens=4) for _ in range(3)]
    eng.close()
    for r in reqs:
        assert r.done.is_set()
        with pytest.raises(RuntimeError, match="closed"):
            r.result(timeout=0)
    # idempotent
    eng.close()


# ---------------------------------------------------------------------------
# TP / DP sharded serving
# ---------------------------------------------------------------------------


def test_serve_tp_dp_sharded_matches_single_device():
    model = GPT2Model(TINY_FLASH)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(5, seed=i)) for i in range(4)]

    def run(mesh):
        eng = ServeEngine(model, _serve_cfg(), mesh=mesh, params=params)
        rs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_idle()
        toks = [r.tokens for r in rs]
        eng.close()
        return toks

    base = run(None)
    sharded = run(build_mesh(dp=2, tp=2, devices=jax.devices()[:4]))
    assert base == sharded


def test_cache_mesh_validation():
    spec = KVCacheSpec(layers=2, slots=3, heads=4, max_len=8, head_dim=8)
    with pytest.raises(ValueError, match="slots"):
        validate_cache_mesh(build_mesh(dp=2, devices=jax.devices()[:2]),
                            spec)
    spec2 = KVCacheSpec(layers=2, slots=4, heads=3, max_len=8, head_dim=8)
    with pytest.raises(ValueError, match="model axis"):
        validate_cache_mesh(
            build_mesh(dp=1, tp=2, devices=jax.devices()[:2]), spec2)
    with pytest.raises(ValueError, match="pipe"):
        validate_cache_mesh(
            build_mesh(pp=2, dp=1, devices=jax.devices()[:2]),
            KVCacheSpec(layers=2, slots=4, heads=4, max_len=8, head_dim=8))


# ---------------------------------------------------------------------------
# chaos: the serve stage rides the shared fault plane
# ---------------------------------------------------------------------------


def test_serve_transient_fault_absorbed(monkeypatch):
    monkeypatch.setenv("DS_STAGE_FAULT", "serve:admit:1,serve:step:2")
    reset_fault_injection()
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(slots=2))
    r = eng.submit(list(_tokens(3, seed=31)), max_new_tokens=4)
    eng.run_until_idle()
    assert r.error is None
    assert r.tokens == _greedy_reference(model, eng.params, r.prompt, 4)
    assert eng.stage.failures == 2
    assert not eng.stage.degraded
    eng.close()


def test_serve_sticky_fault_degrades_and_keeps_serving(monkeypatch):
    """Budget-exhausting sticky faults degrade the serve stage to its
    chaos-free direct path with ONE warning — the run completes with
    correct tokens instead of dying."""
    monkeypatch.setenv("DS_STAGE_FAULT", "serve:step:1+")
    reset_fault_injection()
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(slots=2))
    r = eng.submit(list(_tokens(4, seed=32)), max_new_tokens=5)
    eng.run_until_idle()
    assert eng.stage.degraded
    assert r.error is None
    assert r.tokens == _greedy_reference(model, eng.params, r.prompt, 5)
    eng.close()


def test_serve_injected_delay_applies(monkeypatch):
    monkeypatch.setenv("DS_STAGE_DELAY_S", "serve:0.05")
    import time
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(slots=1))
    eng.submit([1, 2], max_new_tokens=2)
    t0 = time.perf_counter()
    eng.run_until_idle()
    # admit + >=1 decode tick each pay the injected delay
    assert time.perf_counter() - t0 >= 0.1
    eng.close()


# ---------------------------------------------------------------------------
# config block
# ---------------------------------------------------------------------------


def test_serving_config_validation():
    from deepspeed_tpu.config.config import DeepSpeedServingConfig
    ok = DeepSpeedServingConfig({"serving": {"slots": 2}})
    assert ok.slots == 2 and ok.decode_impl == "auto"
    with pytest.raises(DeepSpeedConfigError, match="slots"):
        DeepSpeedServingConfig({"serving": {"slots": 0}})
    with pytest.raises(DeepSpeedConfigError, match="prefill_len"):
        DeepSpeedServingConfig({"serving": {"max_seq_len": 8,
                                            "prefill_len": 16}})
    with pytest.raises(DeepSpeedConfigError, match="decode_impl"):
        DeepSpeedServingConfig({"serving": {"decode_impl": "cuda"}})
    with pytest.raises(DeepSpeedConfigError, match="eos_id"):
        DeepSpeedServingConfig({"serving": {"eos_id": "</s>"}})
    with pytest.raises(DeepSpeedConfigError, match="queue_capacity"):
        DeepSpeedServingConfig({"serving": {"queue_capacity": True}})


def test_serving_block_parses_in_full_config():
    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "serving": {"slots": 16}}, world_size=8)
    assert cfg.serving_config.slots == 16


# ---------------------------------------------------------------------------
# telemetry: summarize gains a serving row
# ---------------------------------------------------------------------------


def test_serving_scalars_flow_to_summarize(tmp_path, capsys):
    from deepspeed_tpu.telemetry.cli import summarize
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(
        slots=2, telemetry_path=tmp_path, flush_interval_ticks=2))
    for i in range(3):
        eng.submit(list(_tokens(3, seed=40 + i)), max_new_tokens=4)
    eng.run_until_idle()
    eng.close()
    events = os.path.join(str(tmp_path), "events.jsonl")
    syncs = [json.loads(l) for l in open(events)
             if json.loads(l).get("kind") == "sync"]
    assert any("serve_tokens_per_s" in (s.get("scalars") or {})
               for s in syncs)
    report = summarize(events)
    out = capsys.readouterr().out
    assert report["serve_tokens_per_s"] is not None
    assert report["serve_token_p50_s"] is not None
    assert "serving" in out


# ---------------------------------------------------------------------------
# scheduler unit contracts
# ---------------------------------------------------------------------------


def test_slot_scheduler_contracts():
    s = SlotScheduler(2)
    r1 = Request(rid=1, prompt=[1], max_new_tokens=3)
    r2 = Request(rid=2, prompt=[2], max_new_tokens=3)
    a = s.admit(r1)
    b = s.admit(r2)
    assert {a, b} == {0, 1} and not s.has_free()
    rel = s.release(a, "eos")
    assert rel is r1 and rel.finish_reason == "eos" and s.has_free()
    # finish reasons
    r = Request(rid=3, prompt=[1], max_new_tokens=2, eos_id=7)
    r.tokens = [7]
    r.kv_len = 4
    assert s.finish_reason(r, 7, 16) == "eos"
    r.eos_id = None
    r.tokens = [1, 2]
    assert s.finish_reason(r, 1, 16) == "length"
    r.tokens = [1]
    r.kv_len = 16
    assert s.finish_reason(r, 1, 16) == "kv_capacity"
    r.kv_len = 4
    assert s.finish_reason(r, 1, 16) is None


def test_kv_cache_shard_roundtrip():
    spec = KVCacheSpec(layers=2, slots=8, heads=4, max_len=8, head_dim=4)
    mesh = build_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    cache = shard_cache(init_cache(spec), mesh)
    assert cache["k"].shape == (2, 8, 4, 8, 4)
    assert (np.asarray(cache["lengths"]) == 0).all()
    assert spec.bytes == 2 * 2 * 8 * 4 * 8 * 4 * 4


# ---------------------------------------------------------------------------
# continuous batching: the same streams in fewer ticks
# ---------------------------------------------------------------------------


def test_continuous_batching_same_streams_in_fewer_ticks():
    """A pool of slots emits exactly what one request at a time emits
    and takes fewer engine ticks to do it: with two slots every decode
    tick carries two requests."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(3, seed=40 + i)) for i in range(4)]

    def serve(slots):
        eng = ServeEngine(model, _serve_cfg(slots=slots), params=params)
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
        assert all(r.error is None for r in reqs)
        ticks = eng._ticks
        eng.close()
        return [r.tokens for r in reqs], ticks

    one_at_a_time, ticks_one = serve(1)
    pooled, ticks_pool = serve(2)
    assert pooled == one_at_a_time
    assert all(len(t) == 5 for t in pooled)
    assert ticks_pool * 2 <= ticks_one, (ticks_pool, ticks_one)
