"""Multi-tenant LoRA serving (docs/serving.md "multi-tenant serving"):

* adapter pool unit tests — refcount/LRU-eviction/double-free, the
  park-on-dry (None, side-effect-free) contract, registry capacity and
  shape validation, deterministic synthesis,
* `DS_STAGE_FAULT=adapter_fetch:fetch:...` chaos — transient fetch
  faults retry invisibly, a sticky fault degrades the stage to the
  synchronous copy and the run completes BITWISE-identical,
* engine parity bars — heterogeneous single-tenant streams == the
  dense-merged (`W + scale·BA`) engine, the zero-tenant arm ==
  lora-off token for token, int8-base + fp16-adapter composition,
  dp2×tp2 == single device,
* the zero-recompile contract over waves mixing >= 8 tenants
  (`recompiles_total{program=serve_decode}` == 0, one cache entry),
* park-on-adapter-dry admission ordering,
* cross-tenant prefix-cache isolation — tenant A never hits tenant
  B's pages; the no-lora namespace stays the pre-change digest chain,
* fleet tenant affinity (bounded by ADAPTER_AFFINITY_SLACK, never
  starving JSQ) + the real-subprocess replica-death reroute e2e,
* config validation and the serve_adapter_* telemetry -> summarize
  "adapters" row.
"""
import os
import time

import numpy as np
import jax
import pytest

from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.config.config import DeepSpeedServingConfig
from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.inference.adapters import (AdapterPool,
                                              AdapterRegistry,
                                              adapter_param_shapes,
                                              merge_adapter,
                                              synth_adapter,
                                              zero_adapter)
from deepspeed_tpu.inference.scheduler import PagePool, PrefixCache
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.runtime.stages import Stage, reset_fault_injection

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


def _lora_cfg(slots=4, hbm_slots=3, rank=4, alpha=8.0,
              targets=("qkv_w", "out_w", "fc_w", "proj_w"),
              telemetry_path=None, **serving_extra):
    cfg = {"serving": {"slots": slots, "max_seq_len": 32,
                       "prefill_len": 24, "page_len": 8, "pages": 40,
                       "lora": {"rank": rank, "alpha": alpha,
                                "hbm_adapter_slots": hbm_slots,
                                "max_adapters": 32,
                                "targets": list(targets)},
                       **serving_extra}}
    if telemetry_path is not None:
        cfg["telemetry"] = {"enabled": True,
                            "output_path": str(telemetry_path)}
    return cfg


def _base_cfg(slots=4, **serving_extra):
    return {"serving": {"slots": slots, "max_seq_len": 32,
                        "prefill_len": 24, "page_len": 8, "pages": 40,
                        **serving_extra}}


_MODEL = None


def _model_params():
    """One shared tiny model across the engine tests (init is the
    slow part; params are read-only)."""
    global _MODEL
    if _MODEL is None:
        model = GPT2Model(TINY)
        _MODEL = (model, model.init(jax.random.PRNGKey(0)))
    return _MODEL


# ---------------------------------------------------------------------------
# adapter pool: refcount / LRU / park-on-dry / double-free
# ---------------------------------------------------------------------------


def _small_pool(slots=2, max_adapters=16):
    shapes = adapter_param_shapes(2, 8, 2, ("qkv_w",))
    reg = AdapterRegistry(max_adapters, shapes)
    uploads = []
    pool = AdapterPool(slots, reg,
                       lambda slot, w: uploads.append(slot))
    return pool, reg, uploads


def test_pool_refcount_hit_fault_eviction_lru():
    pool, _, uploads = _small_pool(slots=2)
    # cold acquire: fault + device upload into slot 1
    assert pool.acquire(7) == 1
    assert (pool.faults, pool.hits, uploads) == (1, 0, [1])
    # second acquire of a resident adapter: hit, refcount 2, no upload
    assert pool.acquire(7) == 1
    assert (pool.faults, pool.hits, len(uploads)) == (1, 1, 1)
    assert pool.refs(7) == 2
    # releases drop to 0: adapter stays RESIDENT (cold, evictable)
    pool.release(7)
    pool.release(7)
    assert pool.refs(7) == 0 and pool.resident() == 1
    # the next acquire is a free hit
    assert pool.acquire(7) == 1 and pool.hits == 2
    pool.release(7)
    # fill the other slot, then a third tenant must LRU-evict the
    # OLDEST cold resident (7 went cold before 8)
    assert pool.acquire(8) == 2
    pool.release(8)
    assert pool.acquire(9) == 1           # evicted 7, reused its slot
    assert pool.evictions == 1
    assert pool.slot_of(7) is None and pool.slot_of(8) == 2
    assert pool.hot_ids() == [8, 9]


def test_pool_slot0_zero_adapter_never_refcounted():
    pool, _, uploads = _small_pool()
    assert pool.acquire(0) == 0
    pool.release(0)
    assert (pool.resident(), pool.hits, pool.faults) == (0, 0, 0)
    assert not uploads


def test_pool_park_on_dry_is_side_effect_free():
    pool, _, uploads = _small_pool(slots=2)
    assert pool.acquire(1) == 1 and pool.acquire(2) == 2
    before = (list(pool.free), dict(pool._slot_of), pool.hits,
              pool.faults, pool.evictions, len(uploads))
    # every slot pinned: acquire returns None and changes NOTHING
    assert pool.acquire(3) is None
    after = (list(pool.free), dict(pool._slot_of), pool.hits,
             pool.faults, pool.evictions, len(uploads))
    assert before == after
    # a release turns the dry pool back into an evictable one
    pool.release(1)
    assert pool.acquire(3) is not None
    assert pool.evictions == 1


def test_pool_double_free_asserts():
    pool, _, _ = _small_pool()
    pool.acquire(5)
    pool.release(5)
    with pytest.raises(AssertionError, match="below zero"):
        pool.release(5)
    with pytest.raises(AssertionError, match="not resident"):
        pool.release(6)


def test_registry_capacity_shapes_and_synthesis():
    shapes = adapter_param_shapes(2, 8, 2, ("qkv_w", "fc_w"))
    assert shapes["qkv_w"] == ((2, 8, 2), (2, 2, 3, 8))
    assert shapes["fc_w"] == ((2, 8, 2), (2, 2, 32))
    with pytest.raises(ValueError, match="unknown lora target"):
        adapter_param_shapes(2, 8, 2, ("qkv_w", "nope"))
    reg = AdapterRegistry(2, shapes)
    reg.get(1)
    reg.get(2)
    with pytest.raises(RuntimeError, match="registry full"):
        reg.get(3)
    # re-touching a known adapter is fine at capacity
    assert 1 in reg and len(reg) == 2
    with pytest.raises(ValueError, match="shapes"):
        reg.register(1, {"qkv_w": (np.zeros((1, 8, 2), np.float32),
                                   np.zeros((2, 2, 3, 8), np.float32))})
    with pytest.raises(ValueError, match="positive"):
        synth_adapter(0, shapes)
    # deterministic synthesis: same id -> byte-identical weights
    w1, w2 = synth_adapter(9, shapes), synth_adapter(9, shapes)
    for t in shapes:
        assert np.array_equal(w1[t][0], w2[t][0])
        assert np.array_equal(w1[t][1], w2[t][1])
    z = zero_adapter(shapes)
    assert all(not z[t][0].any() and not z[t][1].any() for t in shapes)


def test_pool_transient_fetch_fault_retries(monkeypatch):
    """One injected fetch fault is absorbed by the stage budget: the
    acquire succeeds, nothing degrades, the pool bookkeeping is the
    no-fault bookkeeping."""
    monkeypatch.setenv("DS_STAGE_FAULT", "adapter_fetch:fetch:1")
    reset_fault_injection()
    pool, _, uploads = _small_pool()
    assert pool.acquire(4) == 1
    assert not pool.stage.degraded
    assert pool.stage.failures == 1
    assert pool.resident() == 1 and pool.faults == 1


def test_pool_sticky_fetch_fault_degrades_and_recovers(monkeypatch):
    """A sticky fetch fault exhausts the budget: the stage degrades to
    the synchronous copy (ONE loud fallback) and every subsequent cold
    fetch still lands — latency-only, the adapter bytes are
    identical."""
    monkeypatch.setenv("DS_STAGE_FAULT", "adapter_fetch:fetch:1+")
    reset_fault_injection()
    shapes = adapter_param_shapes(2, 8, 2, ("qkv_w",))
    reg = AdapterRegistry(16, shapes)
    uploads = []
    pool = AdapterPool(2, reg, lambda slot, w: uploads.append((slot, w)),
                       stage=Stage("adapter_fetch", max_failures=2))
    assert pool.acquire(4) == 1
    assert pool.stage.degraded
    # degraded = injection plane bypassed: the next cold tenant works
    assert pool.acquire(5) == 2
    assert [s for s, _ in uploads] == [1, 2]
    # the degraded copy carried the REAL registry weights
    want = reg.get(4)["qkv_w"][0]
    assert np.array_equal(uploads[0][1]["qkv_w"][0], want)


def test_pool_nontransient_fetch_error_releases_slot():
    """A non-transient fetch failure (poison class) must not leak the
    slot it grabbed."""
    shapes = adapter_param_shapes(2, 8, 2, ("qkv_w",))
    reg = AdapterRegistry(16, shapes)

    def boom(slot, w):
        raise RuntimeError("device copy failed")

    pool = AdapterPool(2, reg, boom)
    with pytest.raises(RuntimeError, match="device copy failed"):
        pool.acquire(3)
    assert sorted(pool.free) == [1, 2]
    assert pool.resident() == 0 and pool.slot_of(3) is None


# ---------------------------------------------------------------------------
# engine parity bars
# ---------------------------------------------------------------------------


def _run_streams(cfg, prompts, tenants, gen=6, params=None, model=None):
    if model is None:
        model, shared = _model_params()
        params = shared if params is None else params
    eng = ServeEngine(model, cfg, params=params)
    rs = [eng.submit(p, max_new_tokens=gen, adapter_id=t)
          for p, t in zip(prompts, tenants)]
    eng.run_until_idle()
    assert all(r.error is None for r in rs), \
        [repr(r.error) for r in rs if r.error]
    toks = [list(r.tokens) for r in rs]
    stats = {"decode_programs": eng._decode_fn._cache_size(),
             "prefill_programs": eng._prefill_fn._cache_size(),
             "pool": eng.adapters if eng.lora else None,
             "engine": eng}
    eng.close()
    return toks, stats


def test_heterogeneous_tenants_match_dense_merged():
    """THE parity bar: each tenant's stream out of one heterogeneous
    batch (tenants resolved per-slot through the traced adapter table)
    equals a dense-merged ``W + scale·BA`` engine serving that tenant
    alone — and the whole mix rode ONE compiled decode program."""
    model, params = _model_params()
    prompts = [list(_tokens(n, seed=10 + i))
               for i, n in enumerate([5, 9, 13, 7, 11, 6])]
    tenants = [0, 1, 2, 3, 1, 4]
    toks, stats = _run_streams(_lora_cfg(), prompts, tenants)
    assert stats["decode_programs"] == 1
    assert stats["prefill_programs"] == 1
    eng_scale = 8.0 / 4  # alpha / rank of _lora_cfg
    shapes = adapter_param_shapes(
        TINY.n_layer, TINY.d_model, 4,
        ("qkv_w", "out_w", "fc_w", "proj_w"))
    for tid in (0, 1, 4):
        mparams = params if tid == 0 else merge_adapter(
            params, synth_adapter(tid, shapes), eng_scale)
        meng = ServeEngine(model, _base_cfg(), params=mparams)
        refs = [meng.submit(p, max_new_tokens=6)
                for p, t in zip(prompts, tenants) if t == tid]
        meng.run_until_idle()
        got = [s for s, t in zip(toks, tenants) if t == tid]
        assert [list(r.tokens) for r in refs] == got, tid
        meng.close()


def test_zero_tenant_arm_matches_lora_off():
    """lora ON + every request tenant-0 (the all-zero slot-0 adapter)
    emits the SAME streams as the lora-off engine — the no-tenant arm
    computes a mathematically-zero delta through the shared program."""
    prompts = [list(_tokens(n, seed=20 + i))
               for i, n in enumerate([5, 9, 7])]
    base, _ = _run_streams(_base_cfg(), prompts, [0, 0, 0])
    zero, _ = _run_streams(_lora_cfg(), prompts, [0, 0, 0])
    assert zero == base


def test_lora_off_rejects_adapter_ids():
    model, params = _model_params()
    eng = ServeEngine(model, _base_cfg(), params=params)
    with pytest.raises(ValueError, match="lora"):
        eng.submit(list(_tokens(5)), max_new_tokens=2, adapter_id=3)
    with pytest.raises(ValueError, match="adapter"):
        eng.submit(list(_tokens(5)), max_new_tokens=2, adapter_id=-1)
    eng.close()
    leng = ServeEngine(model, _lora_cfg(), params=params)
    with pytest.raises(ValueError, match="adapter"):
        leng.submit(list(_tokens(5)), max_new_tokens=2, adapter_id=-2)
    leng.close()


def test_int8_base_fp16_adapter_composition():
    """Quantized base weights + fp adapters compose: the tenant-0 arm
    stays bitwise the int8-no-lora engine, a real tenant's delta
    lands, and the mix still rides one decode program."""
    quant = {"weights": "int8", "kv": "int8"}
    prompts = [list(_tokens(n, seed=30 + i))
               for i, n in enumerate([5, 9, 7, 6])]
    base, _ = _run_streams(_base_cfg(quantization=quant), prompts,
                           [0] * 4)
    mixed, stats = _run_streams(_lora_cfg(quantization=quant), prompts,
                                [0, 3, 0, 3])
    assert stats["decode_programs"] == 1
    assert [mixed[0], mixed[2]] == [base[0], base[2]]
    # the adapter really applied: at a large alpha the delta is big
    # enough to flip greedy argmaxes on the tiny model
    solo, _ = _run_streams(_lora_cfg(quantization=quant, alpha=512.0),
                           prompts, [3, 3, 3, 3])
    assert solo != base


def test_lora_dp2_tp2_matches_single_device():
    """The sharding story: adapter pools ride the same Megatron splits
    as their base matmuls — dp2×tp2 tenant streams == single device."""
    from deepspeed_tpu.parallel import build_mesh
    model = GPT2Model(TINY_FLASH)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(5, seed=40 + i)) for i in range(4)]
    tenants = [0, 1, 2, 1]

    def run(mesh):
        eng = ServeEngine(model, _lora_cfg(), mesh=mesh, params=params)
        rs = [eng.submit(p, max_new_tokens=6, adapter_id=t)
              for p, t in zip(prompts, tenants)]
        eng.run_until_idle()
        assert all(r.error is None for r in rs)
        toks = [r.tokens for r in rs]
        eng.close()
        return toks

    base = run(None)
    sharded = run(build_mesh(dp=2, tp=2, devices=jax.devices()[:4]))
    assert base == sharded


# ---------------------------------------------------------------------------
# zero-recompile + park-on-dry + chaos through the engine
# ---------------------------------------------------------------------------


def test_zero_recompiles_across_eight_tenant_waves(tmp_path):
    """Waves mixing >= 8 distinct tenants (cold faults, hits, and
    evictions included) never grow the compiled-program caches:
    ``recompiles_total{program=serve_decode}`` stays 0."""
    model, params = _model_params()
    eng = ServeEngine(model, _lora_cfg(
        hbm_slots=3, telemetry_path=tmp_path), params=params)
    tenants = [1, 2, 3, 4, 5, 6, 7, 8, 3, 1, 0, 5]
    for wave in range(3):
        rs = [eng.submit(list(_tokens(5 + (i % 3), seed=50 + i)),
                         max_new_tokens=4, adapter_id=t)
              for i, t in enumerate(tenants)]
        eng.run_until_idle()
        assert all(r.error is None for r in rs)
    assert eng._decode_fn._cache_size() == 1
    assert eng._prefill_fn._cache_size() == 1
    reg = eng.telemetry.registry
    assert reg.counter("recompiles_total").value(
        program="serve_decode") == 0
    assert reg.counter("recompiles_total").value(program="serve_prefill") == 0
    assert eng.adapters.evictions > 0     # the waves churned the pool
    eng.close()


def test_park_on_adapter_dry_admits_in_order():
    """Every HBM slot pinned by long generations: later requests PARK
    (no error, no slot held) and admit oldest-first as pins release —
    the page-pool backpressure contract applied to adapters."""
    model, params = _model_params()
    eng = ServeEngine(model, _lora_cfg(hbm_slots=2, slots=6),
                      params=params)
    hold = [eng.submit(list(_tokens(5, seed=60 + i)),
                       max_new_tokens=16, adapter_id=i + 1)
            for i in range(2)]
    parked = [eng.submit(list(_tokens(5, seed=70 + i)),
                         max_new_tokens=3, adapter_id=8 + i)
              for i in range(2)]
    eng.run_until_idle()
    for r in hold + parked:
        assert r.error is None and len(r.tokens) > 0
    # FIFO under backpressure: the first parked tenant started first
    assert parked[0].token_times[0] <= parked[1].token_times[0]
    assert eng.adapters.evictions >= 1
    eng.close()


def test_engine_adapter_fetch_chaos_streams_bitwise(monkeypatch):
    """Injected adapter-fetch faults (transient AND sticky-degraded)
    change latency, never tokens: the chaos streams equal the
    no-chaos streams token for token."""
    prompts = [list(_tokens(n, seed=80 + i))
               for i, n in enumerate([5, 9, 7, 6])]
    tenants = [1, 2, 1, 3]
    clean, _ = _run_streams(_lora_cfg(), prompts, tenants)

    monkeypatch.setenv("DS_STAGE_FAULT", "adapter_fetch:fetch:2")
    reset_fault_injection()
    transient, tstats = _run_streams(_lora_cfg(), prompts, tenants)
    assert transient == clean

    monkeypatch.setenv("DS_STAGE_FAULT", "adapter_fetch:fetch:1+")
    reset_fault_injection()
    model, params = _model_params()
    eng = ServeEngine(model, _lora_cfg(), params=params)
    rs = [eng.submit(p, max_new_tokens=6, adapter_id=t)
          for p, t in zip(prompts, tenants)]
    eng.run_until_idle()
    assert all(r.error is None for r in rs)
    assert [list(r.tokens) for r in rs] == clean
    assert eng.adapter_stage.degraded   # budget burned, copy degraded
    eng.close()


def test_adapter_telemetry_flows_to_summarize(tmp_path, capsys):
    from deepspeed_tpu.telemetry.cli import summarize
    model, params = _model_params()
    eng = ServeEngine(model, _lora_cfg(
        hbm_slots=2, telemetry_path=tmp_path,
        flush_interval_ticks=2), params=params)
    for i, t in enumerate([1, 2, 3, 1]):
        eng.submit(list(_tokens(6, seed=90 + i)), max_new_tokens=4,
                   adapter_id=t)
    eng.run_until_idle()
    pool = eng.adapters
    want = (pool.resident(), pool.hits, pool.faults, pool.evictions)
    eng.close()
    rep = summarize(os.path.join(str(tmp_path), "events.jsonl"))
    assert rep["serve_adapters_resident"] == want[0]
    assert rep["serve_adapter_hits_total"] == want[1]
    assert rep["serve_adapter_faults_total"] == want[2]
    assert rep["serve_adapter_evictions_total"] == want[3]
    assert rep["serve_adapter_bytes"] > 0
    out = capsys.readouterr().out
    assert "adapters" in out and "faults" in out


# ---------------------------------------------------------------------------
# cross-tenant prefix-cache isolation
# ---------------------------------------------------------------------------


def test_prefix_cache_namespaces_isolate_tenants():
    """The leakage regression, at the cache: the same prompt inserted
    under tenant A's namespace never matches under tenant B's — and
    the default namespace is the pre-change digest chain (a no-lora
    engine's hits are bitwise what they were)."""
    pool = PagePool(pages=32)
    cache = PrefixCache(4, pool)
    prompt = list(range(12))           # 2 full pages + a 3-token tail
    pages = pool.alloc(3)
    cache.insert(prompt, pages, "adapter:1")
    shared, got, _cow = cache.match(prompt, "adapter:2")
    assert (shared, got) == (0, [])
    shared, got, _cow = cache.match(prompt)     # no-lora namespace
    assert (shared, got) == (0, [])
    shared, got, cow = cache.match(prompt, "adapter:1")
    assert (shared, got, cow) == (11, pages, True)
    cache.release(got)
    # default-namespace insert/match round-trips exactly as before
    pages2 = pool.alloc(3)
    cache.insert(prompt, pages2)
    shared, got, _cow = cache.match(prompt)
    assert (shared, got) == (11, pages2)
    cache.release(got)
    # and the explicit "" spelling is the same namespace
    shared2, got2, _cow = cache.match(prompt, "")
    assert (shared2, got2) == (shared, pages2)
    cache.release(got2)


def test_engine_prefix_never_crosses_tenants():
    """Engine-level: tenant B submitting tenant A's exact prompt gets
    ZERO shared prefix pages; tenant A's own repeat still hits."""
    model, params = _model_params()
    eng = ServeEngine(model, _lora_cfg(slots=2), params=params)
    prompt = list(_tokens(16, seed=7))
    a1 = eng.submit(prompt, max_new_tokens=2, adapter_id=1)
    eng.run_until_idle()
    b = eng.submit(prompt, max_new_tokens=2, adapter_id=2)
    eng.run_until_idle()
    a2 = eng.submit(prompt, max_new_tokens=2, adapter_id=1)
    eng.run_until_idle()
    assert a1.shared_len == 0
    assert b.shared_len == 0              # the leakage bar
    assert a2.shared_len > 0              # same tenant still reuses
    # base-tenant reuse is its own namespace too
    z1 = eng.submit(prompt, max_new_tokens=2)
    eng.run_until_idle()
    z2 = eng.submit(prompt, max_new_tokens=2)
    eng.run_until_idle()
    assert z1.shared_len == 0 and z2.shared_len > 0
    eng.close()


# ---------------------------------------------------------------------------
# fleet: tenant affinity + replica-death reroute
# ---------------------------------------------------------------------------


def test_config_validation():
    cfg = DeepSpeedServingConfig({"serving": {}})
    assert cfg.lora["rank"] == 0
    on = DeepSpeedServingConfig({"serving": {
        "page_len": 8, "lora": {"rank": 4}}})
    assert on.lora["alpha"] == 16.0
    assert on.lora["hbm_adapter_slots"] == 8
    assert on.lora["targets"] == ("qkv_w", "out_w")
    with pytest.raises(DeepSpeedConfigError, match="page_len"):
        DeepSpeedServingConfig({"serving": {"lora": {"rank": 4}}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedServingConfig({"serving": {
            "page_len": 8, "lora": {"rank": -1}}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedServingConfig({"serving": {
            "page_len": 8, "lora": {"rank": 4, "targets": ["nope"]}}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedServingConfig({"serving": {
            "page_len": 8, "lora": {"rank": 4, "bogus": 1}}})


def test_fleet_affinity_bounded_by_slack(tmp_path):
    """Tenant affinity picks the replica advertising the adapter hot —
    but only within ADAPTER_AFFINITY_SLACK of the JSQ minimum, so a
    hot tenant can never starve the queue balance."""
    from deepspeed_tpu.inference.fleet import FleetRouter
    from deepspeed_tpu.telemetry.heartbeat import HeartbeatWriter
    from test_fleet import Fleet

    fl = Fleet(tmp_path, {"replicas": 2, "max_replicas": 2}).start()
    try:
        router = fl.router
        assert FleetRouter.ADAPTER_AFFINITY_SLACK == 2
        # replica 1 advertises adapter 7 resident: affinity overrides
        # the lowest-id JSQ tie-break
        w1 = HeartbeatWriter(router.fleet_dir, process_index=1)
        w1.beat(1, extra={"adapters_hot": [7]})
        router._last_beats_read = 0.0
        router.poll(0.01)
        assert router._pick_replica(adapter_id=7).id == 1
        assert router._pick_replica().id == 0          # plain JSQ tie
        assert router._pick_replica(adapter_id=9).id == 0  # nobody hot
        # pile load beyond the slack onto the hot replica: JSQ wins
        w1.beat(2, extra={"adapters_hot": [7],
                          "serve_queue_depth": 3,
                          "serve_active_slots": 0})
        router._last_beats_read = 0.0
        router.poll(0.01)
        assert router._pick_replica(adapter_id=7).id == 0
        # ...and within the slack, affinity still wins
        w1.beat(3, extra={"adapters_hot": [7],
                          "serve_queue_depth": 2,
                          "serve_active_slots": 0})
        router._last_beats_read = 0.0
        router.poll(0.01)
        assert router._pick_replica(adapter_id=7).id == 1
    finally:
        fl.router.close()


def _lora_fleet_config(replicas, **fleet_over):
    return {
        "serving": {"slots": 4, "max_seq_len": 64, "prefill_len": 8,
                    "queue_capacity": 256, "flush_interval_ticks": 5,
                    "page_len": 8, "pages": 64,
                    "lora": {"rank": 4, "alpha": 8.0,
                             "hbm_adapter_slots": 4,
                             "max_adapters": 32}},
        "fleet": {"replicas": replicas, "min_replicas": 1,
                  "max_replicas": max(replicas, 2),
                  "slo_p99_s": 30.0, "scale_up_window_s": 5.0,
                  "scale_down_window_s": 600.0,
                  "spawn_timeout_s": 120.0, "backoff_base_s": 0.2,
                  "heartbeat_timeout_s": 60.0, **fleet_over},
        "fleet_model": {"vocab_size": 128, "n_positions": 64,
                        "d_model": 32, "n_layer": 2, "n_head": 4,
                        "attn_impl": "dense", "seed": 0},
    }


def test_e2e_lora_fleet_replica_death_reroutes(tmp_path, monkeypatch):
    """Real subprocess fleet, tenants spread across replicas: killing
    one replica re-routes its queued tenant requests to a survivor
    that synthesizes the SAME adapter weights locally (no adapter
    bytes on the wire) — zero queued-but-unstarted requests lost,
    survivors' streams intact, and the survivor's heartbeat ends up
    advertising the re-routed tenants hot."""
    from deepspeed_tpu.inference.fleet import (FleetRouter,
                                               ReplicaFailure)
    monkeypatch.setenv("DS_STAGE_DELAY_S", "serve:0.05")
    reset_fault_injection()
    cfg = _lora_fleet_config(2, slo_p99_s=1e9)
    d = str(tmp_path / "fleet")
    router = FleetRouter(cfg, fleet_dir=d)
    rng = np.random.default_rng(3)
    try:
        router.start()
        initial = sorted(router.replicas)
        reqs = [router.submit(
            [int(t) for t in rng.integers(0, 128, (5,))],
            max_new_tokens=8, adapter_id=1 + (i % 3))
            for i in range(16)]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            router.poll(0.02)
            started_by = {rid: any(r.started and r.replica == rid
                                   for r in reqs)
                          for rid in initial}
            if all(started_by.values()):
                break
        assert all(started_by.values()), "replicas never streamed"
        victim = max(router.replicas.values(),
                     key=lambda r: len(r.outstanding)).id
        router.kill_replica(victim)
        router.run_until_idle(max_s=120)
        failed = [r for r in reqs if r.error is not None]
        assert all(r.started for r in failed)   # zero unstarted lost
        assert all(isinstance(r.error, ReplicaFailure) for r in failed)
        survivors = [r for r in reqs if r.error is None]
        assert survivors and all(len(r.tokens) == 8 for r in survivors)
        assert sum(r.failovers for r in reqs) > 0
        # the surviving replica advertises the tenants it now serves
        router._last_beats_read = 0.0
        router.poll(0.05)
        hot = [set(b.get("adapters_hot") or [])
               for b in router._beats.values()]
        assert any(h & {1, 2, 3} for h in hot), router._beats
    finally:
        router.close()
