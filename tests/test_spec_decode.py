"""Speculative decoding (docs/serving.md): multi-query kernel
differentials, widened-verify parity against sequential decode, the
acceptance math (greedy + rejection sampling), the engine-level parity
bar (speculative stream == non-speculative stream, greedy, at every k,
both KV layouts, dp1 and dp2×tp2, zero recompiles), accepted-length-
variance scheduler semantics, and telemetry flow.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.config.config import DeepSpeedServingConfig
from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.inference.speculative import (greedy_accept,
                                                 rejection_sample_accept,
                                                 select_next_token)
from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2Model,
                                       gpt2_decode_step, gpt2_prefill,
                                       gpt2_verify_step)
from deepspeed_tpu.ops.pallas.decode_attention import (
    decode_attention_multi, decode_attention_multi_reference,
    decode_attention_paged_multi, decode_attention_reference,
    paged_gather)
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.runtime.stages import reset_fault_injection

TINY = GPT2Config(vocab_size=128, n_positions=64, d_model=32, n_layer=2,
                  n_head=4, remat=None, attn_impl="dense")
DRAFT_BLOCK = {"d_model": 32, "n_layer": 2, "n_head": 4}

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
# multi-query kernel differentials
# ---------------------------------------------------------------------------


def _multi_case(S=3, H=2, T=128, Dh=32, W=5, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(S, H, W, Dh), jnp.float32)
    k = jnp.asarray(rng.randn(S, H, T, Dh), jnp.float32)
    v = jnp.asarray(rng.randn(S, H, T, Dh), jnp.float32)
    base = np.array([0, 17, T - W - 2][:S], np.int32)
    lens = np.where(base[:, None] > 0,
                    base[:, None] + np.arange(1, W + 1)[None, :],
                    0).astype(np.int32)
    return q, k, v, jnp.asarray(np.minimum(lens, T))


def test_multi_dense_is_stacked_single_queries_bitwise():
    """The dense multi arm is DEFINED as W stacked single-query
    references — the fp32-bitwise anchor the widened program rests
    on."""
    q, k, v, lens = _multi_case()
    out = decode_attention_multi(q, k, v, lens, impl="dense")
    for i in range(q.shape[2]):
        ref = decode_attention_reference(q[:, :, i], k, v, lens[:, i])
        np.testing.assert_array_equal(np.asarray(out[:, :, i]),
                                      np.asarray(ref))
    # slot with all-zero row lengths -> exact zeros
    assert (np.asarray(out[0]) == 0).all()


@pytest.mark.parametrize("block_k", [32, 64, 256])
def test_multi_pallas_matches_dense(block_k):
    q, k, v, lens = _multi_case()
    out_p = decode_attention_multi(q, k, v, lens, impl="pallas",
                                   block_k=block_k)
    out_d = decode_attention_multi(q, k, v, lens, impl="dense")
    np.testing.assert_allclose(out_p, out_d, atol=2e-6, rtol=2e-6)
    assert (np.asarray(out_p[0]) == 0).all()


def test_multi_pallas_w9_sublane_padding():
    """W=9 (k=8) crosses the 8-row sublane tile: the padded rows must
    stay exact-zero and the live rows correct."""
    q, k, v, _ = _multi_case(W=9)
    lens = jnp.asarray(
        np.minimum(np.array([[5], [17], [100]], np.int32)
                   + np.arange(1, 10)[None, :], 128))
    out_p = decode_attention_multi(q, k, v, lens, impl="pallas",
                                   block_k=64)
    out_d = decode_attention_multi(q, k, v, lens, impl="dense")
    np.testing.assert_allclose(out_p, out_d, atol=2e-6, rtol=2e-6)


def test_multi_masks_garbage_tail():
    """Keys at/beyond each ROW's length are garbage (rejected
    speculation, evicted requests) and must never be attended."""
    q, k, v, lens = _multi_case(T=64)
    limit = int(np.asarray(lens).max())
    bad_k = k.at[:, :, limit:].set(1e4)
    bad_v = v.at[:, :, limit:].set(1e4)
    for impl in ("pallas", "dense"):
        clean = decode_attention_multi(q, k, v, lens, impl=impl)
        dirty = decode_attention_multi(q, bad_k, bad_v, lens, impl=impl)
        np.testing.assert_array_equal(np.asarray(clean),
                                      np.asarray(dirty))


def _paged_case(S=3, H=2, W=5, page_len=16, pages=17, max_pages=8,
                seed=1):
    rng = np.random.RandomState(seed)
    Dh = 32
    q = jnp.asarray(rng.randn(S, H, W, Dh), jnp.float32)
    kp = jnp.asarray(rng.randn(pages, H, page_len, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(pages, H, page_len, Dh), jnp.float32)
    pt = np.zeros((S, max_pages), np.int32)
    ids = list(range(1, pages))
    for s in range(S):
        for m in range(max_pages):
            pt[s, m] = ids.pop(0) if ids else 0
    base = np.array([0, 9, 100][:S], np.int32)
    lens = np.where(base[:, None] > 0,
                    base[:, None] + np.arange(1, W + 1)[None, :],
                    0).astype(np.int32)
    lens = np.minimum(lens, max_pages * page_len)
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lens)


def test_paged_multi_dense_matches_gathered_reference():
    q, kp, vp, pt, lens = _paged_case()
    out = decode_attention_paged_multi(q, kp, vp, pt, lens,
                                       impl="dense")
    kg, vg = paged_gather(kp, pt), paged_gather(vp, pt)
    ref = decode_attention_multi_reference(q, kg, vg, lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_paged_multi_pallas_matches_dense():
    q, kp, vp, pt, lens = _paged_case()
    out_p = decode_attention_paged_multi(q, kp, vp, pt, lens,
                                         impl="pallas")
    out_d = decode_attention_paged_multi(q, kp, vp, pt, lens,
                                         impl="dense")
    np.testing.assert_allclose(out_p, out_d, atol=2e-6, rtol=2e-6)
    assert (np.asarray(out_p[0]) == 0).all()


def test_multi_single_compile_across_length_mixes():
    """Traced per-row lengths: one jit cache entry for any accepted-
    length mix."""
    q, k, v, _ = _multi_case(T=64)
    f = jax.jit(lambda q, k, v, l: decode_attention_multi(
        q, k, v, l, impl="pallas"))
    S, _, W, _ = q.shape
    for lens in (np.zeros((S, W)), np.full((S, W), 7),
                 np.arange(S * W).reshape(S, W) % 60):
        f(q, k, v, jnp.asarray(lens, jnp.int32)).block_until_ready()
    assert f._cache_size() == 1


# ---------------------------------------------------------------------------
# widened verify vs sequential decode ticks
# ---------------------------------------------------------------------------


def test_verify_step_matches_sequential_decode():
    """One verify pass over W tokens == W sequential decode ticks:
    same logits (ulp-tier — the qkv einsum widens), same argmaxes,
    same K/V rows written."""
    cfg = TINY
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S, T = 2, 32
    prompt = _tokens(6)[None, :].repeat(S, axis=0)
    logits, ks, vs = gpt2_prefill(cfg, params, jnp.asarray(prompt))
    k_cache = jnp.zeros((cfg.n_layer, S, cfg.n_head, T, cfg.d_head))
    v_cache = jnp.zeros_like(k_cache)
    k_cache = k_cache.at[:, :, :, :6].set(ks.transpose(0, 1, 2, 3, 4))
    v_cache = v_cache.at[:, :, :, :6].set(vs)
    lengths = jnp.full((S,), 6, jnp.int32)
    active = jnp.ones((S,), bool)
    toks = np.stack([_tokens(5, seed=3), _tokens(5, seed=4)])
    # sequential reference
    seq_logits = []
    kc, vc, ln = k_cache, v_cache, lengths
    for i in range(5):
        lg, kc, vc, ln = gpt2_decode_step(
            cfg, params, jnp.asarray(toks[:, i]), kc, vc, ln, active)
        seq_logits.append(lg)
    # one widened pass
    w_logits, kw, vw = gpt2_verify_step(
        cfg, params, jnp.asarray(toks), k_cache, v_cache, lengths,
        active)
    for i in range(5):
        np.testing.assert_allclose(np.asarray(w_logits[:, i]),
                                   np.asarray(seq_logits[i]),
                                   atol=1e-5, rtol=1e-5)
        assert (np.argmax(np.asarray(w_logits[:, i]), -1)
                == np.argmax(np.asarray(seq_logits[i]), -1)).all()
    np.testing.assert_allclose(np.asarray(kw), np.asarray(kc),
                               atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# acceptance math (inference/speculative.py)
# ---------------------------------------------------------------------------


def test_select_next_token_greedy_is_argmax_bitwise():
    """The satellite regression: the shared helper at temperature 0 is
    bitwise the argmax the engine used to inline at its four
    prefill/decode emission sites."""
    rng = np.random.RandomState(0)
    for shape in ((7,), (3, 9), (2, 4, 11)):
        logits = jnp.asarray(rng.randn(*shape), jnp.float32)
        out = select_next_token(logits)
        ref = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert out.dtype == jnp.int32


def test_select_next_token_temperature_needs_rng():
    logits = jnp.zeros((4, 8))
    with pytest.raises(ValueError, match="rng"):
        select_next_token(logits, 0.7)
    a = select_next_token(logits, 0.7, jax.random.PRNGKey(0))
    b = select_next_token(logits, 0.7, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_greedy_accept_prefix_semantics():
    """Hand-built case: acceptance is the longest PREFIX of proposals
    matching the target argmaxes; out tokens are the argmaxes."""
    V = 8
    g = np.array([[3, 5, 2, 7], [1, 1, 1, 1]])       # [S, W=4], k=3
    logits = np.full((2, 4, V), -10.0, np.float32)
    for s in range(2):
        for i in range(4):
            logits[s, i, g[s, i]] = 1.0
    drafts = np.array([[3, 5, 0], [2, 1, 1]])        # [S, k]
    out, acc = greedy_accept(jnp.asarray(logits), jnp.asarray(drafts))
    np.testing.assert_array_equal(np.asarray(out), g)
    # slot 0: d1=3==g0, d2=5==g1, d3=0!=g2 -> m=2 (emit g0,g1,g2)
    # slot 1: d1=2!=g0 -> m=0 (emit g0 only)
    np.testing.assert_array_equal(np.asarray(acc), [2, 0])


def test_rejection_sampling_recovers_target_distribution():
    """The Chen et al. guarantee: draft-proposed + accept/resample ==
    sampling the target, empirically at S=1, k=1 over a tiny vocab."""
    p_log = jnp.log(jnp.asarray(
        [[0.45, 0.30, 0.15, 0.10], [0.25, 0.25, 0.25, 0.25]],
        jnp.float32))                                   # [W=2, V]
    q = jnp.asarray([[0.10, 0.40, 0.30, 0.20]], jnp.float32)

    def one(key):
        kd, ka = jax.random.split(key)
        d = jax.random.categorical(kd, jnp.log(q[0]))[None, None]
        out, _ = rejection_sample_accept(p_log[None], d, q[None], 1.0,
                                         ka)
        return out[0, 0]

    toks = jax.vmap(one)(jax.random.split(jax.random.PRNGKey(0), 30000))
    freq = np.bincount(np.asarray(toks), minlength=4) / 30000
    target = np.asarray(jax.nn.softmax(p_log[0]))
    assert np.abs(freq - target).max() < 0.02, (freq, target)


def test_rejection_residual_excludes_overproposed_token():
    """Where q >= p the residual max(p-q, 0) is zero: a rejected
    proposal can never be resampled as itself."""
    p_log = jnp.log(jnp.asarray([[0.05, 0.90, 0.05],
                                 [1 / 3, 1 / 3, 1 / 3]], jnp.float32))
    q = jnp.asarray([[0.90, 0.05, 0.05]], jnp.float32)  # over-proposes 0

    def one(key):
        out, acc = rejection_sample_accept(
            p_log[None], jnp.asarray([[0]]), q[None], 1.0, key)
        return out[0, 0], acc[0]

    toks, accs = jax.vmap(one)(
        jax.random.split(jax.random.PRNGKey(1), 2000))
    toks, accs = np.asarray(toks), np.asarray(accs)
    rejected = toks[accs == 0]
    assert len(rejected) > 100            # p(0)/q(0) is tiny
    assert (rejected != 0).all()          # residual excludes token 0


# ---------------------------------------------------------------------------
# engine parity bar: spec stream == non-spec stream, greedy, every k
# ---------------------------------------------------------------------------

_PROMPTS = [_tokens(3, seed=10), _tokens(7, seed=11), _tokens(5, seed=12)]
_GEN = 10
_model = GPT2Model(TINY)
_params = None
_noisy_draft = None
_ref_cache = {}


def _target_params():
    global _params
    if _params is None:
        _params = _model.init(jax.random.PRNGKey(0))
    return _params


def _noisy_draft_params():
    """Target params with small noise on the embedding: the draft
    mostly agrees with the target but rejects often enough to exercise
    every rollback path at mid accept ratios."""
    global _noisy_draft
    if _noisy_draft is None:
        p = jax.tree.map(lambda a: a, _target_params())
        noise = jax.random.normal(jax.random.PRNGKey(9),
                                  p["wte"].shape) * 0.02
        p = dict(p)
        p["wte"] = p["wte"] + noise
        _noisy_draft = p
    return _noisy_draft


def _serve(serving, draft_params=None, mesh=None, prompts=None,
           gen=_GEN, telemetry=None, return_engine=False):
    cfgd = {"serving": {"slots": 2, "max_seq_len": 64,
                        "prefill_len": 16, **serving}}
    if telemetry:
        cfgd["telemetry"] = telemetry
    eng = ServeEngine(_model, cfgd, params=_target_params(),
                      draft_params=draft_params, mesh=mesh)
    reqs = [eng.submit(p, max_new_tokens=gen)
            for p in (prompts or _PROMPTS)]
    eng.run_until_idle()
    out = [r.result() for r in reqs]
    if return_engine:
        return out, reqs, eng
    eng.close()
    return out


def _ref_stream(arm, mesh_key=None, mesh=None):
    key = (arm, mesh_key)
    if key not in _ref_cache:
        serving = {"page_len": 8} if arm == "paged" else {}
        _ref_cache[key] = _serve(serving, mesh=mesh)
    return _ref_cache[key]


@pytest.mark.parametrize("arm", ["unpaged", "paged"])
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_spec_stream_parity(arm, k):
    """THE parity bar: the speculative greedy stream equals the
    non-speculative stream at every k, on both KV layouts, with a
    rejection-heavy (noisy) draft."""
    serving = {"speculate_k": k, "draft": DRAFT_BLOCK}
    if arm == "paged":
        serving["page_len"] = 8
    spec = _serve(serving, draft_params=_noisy_draft_params())
    assert spec == _ref_stream(arm)


@pytest.mark.parametrize("arm", ["unpaged", "paged"])
def test_spec_stream_parity_full_accept(arm):
    """draft == target: every proposal accepts (the m=k bonus-token
    edge, incl. the draft's k+1-th KV write) — stream still equal."""
    serving = {"speculate_k": 4, "draft": DRAFT_BLOCK}
    if arm == "paged":
        serving["page_len"] = 8
    out, reqs, eng = _serve(serving, draft_params=_target_params(),
                            return_engine=True)
    assert out == _ref_stream(arm)
    # accounting counts tokens DELIVERED: every decode token beyond
    # each pass's first came from an accepted draft, so the counters
    # reconcile exactly with the emitted streams even though the
    # budget truncates the final block
    decode_tokens = sum(len(t) - 1 for t in out)
    assert eng._spec_accepted_n == decode_tokens - eng._spec_passes
    # and acceptance really was total up to that truncation: every
    # pass emitted its whole surviving block
    assert all(m >= 0 for r in reqs for m in r.spec_accepted)
    assert eng._spec_accepted_n > eng._spec_passes  # blocks, not 1/tick
    # which is the whole point: fewer target passes than tokens
    assert eng._spec_passes < decode_tokens
    eng.close()


@pytest.mark.parametrize("arm", ["unpaged", "paged"])
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_spec_stream_parity_dp2_tp2(arm, k):
    """Same bar on a sharded (data=2, model=2) mesh: TP-sharded heads
    + DP-sharded slots/pages through the ordinary mesh plumbing."""
    mesh = build_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    serving = {"speculate_k": k, "draft": DRAFT_BLOCK}
    if arm == "paged":
        serving["page_len"] = 8
    spec = _serve(serving, draft_params=_noisy_draft_params(),
                  mesh=mesh)
    assert spec == _ref_stream(arm, "dp2tp2", mesh)


def test_spec_zero_recompiles_and_telemetry(tmp_path):
    """Mixed accepted lengths across ticks never recompile the verify/
    propose programs; speculation counters + flush scalars land in the
    summarize 'speculation' row; the flight-recorder depth dict carries
    the live accept ratio."""
    tel = {"enabled": True, "output_path": str(tmp_path),
           "memory": False}
    serving = {"speculate_k": 4, "draft": DRAFT_BLOCK,
               "flush_interval_ticks": 2}
    out, reqs, eng = _serve(serving,
                            draft_params=_noisy_draft_params(),
                            telemetry=tel, return_engine=True)
    assert out == _ref_stream("unpaged")
    reg = eng.telemetry.registry
    assert reg.counter("recompiles_total").value(
        program="serve_verify") == 0
    assert reg.counter("recompiles_total").value(
        program="serve_draft_propose") == 0
    assert eng._verify_fn._cache_size() == 1
    assert eng._propose_fn._cache_size() == 1
    proposed = reg.counter("serve_spec_proposed_total").value()
    accepted = reg.counter("serve_spec_accepted_total").value()
    assert proposed == eng._spec_passes * 4
    assert 0 <= accepted <= proposed
    # uneven per-slot progress: the noisy draft's accepted lengths
    # vary across passes (the scheduler-variance scenario)
    all_acc = [m for r in reqs for m in r.spec_accepted]
    assert len(set(all_acc)) > 1, all_acc
    depth = eng._stage_depth()
    assert depth["spec_accept_ratio"] == round(
        accepted / max(proposed, 1), 4)
    # the flight-recorder ring stamps the ratio as a FLOAT (an int cast
    # would truncate every live ratio to 0)
    eng.stage.record_event("probe")
    ev = eng.stage.flight_snapshot()["events"][-1]
    assert ev["kind"] == "probe"
    assert isinstance(ev["spec_accept_ratio"], float)
    assert ev["spec_accept_ratio"] == depth["spec_accept_ratio"]
    assert ev["depth"] == 0
    eng._flush()
    eng.close()
    from deepspeed_tpu.telemetry.cli import summarize
    with open(os.devnull, "w") as devnull:
        report = summarize(str(tmp_path / "events.jsonl"), out=devnull)
    # ONE ratio formula everywhere: the flush scalar equals the depth
    # dict's rounded value, not a differently-computed cousin
    assert report["serve_spec_accept_ratio"] == depth["spec_accept_ratio"]
    assert report["serve_spec_mean_accepted_len"] == pytest.approx(
        (accepted + eng._spec_passes) / eng._spec_passes)


# ---------------------------------------------------------------------------
# accepted-length-variance scheduler semantics (the satellite matrix)
# ---------------------------------------------------------------------------


def test_uneven_progress_staggered_admissions():
    """A request admitted mid-stream decodes next to one several
    speculative blocks ahead — the masked machinery absorbs the skew
    and both streams stay parity-exact."""
    eng = ServeEngine(_model, {"serving": {
        "slots": 2, "max_seq_len": 64, "prefill_len": 16,
        "speculate_k": 4, "draft": DRAFT_BLOCK}},
        params=_target_params(),
        draft_params=_noisy_draft_params())
    r0 = eng.submit(_PROMPTS[0], max_new_tokens=_GEN)
    eng.step()
    eng.step()
    r1 = eng.submit(_PROMPTS[1], max_new_tokens=_GEN)
    eng.run_until_idle()
    ref = _ref_stream("unpaged")
    assert r0.result() == ref[0]
    assert r1.result() == ref[1]
    # the two slots really did progress unevenly
    assert len(r0.spec_accepted) != len(r1.spec_accepted) or \
        r0.spec_accepted != r1.spec_accepted
    eng.close()


def test_eos_inside_accepted_block():
    """EOS landing mid-block truncates the emission AT the EOS token
    and finishes the request — stream identical to the non-spec arm
    with the same eos_id."""
    ref = _ref_stream("unpaged")
    eos = ref[1][4]                       # a token mid-stream
    base = _serve({"eos_id": int(eos)})
    spec = _serve({"eos_id": int(eos), "speculate_k": 4,
                   "draft": DRAFT_BLOCK},
                  draft_params=_target_params())
    assert spec == base
    assert any(len(s) < _GEN for s in spec)  # EOS actually fired


def test_kv_capacity_inside_accepted_block():
    """The generation hitting the slot's KV capacity mid-block
    truncates exactly where the non-spec arm stops."""
    serving = {"max_seq_len": 12, "prefill_len": 8}
    prompts = [_tokens(5, seed=20), _tokens(3, seed=21)]
    base = _serve(serving, prompts=prompts, gen=16)
    out, reqs, eng = _serve(
        {**serving, "speculate_k": 4, "draft": DRAFT_BLOCK},
        draft_params=_target_params(), prompts=prompts, gen=16,
        return_engine=True)
    assert out == base
    assert any(r.finish_reason == "kv_capacity" for r in reqs)
    eng.close()


def test_paged_pool_exhaustion_during_block_append_no_leaks():
    """A k-token append draining the page pool finishes that request
    kv_capacity (the pool-aware reason), the other slot keeps serving,
    and when everything drains the pool holds ZERO refs — speculated
    pages were freed, not leaked."""
    eng = ServeEngine(_model, {"serving": {
        "slots": 2, "max_seq_len": 64, "prefill_len": 16,
        "page_len": 4, "pages": 9, "prefix_cache": False,
        "speculate_k": 4, "draft": DRAFT_BLOCK}},
        params=_target_params(), draft_params=_target_params())
    # two requests: 8 usable pages = 32 token-rows; both want to grow
    # past that, so one hits pool exhaustion mid-append
    r0 = eng.submit(_tokens(8, seed=30), max_new_tokens=24)
    r1 = eng.submit(_tokens(8, seed=31), max_new_tokens=24)
    eng.run_until_idle()
    assert r0.error is None and r1.error is None
    reasons = {r0.finish_reason, r1.finish_reason}
    assert "kv_capacity" in reasons
    # the survivor kept decoding after the other's exhaustion finish
    assert max(len(r0.tokens), len(r1.tokens)) > \
        min(len(r0.tokens), len(r1.tokens))
    assert eng.pool.refs == {}
    assert eng.pool.free_count == 8
    eng.close()


def test_eviction_mid_speculation_frees_speculated_pages():
    """EOS inside an accepted block on the paged arm: the finish frees
    EVERY page the request held, including the block's speculative
    pre-allocation — no refcount leaks."""
    ref = _ref_stream("paged")
    eos = ref[0][3]
    eng = ServeEngine(_model, {"serving": {
        "slots": 2, "max_seq_len": 64, "prefill_len": 16,
        "page_len": 8, "prefix_cache": False,
        "speculate_k": 4, "draft": DRAFT_BLOCK,
        "eos_id": int(eos)}},
        params=_target_params(), draft_params=_target_params())
    reqs = [eng.submit(p, max_new_tokens=_GEN) for p in _PROMPTS]
    eng.run_until_idle()
    assert all(r.error is None for r in reqs)
    assert eng.pool.refs == {}
    assert eng.pool.free_count == eng.cache_spec.pages - 1
    eng.close()


def test_spec_tick_chaos_transient_absorbed(monkeypatch):
    """The serve stage's chaos semantics hold in spec mode: a
    transient injected fault at the step boundary is retried by the
    stage budget and the emitted stream is unchanged."""
    monkeypatch.setenv("DS_STAGE_FAULT", "serve:step:2")
    reset_fault_injection()
    spec = _serve({"speculate_k": 4, "draft": DRAFT_BLOCK},
                  draft_params=_target_params())
    assert spec == _ref_stream("unpaged")


def test_spec_poison_fails_inflight_typed():
    """A fatal mid-verify failure poisons the pool: every in-flight
    request fails with the ORIGINAL exception (the cache was donated),
    submitters release, and close() stays clean."""
    eng = ServeEngine(_model, {"serving": {
        "slots": 2, "max_seq_len": 64, "prefill_len": 16,
        "speculate_k": 2, "draft": DRAFT_BLOCK}},
        params=_target_params(), draft_params=_target_params())
    reqs = [eng.submit(p, max_new_tokens=_GEN) for p in _PROMPTS[:2]]
    eng.step()
    boom = RuntimeError("verify exploded")

    def bad_tick():
        raise boom
    eng._spec_tick = bad_tick
    with pytest.raises(RuntimeError, match="verify exploded"):
        eng.step()
    for r in reqs:
        assert r.done.is_set()
        with pytest.raises(RuntimeError, match="verify exploded"):
            r.result(timeout=1)
    eng.close()


# ---------------------------------------------------------------------------
# temperature plane
# ---------------------------------------------------------------------------


def test_temperature_sampling_deterministic_under_seed():
    a = _serve({"temperature": 0.8}, gen=6)
    b = _serve({"temperature": 0.8}, gen=6)
    assert a == b
    assert a != _ref_stream("unpaged")  # it really sampled


def test_temperature_spec_serves_end_to_end():
    """T>0 speculation (rejection-sampling acceptance) serves the full
    workload; the stream is a sample, not the greedy stream, so the
    bar is completion + budget-exact lengths."""
    out = _serve({"temperature": 0.8, "speculate_k": 3,
                  "draft": DRAFT_BLOCK},
                 draft_params=_target_params(), gen=6)
    assert [len(t) for t in out] == [6, 6, 6]


# ---------------------------------------------------------------------------
# config + mesh validation
# ---------------------------------------------------------------------------


def test_config_rejects_bad_spec_blocks():
    for bad in ({"speculate_k": -1}, {"speculate_k": True},
                {"temperature": -0.5}, {"temperature": "hot"},
                {"draft": {"bogus": 1}}, {"draft": 3},
                {"draft": {"d_model": 65, "n_head": 4}},
                {"draft": {"n_layer": 0}},
                {"draft": {"attn_impl": "ring"}}):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedServingConfig({"serving": bad})


def test_config_draft_defaults_filled():
    c = DeepSpeedServingConfig({"serving": {"speculate_k": 2}})
    assert c.draft == {"d_model": 256, "n_layer": 2, "n_head": 4,
                       "attn_impl": ""}
    assert c.temperature == 0.0


def test_draft_heads_must_divide_tp():
    mesh = build_mesh(dp=1, tp=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="divisible"):
        ServeEngine(_model, {"serving": {
            "slots": 2, "max_seq_len": 64, "prefill_len": 16,
            "speculate_k": 2,
            "draft": {"d_model": 30, "n_layer": 1, "n_head": 3}}},
            params=_target_params(), mesh=mesh)
