"""Nemotron-H as Nemotron-3 Super configures it: each part against its
equations by hand, the model against the benchmark's plain float32
reference, the paged serving path with request state through ``ServeEngine``
against the reference's full forward, the share of the experts against the
uncut layer, grouped keys against repeated keys, and the refusals.  CPU,
tiny widths, seeded weights.  (Its cell's rehearsal:
tests/test_benchmark_cells.py.)"""
import collections
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
from deepspeed_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel
from deepspeed_tpu.moe.dropless import (HeldMoEStats, dropless_moe,
                                        route_sigmoid_topk)
from deepspeed_tpu.ops.pallas.decode_attention import (
    decode_attention_paged, paged_decode_arm, paged_pages_per_block)
from deepspeed_tpu.ops.pallas.ssm import (ssd_chunked, ssm_decode,
                                          ssm_decode_reference)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import nemotron_h_reference  # noqa: E402

TINY = NemotronHConfig(
    vocab_size=128, hidden_size=64, hybrid_override_pattern="MEM*E",
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
    ssm_state_size=16, chunk_size=8, n_routed_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=32, moe_latent_size=32,
    moe_shared_expert_intermediate_size=48, max_position_embeddings=256,
    experts_held=(4, 8), attn_impl="dense")
SERVING = {"slots": 3, "page_len": 8, "max_seq_len": 64, "prefill_len": 32,
           "prefix_cache": False}

# float32 on the CPU: the model and the reference differ by summation
# order and by the chunked against the step-by-step recurrence (measured
# 3e-7 on logits of size 0.6).  A bfloat16 recurrent state moves the same
# logits by 3e-5 and a bfloat16 router by more (a changed choice of
# expert), so this fails both.
F32_TOL = 5e-6
# through the flash and paged-decode kernels (online softmax: other
# partial sums): measured 4e-7; a bfloat16 state still shows as 3e-5
PAGED_TOL = 5e-6


def _params(cfg=TINY, seed=0):
    return drawn_once(NemotronHModel, cfg, seed)


def _keys(cfg=TINY):
    return dataclasses.asdict(cfg)


def _reference(params, tokens, cfg=TINY, **kw):
    with jax.default_matmul_precision("highest"):
        return nemotron_h_reference.nemotron_h_logits(
            params, jnp.asarray(tokens), _keys(cfg), **kw)


# -- the parts, by hand -----------------------------------------------------

def _recurrence_by_hand(x, dt, a, b, c):
    """h_t = exp(dt A) h_{t-1} + dt x (outer) B; y = h C: numpy loops."""
    T, H, P = x.shape
    G, N = b.shape[1:]
    h = np.zeros((H, P, N))
    ys = np.zeros((T, H, P))
    for t in range(T):
        for i in range(H):
            g = i // (H // G)
            h[i] = np.exp(dt[t, i] * a[i]) * h[i] \
                + dt[t, i] * np.outer(x[t, i], b[t, g])
            ys[t, i] = h[i] @ c[t, g]
    return ys, h


def _recurrence_inputs(T, H=4, P=3, G=2, N=5, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(T, H, P)), rng.uniform(0.01, 0.3, (T, H)),
            -rng.uniform(1, 16, (H,)), rng.normal(size=(T, G, N)),
            rng.normal(size=(T, G, N)))


@pytest.mark.parametrize("T,chunk", [(8, 8), (24, 8), (16, 4)])
def test_chunked_scan_equals_the_recurrence_by_hand(T, chunk):
    x, dt, a, b, c = _recurrence_inputs(T)
    ys, h = _recurrence_by_hand(x, dt, a, b, c)
    y, final = ssd_chunked(*(jnp.asarray(t, jnp.float32)
                             for t in (x, dt, a, b, c)), chunk)
    np.testing.assert_allclose(y, ys, atol=2e-5)
    np.testing.assert_allclose(final, h, atol=2e-5)


def test_chunked_scan_ends_on_the_state_at_the_true_length():
    """Positions at and beyond the live length take dt = 0: they neither
    decay the state nor feed it, whatever the padding holds."""
    x, dt, a, b, c = _recurrence_inputs(24)
    live = 13
    _, h = _recurrence_by_hand(x[:live], dt[:live], a, b[:live], c[:live])
    dt_masked = np.where(np.arange(24)[:, None] < live, dt, 0.0)
    _, final = ssd_chunked(*(jnp.asarray(t, jnp.float32)
                             for t in (x, dt_masked, a, b, c)), 8)
    np.testing.assert_allclose(final, h, atol=2e-5)


@pytest.mark.parametrize("active", [[True, False, True, True],
                                    [False, False, True, False],
                                    [False] * 4, [True] * 4],
                         ids=["some", "one", "none", "all"])
def test_decode_kernel_updates_live_slots_in_place_and_skips_the_rest(active):
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    L, S, H, P, N, G = 2, 4, 8, 16, 128, 2
    state = jax.random.normal(k[0], (L * S, H, P, N), jnp.float32)
    decay = jax.random.uniform(k[1], (S, H))
    dtx = jax.random.normal(k[2], (S, H, P))
    b, c = (jax.random.normal(kk, (S, G, N)) for kk in k[3:])
    active = jnp.asarray(active)
    new, y = ssm_decode(state, decay, dtx, b, c, active, base=S)
    want, want_y = ssm_decode_reference(state[S:], decay, dtx, b, c, active)
    np.testing.assert_allclose(new[S:], want, atol=1e-5)
    np.testing.assert_allclose(y, want_y, atol=1e-4)
    # the other layer's rows and the inactive slots: bit for bit
    np.testing.assert_array_equal(new[:S], state[:S])
    idle = np.flatnonzero(~np.asarray(active))
    np.testing.assert_array_equal(np.asarray(new[S:])[idle],
                                  np.asarray(state[S:])[idle])
    assert not np.asarray(y)[idle].any()


def test_decode_kernel_step_equals_the_recurrence_by_hand():
    x, dt, a, b, c = _recurrence_inputs(6, H=4, P=8, G=2, N=128)
    ys, h = _recurrence_by_hand(x, dt, a, b, c)
    state = jnp.zeros((1, 4, 8, 128), jnp.float32)
    on = jnp.ones((1,), bool)
    for t in range(6):
        state, y = ssm_decode(
            state, jnp.exp(jnp.asarray(dt[t] * a, jnp.float32))[None],
            jnp.asarray(dt[t][:, None] * x[t], jnp.float32)[None],
            jnp.asarray(b[t], jnp.float32)[None],
            jnp.asarray(c[t], jnp.float32)[None], on)
        np.testing.assert_allclose(y[0], ys[t], atol=1e-4)
    np.testing.assert_allclose(state[0], h, atol=1e-5)


# heads, channels, groups, width of a state: the two small shapes of the
# tests above (8 and 2 register tiles a group) and the published one (128)
CONTRACTION_SHAPES = [(8, 16, 2, 128), (4, 8, 2, 128), (128, 64, 8, 128)]
_contraction_ids = ["8x16", "4x8", "published-128x64"]


@pytest.mark.parametrize("H,P,G,N", CONTRACTION_SHAPES, ids=_contraction_ids)
def test_decode_kernel_contracts_y_for_every_head_and_channel(H, P, G, N):
    """``y = h C`` leaves the kernel merged across heads by lane rotations
    (PR 64): every (head, channel) against the plain sum over the lanes,
    two slots of which one is idle, at the tolerances of the tests above."""
    k = jax.random.split(jax.random.PRNGKey(64), 5)
    S = 2
    state = jax.random.normal(k[0], (S, H, P, N), jnp.float32)
    decay = jax.random.uniform(k[1], (S, H))
    dtx = jax.random.normal(k[2], (S, H, P))
    b, c = (jax.random.normal(kk, (S, G, N)) for kk in k[3:])
    active = jnp.asarray([False, True])
    new, y = ssm_decode(state, decay, dtx, b, c, active)
    want, want_y = ssm_decode_reference(state, decay, dtx, b, c, active)
    np.testing.assert_allclose(new, want, atol=1e-5)
    np.testing.assert_allclose(y, want_y, atol=1e-4)
    np.testing.assert_array_equal(new[0], state[0])
    assert not np.asarray(y[0]).any() and np.asarray(y[1]).all()


@pytest.mark.parametrize(
    "H,P,G,N", CONTRACTION_SHAPES + [(256, 8, 4, 128), (8, 8, 2, 16)],
    ids=_contraction_ids + ["two-trees-256x8", "tiny-models-width-16"])
def test_decode_kernel_is_exact_on_operands_that_round_nowhere(H, P, G, N):
    """Small whole numbers and decays of 1, 1/2, 1/4: every product and
    every sum is exact in float32 in ANY order, so the new state is bit
    for bit ``state * decay + dt x (outer) B`` and ``y`` bit for bit its
    sum against ``C``; a head that landed at another head's lane, twice,
    or beside another's partial sum would show in the bits."""
    rng = np.random.default_rng(H * P + N)
    S = 2
    state = rng.integers(-8, 9, (S, H, P, N)).astype(np.float32)
    decay = rng.choice([1.0, 0.5, 0.25], (S, H)).astype(np.float32)
    dtx = rng.integers(-4, 5, (S, H, P)).astype(np.float32)
    b = rng.integers(-4, 5, (S, G, N)).astype(np.float32)
    c = rng.integers(-2, 3, (S, G, N)).astype(np.float32)
    new, y = ssm_decode(*(jnp.asarray(t) for t in (state, decay, dtx, b, c)),
                        jnp.asarray([True, False]))
    bh, ch = (np.repeat(t.astype(np.float64), H // G, axis=1) for t in (b, c))
    want = state * decay[..., None, None] + dtx[..., None] * bh[:, :, None, :]
    want_y = (want * ch[:, :, None, :]).sum(-1)
    assert np.abs(want_y[0]).max() > 100        # not a sum of nothing
    np.testing.assert_array_equal(new[0], want[0])
    np.testing.assert_array_equal(y[0], want_y[0])
    np.testing.assert_array_equal(new[1], state[1])
    assert not np.asarray(y[1]).any()


def _primitive_counts(jaxpr):
    """primitive name -> uses in ``jaxpr``, the bodies of its calls and
    branches included."""
    counts = collections.Counter(e.primitive.name for e in jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            counts += _primitive_counts(sub)
    return counts


def test_decode_kernel_sums_over_no_lanes_at_the_published_widths():
    """The kernel's own jaxpr at 128 heads of 64 x 128: 127 lane
    rotations of a head's tile (a tree over the heads) and no
    ``reduce_sum`` at all, where the body before PR 64 held 128 over the
    lanes (1,024 cross-lane reductions a slot and layer on the chip)."""
    S, H, P, N, G = 4, 128, 64, 128, 8
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(ssm_decode)(
        sds((S, H, P, N), f32), sds((S, H), f32), sds((S, H, P), f32),
        sds((S, G, N), f32), sds((S, G, N), f32), sds((S,), jnp.bool_))
    kernel, = (e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
    counts = _primitive_counts(kernel.params["jaxpr"])
    assert counts["reduce_sum"] == 0, counts
    assert counts["roll"] == H - 1, counts


@pytest.mark.parametrize("H,N", [(6, 128), (192, 128), (8, 96)],
                         ids=["6-heads", "192-heads", "96-wide"])
def test_decode_kernel_refuses_by_name_a_state_it_cannot_merge(H, N):
    S, P, G = 2, 8, 2
    z = jnp.zeros
    with pytest.raises(ValueError, match="ds_ssm_decode.*power of two"):
        ssm_decode(z((S, H, P, N)), z((S, H)), z((S, H, P)), z((S, G, N)),
                   z((S, G, N)), jnp.ones((S,), bool))


def test_sigmoid_router_by_hand():
    """Scores sigmoid; the bias steers the CHOICE only; weights are the
    chosen experts' own scores, renormalised, times the scale."""
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]])
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.5, -0.5, 1.0, 0.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0])      # lifts expert 3 in
    weights, experts = route_sigmoid_topk(x, w, bias, 2, scale=5.0)
    s = 1 / (1 + np.exp(-np.asarray(x @ w)))
    for row in range(2):
        chosen = np.argsort(-(s[row] + np.asarray(bias)))[:2]
        assert set(np.asarray(experts[row])) == set(chosen)
        own = s[row][np.asarray(experts[row])]
        np.testing.assert_allclose(weights[row], own / own.sum() * 5.0,
                                   rtol=1e-6)
    assert 3 in np.asarray(experts[0])            # score 0.27, bias 1
    plain, _ = route_sigmoid_topk(x, w, bias, 2, renormalize=False)
    np.testing.assert_allclose(
        plain[0], s[0][np.asarray(experts[0])], rtol=1e-6)


def _latent_layer(n=9, d=32, f=48, e=16, k=3, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(key[0], (n, d)),
            jax.random.normal(key[1], (d, e)),
            jax.random.normal(key[2], (e,)) * 0.1,
            jax.random.normal(key[3], (e, d, f)) * 0.2,
            jax.random.normal(key[4], (e, f, d)) * 0.2, k)


def _every_expert_masked(x, routing, up, down, held):
    """sum_k w_k relu(x U_e)**2 D_e over the held experts, densely."""
    weights, experts = routing
    out = np.zeros(x.shape)
    first, count = held
    for n in range(x.shape[0]):
        for w, e in zip(np.asarray(weights[n]), np.asarray(experts[n])):
            if first <= e < first + count:
                h = np.maximum(np.asarray(x[n]) @ np.asarray(up[e]), 0) ** 2
                out[n] += w * (h @ np.asarray(down[e]))
    return out


@pytest.mark.parametrize("held", [(0, 16), (4, 8), (12, 4), (0, 1)],
                         ids=["all", "middle", "last_quarter", "one"])
def test_relu2_experts_of_a_share_equal_every_expert_masked(held):
    x, rw, bias, up, down, k = _latent_layer()
    routing = route_sigmoid_topk(x, rw, bias, k, scale=2.5)
    first, count = held
    y, stats = dropless_moe(
        x, rw, None, up[first:first + count], down[first:first + count],
        k, routing=routing, experts_held=held, act="relu2")
    want = _every_expert_masked(x, routing, up, down, held)
    np.testing.assert_allclose(y, want, atol=2e-4)
    assert isinstance(stats, HeldMoEStats)
    here = np.isin(np.asarray(routing[1]), np.arange(first, first + count))
    assert int(stats.rows) == here.sum()
    assert int(stats.rows_elsewhere) == here.size - here.sum()
    assert int(stats.experts_hit) == len(set(
        np.asarray(routing[1])[here].tolist()))


def test_the_four_shares_and_the_shared_expert_once_make_the_whole_layer():
    """THE SHARE TEST (model-configs guide, section 4): the routed parts
    that the four shares of an ``E`` layer give, plus what every chip
    computes alike (the shared expert) counted once, add up to the uncut
    reference layer."""
    whole = dataclasses.replace(TINY, experts_held=None)
    params = _params(whole, seed=3)
    # larger than init, so that the routed part is of the shared expert's
    # size and not lost beside it
    for name in ("latent_down", "latent_up", "up_w", "down_w"):
        params["moe"][name] = params["moe"][name] * 8.0
    p = {k: v[0] for k, v in params["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 11, 64))
    keys = {**_keys(whole), "experts_held": None}
    with jax.default_matmul_precision("highest"):
        full = nemotron_h_reference._experts(
            p, params["moe"]["up_w"][0], params["moe"]["down_w"][0], 0, x,
            keys)[0]
        shared = jnp.square(jnp.maximum(x @ p["shared_up"], 0.0)) \
            @ p["shared_down"]
        from deepspeed_tpu.models.nemotron_h import _experts
        parts = []
        for first in (0, 4, 8, 12):
            cfg = dataclasses.replace(TINY, experts_held=(first, 4))
            stacked = {k: params["moe"][k][0, first:first + 4]
                       for k in ("up_w", "down_w")}
            out, stats = _experts(cfg, p, stacked, 0, x[0], None)
            parts.append(out - shared[0])          # the routed part alone
            assert int(stats.rows + stats.rows_elsewhere) == 11 * 3
    size = float(jnp.abs(full).max())
    np.testing.assert_allclose(sum(parts) + shared[0], full[0],
                               atol=1e-5 * size)
    # and a share is not the whole: the test would pass vacuously otherwise
    for part in parts:
        assert float(jnp.abs(part).max()) > 0.02 * size


@pytest.mark.parametrize("impl", ["pallas", "dense"])
def test_grouped_keys_equal_repeated_keys_on_both_decode_arms(impl):
    """32-on-2 in miniature: 8 query heads on 2 key heads; the grouped
    pool against the same keys repeated for every query head."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    S, Hq, H, page_len, Dh, mp = 5, 8, 2, 8, 32, 12
    P = 1 + S * mp
    q = jax.random.normal(k[0], (S, Hq, Dh))
    kp = jax.random.normal(k[1], (P, H, page_len, Dh))
    vp = jax.random.normal(k[2], (P, H, page_len, Dh))
    lengths = np.array([0, 1, 17, 96, 40], np.int32)
    table = np.zeros((S, mp), np.int32)
    perm = np.random.default_rng(0).permutation(np.arange(1, P))
    at = 0
    for s in range(S):
        n = -(-lengths[s] // page_len)
        table[s, :n] = perm[at:at + n]
        at += n
    got = decode_attention_paged(q, kp, vp, table, lengths, impl=impl)
    rep = decode_attention_paged(
        q, jnp.repeat(kp, Hq // H, axis=1), jnp.repeat(vp, Hq // H, axis=1),
        table, lengths, impl="dense")
    np.testing.assert_allclose(got, rep, atol=2e-6)
    assert not np.asarray(got[0]).any()           # the free slot


def test_grouped_keys_choose_the_direct_arm_and_its_block():
    """The cell's shape: a page [2, 16, 128] is 8 KiB a pool, so a block
    of 128 pages in flight twice over both pools is 4 MiB."""
    assert paged_decode_arm(2, 16, 128, 2, q_heads=32) == "direct"
    assert paged_pages_per_block(2, 16, 128, 2, 320, q_heads=32) == 128
    # 16 on 16 (OLMoE) and 25 on 25 (GPT-2 XL) are what they were
    assert paged_pages_per_block(16, 16, 128, 2, 128) == 16
    assert paged_decode_arm(25, 16, 64, 2) == "packed"
    assert paged_decode_arm(16, 16, 128, 2, q_heads=16) == "direct"


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_apply_matches_the_reference_in_float32(attn_impl):
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    params = _params(cfg, seed=1)
    tokens = np.random.default_rng(0).integers(0, 128, (2, 40))
    with jax.default_matmul_precision("highest"):
        got = NemotronHModel(cfg).apply(params, jnp.asarray(tokens))
    want, _ = _reference(params, tokens, cfg)
    assert float(jnp.abs(got - want).max()) < F32_TOL
    assert float(jnp.abs(want).max()) > 0.3


@pytest.mark.parametrize("what", ["state", "router"])
def test_the_float32_tolerance_fails_a_bfloat16_state_and_router(what):
    params = _params(seed=1)
    # experts larger than init, so that their weights show in the logits
    for name in ("latent_down", "latent_up", "up_w", "down_w"):
        params["moe"][name] = params["moe"][name] * 8.0
    tokens = np.random.default_rng(0).integers(0, 128, (2, 40))
    want, _ = _reference(params, tokens)
    if what == "state":
        low, _ = _reference(params, tokens, state_dtype=jnp.bfloat16)
    else:
        rounded = jax.tree.map(lambda x: x, params)
        rounded["moe"] = dict(params["moe"], router_w=params["moe"][
            "router_w"].astype(jnp.bfloat16).astype(jnp.float32))
        low, _ = _reference(rounded, tokens)
    assert float(jnp.abs(low - want).max()) > F32_TOL


def test_apply_reports_the_share_in_its_counters():
    params = _params(seed=1)
    tokens = np.random.default_rng(0).integers(0, 128, (2, 20))
    _, aux = NemotronHModel(TINY).apply(params, jnp.asarray(tokens),
                                        aux=True)
    every = 2 * 20 * 3 * 2                 # tokens x top-3 x two E layers
    assert int(aux["moe_rows"] + aux["moe_rows_elsewhere"]) == every
    assert 0 < int(aux["moe_rows"]) < every
    assert 0 < int(aux["moe_experts_hit"]) <= 2 * 8
    assert float(aux["moe_load_imbalance"]) >= 1.0


def test_init_is_as_published():
    params = _params(seed=2)
    m = params["mamba"]
    a = np.exp(np.asarray(m["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 + 1e-4
    step = np.log1p(np.exp(np.asarray(m["dt_bias"])))     # softplus
    assert step.min() >= TINY.time_step_floor * 0.999
    assert step.max() <= TINY.time_step_max * 1.001
    assert np.all(np.asarray(m["D"]) == 1)
    assert np.abs(np.asarray(m["conv_w"])).max() <= 0.5
    assert not np.asarray(params["moe"]["router_bias"]).any()
    assert params["moe"]["up_w"].shape == (2, 8, 32, 32)  # the held only
    assert params["moe"]["router_w"].shape == (2, 64, 16)  # all 16 wide
    assert params["attn"]["k_w"].shape == (1, 64, 2 * 16)
    bf = NemotronHModel(dataclasses.replace(
        TINY, param_dtype="bfloat16")).init(jax.random.PRNGKey(0))
    assert {x.dtype for x in jax.tree.leaves(bf)} == {jnp.dtype("bfloat16")}


def test_balancing_the_router_bias_evens_the_load():
    """The source's auxiliary-loss-free balancing, run by the REFERENCE on
    weights from a seed, balances the PROGRAM's routing of other tokens:
    its own counters read the busiest expert's load over the mean lower
    and every expert hit; the bias keeps its shape and dtype."""
    cfg = dataclasses.replace(
        TINY, n_routed_experts=32, num_experts_per_tok=4,
        experts_held=(0, 32))
    model, params = NemotronHModel(cfg), _params(cfg, seed=6)
    # a common component in every token's hidden state, as relu2 gives at
    # the published widths: most tokens then pick the same experts
    params["wte"] = params["wte"] + 0.01
    rng = np.random.default_rng(0)
    cal, held_out = (jnp.asarray(rng.integers(0, 128, (4, 64)))
                     for _ in range(2))

    def counted(p):
        return model.apply(p, held_out, aux=True)[1]

    before = float(counted(params)["moe_load_imbalance"])
    with jax.default_matmul_precision("highest"):
        bias = nemotron_h_reference.balance_router_bias(
            params, cal, _keys(cfg))
    old = params["moe"]["router_bias"]
    assert (bias.shape, bias.dtype) == (old.shape, old.dtype)
    assert float(jnp.abs(bias).max()) > 0
    after = counted(dict(params, moe=dict(params["moe"], router_bias=bias)))
    assert float(after["moe_load_imbalance"]) < 0.75 * before
    assert float(after["moe_load_imbalance"]) < 2.5, (before, after)
    assert int(after["moe_experts_hit"]) == 32 * cfg.count("E")


def test_balancing_takes_each_layer_on_the_input_it_will_get():
    """A later ``E`` layer is balanced on what the layers before it give
    once THEY are balanced: its bias, put back and balanced again, stays
    where it is within one step of the rule."""
    cfg = dataclasses.replace(TINY, n_routed_experts=32,
                              num_experts_per_tok=4, experts_held=(0, 32))
    params = _params(cfg, seed=6)
    params["wte"] = params["wte"] + 0.01
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 128, (4, 64)))
    with jax.default_matmul_precision("highest"):
        bias = nemotron_h_reference.balance_router_bias(
            params, tokens, _keys(cfg))
        again = nemotron_h_reference.balance_router_bias(
            dict(params, moe=dict(params["moe"], router_bias=bias)),
            tokens, _keys(cfg), steps=1)
    assert float(jnp.abs(again - bias).max()) <= 0.02 + 1e-6
    assert float(jnp.abs(bias[1] - bias[0]).max()) > 0


# -- the benchmark's family: the reference, its control, the limits -------

def _family():
    import json
    from lib.nemotron_h_family import NemotronH
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        return NemotronH(json.load(f), rehearse=True)


def test_the_family_reference_is_the_reference_with_its_control_beside_it():
    """One program gives member 0, the float32 reference itself, and
    member 1, the control a precision below (the residual stream in
    float8 from the embedding on): logits and the mixers' states."""
    family = _family()
    params = family.make_params(3, jnp.bfloat16)
    assert float(jnp.abs(params["moe"]["router_bias"].astype(
        jnp.float32)).max()) > 0                # balanced, by the reference
    tokens = list(np.random.default_rng(0).integers(0, family.vocab, 21))
    logits, states = family.reference(params, tokens, 32)
    assert logits.shape == (2, 21, family.vocab)
    assert states.shape[:2] == (2, family.pattern.count("M"))
    want, want_states = _reference(
        params, np.asarray(tokens)[None], family.model.config)
    np.testing.assert_allclose(logits[0], want[0], atol=1e-5)
    np.testing.assert_allclose(states[0], want_states[:, 0], atol=1e-6)
    assert float(jnp.abs(logits[1] - logits[0]).max()) > 1e-2
    assert float(jnp.abs(states[1, 0] - states[0, 0]).max()) > 1e-4


# on the chip at the published widths (PERF.md section 6, PR 34): the
# program's largest readings over its seeds, and each control's smallest
CHIP_PROGRAM = {"probe_logits": 0.4495, "probe_state": 1.3e-2,
                "streams": 0.25, "state_arithmetic": 2.8e-5}
CHIP_CONTROLS = {
    "low_activations": {"probe_logits": 1.46, "probe_state": 7.4e-2,
                        "streams": 1.19},
    "bfloat16_state": {"state_arithmetic": 1.295e-2}}


def test_the_limits_pass_the_program_and_fail_each_control():
    """``judge`` is the one place the cell's limits are applied, to the
    program's readings and to a control's in their place: every limit
    lies between the two readings taken on the chip, with room on both
    sides (a third of the way at least, on a log scale)."""
    from lib.nemotron_h_family import judge
    assert all(judge(CHIP_PROGRAM).values())
    for name, readings in CHIP_CONTROLS.items():
        assert not all(judge(readings).values()), name
    assert not any(judge({k: float("nan") for k in CHIP_PROGRAM}).values())
    low = {**CHIP_CONTROLS["low_activations"],
           **CHIP_CONTROLS["bfloat16_state"]}
    for key, sound in CHIP_PROGRAM.items():
        limit = next(v for v in np.geomspace(sound, low[key], 2001)
                     if not judge({key: v})[f"{key}_within_tolerance"])
        share = np.log(limit / sound) / np.log(low[key] / sound)
        assert 0.2 < share < 0.8, (key, sound, limit, low[key])


# -- through the engine: pages, state, slots out of order -------------------

def _serve(cfg, prompts, budgets, **serving):
    params = _params(cfg, seed=4)
    eng = ServeEngine(NemotronHModel(cfg), {
        "serving": {**SERVING, **serving},
        "telemetry": {"enabled": False}}, params=params)
    reqs = [eng.submit(p, max_new_tokens=k)
            for p, k in zip(prompts, budgets)]
    eng.run_until_idle()
    return params, eng, reqs


@pytest.mark.parametrize("attn_impl", ["flash", "dense"])
def test_engine_streams_sit_on_the_reference_logits(attn_impl):
    """Five requests on three slots: admitted as slots free up, finished
    out of order (budgets 6, 3, 9, 12, 5), every prompt shorter than the
    32-token bucket.  Each emitted token is the reference's argmax on the
    engine's own context, and the reference's logit there is its top
    within PAGED_TOL: a slot's state left over from the request before,
    or taken in past the prompt's true length, would miss by far."""
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, 128, n)) for n in (5, 17, 30, 9, 12)]
    params, eng, reqs = _serve(cfg, prompts, (6, 3, 9, 12, 5))
    try:
        assert [r.finish_reason for r in reqs] == ["length"] * 5
        slots_used = {k for t, k, v in eng.aux_log}
        assert slots_used == {"prefill", "decode"}
        for r in reqs:
            seq = list(r.prompt) + list(r.tokens)
            ref, _ = _reference(params, [seq[:-1]], cfg)
            rows = np.asarray(ref)[0, len(r.prompt) - 1:]
            at = np.arange(len(r.tokens))
            assert float((rows.max(1) - rows[at, r.tokens]).max()) \
                <= PAGED_TOL
    finally:
        eng.close()


def test_paged_steps_against_the_reference_logits_and_state():
    """Prefill of a 13-token prompt in a 32-token bucket into slot 2 of a
    state that is not zero, then 5 forced ticks: logits of every step and
    the recurrent state at the end against the reference; the slot beside
    it is left as it was."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    model, params = NemotronHModel(cfg), _params(cfg, seed=5)
    slots, slot, page_len, max_pages = 4, 2, 8, 8
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 128, (13,))
    forced = rng.integers(0, 128, (5,))
    pool = jnp.zeros((1, 1 + max_pages, 2, page_len, 16), jnp.float32)
    state = {k: jnp.full(v.shape, 0.5, v.dtype)
             for k, v in model.serving_state(slots).items()}
    row = np.zeros((max_pages,), np.int32)
    row[:3] = [5, 2, 7]
    padded = np.zeros((1, 32), np.int32)
    padded[0, :13] = prompt
    logits, k, v, state = model.prefill_paged(
        params, jnp.asarray(padded), np.int32(13), np.int32(0), row, pool,
        pool, state=state, slot=np.int32(slot))
    got = [logits[0, 12]]
    table = np.zeros((slots, max_pages), np.int32)
    table[slot] = row
    lengths = jnp.zeros((slots,), jnp.int32).at[slot].set(13)
    active = np.arange(slots) == slot
    for tok in forced:
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(int(tok))
        lg, k, v, state, lengths = model.decode_step_paged(
            params, tokens, k, v, table, lengths, active, state=state,
            impl="pallas")
        got.append(lg[slot])
    seq = np.concatenate([prompt, forced])
    want, want_state = _reference(params, seq[None], cfg)
    assert float(jnp.abs(jnp.stack(got) - want[0, 12:]).max()) < PAGED_TOL
    assert float(jnp.abs(state["ssm"][:, slot] - want_state[:, 0]).max()) \
        < 1e-5
    assert int(lengths[slot]) == 18
    for name in ("ssm", "conv"):
        others = np.delete(np.asarray(state[name], np.float32), slot, axis=1)
        assert np.all(others == 0.5)


def test_engine_holds_state_by_slot_and_says_its_bytes(tmp_path):
    params = _params(seed=4)
    eng = ServeEngine(NemotronHModel(TINY), {
        "serving": SERVING,
        "telemetry": {"enabled": True,
                      "output_path": str(tmp_path / "nemotron_tel")}},
        params=params)
    try:
        assert eng.cache["state"]["ssm"].shape == (2, 3, 8, 8, 16)
        assert eng.cache["state"]["ssm"].dtype == jnp.float32
        assert eng.cache["state"]["conv"].shape == (2, 3, 3, 64 + 2 * 2 * 16)
        assert eng.cache_spec.heads == 2 and eng.cache_spec.layers == 1
        assert eng.state_bytes == {
            "ssm": 2 * 3 * 8 * 8 * 16 * 4, "conv": 2 * 3 * 3 * 128 * 4,
            "kv": eng.cache_spec.bytes}
        gauge = next(m for m in eng.telemetry.registry.metrics()
                     if m.name == "serve_state_bytes")
        assert gauge.value(kind="ssm") == eng.state_bytes["ssm"]
        assert gauge.value(kind="kv") == eng.cache_spec.bytes
        req = eng.submit([1, 2, 3, 4], max_new_tokens=3)
        eng.run_until_idle()
        assert len(req.tokens) == 3
        with pytest.raises(NotImplementedError, match="state"):
            eng.adopt_request([1, 2, 3], 4, 2, None, [b""])
    finally:
        eng.close()


# -- the refusals -----------------------------------------------------------

@pytest.mark.parametrize("serving,named", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"prefill_chunk_len": 16}, "prefill_chunk_len"),
    ({"prefix_cache": True,
      "kv_tier": {"idle_park_ticks": 4, "host_budget_pages": 8}}, "kv_tier"),
    ({"page_len": 0}, "page_len"),
    ({"speculate_k": 2, "draft": {"d_model": 32, "n_layer": 1,
                                  "n_head": 2}}, "speculate_k"),
    ({"quantization": {"kv": "int8"}}, "quantization"),
    ({"lora": {"rank": 4}}, "lora")],
    ids=["prefix_cache", "chunked_prefill", "kv_tier", "slot_cache",
         "speculation", "int8", "lora"])
def test_engine_refuses_what_request_state_cannot_hold_yet(serving, named):
    with pytest.raises(ValueError, match=named):
        ServeEngine(NemotronHModel(TINY),
                    {"serving": {**SERVING, **serving}})


@pytest.mark.parametrize("field,value,named", [
    ("n_group", 2, "group"), ("num_nextn_predict_layers", 1, "prediction"),
    ("mlp_hidden_act", "silu", "relu2"), ("tie_word_embeddings", True, "tie"),
    ("attention_bias", True, "bias"), ("hybrid_override_pattern", "MEM-E",
                                       "pattern"),
    ("num_hidden_layers", 4, "length"), ("experts_held", (12, 8), "held"),
    ("attn_impl", "ring", "attn")])
def test_config_refuses_what_is_not_built(field, value, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(TINY, **{field: value})


@pytest.mark.parametrize("kwarg", ["k_scale", "lora"])
def test_paged_steps_refuse_gpt2s_arms(kwarg):
    with pytest.raises(NotImplementedError, match=kwarg):
        NemotronHModel(TINY).decode_step_paged(
            None, None, None, None, None, None, None, state=None,
            **{kwarg: object()})


# -- nothing new on the BERT or GPT-2 path ----------------------------------

@pytest.mark.parametrize("path", ["bert_train", "gpt2_serve"])
def test_other_models_import_nothing_of_this_one(path):
    code = {"bert_train": """
import sys, deepspeed_tpu
from deepspeed_tpu.models.bert import BertConfig, BertModel
cfg = {"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
       "bf16": {"enabled": True}, "zero_optimization": {"stage": 0},
       "optimizer": {"type": "Adam", "params": {"lr": 1e-4}}}
model = BertModel(BertConfig(vocab_size=64, hidden_size=16,
    num_hidden_layers=1, num_attention_heads=2, intermediate_size=32,
    max_position_embeddings=16))
engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
""", "gpt2_serve": """
import sys
from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.models import GPT2Model
from deepspeed_tpu.models.gpt2 import GPT2Config
eng = ServeEngine(GPT2Model(GPT2Config(vocab_size=64, n_positions=32,
    d_model=16, n_layer=1, n_head=2, remat=None, attn_impl="flash")),
    {"serving": {"slots": 2, "max_seq_len": 16, "prefill_len": 8,
                 "page_len": 8}})
eng.submit([1, 2, 3], max_new_tokens=2)
eng.run_until_idle()
eng.close()
"""}[path] + """
new = [m for m in sys.modules if m.startswith("deepspeed_tpu")]
assert not [m for m in new if "nemotron" in m or "ssm" in m
            or "dropless" in m], new
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu",
                              "XLA_FLAGS": ""})     # one device
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]
