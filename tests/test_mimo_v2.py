"""MiMo-V2 as MiMo-V2.5 configures it: each new piece against a line of
jax.numpy written by hand (the sink, the window's edge, the partial
rotation and the two thetas, keys wider than values, grouped keys at 8 and
at 16 a head), each new kernel body in interpret mode against its dense
arm, the model against the benchmark's plain float32 reference, prefill then
decode through the page pool and the window state against the reference's
full forward, the shares of the experts against the uncut layer, and the
refusals.  CPU, tiny widths, seeded weights.  (Its cell's rehearsal:
tests/test_benchmark_cells.py.)"""
import dataclasses
import functools
import hashlib
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
from deepspeed_tpu.models.mimo_v2 import MimoV2Config, MimoV2Model
from deepspeed_tpu.models.walked import grouped_causal_attention, rope
from deepspeed_tpu.ops.pallas.decode_attention import (
    decode_attention_paged, decode_attention_slots, paged_decode_arm,
    paged_pages_per_block, slot_decode_reference, window_decode_attention)
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_fwd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import mimo_v2_reference  # noqa: E402

TINY = MimoV2Config(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=4,
    hybrid_layer_pattern=(0, 1, 1, 0), moe_layer_freq=(0, 1, 1, 1),
    num_attention_heads=8, num_key_value_heads=2, head_dim=24, v_head_dim=16,
    swa_num_attention_heads=8, swa_num_key_value_heads=4, swa_head_dim=24,
    swa_v_head_dim=16, sliding_window=8, n_routed_experts=16,
    num_experts_per_tok=3, experts_held=(4, 8), max_position_embeddings=256,
    attn_impl="dense")
SERVING = {"slots": 3, "page_len": 8, "max_seq_len": 64, "prefill_len": 32,
           "prefix_cache": False}
# float32 on the CPU: the model and the reference differ by summation
# order (measured 2e-7 on logits of size 0.65); leaving the sink out moves
# them by 0.2, a window of 16 for 8 by 0.26
F32_TOL = 5e-6


def _params(cfg=TINY, seed=0):
    return drawn_once(MimoV2Model, cfg, seed)


def _keys(cfg=TINY):
    return dataclasses.asdict(cfg)


def _reference(params, tokens, cfg=TINY, **switches):
    with jax.default_matmul_precision("highest"):
        return np.asarray(mimo_v2_reference.mimo_v2_logits(
            params, tokens, _keys(cfg), **switches))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, shape).astype(np.int32)


def _qkv(rng, S, hq, hkv, T, dk, dv):
    q = jnp.asarray(rng.randn(S, hq, dk), jnp.float32)
    k = jnp.asarray(rng.randn(S, hkv, T, dk), jnp.float32)
    v = jnp.asarray(rng.randn(S, hkv, T, dv), jnp.float32)
    return q, k, v


# -- the pieces, each against a line by hand -----------------------------

def test_the_sink_takes_weight_and_gives_no_value():
    """One query, one head: ``p_j = exp(s_j - m) / (sum exp(s - m) +
    exp(b - m))``."""
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng, 1, 1, 1, 8, 24, 16)
    b = jnp.asarray([0.7], jnp.float32)
    s = np.asarray(k[0, 0] @ q[0, 0]) / np.sqrt(24)
    m = max(s.max(), 0.7)
    p = np.exp(s - m) / (np.exp(s - m).sum() + np.exp(0.7 - m))
    want = p @ np.asarray(v[0, 0])
    n = jnp.asarray([8], jnp.int32)
    for impl in ("dense", "pallas"):
        got = decode_attention_slots(q, k, v, n, sink=b, impl=impl)
        np.testing.assert_allclose(got[0, 0], want, atol=1e-6)
    assert p.sum() < 1.0
    no_sink = decode_attention_slots(q, k, v, n, impl="dense")
    assert np.abs(np.asarray(no_sink[0, 0]) - want).max() > 1e-2


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_the_window_edge_key_t_minus_w_is_out_and_the_next_is_in(impl):
    """A window of 8 at query 20: keys 13..20.  Key 12 moved: no change;
    key 13 moved: a change."""
    rng = np.random.RandomState(1)
    B, H, T, W = 1, 2, 24, 8
    q, k, v = (jnp.asarray(rng.randn(B, H, T, 16), jnp.float32)
               for _ in range(3))

    def attend(k, v):
        if impl == "flash":
            return flash_attention_fwd(q, k, v, window=W, block_q=8,
                                       block_k=8)
        return grouped_causal_attention(q, k, v, window=W, sm_scale=0.25)

    base = np.asarray(attend(k, v))[0, :, 20]
    out = np.asarray(attend(k.at[:, :, 12].add(5.0),
                            v.at[:, :, 12].add(5.0)))[0, :, 20]
    np.testing.assert_array_equal(out, base)
    moved = np.asarray(attend(k.at[:, :, 13].add(5.0),
                              v.at[:, :, 13].add(5.0)))[0, :, 20]
    assert np.abs(moved - base).max() > 1e-3
    # by hand, head 0
    s = np.asarray(k[0, 0, 13:21] @ q[0, 0, 20]) * 0.25
    p = np.exp(s - s.max())
    np.testing.assert_allclose(base[0], (p / p.sum()) @ np.asarray(
        v[0, 0, 13:21]), atol=1e-5)


@pytest.mark.parametrize("theta", [1e7, 1e4])
def test_partial_rotation_turns_the_first_dims_and_leaves_the_rest(theta):
    """8 of 24 dims rotated: pair i is (x[i], x[i + 4]) at ``pos *
    theta**(-i/4)``; dims 8.. pass bit for bit."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(1, 2, 5, 24), jnp.float32)
    pos = jnp.asarray([[3, 4, 5, 6, 700]], jnp.int32)
    got = np.asarray(rope(x, pos, theta, rotary_dim=8))
    np.testing.assert_array_equal(got[..., 8:], np.asarray(x)[..., 8:])
    xs = np.asarray(x)
    for t, p in enumerate(np.asarray(pos)[0]):
        for i in range(4):
            a = p * theta ** (-i / 4)
            np.testing.assert_allclose(
                got[0, :, t, i],
                xs[0, :, t, i] * np.cos(a) - xs[0, :, t, i + 4] * np.sin(a),
                atol=2e-5)
            np.testing.assert_allclose(
                got[0, :, t, i + 4],
                xs[0, :, t, i + 4] * np.cos(a) + xs[0, :, t, i] * np.sin(a),
                atol=2e-5)
    # the whole head, as before: the default
    np.testing.assert_array_equal(np.asarray(rope(x, pos, theta)),
                                  np.asarray(rope(x, pos, theta,
                                                  rotary_dim=24)))


def test_the_two_kinds_of_layer_rotate_at_their_own_theta():
    cfg = TINY
    from deepspeed_tpu.models.mimo_v2 import _qkv as model_qkv
    from deepspeed_tpu.models.walked import at as _at
    params = _params()
    h = jnp.asarray(np.random.RandomState(3).randn(1, 6, 64), jnp.float32)
    pos = jnp.arange(6, dtype=jnp.int32)[None] + 50
    for kind, theta in (("full", cfg.rope_theta),
                        ("window", cfg.swa_rope_theta)):
        ap = _at(params[kind], 0)
        q, k, v = model_qkv(cfg, kind, ap, h, pos)
        hkv = cfg.kv_heads(kind)
        raw = (h @ ap["k_w"]).reshape(1, 6, hkv, 24).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(
            k, rope(raw, pos, theta, rotary_dim=cfg.rotary_dim), atol=1e-6)
        assert k.shape == (1, hkv, 6, 24) and v.shape == (1, hkv, 6, 16)
        np.testing.assert_allclose(
            v, 0.707 * (h @ ap["v_w"]).reshape(1, 6, hkv, 16).transpose(
                0, 2, 1, 3), atol=1e-6)


@pytest.mark.parametrize("rep", [8, 16], ids=["8_a_head", "16_a_head"])
@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_keys_wider_than_values_under_grouped_heads(impl, rep):
    """``Dk != Dv`` and ``rep`` query heads a key head through the paged
    kernel: by hand, query head h on key head h // rep."""
    rng = np.random.RandomState(4)
    hkv, page_len, dk, dv, S, M = 2, 8, 24, 16, 3, 4
    hq = hkv * rep
    P = 1 + S * M
    q = jnp.asarray(rng.randn(S, hq, dk), jnp.float32)
    kp = jnp.asarray(rng.randn(P, hkv, page_len, dk), jnp.float32)
    vp = jnp.asarray(rng.randn(P, hkv, page_len, dv), jnp.float32)
    table = jnp.asarray(1 + np.arange(S * M).reshape(S, M), jnp.int32)
    lengths = jnp.asarray([0, 11, 32], jnp.int32)
    got = np.asarray(decode_attention_paged(q, kp, vp, table, lengths,
                                            impl=impl))
    assert got.shape == (S, hq, dv)
    assert (got[0] == 0).all()
    for s, n in ((1, 11), (2, 32)):
        ks = np.asarray(kp)[np.asarray(table)[s]].transpose(1, 0, 2, 3)
        vs = np.asarray(vp)[np.asarray(table)[s]].transpose(1, 0, 2, 3)
        ks, vs = ks.reshape(hkv, -1, dk)[:, :n], vs.reshape(hkv, -1, dv)[:, :n]
        for h in (0, rep - 1, rep, hq - 1):
            sc = ks[h // rep] @ np.asarray(q)[s, h] / np.sqrt(dk)
            p = np.exp(sc - sc.max())
            np.testing.assert_allclose(got[s, h], (p / p.sum()) @ vs[h // rep],
                                       atol=2e-6)


def test_two_widths_keep_the_grouped_body_and_size_its_block():
    shape = (4, 64, 256, 2)
    assert paged_decode_arm(*shape, q_heads=64) == "direct"
    # K and V of a page in flight, double-buffered: 2 x 4 x 64 x (256 +
    # 128) x 2 bytes, twice
    assert paged_pages_per_block(*shape, 128, q_heads=64,
                                 v_head_dim=128) == 16
    # one width: what it was
    assert paged_pages_per_block(2, 16, 128, 2, 320, q_heads=32) == \
        paged_pages_per_block(2, 16, 128, 2, 320, q_heads=32, v_head_dim=128)
    with pytest.raises(NotImplementedError, match="two widths"):
        decode_attention_paged(
            jnp.zeros((1, 2, 24)), jnp.zeros((2, 2, 8, 24)),
            jnp.zeros((2, 2, 8, 16)), jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1,), jnp.int32), impl="dense")


# -- the kernel bodies in interpret mode against their dense arms --------

@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("hq,hkv", [(8, 1), (8, 4), (3, 3)])
def test_slot_kernel_equals_its_dense_arm(hq, hkv, sink):
    """``ds_decode_attn`` / ``ds_window_decode_attn``'s one body: grouped
    heads, keys wider than values, a traced base into the slots of every
    layer, blocks over the cache's length, free slots exact zeros."""
    rng = np.random.RandomState(5)
    S, T, dk, dv = 4, 40, 24, 16
    q = jnp.asarray(rng.randn(S, hq, dk), jnp.float32)
    k = jnp.asarray(rng.randn(3 * S, hkv, T, dk), jnp.float32)
    v = jnp.asarray(rng.randn(3 * S, hkv, T, dv), jnp.float32)
    b = jnp.asarray(rng.randn(hq), jnp.float32) if sink else None
    lengths = jnp.asarray([0, 1, 17, 40], jnp.int32)
    for base in (0, 2 * S):
        want = slot_decode_reference(q, k[base:base + S], v[base:base + S],
                                     lengths, sink=b)
        for block_k in (16, 256):
            got = decode_attention_slots(q, k, v, lengths, sink=b,
                                         base=jnp.int32(base),
                                         block_k=block_k, impl="pallas")
            np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
            assert (np.asarray(got[0]) == 0).all()


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_window_decode_reads_a_wrapped_ring_in_any_order(impl):
    """Position p at row p % W: after 2.5 wraps the ring's rows are out
    of order and softmax does not care; with fewer keys than W only the
    first rows are live, whatever lies in the others."""
    rng = np.random.RandomState(6)
    W, hq, hkv, dk, dv = 8, 8, 4, 24, 16
    ks = rng.randn(hkv, 20, dk).astype(np.float32)
    vs = rng.randn(hkv, 20, dv).astype(np.float32)
    q = jnp.asarray(rng.randn(2, hq, dk), jnp.float32)
    sink = jnp.asarray(rng.randn(hq), jnp.float32)
    ring_k = np.full((2, hkv, W, dk), 1e4, np.float32)
    ring_v = np.full((2, hkv, W, dv), 1e4, np.float32)
    for p in range(20):                     # slot 0: 20 keys so far
        ring_k[0, :, p % W], ring_v[0, :, p % W] = ks[:, p], vs[:, p]
    for p in range(3):                      # slot 1: 3
        ring_k[1, :, p], ring_v[1, :, p] = ks[:, p], vs[:, p]
    got = np.asarray(window_decode_attention(
        q, jnp.asarray(ring_k), jnp.asarray(ring_v),
        jnp.asarray([20, 3], jnp.int32), sink, impl=impl))
    for s, keys in ((0, range(12, 20)), (1, range(3))):
        keys = list(keys)
        want = slot_decode_reference(
            q[s:s + 1], jnp.asarray(ks[None, :, keys]),
            jnp.asarray(vs[None, :, keys]),
            jnp.asarray([len(keys)], jnp.int32), sink=sink)
        np.testing.assert_allclose(got[s], want[0], atol=2e-6)


@pytest.mark.parametrize("window,sink,bq,bk", [
    (None, False, 64, 64), (32, True, 64, 64), (32, True, 32, 64),
    (32, False, 64, 32), (128, True, 32, 32), (5, True, 16, 16),
    (None, True, 64, 64)])
def test_flash_forward_with_a_window_a_sink_grouped_keys_and_two_widths(
        window, sink, bq, bk):
    rng = np.random.RandomState(7)
    B, hq, hkv, T, dk, dv = 2, 8, 2, 200, 24, 16
    q = jnp.asarray(rng.randn(B, hq, T, dk), jnp.float32)
    k = jnp.asarray(rng.randn(B, hkv, T, dk), jnp.float32)
    v = jnp.asarray(rng.randn(B, hkv, T, dv), jnp.float32)
    b = jnp.asarray(rng.randn(hq), jnp.float32) if sink else None
    got = flash_attention_fwd(q, k, v, window=window, sink=b, block_q=bq,
                              block_k=bk)
    want = grouped_causal_attention(q, k, v, window=window, sink=b,
                                    sm_scale=24 ** -0.5)
    np.testing.assert_allclose(got, want, atol=3e-6)


def test_a_window_layers_flash_grid_spans_the_band_only(monkeypatch):
    """4,096 queries in blocks of 256 against a window of 128: two key
    blocks a query block, not sixteen."""
    fa = sys.modules["deepspeed_tpu.ops.pallas.flash_attention"]
    seen = []
    real = fa.pl.pallas_call

    def spy(kernel, **kw):
        seen.append(kw["grid"])
        return real(kernel, **kw)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    q = jax.ShapeDtypeStruct((1, 2, 4096, 128), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention_fwd(
        q, q, q, window=128, block_q=256, block_k=256), q)
    jax.eval_shape(lambda q: flash_attention_fwd(
        q, q, q, block_q=256, block_k=256), q)
    assert seen == [(2, 16, 2), (2, 16, 16)]


@pytest.mark.parametrize("tc,live,window,bq,bk", [
    (64, 64, None, 32, 32), (64, 40, None, 32, 32), (64, 0, None, 32, 32),
    (64, 64, 64, 32, 32), (64, 64, 48, 16, 32), (64, 23, 64, 32, 16),
    (128, 100, 20, 64, 64), (32, 32, 200, 64, 32)])
def test_flash_forward_over_keys_ahead_of_its_queries(tc, live, window, bq,
                                                      bk):
    """A chunk's queries at key positions ``tc ..`` over ``[context ;
    chunk]`` keys of which the last ``live`` context keys are live (traced)
    against the dense mask: causal and band shifted by ``tc``."""
    rng = np.random.RandomState(11)
    B, hq, hkv, T, dk, dv = 1, 8, 2, 72, 24, 16
    q = jnp.asarray(rng.randn(B, hq, T, dk), jnp.float32)
    k = jnp.asarray(rng.randn(B, hkv, tc + T, dk), jnp.float32)
    v = jnp.asarray(rng.randn(B, hkv, tc + T, dv), jnp.float32)
    got = jax.jit(lambda n: flash_attention_fwd(
        q, k, v, window=window, block_q=bq, block_k=bk, ctx_live=n))(
            jnp.int32(live))
    kr, vr = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    s = np.asarray(jnp.einsum("bhqd,bhkd->bhqk", q, kr)) * 24 ** -0.5
    kk, qq = np.arange(tc + T)[None, :], tc + np.arange(T)[:, None]
    ok = (kk <= qq) & (kk >= tc - live)
    if window is not None:
        ok &= kk > qq - window
    s = np.where(ok[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True),
                     np.asarray(vr))
    np.testing.assert_allclose(got, want, atol=3e-6)


def test_flash_forward_without_context_is_the_call_it_was(monkeypatch):
    """``Tc == 0``: the same kernel, name and grid as before context keys
    existed (``ds_flash_fwd``), bit for bit the result of the same call
    with a context that holds no live key taken off."""
    fa = sys.modules["deepspeed_tpu.ops.pallas.flash_attention"]
    seen = []
    real = fa.pl.pallas_call

    def spy(kernel, **kw):
        seen.append((kw["name"], kw.get("grid")))
        return real(kernel, **kw)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    rng = np.random.RandomState(12)
    q, k, v = (jnp.asarray(rng.randn(1, 4, 64, 16), jnp.float32)
               for _ in range(3))
    plain = flash_attention_fwd(q, k, v, window=24, block_q=32, block_k=32)
    assert seen == [("ds_flash_fwd", (4, 2, 2))]
    dead = jnp.full((1, 4, 32, 16), 7.0, jnp.float32)
    ahead = flash_attention_fwd(
        q, jnp.concatenate([dead, k], 2), jnp.concatenate([dead, v], 2),
        window=24, block_q=32, block_k=32, ctx_live=jnp.int32(0))
    assert seen[1][0] == "ds_flash_fwd_ctx"
    np.testing.assert_allclose(ahead, plain, atol=1e-6)
    with pytest.raises(AssertionError):
        flash_attention_fwd(q, jnp.concatenate([dead, k], 2),
                            jnp.concatenate([dead, v], 2))


@pytest.mark.parametrize("T,bk,sink,lens", [
    (32, 8, False, [0, 1, 7, 8, 9, 31, 32, 100]),
    (32, 16, True, [5, 32, 33, 0]),
    (64, 16, False, [17, 64, 4097])])
def test_window_decode_walks_a_ring_in_blocks(T, bk, sink, lens,
                                              monkeypatch):
    """The blocked walk (the slot body, ``bk`` rows of every key head a
    grid step, online softmax, dead blocks skipped) against the dense
    reference, at rings partly filled, just full and long wrapped; a free
    slot gives zeros; a layer's base is traced.  The block comes from the
    shapes: the budget is cut so that these small rings do not fit it."""
    da = sys.modules["deepspeed_tpu.ops.pallas.decode_attention"]
    monkeypatch.setattr(da, "PAGED_KV_VMEM_BUDGET",
                        2 * 2 * bk * (24 + 16) * 4)
    assert da.window_ring_block(2, T, 24 + 16, 4) == bk
    rng = np.random.RandomState(13)
    S, hq, hkv, dk, dv = len(lens), 16, 2, 24, 16
    q = jnp.asarray(rng.randn(S, hq, dk), jnp.float32)
    k = jnp.asarray(rng.randn(2 * S, hkv, T, dk), jnp.float32)
    v = jnp.asarray(rng.randn(2 * S, hkv, T, dv), jnp.float32)
    b = jnp.asarray(rng.randn(hq), jnp.float32) if sink else None
    n = jnp.asarray(lens, jnp.int32)
    got = jax.jit(lambda base: window_decode_attention(
        q, k, v, n, b, base=base))(jnp.int32(S))
    want = slot_decode_reference(q, k[S:], v[S:], jnp.minimum(n, T), sink=b)
    np.testing.assert_allclose(got, want, atol=3e-6)
    for s, length in enumerate(lens):
        if length == 0:
            assert not np.asarray(got[s]).any()


def test_window_decode_chooses_its_walk_from_the_shapes(monkeypatch):
    """MiMo's rings (128 keys on 8 heads of 256 + 128) stay ONE block a
    slot, the call it was: the slot body, its name, grid ``(slots, 1)``.
    Command A+'s (4,096 keys on 8 heads of 128 + 128) are walked by the
    same body in blocks of 512 rows of every key head."""
    da = sys.modules["deepspeed_tpu.ops.pallas.decode_attention"]
    assert da.window_ring_block(8, 128, 256 + 128, 2) == 128
    assert da.window_ring_block(8, 4096, 128 + 128, 2) == 512
    seen = []
    real = da.pl.pallas_call

    def spy(kernel, **kw):
        seen.append((kw["name"], kw["grid_spec"].grid))
        return real(kernel, **kw)

    monkeypatch.setattr(da.pl, "pallas_call", spy)
    bf = jnp.bfloat16
    for hq, t, dk in ((64, 128, 256), (128, 4096, 128)):
        jax.eval_shape(
            lambda q, k, v, n: window_decode_attention(q, k, v, n, None),
            jax.ShapeDtypeStruct((4, hq, dk), bf),
            jax.ShapeDtypeStruct((4, 8, t, dk), bf),
            jax.ShapeDtypeStruct((4, 8, t, 128), bf),
            jax.ShapeDtypeStruct((4,), jnp.int32))
    assert seen == [("ds_window_decode_attn", (4, 1)),
                    ("ds_window_decode_attn", (4, 8))]


def test_mimos_window_decode_is_bit_for_bit_the_slot_body():
    """What ``window_decode_attention`` gives at MiMo's shapes is what
    ``decode_attention_slots`` gives for the whole ring a step."""
    rng = np.random.RandomState(14)
    q, k, v = _qkv(rng, 3, 8, 4, 8, 24, 16)
    b = jnp.asarray(rng.randn(8), jnp.float32)
    n = jnp.asarray([3, 8, 30], jnp.int32)
    got = window_decode_attention(q, k, v, n, b)
    want = decode_attention_slots(q, k, v, jnp.minimum(n, 8), sink=b,
                                  block_k=8, name="ds_window_decode_attn")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- the model against the reference -------------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_apply_matches_the_reference_in_float32(attn_impl):
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    params = _params()
    tokens = _tokens((2, 40))
    got = np.asarray(MimoV2Model(cfg).apply(params, tokens))
    want = _reference(params, tokens, block=16)
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < F32_TOL


@pytest.mark.parametrize("switch", [{"sink_on": False}, {"window": 16},
                                    {"round_acts": True,
                                     "act_dtype": jnp.float8_e5m2}],
                         ids=["no_sink", "window_16", "e5m2_stream"])
def test_the_float32_tolerance_fails_each_control(switch):
    params = _params()
    tokens = _tokens((1, 40))
    want = _reference(params, tokens)
    assert np.abs(_reference(params, tokens, **switch) - want).max() \
        > 1000 * F32_TOL


def test_the_sixteenth_shares_counted_once_make_the_uncut_layer():
    """The parts of the expert layers of all shares, added to what every
    chip computes alike counted once, are the uncut model's layer: here
    through the whole forward of a model with ONE expert layer last, whose
    logits are linear in that layer's output."""
    cfg = dataclasses.replace(
        TINY, num_hidden_layers=2, hybrid_layer_pattern=(0, 1),
        moe_layer_freq=(0, 1), experts_held=None)
    whole = MimoV2Model(cfg)
    params = whole.init(jax.random.PRNGKey(2))
    x = jnp.asarray(np.random.RandomState(8).randn(12, 64), jnp.float32)
    from deepspeed_tpu.models.mimo_v2 import _experts
    from deepspeed_tpu.models.walked import at as _at
    from deepspeed_tpu.models.walked import stacked_experts as _stacked_experts

    def layer(c, p):
        return _experts(c, _at(p["moe"], 0), _stacked_experts(p), 0, x, None)

    full, stats = layer(cfg, params)
    assert int(stats.rows) == 12 * 3
    total, rows, elsewhere = 0.0, 0, 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=(first, 4))
        held = dict(params, moe={
            k: (v[:, first:first + 4] if k in ("gate_w", "up_w", "down_w")
                else v) for k, v in params["moe"].items()})
        part, st = layer(share, held)
        total = total + part
        rows += int(st.rows)
        elsewhere += int(st.rows_elsewhere)
    np.testing.assert_allclose(total, full, atol=1e-6)
    assert rows == 12 * 3 and elsewhere == 3 * 12 * 3
    # and the reference's share is the program's
    held = dataclasses.replace(cfg, experts_held=(4, 8))
    cut = dict(params, moe={
        k: (v[:, 4:12] if k in ("gate_w", "up_w", "down_w") else v)
        for k, v in params["moe"].items()})
    tokens = _tokens((1, 20))
    got = np.asarray(MimoV2Model(held).apply(cut, tokens))
    assert np.abs(got - _reference(cut, tokens, held)).max() < F32_TOL


def test_apply_reports_the_share_and_init_is_as_assumed():
    model = MimoV2Model(TINY)
    params = _params()
    _, aux = model.apply(params, _tokens((2, 16)), aux=True)
    assert set(aux) == set(model.serving_aux)
    live = 2 * 16 * 3 * 3                   # tokens x top-k x expert layers
    assert int(aux["moe_rows"]) + int(aux["moe_rows_elsewhere"]) == live
    assert "sink" in params["window"] and "sink" not in params["full"]
    assert np.abs(_whole(params["moe"]["router_bias"])).max() == 0.0
    sinks = _whole(params["window"]["sink"])
    assert sinks.std() > 0.5 and abs(sinks.mean() - np.log(8)) < 0.5
    # the experts alone are stacked; a layer's own leaves are a tuple of
    # one array a layer
    assert params["moe"]["gate_w"].shape == (3, 8, 64, 32)
    assert [a.shape for a in params["full"]["k_w"]] == [(64, 2 * 24)] * 2
    assert [a.shape for a in params["window"]["k_w"]] == [(64, 4 * 24)] * 2
    assert [a.shape for a in params["moe"]["router_w"]] == [(64, 16)] * 3


# -- the parameter tree: a leaf a layer, the numbers of the stacked draw ---

def _whole(leaf):
    """A kind's leaf as one array [layers, ...]: the per-layer tuple
    stacked, the experts' as they are."""
    return np.stack([np.asarray(a) for a in leaf]) \
        if isinstance(leaf, tuple) else np.asarray(leaf)


def _draw_digests(params):
    """sha256 (12 hex digits) of each kind's leaves as the arrays
    ``init`` drew when it stacked every leaf by kind ([layers, ...], names
    sorted), and of the leaves outside the layers."""
    def digest(tree):
        h = hashlib.sha256()
        for name in sorted(tree):
            a = _whole(tree[name])
            h.update(f"{name}{a.shape}{a.dtype}".encode())
            h.update(np.ascontiguousarray(a).tobytes())
        return h.hexdigest()[:12]

    kinds = ("full", "window", "dense", "moe")
    out = {k: digest(params[k]) for k in kinds}
    out["top"] = digest({k: v for k, v in params.items() if k not in kinds})
    return out


@functools.lru_cache(maxsize=None)
def _logits_by_each_door(seed):
    """Every 16th logit of the last position: ``apply`` on two sequences
    of 40; ``prefill_paged`` of the first 21 tokens into slot 1; the third
    ``decode_step_paged`` tick after it (past a page's end and a wrap of
    the ring)."""
    model, params = MimoV2Model(TINY), _params(seed=seed)
    tokens = _tokens((2, 40), seed=seed)
    out = {"apply": model.apply(params, tokens)[:, -1, ::16]}
    slots, max_pages, n = 3, 8, 21
    spec = PagedKVCacheSpec(
        layers=TINY.n_layer, slots=slots, heads=TINY.n_kv_head,
        pages=1 + max_pages, page_len=8, head_dim=TINY.d_head,
        max_pages=max_pages, v_head_dim=TINY.d_head_v)
    cache = init_paged_cache(spec)
    state = {k: jnp.zeros(v.shape, v.dtype)
             for k, v in model.serving_state(slots).items()}
    padded = np.zeros((1, 32), np.int32)
    padded[0, :n] = tokens[0, :n]
    row = 1 + np.arange(max_pages, dtype=np.int32)
    table = jnp.zeros((slots, max_pages), jnp.int32).at[1].set(row)
    logits, k, v, state = model.prefill_paged(
        params, padded, np.int32(n), np.int32(0), row, cache["k"],
        cache["v"], state=state, slot=np.int32(1))
    out["prefill"] = logits[0, n - 1, ::16]
    active = jnp.asarray([False, True, False])
    lengths = jnp.asarray([0, n, 0], jnp.int32)
    for t in range(3):
        step = jnp.zeros((slots,), jnp.int32).at[1].set(tokens[0, n + t])
        lg, k, v, state, lengths = model.decode_step_paged(
            params, step, k, v, table, lengths, active, state=state)
    out["decode"] = lg[1, ::16]
    return {k: np.asarray(v, np.float32) for k, v in out.items()}


# what ``init`` drew and the three doors gave while every leaf was stacked
# by kind (``jax.lax.map`` over the kind's keys; commit 8d5d562, PR 38),
# float32 then bfloat16, on this CPU
STACKED_DRAW = {
    (0, "float32"): {"full": "5f1706c86e9b", "window": "8dceb2e7a99b",
                     "dense": "acd405a363ef", "moe": "33e8ddf7eab4",
                     "top": "0d97f145cfa1"},
    (0, "bfloat16"): {"full": "9b832e07a8ec", "window": "41ac94a88b92",
                      "dense": "6c754c99c1fd", "moe": "0850d4f1a040",
                      "top": "09412b1d94b4"},
    (7, "float32"): {"full": "289f6deab391", "window": "eff441513b1d",
                     "dense": "3284245d39f5", "moe": "e7d39a53ddf3",
                     "top": "79d3b3acdd8a"},
    (7, "bfloat16"): {"full": "850f781a13a9", "window": "49e075e5a52c",
                      "dense": "c67adaa4dbd9", "moe": "10ee289d2bcc",
                      "top": "e7a99cdf099c"},
}
STACKED_LOGITS = {
    0: {"apply": [[-0.17135265469551086, -0.008733145892620087,
                   0.00838280189782381, 0.1593676209449768,
                   0.1284223049879074, 0.17724989354610443,
                   -0.1113058552145958, 0.2149115353822708],
                  [0.1598157286643982, -0.03689465671777725,
                   -0.06399724632501602, -0.19897069036960602,
                   -0.08582665771245956, 0.1457510143518448,
                   0.06297678500413895, 0.0348154678940773]],
        "prefill": [-0.18407219648361206, -0.18379442393779755,
                    0.16945233941078186, -0.10693280398845673,
                    0.007602738216519356, 0.1090153157711029,
                    -0.20610584318637848, 0.13520079851150513],
        "decode": [-0.10981537401676178, 0.12585003674030304,
                   0.10307719558477402, 0.0029998822137713432,
                   0.3080373704433441, 0.04799863323569298,
                   -0.35294708609580994, -0.17983035743236542]},
    7: {"apply": [[-0.07585667073726654, -0.07533682137727737,
                   0.020958950743079185, -0.13027839362621307,
                   0.18750856816768646, -0.07074227929115295,
                   0.0523948110640049, -0.02452256716787815],
                  [-0.080367311835289, -0.01886606402695179,
                   -0.2987804114818573, 0.27869874238967896,
                   0.002776301233097911, -0.09498466551303864,
                   -0.1317056119441986, -0.09103652089834213]],
        "prefill": [0.31082215905189514, -0.08211587369441986,
                    -0.1832636296749115, -0.042090270668268204,
                    -0.027796011418104172, -0.2038029283285141,
                    0.11534261703491211, -0.07496299594640732],
        "decode": [-0.04228822514414787, -0.21429774165153503,
                   0.17444942891597748, -0.06007743999361992,
                   0.1557263880968094, -0.058017924427986145,
                   -0.1635691523551941, -0.17934563755989075]},
}


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("seed,dtype", sorted(STACKED_DRAW))
def test_a_leaf_a_layer_stacked_is_the_stacked_draw_bit_for_bit(
        seed, dtype, jitted):
    """The layout changed, the numbers did not: each layer's leaves come
    from the key the stacked draw gave that layer, called eagerly (these
    tests) or inside a caller's jit (the benchmark makes its weights so)."""
    model = MimoV2Model(dataclasses.replace(TINY, param_dtype=dtype))
    init = jax.jit(model.init) if jitted else model.init
    params = init(jax.random.PRNGKey(seed))
    got, want = _draw_digests(params), dict(STACKED_DRAW[seed, dtype])
    if jitted:
        # pinned from the eager call; ``wte`` and ``lm_head``, drawn
        # outside any layer, round an ulp apart inside a jit, then as now
        got.pop("top"), want.pop("top")
    assert got == want
    for kind in ("full", "window", "dense", "moe"):
        for name, leaf in params[kind].items():
            stacked = kind == "moe" and name in ("gate_w", "up_w", "down_w")
            assert isinstance(leaf, tuple) != stacked, (kind, name)
            assert len(leaf) == TINY.count(kind)


@pytest.mark.parametrize("door", ["apply", "prefill", "decode"])
@pytest.mark.parametrize("seed", sorted(STACKED_LOGITS))
def test_each_door_gives_the_logits_it_gave_on_stacked_leaves(seed, door):
    got = _logits_by_each_door(seed)[door]
    want = np.asarray(STACKED_LOGITS[seed][door], np.float32)
    assert got.shape == want.shape and np.abs(want).max() > 0.2
    assert np.abs(got - want).max() < F32_TOL


# -- prefill then decode through the pool and the window state -----------

@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
@pytest.mark.parametrize("prompt_len", [3, 8, 21, 30])
def test_paged_steps_against_the_reference_logits(attn_impl, prompt_len):
    """Prefill of a prompt into slot 1, then 30 forced decode ticks:
    every step's logits against the reference on the whole context.  The
    window is 8 and a page 8: the contexts pass 2 wraps of the ring and
    cross three page boundaries; a prompt of 3 leaves ring rows 3..7
    unwritten (poisoned here) until decode fills them."""
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    model, params = MimoV2Model(cfg), _params()
    slots, page_len, max_pages, ticks = 3, 8, 8, 30
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, slots=slots, heads=cfg.n_kv_head,
        pages=1 + max_pages, page_len=page_len, head_dim=cfg.d_head,
        max_pages=max_pages, v_head_dim=cfg.d_head_v)
    cache = init_paged_cache(spec)
    assert cache["k"].shape[-1] == 24 and cache["v"].shape[-1] == 16
    state = {k: jnp.full(v.shape, 1e4, v.dtype)
             for k, v in model.serving_state(slots).items()}
    seq = _tokens((prompt_len + ticks,), seed=prompt_len)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :prompt_len] = seq[:prompt_len]
    row = np.zeros((max_pages,), np.int32)
    row[:] = 1 + np.arange(max_pages)
    table = np.zeros((slots, max_pages), np.int32)
    table[1] = row
    table = jnp.asarray(table)
    logits, k, v, state = model.prefill_paged(
        params, padded, np.int32(prompt_len), np.int32(0), row, cache["k"],
        cache["v"], state=state, slot=np.int32(1))
    got = [np.asarray(logits[0, prompt_len - 1])]
    # the slots beside it keep what they held
    for leaf in state.values():
        assert (np.asarray(leaf[:, 0]) == 1e4).all()
        assert (np.asarray(leaf[:, 2]) == 1e4).all()
    active = jnp.asarray([False, True, False])
    lengths = jnp.asarray([0, prompt_len, 0], jnp.int32)
    step = jax.jit(lambda t, k, v, st, ln: model.decode_step_paged(
        params, t, k, v, table, ln, active, state=st))
    for t in range(ticks):
        tokens = np.zeros((slots,), np.int32)
        tokens[1] = seq[prompt_len + t]
        lg, k, v, state, lengths = step(tokens, k, v, state, lengths)
        got.append(np.asarray(lg[1]))
    want = _reference(params, seq[None])[0, prompt_len - 1:]
    assert np.abs(np.stack(got) - want).max() < F32_TOL
    assert int(lengths[1]) == prompt_len + ticks and int(lengths[0]) == 0
    for leaf in state.values():
        assert (np.asarray(leaf[:, 0]) == 1e4).all()


@pytest.mark.parametrize("attn_impl", ["flash", "dense"])
def test_engine_streams_sit_on_the_reference_logits(attn_impl):
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    params = _params()
    eng = ServeEngine(MimoV2Model(cfg), {"serving": SERVING}, params=params)
    try:
        toks = _tokens((2, 40), seed=3)
        prompts = [list(toks[0, :20]), list(toks[1, :5]),
                   list(toks[0, 5:36]), [3], list(toks[1, 10:27])]
        reqs = [eng.submit(p, max_new_tokens=24) for p in prompts]
        eng.run_until_idle()
        assert eng._decode_fn._cache_size() == 1
        assert eng._prefill_fn._cache_size() == 1
        for p, r in zip(prompts, reqs):
            out = list(r.result())
            assert len(out) == 24
            rows = _reference(params, np.asarray([p + out[:-1]]))[
                0, len(p) - 1:]
            slack = rows.max(-1) - rows[np.arange(len(out)), out]
            assert slack.max() < 1e-5
        kinds = {kind for _, kind, _ in eng.aux_log}
        assert kinds == {"prefill", "decode"}
        last = [v for _, kind, v in eng.aux_log if kind == "decode"][-1]
        assert last["window_kv_rows"] <= last["full_kv_tokens"]
    finally:
        eng.close()


def test_engine_holds_two_kinds_of_cache_and_says_their_bytes(tmp_path):
    model = MimoV2Model(TINY)
    eng = ServeEngine(model, {
        "serving": SERVING,
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}},
        params=_params())
    try:
        assert eng.cache["k"].shape == (2, 25, 2, 8, 24)
        assert eng.cache["v"].shape == (2, 25, 2, 8, 16)
        wk = eng.cache["state"]["window_k"]
        assert wk.shape == (2, 3, 4, 8, 24)
        # a window layer's bytes a slot do not grow with the context
        assert eng.state_bytes == {
            "window_k": wk.size * 4,
            "window_v": eng.cache["state"]["window_v"].size * 4,
            "kv": eng.cache_spec.bytes}
        assert eng.cache_spec.bytes == 2 * 25 * 2 * 8 * (24 + 16) * 4
        reg = eng.telemetry.registry
        gauge = reg.gauge("serve_cache_layers", "")
        assert (gauge.value(kind="full"), gauge.value(kind="window")) == (2, 2)
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=2)
        eng.submit([1] * 30, max_new_tokens=2)
        eng.run_until_idle()
        # one bucket of 32: 27 + 2 tokens of padding
        assert (eng.prefill_tokens, eng.prefill_pad_tokens) == (35, 29)
        assert reg.counter("serve_prefill_pad_tokens_total", "").value() == 29
    finally:
        eng.close()


@pytest.mark.parametrize("serving,named", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"prefill_chunk_len": 8}, "prefill_chunk_len"),
    ({"page_len": 0}, "page_len"),
    ({"speculate_k": 2, "draft": {"d_model": 32, "n_layer": 1,
                                  "n_head": 2}}, "speculate_k"),
    ({"quantization": {"kv": "int8"}}, "quantization"),
])
def test_engine_refuses_what_window_state_cannot_hold_yet(serving, named):
    with pytest.raises(ValueError, match=named):
        ServeEngine(MimoV2Model(TINY), {"serving": {**SERVING, **serving}},
                    params=_params())


@pytest.mark.parametrize("field,value,named", [
    ("n_group", 2, "group-limited"),
    ("n_shared_experts", 1, "shared expert"),
    ("add_full_attention_sink_bias", True, "add_full_attention_sink_bias"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("num_nextn_predict_layers", 3, "multi-token-prediction"),
    ("swa_head_dim", 32, "other head counts or widths"),
    ("scoring_func", "softmax", "scoring_func"),
])
def test_config_refuses_what_is_not_built(field, value, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(TINY, **{field: value})


def test_config_reads_the_published_row():
    """The catalog's own keys build the configuration as published: 9 full
    and 39 window layers, one dense FFN, 64 of 192 dims rotated, keys 256
    wide at rest."""
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2.5.json")) as f:
        file = json.load(f)
    fields = {f.name for f in dataclasses.fields(MimoV2Config)}
    keys = {k: v for k, v in file.items() if k in fields}
    keys.update(file["published"])
    cfg = MimoV2Config(**keys)
    assert (cfg.count("full"), cfg.count("window")) == (9, 39)
    assert (cfg.count("dense"), cfg.count("moe")) == (1, 47)
    assert (cfg.rotary_dim, cfg.k_width, cfg.d_head_v) == (64, 256, 128)
    assert cfg.kv_heads("full") == 4 and cfg.kv_heads("window") == 8
    cut = MimoV2Config(**{**keys, **{k: file[k] for k in file["reduced"]},
                          "n_routed_experts": 256, "experts_held": (0, 16)})
    assert (cut.n_layer, cut.count("window"), cut.count("moe")) == (2, 5, 6)
    state = MimoV2Model(cut).serving_state(192)
    assert state["window_k"].shape == (5, 192, 8, 128, 256)
    assert state["window_v"].shape == (5, 192, 8, 128, 128)
