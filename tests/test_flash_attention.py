"""Differential tests: Pallas flash attention vs dense XLA reference.

Mirrors the reference's kernel-vs-reference-implementation strategy
(reference: tests/unit/test_cuda_forward.py / test_cuda_backward.py —
DeepSpeedTransformerLayer vs a vendored HuggingFace BertEncoder over a
grid of shapes/dtypes).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def _rand_qkv(b, h, t, d, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, t, d), dtype)
    return mk(), mk(), mk()


def _dense(q, k, v, causal):
    if causal:
        return causal_attention(q, k, v)
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("t", [64, 128, 200, 384])
@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_dense(t, causal):
    q, k, v = _rand_qkv(2, 2, t, 64)
    out = flash_attention(q, k, v, causal=causal)
    ref = _dense(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_bf16():
    q, k, v = _rand_qkv(1, 2, 128, 64, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = causal_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), atol=3e-2)


@pytest.mark.parametrize("t", [128, 200])
@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_dense(t, causal):
    q, k, v = _rand_qkv(1, 2, t, 32, seed=1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)


def test_small_block_sizes_exercise_multiblock_path():
    q, k, v = _rand_qkv(1, 1, 64, 32, seed=2)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    ref = causal_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=16, block_k=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(causal_attention(q, k, v) ** 2)

    gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)


def test_cross_attention_lengths():
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 2, 96, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 160, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 160, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=False)
    ref = _dense(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def _dense_dropout_oracle(q, k, v, rate, rng, causal=True):
    """Dense attention applying the kernel's EXACT keep mask (same hash,
    same seed derivation) — fwd and grads must match the kernel bitwise
    up to fp32 reduction noise."""
    from attention_oracles import dense_dropout_oracle
    seed = jax.random.bits(rng, (), jnp.uint32)
    return dense_dropout_oracle(q, k, v, rate, seed, causal=causal)


def test_dropout_zero_rate_is_identity():
    q, k, v = _rand_qkv(1, 2, 96, 32)
    base = flash_attention(q, k, v)
    out = flash_attention(q, k, v, dropout_rate=0.0,
                          dropout_rng=jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


@pytest.mark.parametrize("causal", [True, False])
def test_dropout_forward_matches_masked_oracle(causal):
    q, k, v = _rand_qkv(2, 2, 128, 32, seed=3)
    rng = jax.random.PRNGKey(7)
    out = flash_attention(q, k, v, causal=causal, dropout_rate=0.2,
                          dropout_rng=rng)
    ref = _dense_dropout_oracle(q, k, v, 0.2, rng, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_dropout_multiblock_mask_offsets():
    """Small blocks: the in-kernel mask must hash GLOBAL positions, so a
    multi-block run agrees with the one-block oracle."""
    q, k, v = _rand_qkv(1, 2, 200, 32, seed=4)
    rng = jax.random.PRNGKey(11)
    out = flash_attention(q, k, v, dropout_rate=0.3, dropout_rng=rng,
                          block_q=64, block_k=64)
    ref = _dense_dropout_oracle(q, k, v, 0.3, rng)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_dropout_backward_matches_masked_oracle():
    q, k, v = _rand_qkv(1, 2, 128, 32, seed=5)
    rng = jax.random.PRNGKey(13)
    wt = jnp.asarray(np.random.RandomState(9).randn(*q.shape), q.dtype)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, dropout_rate=0.25,
                                       dropout_rng=rng) * wt)

    def loss_oracle(q, k, v):
        return jnp.sum(_dense_dropout_oracle(q, k, v, 0.25, rng) * wt)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, go, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_dropout_seed_sensitivity_and_determinism():
    q, k, v = _rand_qkv(1, 1, 96, 32)
    r1, r2 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
    a1 = flash_attention(q, k, v, dropout_rate=0.5, dropout_rng=r1)
    a1b = flash_attention(q, k, v, dropout_rate=0.5, dropout_rng=r1)
    a2 = flash_attention(q, k, v, dropout_rate=0.5, dropout_rng=r2)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a1b))
    assert np.abs(np.asarray(a1) - np.asarray(a2)).max() > 0


def test_dropout_is_unbiased():
    """Averaged over many seeds, dropped attention approaches the
    undropped output (inverted-dropout scaling)."""
    q, k, v = _rand_qkv(1, 1, 64, 32)
    base = np.asarray(flash_attention(q, k, v))
    acc = np.zeros_like(base)
    n = 48
    for s in range(n):
        acc += np.asarray(flash_attention(
            q, k, v, dropout_rate=0.3, dropout_rng=jax.random.PRNGKey(s)))
    err = np.abs(acc / n - base).mean() / np.abs(base).mean()
    assert err < 0.15, f"dropout mean deviates {err:.3f} from base"


def test_model_entry_routes_dropout_into_kernel():
    """The models' entry (no mesh here: the bare kernel) draws the same
    seed from the rng as the kernel's own API."""
    from deepspeed_tpu.parallel.attention import sharded_flash_attention
    q, k, v = _rand_qkv(1, 1, 64, 32)
    rng = jax.random.PRNGKey(0)
    out = sharded_flash_attention(q, k, v, dropout_rate=0.1, dropout_rng=rng)
    ref = flash_attention(q, k, v, dropout_rate=0.1, dropout_rng=rng)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_jit_compiles_once():
    q, k, v = _rand_qkv(1, 1, 128, 32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v))
    np.testing.assert_allclose(f(q, k, v),
                               causal_attention(q, k, v),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# key (padding) masks — the BERT/HF case
# ---------------------------------------------------------------------------
def _dense_masked(q, k, v, add_mask):
    """Dense oracle with an additive [B, Tk] key mask."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + add_mask[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("t,lens", [(128, (128, 70)), (200, (200, 33))])
def test_key_mask_forward_matches_dense(t, lens):
    q, k, v = _rand_qkv(2, 2, t, 32, seed=4)
    valid = jnp.asarray(
        np.arange(t)[None, :] < np.asarray(lens)[:, None])   # [B, T] bool
    add = jnp.where(valid, 0.0, -1e9).astype(jnp.float32)
    out_bool = flash_attention(q, k, v, causal=False, key_mask=valid)
    out_add = flash_attention(q, k, v, causal=False, key_mask=add)
    ref = _dense_masked(q, k, v, add)
    np.testing.assert_allclose(out_bool, ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out_add, ref, atol=2e-5, rtol=2e-5)


def test_key_mask_backward_matches_dense():
    t = 200  # multi-block with block_q=block_k=64
    q, k, v = _rand_qkv(1, 2, t, 32, seed=5)
    valid = jnp.asarray(np.arange(t)[None, :] < 131)
    add = jnp.where(valid, 0.0, -1e9).astype(jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=False, key_mask=valid,
            block_q=64, block_k=64) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_masked(q, k, v, add) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)
    # masked keys receive zero dK/dV
    np.testing.assert_allclose(np.asarray(gf[1])[:, :, 131:], 0.0,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gf[2])[:, :, 131:], 0.0,
                               atol=1e-6)


def test_key_mask_composes_with_causal_and_dropout():
    """Mask × causal × in-kernel dropout: against the dense oracle that
    applies the kernel's exact keep mask plus the key mask."""
    from attention_oracles import dense_dropout_oracle
    t = 128
    q, k, v = _rand_qkv(1, 2, t, 32, seed=6)
    valid = jnp.asarray(np.arange(t)[None, :] < 99)
    seed = jnp.uint32(42)
    out = flash_attention(q, k, v, causal=True, dropout_rate=0.3,
                          dropout_seed=seed, key_mask=valid)
    ref = dense_dropout_oracle(q, k, v, 0.3, seed, causal=True,
                               key_mask=valid)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


def test_key_mask_per_head_shape():
    """[B*H, Tk] masks (per-head) are accepted verbatim."""
    b, h, t = 2, 2, 64
    q, k, v = _rand_qkv(b, h, t, 32, seed=7)
    lens = np.array([50, 64, 20, 40])                      # one per b*h row
    valid = jnp.asarray(np.arange(t)[None, :] < lens[:, None])
    out = flash_attention(q, k, v, causal=False, key_mask=valid)
    add = jnp.where(valid, 0.0, -1e9).astype(jnp.float32).reshape(b, h, t)
    scale = 1.0 / np.sqrt(32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + add[:, :, None, :]
    ref = jnp.einsum("bhqk,bhkd->bhqd",
                     jax.nn.softmax(s, -1).astype(q.dtype), v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------
# mis-masking hazard regressions (the KV-cache decode class): kv_length
# hard-masks out-of-range keys, all-masked rows hard-zero with zero
# gradients — never silently attend
# ---------------------------------------------------------------------
def test_kv_length_masks_garbage_tail():
    """A KV buffer whose tail is garbage (the decode-cache shape) must
    match the dense reference truncated to the live length — forward
    AND gradients."""
    q, k, v = _rand_qkv(2, 2, 96, 32, seed=8)
    live = 60
    k = k.at[:, :, live:].set(1e4)
    v = v.at[:, :, live:].set(1e4)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=False,
                                       kv_length=live) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense(q, k[:, :, :live], v[:, :, :live],
                              causal=False) ** 2)

    out = flash_attention(q, k, v, causal=False, kv_length=live)
    ref = _dense(q, k[:, :, :live], v[:, :, :live], causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(gf[0], gd[0], atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(gf[1][:, :, :live], gd[1][:, :, :live],
                               atol=3e-4, rtol=3e-4)
    # masked tail keys take ZERO gradient (they were never attended)
    assert (np.asarray(gf[1][:, :, live:]) == 0).all()
    assert (np.asarray(gf[2][:, :, live:]) == 0).all()


def test_kv_length_out_of_range_raises():
    q, k, v = _rand_qkv(1, 1, 32, 16, seed=9)
    with pytest.raises(ValueError, match="out of range"):
        flash_attention(q, k, v, causal=False, kv_length=33)
    with pytest.raises(ValueError, match="out of range"):
        flash_attention(q, k, v, causal=False, kv_length=-1)


def test_kv_length_zero_hard_zeros():
    """kv_length=0 (no live key at all) outputs exact zeros instead of
    the mean of V (the silent-attend failure this satellite closes)."""
    q, k, v = _rand_qkv(1, 2, 32, 16, seed=10)
    out = flash_attention(q, k, v, causal=False, kv_length=0)
    assert (np.asarray(out) == 0).all()


def test_all_masked_key_rows_zero_output_and_grads():
    """A key_mask dropping EVERY key of a batch row previously
    renormalized over the masked keys (silently attending to the
    max-scoring masked key); now: exact zeros, zero gradients, other
    rows untouched."""
    b, h, t = 2, 2, 64
    q, k, v = _rand_qkv(b, h, t, 32, seed=11)
    km = np.ones((b, t), bool)
    km[0, :] = False

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=False,
                                       key_mask=jnp.asarray(km)) ** 2)

    out = flash_attention(q, k, v, causal=False, key_mask=jnp.asarray(km))
    assert (np.asarray(out[0]) == 0).all()
    ref = _dense(q[1:], k[1:], v[1:], causal=False)
    np.testing.assert_allclose(out[1:], ref, atol=2e-5, rtol=2e-5)
    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert (np.asarray(gq[0]) == 0).all()
    assert (np.asarray(gk[0]) == 0).all()
    assert (np.asarray(gv[0]) == 0).all()
    assert np.abs(np.asarray(gq[1])).max() > 0


# ---------------------------------------------------------------------------
# on a mesh of several devices the models call the kernel through
# parallel.attention.sharded_flash_attention, inside a shard_map (a Mosaic
# kernel cannot be partitioned automatically on real chips)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dp,tp", [(4, 1), (2, 2), (1, 4)])
@pytest.mark.parametrize("dropout,mask", [(0.0, None), (0.2, None),
                                          (0.2, "batch"), (0.0, "bh")],
                         ids=["plain", "dropout", "dropout+mask", "bh_mask"])
def test_mesh_sharded_call_equals_single_device(dp, tp, dropout, mask):
    """Rows (batch over 'data', heads over 'model') are independent, so
    the sharded call is the single-device call bit for bit — forward and
    gradients, including the dropout realization, whose hash must see
    GLOBAL batch·head ids from every shard."""
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.parallel.attention import sharded_flash_attention
    b, h, t, d = 4, 4, 64, 16
    q, k, v = _rand_qkv(b, h, t, d, seed=3)
    key_mask = None
    if mask is not None:
        rows = b if mask == "batch" else b * h
        key_mask = jnp.asarray(
            np.random.RandomState(0).rand(rows, t) > 0.3)

    def loss(attend, q, k, v):
        out = attend(q, k, v, causal=True, block_q=32, block_k=32,
                     dropout_rate=dropout, dropout_seed=jnp.uint32(7),
                     key_mask=key_mask)
        return (out * out).sum(), out

    grad = jax.value_and_grad(loss, argnums=(1, 2, 3), has_aux=True)
    (want_l, want_o), want_g = jax.jit(
        functools.partial(grad, flash_attention))(q, k, v)
    step = jax.jit(functools.partial(grad, sharded_flash_attention))
    mesh = build_mesh(dp=dp, tp=tp, devices=jax.devices()[:dp * tp])
    with jax.set_mesh(mesh):
        text = step.lower(q, k, v).as_text()
        (got_l, got_o), got_g = step(q, k, v)
    assert "shard_map" in text or "manual" in text.lower()
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(want_o))
    for g, w in zip(got_g, want_g):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
