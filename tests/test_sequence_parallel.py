"""Ring attention + Ulysses sequence parallelism tests: both schemes must
reproduce dense full-sequence attention (fwd + bwd) on the 8-device mesh."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.parallel.sequence import ring_attention, ulysses_attention

shard_map = partial(jax.shard_map, check_vma=False)

N = 8


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("seq",))


def dense_attention(q, k, v, causal):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        T = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def make_qkv(B=2, H=8, T=128, D=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    return mk(), mk(), mk()


def run_sharded(fn, q, k, v):
    """Shard the seq dim (axis 2) over the mesh and run fn in shard_map."""
    mesh = _mesh()
    spec = P(None, None, "seq", None)
    wrapped = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec)
    return jax.jit(wrapped)(q, k, v)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    q, k, v = make_qkv(seed=1)
    out = run_sharded(
        lambda a, b, c: ring_attention(a, b, c, "seq", causal=causal),
        q, k, v)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["ring", "ulysses", "ulysses-dense"])
def test_sp_dropout_matches_dense_oracle(impl):
    """Dropout masks hash GLOBAL positions, so the sharded schemes must
    reproduce the dense oracle exactly for the same seed — across
    different shardings of the same computation and both Ulysses local
    kernels (flash default, dense debug path)."""
    from attention_oracles import dense_dropout_oracle
    if impl == "ring":
        fn = ring_attention
    elif impl == "ulysses":
        fn = ulysses_attention
    else:
        fn = partial(ulysses_attention, local_impl="dense")
    q, k, v = make_qkv(seed=7)
    seed = jnp.uint32(42)
    out = run_sharded(
        lambda a, b, c: fn(a, b, c, "seq", causal=True,
                           dropout_rate=0.2, dropout_seed=seed),
        q, k, v)
    ref = dense_dropout_oracle(q, k, v, 0.2, seed, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sp_dropout_grads_flow():
    q, k, v = make_qkv(seed=9)
    seed = jnp.uint32(3)

    def loss(q, k, v):
        out = run_sharded(
            lambda a, b, c: ring_attention(a, b, c, "seq", causal=True,
                                           dropout_rate=0.3,
                                           dropout_seed=seed),
            q, k, v)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(q, k, v)
    assert np.isfinite(np.asarray(g)).all()
    assert np.abs(np.asarray(g)).max() > 0


def test_ulysses_flash_dropout_grads_match_oracle():
    """The Ulysses-flash backward path threads bh_ids through both
    backward kernels; its gradients must equal the dense oracle's for
    the same seed (catches a wrong per-head mask in bwd that forward
    tests cannot see)."""
    from attention_oracles import dense_dropout_oracle
    q, k, v = make_qkv(seed=11)
    seed = jnp.uint32(17)
    wt = jnp.asarray(np.random.default_rng(2).standard_normal(q.shape),
                     jnp.float32)

    def loss_sp(q, k, v):
        out = run_sharded(
            lambda a, b, c: ulysses_attention(a, b, c, "seq", causal=True,
                                              dropout_rate=0.25,
                                              dropout_seed=seed),
            q, k, v)
        return jnp.sum(out * wt)

    def loss_oracle(q, k, v):
        return jnp.sum(dense_dropout_oracle(q, k, v, 0.25, seed) * wt)

    gs = jax.grad(loss_sp, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gs, go, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("dp,sp,tp", [(2, 2, 1), (2, 2, 2), (1, 4, 2)])
def test_ulysses_flash_on_mesh_with_other_automatic_axes(dp, sp, tp):
    """Ulysses is manual over 'seq' only; 'data' and 'model' stay under
    GSPMD around its flash call, whose batch·head base is traced from the
    seq rank.  Forward and gradients equal the dense dropout oracle."""
    from attention_oracles import dense_dropout_oracle
    from deepspeed_tpu.parallel import build_mesh
    q, k, v = make_qkv(B=4, seed=13)
    seed = jnp.uint32(5)
    mesh = build_mesh(dp=dp, sp=sp, tp=tp,
                      devices=jax.devices()[:dp * sp * tp])
    spec = P(None, None, "seq", None)

    def loss(attend, q, k, v):
        out = attend(q, k, v)
        return jnp.sum(out ** 2), out

    def sp_attend(q, k, v):
        return jax.shard_map(
            lambda a, b, c: ulysses_attention(a, b, c, "seq", causal=True,
                                              dropout_rate=0.25,
                                              dropout_seed=seed),
            in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={"seq"}, check_vma=False)(q, k, v)

    grad = jax.value_and_grad(loss, argnums=(1, 2, 3), has_aux=True)
    with jax.set_mesh(mesh):
        (_, out), gs = jax.jit(partial(grad, sp_attend))(q, k, v)
    (_, ref), go = grad(
        lambda q, k, v: dense_dropout_oracle(q, k, v, 0.25, seed), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for a, b, name in zip(gs, go, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_dense(causal):
    q, k, v = make_qkv(seed=2)
    out = run_sharded(
        lambda a, b, c: ulysses_attention(a, b, c, "seq", causal=causal),
        q, k, v)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention],
                         ids=["ring", "ulysses"])
def test_gradients_match_dense(impl):
    # B=2 on purpose: the untiled all_to_all formulation mis-lowered the
    # Ulysses backward exactly (and only) at B > 1
    q, k, v = make_qkv(B=2, H=8, T=64, D=8, seed=3)
    mesh = _mesh()
    spec = P(None, None, "seq", None)

    def sp_loss(q, k, v):
        fn = shard_map(lambda a, b, c: impl(a, b, c, "seq", causal=True),
                       mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
        return jnp.sum(fn(q, k, v) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(dense_attention(q, k, v, True) ** 2)

    g_sp = jax.jit(jax.grad(sp_loss, argnums=(0, 1, 2)))(q, k, v)
    g_dn = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_sp, g_dn, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_ring_attention_bf16_io():
    q, k, v = make_qkv(seed=4)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = run_sharded(
        lambda a, b, c: ring_attention(a, b, c, "seq", causal=True),
        q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        rtol=0.05, atol=0.05)


def test_ulysses_head_divisibility_guard():
    q, k, v = make_qkv(H=4)  # 4 heads, 8 shards
    with pytest.raises(AssertionError, match="divisible"):
        run_sharded(lambda a, b, c: ulysses_attention(a, b, c, "seq"),
                    q, k, v)


def test_ring_attention_long_sequence_memory_shape():
    """T=1024 over 8 shards: each device's score block is 128x128 — the
    full 1024x1024 matrix is never materialized per device (shape-level
    check via the compiled HLO's largest intermediate)."""
    q, k, v = make_qkv(B=1, H=2, T=1024, D=16, seed=5)
    mesh = _mesh()
    spec = P(None, None, "seq", None)
    fn = shard_map(lambda a, b, c: ring_attention(a, b, c, "seq"),
                   mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = jax.jit(fn)(q, k, v)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.slow
def test_engine_level_sp_training_matches_dense():
    """Full engine training with ring attention over the seq axis (the
    'modern slot' for the reference's long-sequence feature, SURVEY §5.7)
    must match the dense-attention engine on the same batch."""
    import numpy as np
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.models import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    kw = dict(vocab_size=256, n_positions=128, d_model=64, n_layer=2,
              n_head=4, remat=None, dropout=0.0)
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }, world_size=2)
    toks = np.random.default_rng(0).integers(0, 256, (4, 65),
                                             dtype=np.int32)

    eng_sp = DeepSpeedEngine(
        GPT2Model(GPT2Config(attn_impl="ring", **kw)), cfg,
        mesh=build_mesh(pp=1, dp=2, sp=2, tp=2))
    eng_dense = DeepSpeedEngine(
        GPT2Model(GPT2Config(attn_impl="dense", **kw)), cfg,
        mesh=build_mesh(pp=1, dp=2, tp=1, devices=jax.devices()[:2]))
    # The ring implementation must ACTUALLY engage inside the engine's
    # jitted step.  The model discovers the 'seq' axis from
    # jax.sharding.get_abstract_mesh() at trace time — empty inside jit
    # unless the engine establishes the ambient mesh (jax.set_mesh in
    # _pallas_scope), in which case ring would silently degrade to the
    # GSPMD dense fallback and this parity test would still pass
    # (regression guard for the round-4 ambient-mesh fix).
    import deepspeed_tpu.parallel.sequence as seq_mod
    calls = []
    real_ring = seq_mod.ring_attention

    def counting_ring(*a, **k):
        calls.append(1)
        return real_ring(*a, **k)

    seq_mod.ring_attention = counting_ring
    try:
        for _ in range(3):
            loss_sp = eng_sp.train_batch(toks)
            loss_dense = eng_dense.train_batch(toks)
    finally:
        seq_mod.ring_attention = real_ring
    assert calls, ("ring_attention never traced — the engine step saw "
                   "an empty abstract mesh (sp silently degraded)")
    assert abs(float(np.asarray(loss_sp))
               - float(np.asarray(loss_dense))) < 0.05
