"""ZeRO-Infinity-style parameter streaming (xla offload tier).

``zero_optimization.param_streaming`` keeps the compute copies of the
model's stacked scan leaves in HOST memory; the model fetches one
layer's slice per scan tick (``TrainModule.streaming_param_spec`` +
GPT2's ``stream_scan``), so device-resident parameter bytes ~ one layer
instead of 2 bytes/param for the whole model.  The reference reaches the
same capacity point by partitioning fp16 params to CPU/NVMe (reference:
deepspeed/runtime/zero/stage2.py fp16 partition machinery; generalized
by the ZeRO-Infinity paper).  On the CPU test mesh memory kinds degrade
to one space — these tests pin down numerics, composition, and the
config contract; the capacity claim itself needs hardware and has no
run on the current chip (ROADMAP.md S8).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.models import GPT2Config, GPT2Model
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.runtime.engine import DeepSpeedEngine


def _model_cfg(stream: bool, scan: bool = True):
    return GPT2Config(d_model=64, n_layer=3, n_head=4, vocab_size=256,
                      n_positions=64, remat="block", scan_layers=scan,
                      stream_scan=stream, attn_impl="dense")


def _ds_cfg(world: int, stage: int = 2, stream: bool = True, **zero_extra):
    return DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 2 if world == 1 else 1,
        "gradient_accumulation_steps": 2 if world == 1 else 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": dict(
            {"stage": stage, "cpu_offload": True, "offload_impl": "xla",
             "param_streaming": stream}, **zero_extra),
    }, world_size=world)


def _tokens():
    return np.random.default_rng(0).integers(0, 256, (4, 33),
                                             dtype=np.int32)


def _run(engine, tokens, steps=5):
    return [float(engine.train_batch(tokens)) for _ in range(steps)]


# ---------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------
def test_streaming_matches_plain_offload():
    """Streaming is a memory PLACEMENT change — losses must match the
    non-streamed offload path exactly (same math, same rng)."""
    mesh = build_mesh(devices=jax.devices()[:1])
    tok = _tokens()
    plain = DeepSpeedEngine(GPT2Model(_model_cfg(False)),
                            _ds_cfg(1, stream=False), mesh=mesh)
    stream = DeepSpeedEngine(GPT2Model(_model_cfg(True)),
                             _ds_cfg(1, stream=True), mesh=mesh)
    lp, ls = _run(plain, tok), _run(stream, tok)
    np.testing.assert_allclose(ls, lp, rtol=1e-5, atol=1e-5)
    assert lp[-1] < lp[0]  # and it actually trains


def test_streaming_model_apply_matches_plain_apply():
    """Model-level: the stream_scan fetch form computes the same function
    as the xs-scan form."""
    rng = jax.random.PRNGKey(0)
    m_plain = GPT2Model(_model_cfg(False))
    m_stream = GPT2Model(_model_cfg(True))
    params = m_plain.init(rng)
    tok = jnp.asarray(_tokens()[:, :32])
    lo_p = m_plain.apply(params, tok, rng, train=False)
    lo_s = m_stream.apply(params, tok, rng, train=False)
    np.testing.assert_allclose(np.asarray(lo_s), np.asarray(lo_p),
                               rtol=1e-5, atol=1e-5)


def test_streaming_composes_with_grad_chunks():
    """param_streaming × offload_grad_chunks: the full capacity stack
    (device grads bounded by group, device params ~ one layer)."""
    mesh = build_mesh(devices=jax.devices()[:1])
    tok = _tokens()
    ref = DeepSpeedEngine(GPT2Model(_model_cfg(False)),
                          _ds_cfg(1, stream=False), mesh=mesh)
    stk = DeepSpeedEngine(GPT2Model(_model_cfg(True)),
                          _ds_cfg(1, stream=True, offload_grad_chunks=3),
                          mesh=mesh)
    lr_, ls = _run(ref, tok, 3), _run(stk, tok, 3)
    np.testing.assert_allclose(ls, lr_, rtol=5e-4, atol=5e-4)


def test_streaming_zero3_dp4():
    """ZeRO-3 × streaming × dp>1: host leaves stay data-sharded (no
    host-side collectives) and the run matches the dp=1 trajectory."""
    tok = _tokens()
    mesh1 = build_mesh(devices=jax.devices()[:1])
    ref = DeepSpeedEngine(GPT2Model(_model_cfg(True)),
                          _ds_cfg(1, stream=True), mesh=mesh1)
    mesh4 = build_mesh(dp=4, devices=jax.devices()[:4])
    eng = DeepSpeedEngine(GPT2Model(_model_cfg(True)),
                          _ds_cfg(4, stage=3, stream=True), mesh=mesh4)
    l1, l4 = _run(ref, tok, 3), _run(eng, tok, 3)
    np.testing.assert_allclose(l4, l1, rtol=2e-3, atol=2e-3)


def test_streaming_with_delayed_param_update():
    """DPU staleness semantics are placement-independent."""
    mesh = build_mesh(devices=jax.devices()[:1])
    tok = _tokens()
    a = DeepSpeedEngine(GPT2Model(_model_cfg(False)),
                        _ds_cfg(1, stream=False, delayed_param_update=True),
                        mesh=mesh)
    b = DeepSpeedEngine(GPT2Model(_model_cfg(True)),
                        _ds_cfg(1, stream=True, delayed_param_update=True),
                        mesh=mesh)
    la, lb = _run(a, tok), _run(b, tok)
    np.testing.assert_allclose(lb, la, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------
# contract
# ---------------------------------------------------------------------
def test_config_rejects_streaming_without_offload():
    with pytest.raises(DeepSpeedConfigError, match="param_streaming"):
        DeepSpeedConfig({
            "train_micro_batch_size_per_gpu": 1,
            "bf16": {"enabled": True},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2, "param_streaming": True},
        }, world_size=1)


def test_config_rejects_streaming_on_host_tier():
    with pytest.raises(DeepSpeedConfigError, match="xla-tier"):
        DeepSpeedConfig({
            "train_micro_batch_size_per_gpu": 1,
            "bf16": {"enabled": True},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2, "cpu_offload": True,
                                  "offload_impl": "host",
                                  "param_streaming": True},
        }, world_size=1)


def test_engine_rejects_streaming_dp_gt1_below_stage3():
    mesh = build_mesh(dp=4, devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="requires ZeRO-3"):
        DeepSpeedEngine(GPT2Model(_model_cfg(True)),
                        _ds_cfg(4, stage=2, stream=True), mesh=mesh)


def test_engine_rejects_streaming_without_model_support():
    """A model whose streaming_param_spec is None must fail loudly, not
    silently run un-streamed."""
    mesh = build_mesh(devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="streaming_param_spec"):
        DeepSpeedEngine(GPT2Model(_model_cfg(False)),
                        _ds_cfg(1, stream=True), mesh=mesh)


def test_streaming_chunks_dpu_triple_composition():
    """The full capacity+overlap stack at once: host-resident streamed
    params × K-group chunked grads × delayed parameter update."""
    mesh = build_mesh(devices=jax.devices()[:1])
    tok = _tokens()
    eng = DeepSpeedEngine(
        GPT2Model(_model_cfg(True)),
        _ds_cfg(1, stream=True, offload_grad_chunks=3,
                delayed_param_update=True),
        mesh=mesh)
    ls = _run(eng, tok, 5)
    assert all(np.isfinite(v) for v in ls), ls
    assert ls[-1] < ls[0], ls


def test_moe_streaming_matches_plain_offload():
    """MoE param streaming (one GROUP of stacked attn/dense/expert
    params fetched per scan tick) must match the unstreamed group-scan
    offload path exactly — placement, not math."""
    from deepspeed_tpu.models import GPT2MoEConfig, GPT2MoEModel

    tok = _tokens()
    mesh = build_mesh(devices=jax.devices()[:1])
    losses = {}
    for stream in (False, True):
        cfg_m = GPT2MoEConfig(
            vocab_size=256, n_positions=64, d_model=64, n_layer=4,
            n_head=4, n_experts=4, moe_layer_freq=2, attn_impl="dense",
            remat="block", scan_groups=True, stream_scan=stream,
            dropout=0.0)
        ds = DeepSpeedConfig({
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "steps_per_print": 10 ** 9,
            "bf16": {"enabled": True},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": dict(
                {"stage": 2, "cpu_offload": True, "offload_impl": "xla"},
                **({"param_streaming": True} if stream else {})),
        }, world_size=1)
        eng = DeepSpeedEngine(GPT2MoEModel(cfg_m), ds, mesh=mesh)
        losses[stream] = _run(eng, tok, 4)
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=1e-5, atol=1e-5)
    assert losses[True][-1] < losses[True][0]


def test_moe_stream_scan_requires_scan_groups():
    from deepspeed_tpu.models import GPT2MoEConfig

    with pytest.raises(ValueError, match="scan_groups"):
        GPT2MoEConfig(vocab_size=256, n_positions=64, d_model=64,
                      n_layer=4, n_head=4, n_experts=4,
                      moe_layer_freq=2, stream_scan=True)


def test_streaming_composes_with_ring_sequence_parallel():
    """Long-context × capacity: host-resident stacked params fetched per
    scan tick WHILE the attention inside each layer runs ring-parallel
    over the 'seq' axis (the fetch's device placement and the ring's
    shard_map both read the engine's ambient mesh)."""
    tok = _tokens()[:2]
    mesh = build_mesh(dp=1, sp=2, devices=jax.devices()[:2])
    cfg_m = GPT2Config(d_model=64, n_layer=3, n_head=4, vocab_size=256,
                      n_positions=64, remat="block", scan_layers=True,
                      stream_scan=True, attn_impl="ring", dropout=0.0)
    ds = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2, "cpu_offload": True,
                              "offload_impl": "xla",
                              "param_streaming": True},
    }, world_size=1)
    eng = DeepSpeedEngine(GPT2Model(cfg_m), ds, mesh=mesh)
    ls = _run(eng, tok, 3)
    assert all(np.isfinite(v) for v in ls) and ls[-1] < ls[0], ls


def test_engine_step_traces_under_ambient_mesh():
    """The engine must establish jax.set_mesh around compiled-step
    tracing: the streaming fetch, sequence-parallel axis discovery, and
    the MoE constraint all read jax.sharding.get_abstract_mesh() during
    trace, and WITHOUT the ambient mesh that read returns an empty
    AbstractMesh inside jit (argument shardings do not populate it) —
    every one of those features would silently degrade."""
    from deepspeed_tpu.runtime.module import TrainModule

    seen = []

    class Probe(TrainModule):
        def init(self, rng):
            return {"w": jnp.ones((8, 4))}

        def loss_fn(self, params, batch, rng, train=True):
            am = jax.sharding.get_abstract_mesh()
            seen.append(dict(getattr(am, "shape", {})))
            return jnp.mean((batch[0] @ params["w"] - batch[1]) ** 2)

    mesh = build_mesh(dp=4, devices=jax.devices()[:4])
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
    }, world_size=4)
    eng = DeepSpeedEngine(Probe(), cfg, mesh=mesh)
    x = np.ones((4, 8), np.float32)
    y = np.ones((4, 4), np.float32)
    eng.train_batch((x, y))
    assert seen, "loss_fn never traced"
    assert any(s.get("data") == 4 for s in seen), seen


def test_stream_mask_marks_blocks_only():
    """The engine's flat-order mask must cover exactly the stacked block
    leaves — embeddings and final LN stay device-resident."""
    mesh = build_mesh(devices=jax.devices()[:1])
    eng = DeepSpeedEngine(GPT2Model(_model_cfg(True)),
                          _ds_cfg(1, stream=True), mesh=mesh)
    model = GPT2Model(_model_cfg(True))
    params = model.init(jax.random.PRNGKey(0))
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    assert len(paths) == len(eng._stream_mask)
    for path, m in zip(paths, eng._stream_mask):
        assert m == ("blocks" in path), (path, m)


def test_streaming_composes_with_split_update():
    """param_streaming x offload_split_update x grad chunks: the deepest
    capacity stack the 1.5B single-chip chain can select.  Trajectory
    must match the fused-update streaming engine."""
    mesh = build_mesh(dp=1, devices=jax.devices()[:1])
    es = DeepSpeedEngine(GPT2Model(_model_cfg(True)),
                         _ds_cfg(1, offload_split_update=True,
                                 offload_grad_chunks=2),
                         mesh=mesh, seed=3)
    ef = DeepSpeedEngine(GPT2Model(_model_cfg(True)),
                         _ds_cfg(1, offload_grad_chunks=2),
                         mesh=mesh, seed=3)
    toks = _tokens()
    ls = _run(es, toks)
    lf = _run(ef, toks)
    np.testing.assert_allclose(ls, lf, rtol=0, atol=3e-4)
    assert ls[-1] < ls[0]


def test_zero3_dp4_split_update():
    """ZeRO-3 x split update at dp=4: per-piece programs must respect the
    data-sharded piece placement (each update touches only local rows)."""
    mesh = build_mesh(dp=4, devices=jax.devices()[:4])
    e3 = DeepSpeedEngine(GPT2Model(_model_cfg(False)),
                         _ds_cfg(4, stage=3, stream=False,
                                 offload_split_update=True),
                         mesh=mesh, seed=3)
    ef = DeepSpeedEngine(GPT2Model(_model_cfg(False)),
                         _ds_cfg(4, stage=3, stream=False),
                         mesh=mesh, seed=3)
    toks = _tokens()
    ls = _run(e3, toks)
    lf = _run(ef, toks)
    np.testing.assert_allclose(ls, lf, rtol=0, atol=3e-4)
