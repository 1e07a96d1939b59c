"""CPU Adam + ZeRO-Offload tests.

Differential pattern from the reference (reference:
tests/unit/test_cpu_adam.py compares DeepSpeedCPUAdam vs torch.optim.Adam):
the native kernel is checked against the device fused_adam and the numpy
fallback, and the offload engine path is trained end-to-end.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, "tests")

from deepspeed_tpu.config import DeepSpeedConfig
from deepspeed_tpu.ops.adam import fused_adam
from deepspeed_tpu.ops.cpu_adam import DeepSpeedCPUAdam
from deepspeed_tpu.ops.op_builder import cpu_ops_available, cpu_ops_status
from deepspeed_tpu.runtime.engine import DeepSpeedEngine

from simple_model import SimpleModel, base_config, random_batches

NATIVE = cpu_ops_available()


def test_native_ops_build():
    """The C++ toolchain is present in CI and on TPU-VMs; the native op
    must build there (the numpy fallback is for exotic hosts only)."""
    assert NATIVE, cpu_ops_status()


@pytest.mark.parametrize("adamw", [True, False])
@pytest.mark.parametrize("native", [True, False] if NATIVE else [False])
def test_cpu_adam_matches_fused_adam(adamw, native):
    rng = np.random.default_rng(0)
    p0 = {"w": rng.standard_normal((64, 32)).astype(np.float32),
          "b": rng.standard_normal(32).astype(np.float32)}
    host = DeepSpeedCPUAdam(lr=1e-2, weight_decay=0.01, adamw_mode=adamw,
                            use_native=native)
    p_host = jax.tree.map(np.copy, p0)
    tx = fused_adam(1e-2, weight_decay=0.01, adam_w_mode=adamw)
    p_dev = jax.tree.map(jnp.asarray, p0)
    st = tx.init(p_dev)
    for _ in range(10):
        g = {"w": rng.standard_normal((64, 32)).astype(np.float32),
             "b": rng.standard_normal(32).astype(np.float32)}
        host.step(p_host, g)
        u, st = tx.update(jax.tree.map(jnp.asarray, g), st, p_dev)
        p_dev = optax.apply_updates(p_dev, u)
    for k in p0:
        np.testing.assert_allclose(p_host[k], np.asarray(p_dev[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)


@pytest.mark.skipif(not NATIVE, reason="no C++ toolchain")
def test_native_matches_numpy_fallback():
    rng = np.random.default_rng(1)
    p_n = {"x": rng.standard_normal(1000).astype(np.float32)}
    p_f = jax.tree.map(np.copy, p_n)
    on = DeepSpeedCPUAdam(lr=3e-3, weight_decay=0.1, use_native=True)
    of = DeepSpeedCPUAdam(lr=3e-3, weight_decay=0.1, use_native=False)
    for _ in range(5):
        g = {"x": rng.standard_normal(1000).astype(np.float32)}
        lo_n = on.step(p_n, g, out_dtype="bfloat16")
        lo_f = of.step(p_f, g, out_dtype="bfloat16")
    np.testing.assert_allclose(p_n["x"], p_f["x"], rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(
        np.asarray(lo_n["x"]).view(np.uint16),
        np.asarray(lo_f["x"]).view(np.uint16))  # bitwise-equal bf16 rounding


@pytest.mark.skipif(not NATIVE, reason="no C++ toolchain")
def test_fused_bf16_copyback_matches_cast():
    rng = np.random.default_rng(2)
    p = {"x": rng.standard_normal(257).astype(np.float32)}  # odd size
    opt = DeepSpeedCPUAdam(lr=1e-2, use_native=True)
    lowp = opt.step(p, {"x": rng.standard_normal(257).astype(np.float32)},
                    out_dtype="bfloat16")
    import ml_dtypes
    np.testing.assert_array_equal(
        np.asarray(lowp["x"]).view(np.uint16),
        p["x"].astype(ml_dtypes.bfloat16).view(np.uint16))


def _offload_config(**over):
    cfg = base_config(micro_bs=4, grad_acc=2, stage=2)
    cfg["zero_optimization"]["cpu_offload"] = True
    cfg.update(over)
    return DeepSpeedConfig(cfg, world_size=8)


def test_offload_engine_trains():
    cfg = _offload_config()
    engine = DeepSpeedEngine(SimpleModel(hidden_dim=16), cfg)
    assert engine._offload and engine._host_opt.is_native == NATIVE
    losses = [float(engine.train_batch(b)) for b in
              random_batches(cfg.train_batch_size, 16, num_batches=20,
                             seed=9)]
    assert losses[-1] < losses[0] * 0.7, losses
    # master + moments really live on host numpy
    assert isinstance(jax.tree.leaves(engine.state.master_params)[0],
                      np.ndarray)
    assert isinstance(jax.tree.leaves(engine.state.opt_state["mu"])[0],
                      np.ndarray)


def test_offload_matches_device_path():
    """Same data, same seeds: offload and in-device ZeRO-2 must track each
    other closely (bf16 upload rounding is the only divergence source)."""
    torch_batches = list(random_batches(32, 16, num_batches=8, seed=13))
    cfg_dev = DeepSpeedConfig(base_config(micro_bs=4, grad_acc=1, stage=2),
                              world_size=8)
    cfg_off = _offload_config(gradient_accumulation_steps=1)
    e_dev = DeepSpeedEngine(SimpleModel(hidden_dim=16), cfg_dev, seed=3)
    e_off = DeepSpeedEngine(SimpleModel(hidden_dim=16), cfg_off, seed=3)
    l_dev = [float(e_dev.train_batch(b)) for b in torch_batches]
    l_off = [float(e_off.train_batch(b)) for b in torch_batches]
    np.testing.assert_allclose(l_off, l_dev, rtol=0.05, atol=0.02)


def test_offload_checkpoint_roundtrip(tmp_path):
    cfg = _offload_config()
    engine = DeepSpeedEngine(SimpleModel(hidden_dim=16), cfg, seed=5)
    for b in random_batches(cfg.train_batch_size, 16, num_batches=3,
                            seed=1):
        engine.train_batch(b)
    engine.save_checkpoint(str(tmp_path))
    master_before = jax.tree.map(np.copy, engine.state.master_params)
    mu_before = jax.tree.map(np.copy, engine.state.opt_state["mu"])

    engine2 = DeepSpeedEngine(SimpleModel(hidden_dim=16), cfg, seed=99)
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    for k in master_before:
        np.testing.assert_array_equal(engine2.state.master_params[k],
                                      master_before[k])
        np.testing.assert_array_equal(engine2.state.opt_state["mu"][k],
                                      mu_before[k])
    assert engine2._host_opt.opt.step_count == 3
    # and it keeps training from there
    loss = engine2.train_batch(next(random_batches(
        cfg.train_batch_size, 16, num_batches=1, seed=2)))
    assert np.isfinite(float(loss))


@pytest.mark.skipif(not NATIVE, reason="no C++ toolchain")
def test_native_fp16_conversion_bit_exact():
    """The fused fp16 copy-back must match numpy's conversion bit-for-bit,
    including subnormals, NaN (preserved, not laundered to Inf), Inf, and
    overflow."""
    import ctypes
    import warnings
    from deepspeed_tpu.ops.op_builder import load_cpu_ops
    lib = load_cpu_ops()
    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.standard_normal(20000) * rng.choice([1e-8, 1e-4, 1, 1e4], 20000),
        np.array([np.nan, np.inf, -np.inf, 0.0, -0.0, 65519.0, 65520.0,
                  1e-8, 5.96e-8, 6.1e-5])]).astype(np.float32)
    p = x.copy()
    zeros = np.zeros_like(x)
    out = np.empty(x.shape, np.uint16)
    fp = ctypes.POINTER(ctypes.c_float)
    u16 = ctypes.POINTER(ctypes.c_uint16)
    lib.ds_cpu_adam_step(
        x.size, p.ctypes.data_as(fp), zeros.ctypes.data_as(fp),
        zeros.copy().ctypes.data_as(fp), zeros.copy().ctypes.data_as(fp),
        0.0, 0.9, 0.999, 1e-8, 0.0, 1, 1, 1,
        out.ctypes.data_as(u16), 2)  # lr=0: pure conversion
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # expected overflow-in-cast
        ref = x.astype(np.float16)
    got = out.view(np.float16)
    both_nan = np.isnan(got) & np.isnan(ref)
    np.testing.assert_array_equal(got.view(np.uint16)[~both_nan],
                                  ref.view(np.uint16)[~both_nan])


# ---------------------------------------------------------------------------
# Delayed parameter update (host tier): ZeRO-Offload paper's DPU
# ---------------------------------------------------------------------------
def _dpu_cfg(dpu: bool):
    from deepspeed_tpu.config import DeepSpeedConfig
    return DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2, "cpu_offload": True,
                              "offload_impl": "host",
                              "delayed_param_update": dpu},
    }, world_size=1)


def test_dpu_staleness_and_convergence():
    """Steps 0 and 1 both compute at the INITIAL params under DPU (the
    first update is applied during step 1's dispatch window), so with a
    fixed batch their losses are identical — and differ without DPU.
    Training still converges."""
    import jax
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from simple_model import SimpleModel

    mesh = build_mesh(dp=1, devices=jax.devices()[:1])
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 16)).astype(np.float32)
    batch = (x, (0.5 * x).astype(np.float32))

    ed = DeepSpeedEngine(SimpleModel(hidden_dim=16), _dpu_cfg(True),
                         mesh=mesh, seed=3)
    l0 = float(np.asarray(ed.train_batch(batch)))
    l1 = float(np.asarray(ed.train_batch(batch)))
    assert l0 == pytest.approx(l1, abs=1e-7), "DPU steps 0/1 share params"

    en = DeepSpeedEngine(SimpleModel(hidden_dim=16), _dpu_cfg(False),
                         mesh=mesh, seed=3)
    n0 = float(np.asarray(en.train_batch(batch)))
    n1 = float(np.asarray(en.train_batch(batch)))
    assert n0 == pytest.approx(l0, abs=1e-7)  # step 0 identical
    assert abs(n1 - n0) > 1e-6, "non-DPU step 1 must use updated params"

    losses = [float(np.asarray(ed.train_batch(batch))) for _ in range(30)]
    assert losses[-1] < l0 * 0.9, (l0, losses[-5:])


def test_dpu_checkpoint_flushes_pending():
    """save_checkpoint applies the pending update; the loaded engine and
    the original continue identically from the flushed state."""
    import jax
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from simple_model import SimpleModel
    import tempfile

    mesh = build_mesh(dp=1, devices=jax.devices()[:1])
    rng = np.random.default_rng(1)
    x = rng.normal(size=(4, 16)).astype(np.float32)
    batch = (x, (0.5 * x).astype(np.float32))
    ed = DeepSpeedEngine(SimpleModel(hidden_dim=16), _dpu_cfg(True),
                         mesh=mesh, seed=3)
    for _ in range(3):
        ed.train_batch(batch)
    d = tempfile.mkdtemp()
    ed.save_checkpoint(d, tag="t")
    assert ed._dpu_pending is None  # flushed
    ref = float(np.asarray(ed.train_batch(batch)))

    e2 = DeepSpeedEngine(SimpleModel(hidden_dim=16), _dpu_cfg(True),
                         mesh=mesh, seed=9)
    path, _ = e2.load_checkpoint(d, tag="t")
    assert path is not None
    got = float(np.asarray(e2.train_batch(batch)))
    assert got == pytest.approx(ref, abs=1e-6)


def test_poisoned_host_tier_blocks_save(tmp_path):
    """The save path must honor the poison guard (advisor, round 4):
    after a mid-step pull failure the native Adam buffers are partially
    updated, so save_checkpoint must refuse — serializing them would
    turn a clean failure into silent divergence on restore."""
    cfg = _offload_config()
    engine = DeepSpeedEngine(SimpleModel(hidden_dim=16), cfg, seed=7)
    engine.train_batch(next(random_batches(
        cfg.train_batch_size, 16, num_batches=1, seed=1)))
    engine._host_opt._poisoned = ValueError("link died mid-pull")
    with pytest.raises(RuntimeError, match="refusing to serialize"):
        engine.save_checkpoint(str(tmp_path))
