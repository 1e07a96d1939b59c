"""Streaming offload update pipeline (ZeRO-Offload overlap, H2D half).

The serial host tier only overlapped the D2H direction: grads prefetch
under the C++ Adam, but every updated leaf's re-upload waited for the
WHOLE CPU step.  The pipeline streams each leaf's H2D the moment its
block is written (``on_leaf`` → ``StreamingUploader``), so while Adam
updates leaf i, leaf i+1's grad pull and leaf i-1's upload are both in
flight.  Contracts these tests pin:

  - bitwise equivalence with the serial path (DS_OFFLOAD_PIPELINE=0),
    master + moments + uploaded compute params, both tiers, with and
    without DPU;
  - a mid-pipeline upload failure poisons the optimizer and leaves
    ``_compute_params`` fully intact (never half-swapped);
  - real concurrency, proven from tracer timestamps with injected
    transfer delays: the H2D span for leaf i-1 overlaps the CPU-Adam
    span for leaf i.
"""
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, "tests")

import deepspeed_tpu.runtime.offload as offload
from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.offload import (ShardedHostOffloadOptimizer,
                                           StreamingUploader)
from deepspeed_tpu.telemetry.tracing import TraceRecorder

from simple_model import SimpleModel, base_config, random_batches


def _dp1_mesh():
    from deepspeed_tpu.parallel import build_mesh
    return build_mesh(dp=1, devices=jax.devices()[:1])


def _cfg(pipeline=None, dpu=False, micro_bs=4, grad_acc=1, world_size=1):
    cfg = base_config(micro_bs=micro_bs, grad_acc=grad_acc, stage=2)
    cfg["zero_optimization"].update({"cpu_offload": True,
                                     "offload_impl": "host",
                                     "delayed_param_update": dpu})
    if pipeline is not None:
        cfg["zero_optimization"]["offload_pipeline"] = pipeline
    cfg["steps_per_print"] = 10 ** 9
    return DeepSpeedConfig(cfg, world_size=world_size)


def _train(engine, steps=4, hidden=16, seed=11):
    losses = []
    for b in random_batches(engine.train_batch_size, hidden,
                            num_batches=steps, seed=seed):
        losses.append(float(np.asarray(engine.train_batch(b))))
    return losses


def _assert_state_bitwise(e_a, e_b):
    for name, (la, lb) in (
            ("master", (jax.tree.leaves(e_a.state.master_params),
                        jax.tree.leaves(e_b.state.master_params))),
            ("mu", (jax.tree.leaves(e_a.state.opt_state["mu"]),
                    jax.tree.leaves(e_b.state.opt_state["mu"]))),
            ("nu", (jax.tree.leaves(e_a.state.opt_state["nu"]),
                    jax.tree.leaves(e_b.state.opt_state["nu"])))):
        assert len(la) == len(lb)
        for i, (x, y) in enumerate(zip(la, lb)):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y), err_msg=f"{name}[{i}]")
    ca = jax.tree.leaves(e_a._compute_params)
    cb = jax.tree.leaves(e_b._compute_params)
    assert len(ca) == len(cb)
    for i, (x, y) in enumerate(zip(ca, cb)):
        assert x.dtype == y.dtype, f"compute[{i}] dtype"
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"compute_params[{i}]")


# ---------------------------------------------------------------------
# bitwise equivalence: pipelined vs serial (DS_OFFLOAD_PIPELINE=0)
# ---------------------------------------------------------------------
@pytest.mark.parametrize("dpu", [False, True])
def test_pipelined_bitwise_equals_serial(dpu, monkeypatch):
    """The acceptance contract: identical master, moments, AND uploaded
    compute params after N steps — the env escape hatch IS the serial
    reference (so it is exercised too).  DPU composes: the flush during
    step t+1's dispatch window streams the same bytes."""
    mesh_devs = jax.devices()[:1]
    from deepspeed_tpu.parallel import build_mesh
    monkeypatch.delenv("DS_OFFLOAD_PIPELINE", raising=False)
    e_pipe = DeepSpeedEngine(SimpleModel(hidden_dim=16), _cfg(dpu=dpu),
                             mesh=build_mesh(dp=1, devices=mesh_devs),
                             seed=3)
    assert e_pipe._offload_pipeline
    monkeypatch.setenv("DS_OFFLOAD_PIPELINE", "0")
    e_ser = DeepSpeedEngine(SimpleModel(hidden_dim=16), _cfg(dpu=dpu),
                            mesh=build_mesh(dp=1, devices=mesh_devs),
                            seed=3)
    assert not e_ser._offload_pipeline
    monkeypatch.delenv("DS_OFFLOAD_PIPELINE")
    l_pipe = _train(e_pipe)
    l_ser = _train(e_ser)
    assert l_pipe == l_ser
    if dpu:  # compare the fully-applied state
        e_pipe._dpu_flush()
        e_ser._dpu_flush()
    _assert_state_bitwise(e_pipe, e_ser)


def test_pipelined_bitwise_dp8():
    """dp=8 single-process (replicated-compute host tier): the per-leaf
    uploads target the real compute shardings."""
    e_pipe = DeepSpeedEngine(SimpleModel(hidden_dim=16),
                             _cfg(pipeline=True, world_size=8), seed=5)
    e_ser = DeepSpeedEngine(SimpleModel(hidden_dim=16),
                            _cfg(pipeline=False, world_size=8), seed=5)
    assert e_pipe._offload_pipeline and not e_ser._offload_pipeline
    l_pipe = _train(e_pipe, steps=3)
    l_ser = _train(e_ser, steps=3)
    assert l_pipe == l_ser
    _assert_state_bitwise(e_pipe, e_ser)


def _sharded_fixture():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("data",))
    master = {
        "w": jax.device_put(
            np.arange(64, dtype=np.float32).reshape(16, 4) * 0.1,
            NamedSharding(mesh, P("data", None))),
        "b": jax.device_put(np.linspace(-1, 1, 4).astype(np.float32),
                            NamedSharding(mesh, P())),
    }
    grads = {
        "w": jax.device_put(np.ones((16, 4), np.float32),
                            NamedSharding(mesh, P("data", None))),
        "b": jax.device_put(np.full((4,), 0.5, np.float32),
                            NamedSharding(mesh, P())),
    }
    kw = dict(lr=1e-2, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
              compute_dtype=jnp.bfloat16)
    return master, grads, kw


def test_sharded_tier_streamed_bitwise():
    """ShardedHostOffloadOptimizer: on_leaf + upload_block +
    assemble_uploaded produce the SAME global arrays as the serial
    step's _assemble — including replicated (multi-device group)
    leaves — and identical blocks/moments.  Covers step_local (the DPU
    stash half) too."""
    master, grads, kw = _sharded_fixture()
    opt_a = ShardedHostOffloadOptimizer(master, **kw)
    opt_b = ShardedHostOffloadOptimizer(master, **kw)

    serial = opt_a.step(grads)
    uploaded = {}
    ret = opt_b.step(grads, on_leaf=lambda i, blk: uploaded.__setitem__(
        i, opt_b.upload_block(i, blk)))
    assert ret is None  # streamed mode: engine assembles
    pipe = opt_b.assemble_uploaded(
        [uploaded[i] for i in range(len(uploaded))])
    for k in serial:
        assert serial[k].dtype == pipe[k].dtype
        np.testing.assert_array_equal(np.asarray(serial[k]),
                                      np.asarray(pipe[k]), err_msg=k)
    for (_, _, ga), (_, _, gb) in zip(opt_a._flat_groups,
                                      opt_b._flat_groups):
        np.testing.assert_array_equal(ga["block"], gb["block"])
    for i in range(len(opt_a._flat_groups)):
        ma, va = opt_a.opt._moments(i, opt_a._flat_groups[i][2]["block"])
        mb, vb = opt_b.opt._moments(i, opt_b._flat_groups[i][2]["block"])
        np.testing.assert_array_equal(ma, mb)
        np.testing.assert_array_equal(va, vb)

    # the DPU half: pull_local + step_local, streamed vs serial
    blocks_a = opt_a.pull_local(grads)
    blocks_b = opt_b.pull_local(grads)
    serial2 = opt_a.step_local(blocks_a)
    uploaded2 = {}
    opt_b.step_local(blocks_b, on_leaf=lambda i, blk: uploaded2.__setitem__(
        i, opt_b.upload_block(i, blk)))
    pipe2 = opt_b.assemble_uploaded(
        [uploaded2[i] for i in range(len(uploaded2))])
    for k in serial2:
        np.testing.assert_array_equal(np.asarray(serial2[k]),
                                      np.asarray(pipe2[k]), err_msg=k)


def test_assemble_batches_device_puts(monkeypatch):
    """Satellite: _assemble must issue ONE batched jax.device_put call
    for all groups x replica devices, not one blocking-ish put per
    device in a serial python loop."""
    master, grads, kw = _sharded_fixture()
    opt = ShardedHostOffloadOptimizer(master, **kw)
    calls = []
    real_put = jax.device_put

    def spy(x, device=None, **kwargs):
        calls.append(x)
        return real_put(x, device, **kwargs)

    monkeypatch.setattr(offload.jax, "device_put", spy)
    cp = opt.compute_params()
    assert len(calls) == 1, f"{len(calls)} device_put calls (want 1)"
    # the replicated leaf fanned out under that one call: 8 devices for
    # "b" + one per "w" shard group
    assert len(calls[0]) == len(jax.devices()) + len(opt._local[1])
    assert cp["w"].dtype == jnp.bfloat16


class _ShardedShim:
    """Drives the REAL engine pipelined-update method against the
    sharded tier in one process (the engine only picks that tier under
    process_count > 1, which this container cannot run — the two-process
    e2e lives in test_multiprocess.py's slow tier)."""

    _offload_sharded = True
    _offload_pipeline = True
    telemetry = None

    def __init__(self, master, kw):
        import contextlib
        self._span = contextlib.nullcontext
        self._host_opt = ShardedHostOffloadOptimizer(master, **kw)
        shardings = jax.tree.map(lambda l: l.sharding, master)
        self._sharded_gather = jax.jit(lambda t: t,
                                       out_shardings=shardings)
        self._reshard_to_master = jax.jit(lambda t: t,
                                          out_shardings=shardings)
        self._compute_params = object()  # sentinel: must be REPLACED

    def _tel_span(self, *a, **k):
        return self._span()

    def _record_offload_overlap(self, *a):
        DeepSpeedEngine._record_offload_overlap(self, *a)


def test_engine_sharded_pipelined_update_bitwise():
    """The engine's sharded pipelined arm (upload_block → uploader →
    assemble_uploaded → _sharded_gather) against the serial sharded
    step, including the DPU-stash (_HostBlockStash) routing."""
    from deepspeed_tpu.runtime.engine import _HostBlockStash

    master, grads, kw = _sharded_fixture()
    shim = _ShardedShim(master, kw)
    ref_opt = ShardedHostOffloadOptimizer(master, **kw)

    DeepSpeedEngine._apply_host_update_pipelined(shim, grads)
    serial = ref_opt.step(grads)
    for k in serial:
        assert shim._compute_params[k].dtype == serial[k].dtype
        np.testing.assert_array_equal(
            np.asarray(shim._compute_params[k]), np.asarray(serial[k]),
            err_msg=k)
    assert shim.last_offload_breakdown["pipelined"]

    # DPU composition: stash → step_local through the same arm
    stash = _HostBlockStash(shim._host_opt.pull_local(grads))
    ref_blocks = ref_opt.pull_local(grads)
    DeepSpeedEngine._apply_host_update_pipelined(shim, stash)
    serial2 = ref_opt.step_local(ref_blocks)
    for k in serial2:
        np.testing.assert_array_equal(
            np.asarray(shim._compute_params[k]), np.asarray(serial2[k]),
            err_msg=k)


def test_engine_sharded_pipelined_upload_failure_poisons(monkeypatch):
    """Sharded arm of the poison contract: a failing batched put must
    poison the optimizer and leave the compute-param object untouched."""
    master, grads, kw = _sharded_fixture()
    shim = _ShardedShim(master, kw)
    sentinel = shim._compute_params

    def boom(blk, devices):
        raise ValueError("h2d link died")

    monkeypatch.setattr(offload, "_batched_device_put", boom)
    with pytest.raises(ValueError, match="h2d link died"):
        DeepSpeedEngine._apply_host_update_pipelined(shim, grads)
    assert shim._compute_params is sentinel
    assert shim._host_opt._poisoned is not None
    with pytest.raises(RuntimeError, match="poisoned"):
        shim._host_opt.step(grads)


# ---------------------------------------------------------------------
# failure injection: poison + intact _compute_params
# ---------------------------------------------------------------------
def test_upload_failure_poisons_and_preserves_compute_params(monkeypatch):
    """Adam completes, an H2D upload dies mid-pipeline: the optimizer
    must poison (master carries step t, device would keep t-1) and the
    engine must NOT have half-swapped _compute_params."""
    engine = DeepSpeedEngine(SimpleModel(hidden_dim=16),
                             _cfg(pipeline=True), mesh=_dp1_mesh(),
                             seed=7)
    batches = list(random_batches(engine.train_batch_size, 16,
                                  num_batches=3, seed=2))
    engine.train_batch(batches[0])  # healthy step
    old_params = engine._compute_params
    old_leaves = [np.asarray(x).copy()
                  for x in jax.tree.leaves(old_params)]

    fail_after = {"n": 0}

    def boom(arr, sharding):
        # let a couple of leaves through so the failure is genuinely
        # mid-pipeline, not at the first put
        fail_after["n"] += 1
        if fail_after["n"] > 2:
            raise ValueError("h2d link died")
        return jax.device_put(arr, sharding)

    monkeypatch.setattr(offload, "device_put_leaf", boom)
    with pytest.raises(ValueError, match="h2d link died"):
        engine.train_batch(batches[1])
    monkeypatch.undo()

    # old tree object untouched, values untouched
    assert engine._compute_params is old_params
    for x, ref in zip(jax.tree.leaves(engine._compute_params), old_leaves):
        np.testing.assert_array_equal(np.asarray(x), ref)
    # poisoned: no further training, no serialization
    assert engine._host_opt._poisoned is not None
    with pytest.raises(RuntimeError, match="poisoned"):
        engine.train_batch(batches[2])
    with pytest.raises(RuntimeError, match="refusing to serialize"):
        engine._host_opt.state_tree()


def test_adam_failure_with_pipeline_keeps_compute_params(monkeypatch):
    """The OTHER failure side: a grad-pull death mid-Adam (existing
    poison contract) must also leave _compute_params intact under the
    pipeline, and must not wedge on the upload worker."""
    engine = DeepSpeedEngine(SimpleModel(hidden_dim=16),
                             _cfg(pipeline=True), mesh=_dp1_mesh(),
                             seed=8)
    batch = next(random_batches(engine.train_batch_size, 16,
                                num_batches=1, seed=4))
    engine.train_batch(batch)
    old_params = engine._compute_params

    def broken(x):
        raise ValueError("link is dead")

    monkeypatch.setattr(offload.jax, "device_get", broken)
    with pytest.raises(ValueError, match="link is dead"):
        engine.train_batch(batch)
    monkeypatch.undo()
    assert engine._compute_params is old_params
    assert engine._host_opt._poisoned is not None


def test_streaming_uploader_blocks_until_transfer_done(monkeypatch):
    """The per-leaf timing window must contain the TRANSFER, not just
    the dispatch (device_put is async — the JL006 bug class), and an
    async transfer failure must surface inside the worker so the poison
    contract holds: the worker calls block_until_ready on every put."""
    drained = []
    real_block = jax.block_until_ready

    def spy(x):
        drained.append(x)
        return real_block(x)

    monkeypatch.setattr(offload.jax, "block_until_ready", spy)
    up = StreamingUploader(lambda i, a: jax.device_put(a))
    for i in range(3):
        up.submit(i, np.full((2,), float(i), np.float32))
    results, timings = up.finish()
    assert len(drained) == 3
    assert len(results) == 3 and len(timings) == 3

    # an error raised by the drain (async transfer failure) is caught
    # and re-raised from finish(), not leaked past it
    def boom(x):
        raise ValueError("async transfer died")

    monkeypatch.setattr(offload.jax, "block_until_ready", boom)
    up2 = StreamingUploader(lambda i, a: jax.device_put(a))
    up2.submit(0, np.zeros(2, np.float32))
    with pytest.raises(ValueError, match="async transfer died"):
        up2.finish()


def test_streaming_uploader_drains_after_failure():
    """A failed put poisons the uploader: later submissions are drained
    without touching the device, finish() raises the FIRST error, and
    the worker thread exits."""
    calls = []

    def put(idx, arr):
        calls.append(idx)
        if idx == 1:
            raise ValueError("boom")
        return arr

    before = set(threading.enumerate())
    up = StreamingUploader(put)
    workers = set(threading.enumerate()) - before
    for i in range(5):
        up.submit(i, np.zeros(2))
    with pytest.raises(ValueError, match="boom"):
        up.finish()
    assert calls == [0, 1], calls  # 2..4 drained, device untouched
    deadline = time.perf_counter() + 5.0
    while any(t.is_alive() for t in workers) and \
            time.perf_counter() < deadline:
        time.sleep(0.01)
    assert not any(t.is_alive() for t in workers), "worker leaked"


# ---------------------------------------------------------------------
# the concurrency proof: tracer timestamps with injected delays
# ---------------------------------------------------------------------
def _span_intervals(events, name):
    out = {}
    for e in events:
        if e.get("name") == name and e.get("ph") == "X":
            out[e["args"]["leaf"]] = (e["ts"], e["ts"] + e["dur"])
    return out


def test_pipeline_overlap_proven_by_tracer(monkeypatch):
    """With slow grad pulls (20ms) and slow uploads (30ms), the H2D span
    for leaf i-1 MUST overlap the CPU-Adam span for leaf i — the
    acceptance criterion, read straight off tracer timestamps — and the
    engine's measured overlap must be positive."""
    monkeypatch.setenv("DS_OFFLOAD_H2D_DELAY_S", "0.03")
    real_get = jax.device_get

    def slow_get(x):
        time.sleep(0.02)
        return real_get(x)

    tracer = TraceRecorder()
    offload.set_transfer_tracer(tracer)
    try:
        engine = DeepSpeedEngine(SimpleModel(hidden_dim=16, nlayers=3),
                                 _cfg(pipeline=True), mesh=_dp1_mesh(),
                                 seed=9)
        batch = next(random_batches(engine.train_batch_size, 16,
                                    num_batches=1, seed=5))
        monkeypatch.setattr(offload.jax, "device_get", slow_get)
        engine.train_batch(batch)
        monkeypatch.undo()
    finally:
        offload.set_transfer_tracer(None)

    evs = tracer.events()
    adam = _span_intervals(evs, "offload/adam_leaf")
    h2d = _span_intervals(evs, "offload/h2d_params")
    assert len(adam) >= 2 and len(h2d) >= 2, (len(adam), len(h2d))
    overlaps = []
    for i in sorted(adam):
        if i - 1 in h2d:
            a0, a1 = adam[i]
            u0, u1 = h2d[i - 1]
            overlaps.append(min(a1, u1) - max(a0, u0))
    assert overlaps and max(overlaps) > 0, (
        f"no H2D(i-1) x Adam(i) overlap observed: {overlaps}")

    bd = engine.last_offload_breakdown
    assert bd["pipelined"]
    assert bd["h2d_hidden_s"] > 0, bd
    assert 0 < bd["overlap_ratio"] <= 1, bd


@pytest.mark.parametrize("how", ["env", "config"])
def test_serial_path_reports_zero_overlap(how, monkeypatch):
    """Either way of switching the pipeline off (the escape-hatch
    variable, or ``offload_pipeline: false``) reports all-tail: nothing
    hidden, whatever the link's speed."""
    monkeypatch.setenv("DS_OFFLOAD_H2D_DELAY_S", "0.02")
    if how == "env":
        monkeypatch.setenv("DS_OFFLOAD_PIPELINE", "0")
    engine = DeepSpeedEngine(
        SimpleModel(hidden_dim=16),
        _cfg(pipeline=False if how == "config" else None),
        mesh=_dp1_mesh(), seed=10)
    batch = next(random_batches(engine.train_batch_size, 16,
                                num_batches=1, seed=6))
    engine.train_batch(batch)
    bd = engine.last_offload_breakdown
    assert not bd["pipelined"]
    assert bd["h2d_hidden_s"] == 0.0
    assert bd["overlap_ratio"] == 0.0
    assert bd["cpu_adam_s"] > 0


# ---------------------------------------------------------------------
# telemetry: gauge + sync scalar + summarize row
# ---------------------------------------------------------------------
def test_overlap_ratio_reaches_telemetry_artifacts(tmp_path):
    """offload_overlap_ratio must flow end-to-end: registry gauge →
    metrics.prom, sync scalar → events.jsonl → summarize report/row."""
    import json as _json
    from deepspeed_tpu.telemetry.cli import summarize

    cfg = base_config(micro_bs=4, grad_acc=1, stage=2)
    cfg["zero_optimization"].update({"cpu_offload": True,
                                     "offload_impl": "host"})
    cfg["steps_per_print"] = 1
    cfg["telemetry"] = {"enabled": True, "output_path": str(tmp_path)}
    engine = DeepSpeedEngine(SimpleModel(hidden_dim=16),
                             DeepSpeedConfig(cfg, world_size=1),
                             mesh=_dp1_mesh(), seed=12)
    for b in random_batches(engine.train_batch_size, 16, num_batches=2,
                            seed=7):
        engine.train_batch(b)
    gauge = engine.telemetry.registry.gauge("offload_overlap_ratio")
    assert gauge.value() is not None
    engine.close()

    prom = (tmp_path / "metrics.prom").read_text()
    assert "offload_overlap_ratio" in prom
    syncs = [_json.loads(l) for l in
             (tmp_path / "events.jsonl").read_text().splitlines()
             if _json.loads(l).get("kind") == "sync"]
    assert any("offload_overlap_ratio" in (s.get("scalars") or {})
               for s in syncs)
    rep = summarize(str(tmp_path / "events.jsonl"))
    assert rep["offload_overlap_ratio"] is not None


def test_summarize_overlap_row(tmp_path, capsys):
    import json as _json
    from deepspeed_tpu.telemetry.cli import summarize
    p = tmp_path / "events.jsonl"
    lines = [{"kind": "sync", "step": 10 * (i + 1), "interval_s": 1.0,
              "steps": 10, "step_avg_s": 0.1,
              "scalars": {"offload_overlap_ratio": r}}
             for i, r in enumerate((0.5, 0.7))]
    p.write_text("\n".join(_json.dumps(l) for l in lines) + "\n")
    rep = summarize(str(p))
    assert rep["offload_overlap_ratio"] == pytest.approx(0.6)
    assert "offload H2D overlap" in capsys.readouterr().out


# ---------------------------------------------------------------------
# config knob
# ---------------------------------------------------------------------
def test_offload_pipeline_config_validation():
    cfg = base_config(stage=2)
    cfg["zero_optimization"]["offload_pipeline"] = True
    with pytest.raises(DeepSpeedConfigError, match="requires cpu_offload"):
        DeepSpeedConfig(cfg, world_size=1)
    # explicit false is benign anywhere; the default never validates
    cfg["zero_optimization"]["offload_pipeline"] = False
    DeepSpeedConfig(cfg, world_size=1)
    DeepSpeedConfig(base_config(stage=2), world_size=1)


def test_explicit_pipeline_on_xla_tier_warns():
    """Explicit offload_pipeline:true on the xla tier must warn, not be
    silently ignored (the DS_OFFLOAD_SPLIT_UPDATE precedent)."""
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    cfg = base_config(micro_bs=4, grad_acc=1, stage=2)
    cfg["zero_optimization"].update({"cpu_offload": True,
                                     "offload_impl": "xla",
                                     "offload_pipeline": True})
    cfg["steps_per_print"] = 10 ** 9
    records = []

    class Rec(logging.Handler):
        def emit(self, record):
            records.append(record)

    h = Rec(level=logging.WARNING)
    ds_logger.addHandler(h)
    try:
        DeepSpeedEngine(SimpleModel(hidden_dim=16),
                        DeepSpeedConfig(cfg, world_size=1),
                        mesh=_dp1_mesh(), seed=13)
    finally:
        ds_logger.removeHandler(h)
    assert any("offload_pipeline is a host-tier knob" in r.getMessage()
               for r in records)


def test_offload_pipeline_default_on():
    cfg = _cfg()
    assert cfg.zero_config.offload_pipeline is True
    assert cfg.zero_config.offload_pipeline_explicit is False
