"""ZeRO-Infinity disk tier (runtime/disk_offload.py, docs/stages.md).

Contracts these tests pin — the PR 3/7 discipline applied to the new
bottom tier:

  - BITWISE equivalence: disk-tier training loss, master, moments, and
    uploaded compute params equal the host tier's, which equal the
    serial read-update-write loop's (the degradation target);
  - the chaos/torture matrix: transient ``disk_read``/``disk_write``
    faults are absorbed bitwise, sticky faults degrade to the serial
    loop bitwise, a CRC flip raises TYPED before any engine state is
    touched, and a kill mid-write-back resumes from checkpoint bitwise;
  - the capacity claim: total master+moment state larger than a
    configured host-RAM budget trains to completion with the resident
    window under the budget (the accounting assert);
  - real concurrency, proven from tracer timestamps with injected disk
    latency: the disk_read span for leaf i+1 overlaps the Adam span
    for leaf i.
"""
import json as _json
import os
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, "tests")

import deepspeed_tpu.runtime.offload as offload
from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.runtime.disk_offload import (DiskLeafStore,
                                                DiskOffloadOptimizer,
                                                DiskStateCorruptError,
                                                disk_fsync_enabled)
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.stages import reset_fault_injection
from deepspeed_tpu.telemetry.tracing import TraceRecorder

from simple_model import SimpleModel, base_config, random_batches


def _dp1_mesh():
    from deepspeed_tpu.parallel import build_mesh
    return build_mesh(dp=1, devices=jax.devices()[:1])


def _cfg(tier="disk", disk_dir=None, io_depth=2, dpu=False, micro_bs=4,
         telemetry_path=None, steps_per_print=10 ** 9):
    cfg = base_config(micro_bs=micro_bs, grad_acc=1, stage=2)
    cfg["zero_optimization"].update({"cpu_offload": True,
                                     "offload_impl": "host",
                                     "delayed_param_update": dpu})
    if tier == "disk":
        cfg["offload"] = {"tier": "disk", "disk_dir": str(disk_dir),
                          "io_depth": io_depth}
    cfg["steps_per_print"] = steps_per_print
    if telemetry_path is not None:
        cfg["telemetry"] = {"enabled": True,
                            "output_path": str(telemetry_path)}
    return DeepSpeedConfig(cfg, world_size=1)


def _engine(tmp_path, name="disk", seed=3, **kw):
    disk_dir = tmp_path / f"state_{name}"
    return DeepSpeedEngine(SimpleModel(hidden_dim=16),
                           _cfg(disk_dir=disk_dir, **kw),
                           mesh=_dp1_mesh(), seed=seed)


def _host_engine(seed=3, **kw):
    return DeepSpeedEngine(SimpleModel(hidden_dim=16),
                           _cfg(tier="host", **kw),
                           mesh=_dp1_mesh(), seed=seed)


def _train(engine, steps=4, hidden=16, seed=11):
    losses = []
    for b in random_batches(engine.train_batch_size, hidden,
                            num_batches=steps, seed=seed):
        losses.append(float(np.asarray(engine.train_batch(b))))
    return losses


def _assert_state_bitwise(e_a, e_b):
    for name, (ta, tb) in (
            ("master", (e_a.state.master_params, e_b.state.master_params)),
            ("mu", (e_a.state.opt_state["mu"], e_b.state.opt_state["mu"])),
            ("nu", (e_a.state.opt_state["nu"],
                    e_b.state.opt_state["nu"]))):
        la, lb = jax.tree.leaves(ta), jax.tree.leaves(tb)
        assert len(la) == len(lb)
        for i, (x, y) in enumerate(zip(la, lb)):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y), err_msg=f"{name}[{i}]")
    ca = jax.tree.leaves(e_a._compute_params)
    cb = jax.tree.leaves(e_b._compute_params)
    for i, (x, y) in enumerate(zip(ca, cb)):
        assert x.dtype == y.dtype, f"compute[{i}] dtype"
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"compute_params[{i}]")


# ---------------------------------------------------------------------
# bitwise equivalence: disk == host == serial reference
# ---------------------------------------------------------------------
@pytest.mark.parametrize("dpu", [False, True])
def test_disk_bitwise_equals_host_tier(dpu, tmp_path):
    """The acceptance contract: identical losses, master, moments, AND
    uploaded compute params after N steps, disk tier vs host tier —
    with and without the delayed parameter update composed on top."""
    e_disk = _engine(tmp_path, dpu=dpu, seed=3)
    e_host = _host_engine(dpu=dpu, seed=3)
    l_disk = _train(e_disk)
    l_host = _train(e_host)
    assert l_disk == l_host
    if dpu:
        e_disk._dpu_flush()
        e_host._dpu_flush()
    _assert_state_bitwise(e_disk, e_host)


def test_disk_pipelined_bitwise_equals_serial(tmp_path, monkeypatch):
    """The serial read-update-write loop IS the degradation target, so
    the escape hatch (DS_DISK_OFFLOAD_PIPELINE=0) must be bitwise the
    pipelined path — and this exercises the serial loop itself."""
    monkeypatch.delenv("DS_DISK_OFFLOAD_PIPELINE", raising=False)
    e_pipe = _engine(tmp_path, name="pipe", seed=5)
    monkeypatch.setenv("DS_DISK_OFFLOAD_PIPELINE", "0")
    e_ser = _engine(tmp_path, name="ser", seed=5)
    l_ser = _train(e_ser)
    monkeypatch.delenv("DS_DISK_OFFLOAD_PIPELINE")
    l_pipe = _train(e_pipe)
    assert l_pipe == l_ser
    _assert_state_bitwise(e_pipe, e_ser)
    assert e_ser.last_offload_breakdown["disk_serial"]
    assert not e_pipe.last_offload_breakdown["disk_serial"]
    # serial loop: I/O sits between Adam calls — zero hidden by
    # construction (the same shape as the host tier's all-tail rule)
    assert e_ser.last_offload_breakdown["disk_hidden_s"] == 0.0


# ---------------------------------------------------------------------
# the chaos/torture matrix (DS_STAGE_FAULT, docs/stages.md)
# ---------------------------------------------------------------------
def test_transient_disk_faults_bitwise(tmp_path, monkeypatch):
    """Transient faults at BOTH disk I/O points: absorbed by the stage
    retry budget, training bitwise-equal to the fault-free run, and no
    degradation (the budget counts CONSECUTIVE failures)."""
    e_fault = _engine(tmp_path, name="fault", seed=7)
    e_ref = _engine(tmp_path, name="ref", seed=7)
    reset_fault_injection()
    monkeypatch.setenv("DS_STAGE_FAULT",
                       "disk_read:read:2,disk_write:write:3")
    l_fault = _train(e_fault)
    monkeypatch.delenv("DS_STAGE_FAULT")
    reset_fault_injection()
    l_ref = _train(e_ref)
    assert l_fault == l_ref
    _assert_state_bitwise(e_fault, e_ref)
    assert not e_fault._stage_records["disk_read"].degraded
    assert not e_fault._stage_records["disk_write"].degraded
    assert e_fault._stage_records["disk_read"].failures >= 1


@pytest.mark.parametrize("stage,spec", [
    ("disk_read", "disk_read:read:1+"),
    ("disk_write", "disk_write:write:1+"),
])
def test_sticky_fault_degrades_to_serial_bitwise(stage, spec, tmp_path,
                                                 monkeypatch):
    """A sticky fault at EITHER disk I/O point (dead disk, not a blip)
    exhausts the budget, DEGRADES the stage to the serial
    read-update-write loop with training still completing, and the
    result is bitwise the fault-free reference — degradation costs
    latency, never bytes."""
    e_fault = _engine(tmp_path, name=f"sticky_{stage}", seed=9)
    e_ref = _engine(tmp_path, name=f"sref_{stage}", seed=9)
    reset_fault_injection()
    monkeypatch.setenv("DS_STAGE_FAULT", spec)
    l_fault = _train(e_fault)
    monkeypatch.delenv("DS_STAGE_FAULT")
    reset_fault_injection()
    assert e_fault._stage_records[stage].degraded
    # post-degradation steps took the serial loop
    assert e_fault.last_offload_breakdown["disk_serial"]
    l_ref = _train(e_ref)
    assert l_fault == l_ref
    _assert_state_bitwise(e_fault, e_ref)


def test_crc_flip_raises_typed_before_state_touched(tmp_path):
    """Bit-rot on a state file: the read raises
    :class:`DiskStateCorruptError` (typed, non-transient — retries
    cannot heal it) BEFORE the corrupt bytes reach the Adam kernel;
    the engine's compute params stay the old tree and the optimizer
    poisons so the torn state can neither train nor serialize."""
    engine = _engine(tmp_path, name="crc", seed=11)
    batches = list(random_batches(engine.train_batch_size, 16,
                                  num_batches=3, seed=2))
    engine.train_batch(batches[0])
    old_params = engine._compute_params
    # flip one payload byte of leaf 0's state file
    path = engine._host_opt._store.path(0)
    with open(path, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(DiskStateCorruptError, match="CRC32 mismatch"):
        engine.train_batch(batches[1])
    assert engine._compute_params is old_params
    assert engine._host_opt._poisoned is not None
    with pytest.raises(RuntimeError, match="poisoned"):
        engine.train_batch(batches[2])
    with pytest.raises(RuntimeError, match="refusing to serialize"):
        engine._host_opt.state_tree()


def test_kill_during_writeback_resumes_from_checkpoint_bitwise(
        tmp_path, monkeypatch):
    """A write-back that dies mid-step (power cut / kill) leaves leaf
    files torn across steps t-1/t: the step raises, the optimizer
    poisons, and a checkpoint restore REWRITES every leaf file —
    training then continues bitwise-identical to an uninterrupted
    run."""
    batches = list(random_batches(4, 16, num_batches=4, seed=13))
    # uninterrupted reference
    e_ref = _engine(tmp_path, name="kref", seed=15)
    l_ref = [float(np.asarray(e_ref.train_batch(b))) for b in batches]
    # victim: save after step 2, die mid-write-back on step 3
    e_vic = _engine(tmp_path, name="kvic", seed=15)
    for b in batches[:2]:
        e_vic.train_batch(b)
    save_dir = tmp_path / "ckpt"
    e_vic.save_checkpoint(str(save_dir), tag="t2", async_write=False)

    real_write = DiskLeafStore.write
    state = {"writes": 0}

    def dying_write(self, idx, sections):
        state["writes"] += 1
        if state["writes"] > 1:
            raise RuntimeError("power cut mid write-back")
        return real_write(self, idx, sections)

    monkeypatch.setattr(DiskLeafStore, "write", dying_write)
    with pytest.raises(RuntimeError, match="power cut"):
        e_vic.train_batch(batches[2])
    monkeypatch.undo()
    assert e_vic._host_opt._poisoned is not None
    # restore heals the torn per-leaf state and clears the poison
    e_vic.load_checkpoint(str(tmp_path / "ckpt"), tag="t2")
    assert e_vic._host_opt._poisoned is None
    l_resumed = [float(np.asarray(e_vic.train_batch(b)))
                 for b in batches[2:]]
    assert l_resumed == l_ref[2:]
    _assert_state_bitwise(e_vic, e_ref)


def test_async_save_downgrades_to_sync(tmp_path):
    """An async save on the disk tier would _host_snapshot the FULL
    master+moments into RAM — the exact bytes the tier keeps on disk —
    so the engine downgrades it to the sync path (which streams the
    fp32 planes leaf-by-leaf through save_tree) and the checkpoint is
    still produced, verified, and loadable."""
    engine = _engine(tmp_path, name="async", seed=25)
    batches = list(random_batches(engine.train_batch_size, 16,
                                  num_batches=2, seed=8))
    engine.train_batch(batches[0])
    sd = tmp_path / "async_ckpt"
    engine.save_checkpoint(str(sd), tag="t1", async_write=True)
    # downgraded: the writer never got a job (no coalescing/pending)
    assert not engine._ckpt_writer.in_flight()
    e2 = _engine(tmp_path, name="async2", seed=99)
    e2.load_checkpoint(str(sd), tag="t1")
    l1 = float(np.asarray(engine.train_batch(batches[1])))
    l2 = float(np.asarray(e2.train_batch(batches[1])))
    assert l1 == l2


# ---------------------------------------------------------------------
# capacity: state > RAM budget trains; resident window stays under it
# ---------------------------------------------------------------------
def test_capacity_state_exceeds_ram_budget(tmp_path, monkeypatch):
    """The ZeRO-Infinity claim, CPU-scaled: total master+moment bytes
    on disk EXCEED the configured host-RAM budget, yet training
    completes (the io_depth window stays under it — enforced by the
    accounting assert inside the optimizer) with loss bitwise the
    unbudgeted host tier's.  The budget is the ANALYTIC window bound
    (``(2*io_depth + 3)`` leaf states: read-ahead queue + leaf being
    staged + leaf in update + write-back queue + leaf being written),
    not a measured peak — so the assert can never flake on worker
    timing."""
    def mk(name, seed=17):
        disk_dir = tmp_path / f"state_{name}"
        return DeepSpeedEngine(
            SimpleModel(hidden_dim=16, nlayers=12),
            _cfg(disk_dir=disk_dir, io_depth=1),
            mesh=_dp1_mesh(), seed=seed)

    probe = mk("probe")
    opt = probe._host_opt
    max_leaf_state = max(
        (3 if prom else 1)
        * int(np.prod(shape, dtype=np.int64)) * dt.itemsize
        for shape, dt, prom in opt._meta)
    budget = (2 * opt.io_depth + 3) * max_leaf_state
    total = opt.total_state_bytes
    assert total > budget, (total, budget)
    l_probe = _train(probe, steps=2)
    monkeypatch.setenv("DS_OFFLOAD_DISK_RAM_BUDGET_MB",
                       str(budget / (1 << 20)))
    e_cap = mk("cap")
    l_cap = _train(e_cap, steps=2)
    monkeypatch.delenv("DS_OFFLOAD_DISK_RAM_BUDGET_MB")
    assert e_cap._host_opt.ram_budget_bytes == budget
    assert e_cap._host_opt.total_state_bytes > budget
    assert 0 < e_cap._host_opt.peak_resident_bytes <= budget
    assert l_cap == l_probe
    e_host = DeepSpeedEngine(SimpleModel(hidden_dim=16, nlayers=12),
                             _cfg(tier="host"), mesh=_dp1_mesh(),
                             seed=17)
    l_host = _train(e_host, steps=2)
    assert l_cap == l_host


def test_budget_violation_raises(tmp_path):
    """A window that genuinely does not fit must raise the accounting
    assert (non-transient), not silently blow past the budget."""
    import jax.numpy as jnp
    master = {"w": np.ones((64, 64), np.float32)}
    opt = DiskOffloadOptimizer(
        master, lr=1e-2, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
        compute_dtype=jnp.bfloat16, disk_dir=str(tmp_path / "tiny"),
        io_depth=1, ram_budget_bytes=1024)
    with pytest.raises(RuntimeError, match="exceeds the configured"):
        opt.step({"w": np.ones((64, 64), np.float32)})


# ---------------------------------------------------------------------
# the concurrency proof: tracer timestamps with injected disk latency
# ---------------------------------------------------------------------
def _span_intervals(events, name):
    out = {}
    for e in events:
        if e.get("name") == name and e.get("ph") == "X":
            out[e["args"]["leaf"]] = (e["ts"], e["ts"] + e["dur"])
    return out


def test_disk_overlap_proven_by_tracer(tmp_path, monkeypatch):
    """With injected disk latency (20ms/read, 10ms/write) and slow
    grad pulls (15ms), the disk_read span for leaf i+1 MUST overlap
    the Adam span for leaf i — the acceptance criterion, read straight
    off tracer timestamps — and the engine's measured disk overlap
    must be positive."""
    real_get = jax.device_get

    def slow_get(x):
        time.sleep(0.015)
        return real_get(x)

    tracer = TraceRecorder()
    offload.set_transfer_tracer(tracer)
    try:
        engine = DeepSpeedEngine(
            SimpleModel(hidden_dim=16, nlayers=3),
            _cfg(disk_dir=tmp_path / "ovl"), mesh=_dp1_mesh(), seed=19)
        batch = next(random_batches(engine.train_batch_size, 16,
                                    num_batches=1, seed=5))
        monkeypatch.setenv("DS_STAGE_DELAY_S",
                           "disk_read:0.02,disk_write:0.01")
        monkeypatch.setattr(offload.jax, "device_get", slow_get)
        engine.train_batch(batch)
        monkeypatch.undo()  # also reverts DS_STAGE_DELAY_S
    finally:
        offload.set_transfer_tracer(None)

    evs = tracer.events()
    adam = _span_intervals(evs, "offload/adam_leaf")
    reads = _span_intervals(evs, "offload/disk_read")
    assert len(adam) >= 2 and len(reads) >= 2, (len(adam), len(reads))
    overlaps = []
    for i in sorted(adam):
        if i + 1 in reads:
            a0, a1 = adam[i]
            r0, r1 = reads[i + 1]
            overlaps.append(min(a1, r1) - max(a0, r0))
    assert overlaps and max(overlaps) > 0, (
        f"no disk_read(i+1) x Adam(i) overlap observed: {overlaps}")

    bd = engine.last_offload_breakdown
    assert bd["disk_hidden_s"] > 0, bd
    assert 0 < bd["disk_overlap_ratio"] <= 1, bd
    assert bd["disk_bytes_read"] > 0 and bd["disk_bytes_written"] > 0


# ---------------------------------------------------------------------
# fsync: default-on pin + config/env gating
# ---------------------------------------------------------------------
def test_fsync_on_by_default(monkeypatch):
    """The production default is fsync ON (power-loss durability); the
    conftest's DS_DISK_FSYNC=0 is a test-suite override of that
    default, not the default itself — and the config knob can force
    it off without touching the env."""
    monkeypatch.delenv("DS_DISK_FSYNC", raising=False)
    assert disk_fsync_enabled() is True
    assert disk_fsync_enabled(config_default=False) is False
    monkeypatch.setenv("DS_DISK_FSYNC", "0")
    assert disk_fsync_enabled() is False
    monkeypatch.setenv("DS_DISK_FSYNC", "1")
    assert disk_fsync_enabled() is True


# ---------------------------------------------------------------------
# config validation (eager) + drain order
# ---------------------------------------------------------------------
def test_offload_config_validation(tmp_path):
    def cfg(**offload):
        c = base_config(micro_bs=4, grad_acc=1, stage=2)
        c["zero_optimization"].update({"cpu_offload": True,
                                       "offload_impl": "host"})
        c["offload"] = offload
        return c

    with pytest.raises(DeepSpeedConfigError, match="'host' or 'disk'"):
        DeepSpeedConfig(cfg(tier="nvme"), world_size=1)
    with pytest.raises(DeepSpeedConfigError, match="io_depth"):
        DeepSpeedConfig(cfg(tier="disk", disk_dir=str(tmp_path),
                            io_depth=0), world_size=1)
    with pytest.raises(DeepSpeedConfigError, match="io_depth"):
        DeepSpeedConfig(cfg(tier="disk", disk_dir=str(tmp_path),
                            io_depth=True), world_size=1)
    with pytest.raises(DeepSpeedConfigError, match="fsync"):
        DeepSpeedConfig(cfg(tier="disk", disk_dir=str(tmp_path),
                            fsync="yes"), world_size=1)
    with pytest.raises(DeepSpeedConfigError, match="requires "
                                                   "offload.disk_dir"):
        DeepSpeedConfig(cfg(tier="disk"), world_size=1)
    # tier=disk without cpu_offload
    c = base_config(micro_bs=4, grad_acc=1, stage=2)
    c["offload"] = {"tier": "disk", "disk_dir": str(tmp_path)}
    with pytest.raises(DeepSpeedConfigError, match="requires\n?.*"
                                                   "cpu_offload"):
        DeepSpeedConfig(c, world_size=1)
    # tier=disk with an explicit xla impl
    c = base_config(micro_bs=4, grad_acc=1, stage=2)
    c["zero_optimization"].update({"cpu_offload": True,
                                   "offload_impl": "xla"})
    c["offload"] = {"tier": "disk", "disk_dir": str(tmp_path)}
    with pytest.raises(DeepSpeedConfigError, match="host-impl"):
        DeepSpeedConfig(c, world_size=1)
    # the default tier never validates anything
    DeepSpeedConfig(base_config(micro_bs=4, grad_acc=1, stage=2),
                    world_size=1)


def test_drain_order_includes_disk_writeback(tmp_path):
    """THE documented drain order gains the disk write-back entry
    between the offload uploads and the checkpoint writer
    (docs/stages.md)."""
    engine = _engine(tmp_path, name="drain", seed=21)
    order = engine._stage_graph.order
    assert order.index("offload_uploads") < order.index("disk_writeback")
    assert order.index("disk_writeback") < order.index("ckpt_writer")
    engine.close()  # the disk entry must be close-safe between steps


# ---------------------------------------------------------------------
# telemetry: gauge + counters + sync scalar + summarize row
# ---------------------------------------------------------------------
def test_disk_telemetry_reaches_artifacts(tmp_path):
    """offload_disk_overlap_ratio and the disk byte counters must flow
    end-to-end: registry -> metrics.prom, sync scalar -> events.jsonl
    -> summarize report + printed row."""
    from deepspeed_tpu.telemetry.cli import summarize

    tel = tmp_path / "tel"
    engine = _engine(tmp_path, name="tel", telemetry_path=tel,
                     steps_per_print=1, seed=23)
    _train(engine, steps=2)
    assert engine.telemetry.registry.gauge(
        "offload_disk_overlap_ratio").value() is not None
    engine.close()

    prom = (tel / "metrics.prom").read_text()
    assert "offload_disk_overlap_ratio" in prom
    assert "disk_bytes_read_total" in prom
    assert "disk_bytes_written_total" in prom
    syncs = [_json.loads(l) for l in
             (tel / "events.jsonl").read_text().splitlines()
             if _json.loads(l).get("kind") == "sync"]
    assert any("offload_disk_overlap_ratio" in (s.get("scalars") or {})
               for s in syncs)
    rep = summarize(str(tel / "events.jsonl"))
    assert rep["offload_disk_overlap_ratio"] is not None


def test_summarize_disk_row(tmp_path, capsys):
    from deepspeed_tpu.telemetry.cli import summarize
    p = tmp_path / "events.jsonl"
    lines = [{"kind": "sync", "step": 10 * (i + 1), "interval_s": 1.0,
              "steps": 10, "step_avg_s": 0.1,
              "scalars": {"offload_disk_overlap_ratio": r,
                          "disk_read_s": 0.02, "disk_write_s": 0.01}}
             for i, r in enumerate((0.4, 0.8))]
    p.write_text("\n".join(_json.dumps(l) for l in lines) + "\n")
    rep = summarize(str(p))
    assert rep["offload_disk_overlap_ratio"] == pytest.approx(0.6)
    assert rep["disk_read_s"] == pytest.approx(0.02)
    out = capsys.readouterr().out
    assert "disk tier" in out
