"""Fault-tolerant checkpointing (ISSUE 5): async saves bitwise-identical
to sync, CRC integrity + typed corruption errors, the corrupt-latest
fallback chain, retention GC + orphaned-tmp sweep, transient-I/O retry,
the kill-during-save torture matrix, and the SIGTERM preemption hook."""
import json
import os
import signal
import threading
import time

import numpy as np
import jax
import pytest

from deepspeed_tpu.config import DeepSpeedConfig
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime import resilience
from deepspeed_tpu.runtime.resilience import (
    AsyncCheckpointWriter, CheckpointCorruptError, CheckpointJob,
    RetryPolicy, io_retry, reset_fault_injection)

from simple_model import SimpleModel, base_config, random_batches

HIDDEN = 16


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("DS_CKPT_FAULT", raising=False)
    monkeypatch.delenv("DS_CKPT_DELAY_S", raising=False)
    reset_fault_injection()
    yield
    reset_fault_injection()


def _engine(stage=0, precision="bf16", dp=1, seed=0, **over):
    # dp=1 default: the resilience plane (integrity, retention, retry,
    # writer semantics) is sharding-agnostic, and 1-device programs
    # compile several times faster — the multi-device save/load paths are
    # covered by tests/test_checkpointing.py's dp=8 matrix
    devices = jax.devices()
    if dp is not None:
        devices = devices[:dp]
    mesh = build_mesh(devices=devices)
    cfg = DeepSpeedConfig(
        base_config(micro_bs=2, grad_acc=1, stage=stage, precision=precision,
                    **over),
        world_size=mesh.shape["data"])
    return DeepSpeedEngine(SimpleModel(hidden_dim=HIDDEN), cfg, mesh=mesh,
                           seed=seed)


def _train(eng, steps=2, seed=0):
    losses = []
    for batch in random_batches(eng.train_batch_size, HIDDEN,
                                num_batches=steps, seed=seed):
        losses.append(float(eng.train_batch(batch)))
    return losses


def _state_equal(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y)))


def _dir_bytes(root):
    """relpath -> file bytes for a checkpoint dir (the bitwise contract)."""
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


HOST_OFFLOAD = {"zero_optimization": {"stage": 2, "cpu_offload": True,
                                      "offload_impl": "host"}}


# ---------------------------------------------------------------------------
# tentpole: async == sync, bitwise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("over", [{}, HOST_OFFLOAD],
                         ids=["plain", "host_offload"])
def test_async_save_bitwise_equals_sync(over, tmp_path):
    """Async and sync saves share ONE serialization path; the artifact
    bytes must be identical file for file (manifests, CRCs, meta, leaf
    data) — on the plain engine and across the offload boundary."""
    kw = dict(dp=1) if over else {}
    eng = _engine(stage=over and 2 or 0, **kw, **over)
    _train(eng, steps=2)
    eng.save_checkpoint(str(tmp_path / "sync"), tag="t", async_write=False)
    eng.save_checkpoint(str(tmp_path / "async"), tag="t", async_write=True)
    err = eng._ckpt_writer.drain()
    assert err is None
    a = _dir_bytes(str(tmp_path / "sync"))
    b = _dir_bytes(str(tmp_path / "async"))
    assert a.keys() == b.keys()
    for rel in a:
        assert a[rel] == b[rel], f"{rel} differs between sync and async"


def test_async_snapshot_immune_to_later_steps(tmp_path):
    """The snapshot COPIES host-tier numpy leaves: training steps taken
    while the writer is still serializing must not bleed into the saved
    bytes (the offload staging buffers are mutated in place by the C++
    Adam).  Sync ground truth is taken at the same step."""
    eng = _engine(stage=2, dp=1, **HOST_OFFLOAD)
    _train(eng, steps=2)
    eng.save_checkpoint(str(tmp_path / "truth"), tag="t", async_write=False)
    # slow the async write so the next steps overlap it
    os.environ["DS_CKPT_DELAY_S"] = "0.3"
    try:
        eng.save_checkpoint(str(tmp_path / "live"), tag="t",
                            async_write=True)
        _train(eng, steps=2, seed=7)  # mutates staging while writing
        err = eng._ckpt_writer.drain()
    finally:
        os.environ.pop("DS_CKPT_DELAY_S", None)
    assert err is None
    a = _dir_bytes(str(tmp_path / "truth"))
    b = _dir_bytes(str(tmp_path / "live"))
    assert a.keys() == b.keys()
    for rel in a:
        assert a[rel] == b[rel], f"{rel} corrupted by post-snapshot steps"


def test_async_roundtrip_restores(tmp_path):
    eng = _engine()
    _train(eng, steps=3)
    eng.save_checkpoint(str(tmp_path), tag="t", async_write=True)
    assert eng._ckpt_writer.drain() is None
    eng2 = _engine(seed=9)
    path, _ = eng2.load_checkpoint(str(tmp_path), tag="t")
    assert path is not None
    _state_equal(eng.state.master_params, eng2.state.master_params)
    assert eng2.global_steps == 3


def test_pipeline_engine_async_bitwise(tmp_path):
    """The pipe engine inherits the checkpoint machinery; async==sync
    must hold for its stage-stacked state too (pp=2 stays in the core
    tier; no train step — the save plane alone is under test)."""
    from deepspeed_tpu.pipe.engine import PipelineEngine
    from deepspeed_tpu.models import GPT2Config
    from deepspeed_tpu.models.gpt2_pipe import build_gpt2_pipe

    mesh = build_mesh(pp=2)
    cfg_model = GPT2Config(vocab_size=64, n_positions=16, d_model=16,
                           n_layer=2, n_head=2, remat=None)
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }, world_size=mesh.shape["data"])
    eng = PipelineEngine(build_gpt2_pipe(cfg_model, num_stages=2), cfg, mesh)
    eng.save_checkpoint(str(tmp_path / "sync"), tag="t", async_write=False)
    eng.save_checkpoint(str(tmp_path / "async"), tag="t", async_write=True)
    assert eng._ckpt_writer.drain() is None
    a = _dir_bytes(str(tmp_path / "sync"))
    b = _dir_bytes(str(tmp_path / "async"))
    assert a.keys() == b.keys()
    for rel in a:
        assert a[rel] == b[rel], f"{rel} differs between sync and async"


# ---------------------------------------------------------------------------
# writer semantics
# ---------------------------------------------------------------------------
def test_writer_coalesces_latest_wins(tmp_path):
    ran = []
    gate = threading.Event()

    def slow_job(tag):
        def run():
            if tag == "a":
                gate.wait(5.0)
            ran.append(tag)
        return CheckpointJob(tag=tag, tmp_dir=str(tmp_path / f"{tag}.tmp"),
                             final_dir=str(tmp_path / tag), run=run)

    w = AsyncCheckpointWriter()
    w.submit(slow_job("a"))
    deadline = time.time() + 5.0
    while w._busy is None and time.time() < deadline:
        time.sleep(0.002)     # wait until the worker holds "a" (gated)
    assert w._busy is not None
    w.submit(slow_job("b"))   # pending
    w.submit(slow_job("c"))   # replaces "b" — latest wins
    assert w.active_tmp() >= {str(tmp_path / "a.tmp"),
                              str(tmp_path / "c.tmp")}
    gate.set()
    assert w.drain() is None
    assert ran == ["a", "c"]  # "b" was coalesced away
    assert w.coalesced == 1
    w.close()
    w.close()  # idempotent


def test_writer_failure_poisons_only_pending():
    w = AsyncCheckpointWriter()

    def boom():
        raise OSError("disk gone")
    w.submit(CheckpointJob("bad", "/tmp/x.tmp", "/tmp/x", boom))
    err = w.drain()
    assert isinstance(err, OSError)
    assert w.pop_error() is None  # drain cleared it
    ok = []
    w.submit(CheckpointJob("good", "/tmp/y.tmp", "/tmp/y",
                           lambda: ok.append(1)))
    assert w.drain() is None  # writer survived; next save succeeded
    assert ok == [1]
    assert w.failed == 1 and w.completed == 1
    w.close()


def test_engine_survives_async_save_failure(tmp_path):
    """A writer failure poisons only the pending save: training continues,
    the error surfaces on the next train_batch (last_ckpt_error), and the
    next save — fault cleared — succeeds and is loadable."""
    eng = _engine()
    _train(eng, steps=1)
    os.environ["DS_CKPT_FAULT"] = "meta:1+"
    try:
        eng.save_checkpoint(str(tmp_path), tag="doomed", async_write=True)
        eng._ckpt_writer.drain()
    finally:
        os.environ.pop("DS_CKPT_FAULT", None)
    # drain() cleared the writer-side error; the tick path is exercised
    # by a fresh failure left un-drained:
    reset_fault_injection()
    os.environ["DS_CKPT_FAULT"] = "meta:1+"
    try:
        eng.save_checkpoint(str(tmp_path), tag="doomed2", async_write=True)
        eng._ckpt_writer.drain(timeout=10.0)
        eng._ckpt_writer._last_error = OSError("kept for tick")  # rearm
        _train(eng, steps=1, seed=5)  # pre-step tick surfaces it
    finally:
        os.environ.pop("DS_CKPT_FAULT", None)
    assert isinstance(eng.last_ckpt_error, OSError)
    reset_fault_injection()
    eng.save_checkpoint(str(tmp_path), tag="ok", async_write=True)
    assert eng._ckpt_writer.drain() is None
    eng2 = _engine(seed=3)
    path, _ = eng2.load_checkpoint(str(tmp_path), tag="ok")
    assert path is not None


# ---------------------------------------------------------------------------
# integrity plane
# ---------------------------------------------------------------------------
def _corrupt_one_leaf(ckpt_dir, plane="model"):
    """Flip bytes inside the first leaf's .npy payload (header intact)."""
    mpath = os.path.join(ckpt_dir, plane, "manifest.json")
    manifest = json.load(open(mpath))
    key, entry = next((k, e) for k, e in manifest.items()
                      if e.get("nbytes", 0) > 4)
    fpath = os.path.join(ckpt_dir, plane, entry["file"])
    data = bytearray(open(fpath, "rb").read())
    data[-4] ^= 0xFF  # inside the array payload, not the npy header
    open(fpath, "wb").write(bytes(data))
    return key, entry["file"]


def test_crc_detects_flipped_bit(tmp_path):
    eng = _engine()
    _train(eng, steps=2)
    eng.save_checkpoint(str(tmp_path), tag="t")
    key, fname = _corrupt_one_leaf(str(tmp_path / "t"), "optim")
    eng2 = _engine(seed=9)
    with pytest.raises(CheckpointCorruptError) as ei:
        eng2.load_checkpoint(str(tmp_path), tag="t")
    # the typed error names the leaf and the file
    assert fname in str(ei.value) and "CRC32" in str(ei.value)
    # and no half-restored state: the engine still trains
    assert np.isfinite(_train(eng2, steps=1)).all()


def test_manifest_digest_detects_tamper(tmp_path):
    eng = _engine()
    _train(eng, steps=1)
    eng.save_checkpoint(str(tmp_path), tag="t")
    mpath = tmp_path / "t" / "optim" / "manifest.json"
    m = json.load(open(mpath))
    json.dump(m, open(mpath, "w"), indent=4)  # re-serialized != digest
    eng2 = _engine(seed=1)
    with pytest.raises(CheckpointCorruptError, match="digest"):
        eng2.load_checkpoint(str(tmp_path), tag="t")


def test_truncated_leaf_detected(tmp_path):
    eng = _engine()
    _train(eng, steps=1)
    eng.save_checkpoint(str(tmp_path), tag="t")
    mpath = tmp_path / "t" / "optim" / "manifest.json"
    manifest = json.load(open(mpath))
    key, entry = next((k, e) for k, e in manifest.items()
                      if e.get("nbytes", 0) > 16)
    fpath = tmp_path / "t" / "optim" / entry["file"]
    data = open(fpath, "rb").read()
    open(fpath, "wb").write(data[:-8])  # truncate mid-payload
    eng2 = _engine(seed=1)
    with pytest.raises(CheckpointCorruptError):
        eng2.load_checkpoint(str(tmp_path), tag="t")
    # the model plane arm too (module-only restore)
    mpath = tmp_path / "t" / "model" / "manifest.json"
    manifest = json.load(open(mpath))
    key, entry = next((k, e) for k, e in manifest.items()
                      if e.get("nbytes", 0) > 16)
    fpath = tmp_path / "t" / "model" / entry["file"]
    data = open(fpath, "rb").read()
    open(fpath, "wb").write(data[:-8])
    with pytest.raises(CheckpointCorruptError):
        eng2.load_checkpoint(str(tmp_path), tag="t",
                             load_module_only=True)


# ---------------------------------------------------------------------------
# fallback chain
# ---------------------------------------------------------------------------
def test_corrupt_latest_falls_back_to_older_tag(tmp_path):
    eng = _engine()
    _train(eng, steps=2)
    eng.save_checkpoint(str(tmp_path), tag="t1")
    good_master = jax.tree.map(
        lambda x: np.array(jax.device_get(x)), eng.state.master_params)
    _train(eng, steps=1, seed=3)
    eng.save_checkpoint(str(tmp_path), tag="t2")  # latest -> t2
    _corrupt_one_leaf(str(tmp_path / "t2"), "optim")

    eng2 = _engine(seed=9)
    path, _ = eng2.load_checkpoint(str(tmp_path))  # tag=None
    assert path is not None and path.endswith("t1")
    _state_equal(good_master, eng2.state.master_params)
    assert eng2.global_steps == 2


def test_latest_points_to_deleted_tag(tmp_path):
    """Manual cleanup / partial rsync: `latest` names a tag whose dir is
    gone — fall back to the newest on-disk tag that verifies instead of
    reporting "nothing to load" (ISSUE 5 satellite)."""
    import shutil
    eng = _engine()
    _train(eng, steps=2)
    eng.save_checkpoint(str(tmp_path), tag="a")
    _train(eng, steps=1, seed=3)
    eng.save_checkpoint(str(tmp_path), tag="b")  # latest -> b
    shutil.rmtree(tmp_path / "b")

    eng2 = _engine(seed=9)
    path, _ = eng2.load_checkpoint(str(tmp_path))
    assert path is not None and path.endswith("a")
    assert eng2.global_steps == 2


def test_fallback_bounded_by_config(tmp_path):
    """load_fallback=0 disables walking back: a corrupt latest raises
    instead of silently resuming from an older tag."""
    over = {"checkpoint": {"load_fallback": 0}}
    eng = _engine(**over)
    _train(eng, steps=1)
    eng.save_checkpoint(str(tmp_path), tag="t1")
    _train(eng, steps=1, seed=3)
    eng.save_checkpoint(str(tmp_path), tag="t2")
    _corrupt_one_leaf(str(tmp_path / "t2"), "optim")
    eng2 = _engine(seed=9, **over)
    with pytest.raises(CheckpointCorruptError, match="load_fallback"):
        eng2.load_checkpoint(str(tmp_path))


def test_all_candidates_corrupt_raises(tmp_path):
    eng = _engine()
    _train(eng, steps=1)
    eng.save_checkpoint(str(tmp_path), tag="t1")
    _train(eng, steps=1, seed=3)
    eng.save_checkpoint(str(tmp_path), tag="t2")
    _corrupt_one_leaf(str(tmp_path / "t1"), "optim")
    _corrupt_one_leaf(str(tmp_path / "t2"), "optim")
    eng2 = _engine(seed=9)
    with pytest.raises(CheckpointCorruptError, match="no loadable"):
        eng2.load_checkpoint(str(tmp_path))


# ---------------------------------------------------------------------------
# retention GC + orphan sweep
# ---------------------------------------------------------------------------
def test_retention_keep_last_n(tmp_path):
    over = {"checkpoint": {"keep_last_n": 2}}
    eng = _engine(**over)
    for i in range(4):
        _train(eng, steps=1, seed=i)
        eng.save_checkpoint(str(tmp_path), tag=f"t{i}")
        time.sleep(0.02)  # distinct mtimes for newest-first ordering
    tags = {d for d in os.listdir(tmp_path)
            if os.path.isdir(tmp_path / d)}
    assert tags == {"t2", "t3"}
    assert (tmp_path / "latest").read_text().strip() == "t3"
    # the survivors load fine
    eng2 = _engine(seed=9, **over)
    path, _ = eng2.load_checkpoint(str(tmp_path))
    assert path.endswith("t3")


def test_stale_tmp_sweep(tmp_path):
    """A crash mid-save leaves <tag>.tmp forever unless the SAME tag is
    re-saved (the old behavior); any save now sweeps every orphaned
    *.tmp under save_dir (ISSUE 5 satellite)."""
    orphan = tmp_path / "dead_tag.tmp"
    orphan.mkdir()
    (orphan / "leaf_00000.npy").write_bytes(b"partial")
    eng = _engine()
    _train(eng, steps=1)
    eng.save_checkpoint(str(tmp_path), tag="fresh")
    assert not orphan.exists()
    assert (tmp_path / "fresh").is_dir()


def test_gc_never_removes_before_save_verifies(tmp_path):
    """A save that dies mid-write must not trigger retention: the old
    tags — the fallback chain's substance — survive."""
    over = {"checkpoint": {"keep_last_n": 1, "io_retry_attempts": 1}}
    eng = _engine(**over)
    for i in range(2):
        _train(eng, steps=1, seed=i)
        eng.save_checkpoint(str(tmp_path), tag=f"t{i}")
        time.sleep(0.02)
    assert {d for d in os.listdir(tmp_path)
            if os.path.isdir(tmp_path / d)} == {"t1"}
    _train(eng, steps=1, seed=9)
    os.environ["DS_CKPT_FAULT"] = "meta:1+"
    try:
        with pytest.raises(Exception):
            eng.save_checkpoint(str(tmp_path), tag="t2")
    finally:
        os.environ.pop("DS_CKPT_FAULT", None)
    # t1 survived the failed save; nothing was GC'd
    assert (tmp_path / "t1" / "meta.json").is_file()
    eng2 = _engine(seed=5, **over)
    path, _ = eng2.load_checkpoint(str(tmp_path))
    assert path.endswith("t1")


# ---------------------------------------------------------------------------
# transient-I/O retry
# ---------------------------------------------------------------------------
def test_io_retry_transient_blip():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("blip")
        return "ok"
    assert io_retry(flaky, "flaky", RetryPolicy(3, 0.001)) == "ok"
    assert len(calls) == 3
    with pytest.raises(OSError):
        io_retry(lambda: (_ for _ in ()).throw(OSError("dead")),
                 "dead", RetryPolicy(2, 0.001))


def test_save_retries_injected_fault(tmp_path):
    """A single-shot injected fault (leaf write #2 fails once) is
    absorbed by the retry plane; the save completes, loads back, and the
    ckpt_retries_total counter records the blip."""
    over = {"checkpoint": {"io_retry_base_s": 0.001},
            "telemetry": {"enabled": True,
                          "output_path": str(tmp_path / "tel"),
                          "compile_events": False, "memory": False}}
    eng = _engine(**over)
    _train(eng, steps=1)
    os.environ["DS_CKPT_FAULT"] = "leaf:2"
    try:
        eng.save_checkpoint(str(tmp_path), tag="t")
    finally:
        os.environ.pop("DS_CKPT_FAULT", None)
    assert eng.telemetry.registry.counter(
        "ckpt_retries_total", "").value() >= 1
    eng2 = _engine(seed=5)
    path, _ = eng2.load_checkpoint(str(tmp_path), tag="t")
    assert path is not None
    _state_equal(eng.state.master_params, eng2.state.master_params)
    eng.close()


# ---------------------------------------------------------------------------
# kill-during-save torture matrix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("point", ["leaf:1+", "leaf:4+", "manifest:1+",
                                   "manifest:2+", "meta:1+", "rename:1+",
                                   "latest:1+"])
def test_torture_kill_at_every_write_point(point, tmp_path):
    """Sustained failure (≈ SIGKILL mid-save) at EVERY write point —
    each leaf file, the manifests, meta.json, the rename, the latest
    update: a subsequent load must always restore the last GOOD
    checkpoint bitwise, never a partial one."""
    over = {"checkpoint": {"io_retry_attempts": 2,
                           "io_retry_base_s": 0.001}}
    eng = _engine(**over)
    _train(eng, steps=2)
    eng.save_checkpoint(str(tmp_path), tag="good")
    good_bytes = _dir_bytes(str(tmp_path / "good"))
    good_master = jax.tree.map(
        lambda x: np.array(jax.device_get(x)), eng.state.master_params)
    good_opt = jax.tree.map(
        lambda x: np.array(jax.device_get(x)), eng.state.opt_state)

    _train(eng, steps=1, seed=7)
    os.environ["DS_CKPT_FAULT"] = point
    try:
        if point.startswith("latest"):
            # everything else landed; only the pointer update died —
            # the save fails loudly but `latest` still names "good"
            with pytest.raises(Exception):
                eng.save_checkpoint(str(tmp_path), tag="doomed")
        else:
            with pytest.raises(Exception):
                eng.save_checkpoint(str(tmp_path), tag="doomed")
            # the kill left no loadable-looking doomed checkpoint
            assert not os.path.isfile(
                tmp_path / "doomed" / "meta.json") or point == "latest:1+"
    finally:
        os.environ.pop("DS_CKPT_FAULT", None)
    reset_fault_injection()

    # the good checkpoint's bytes are untouched
    assert _dir_bytes(str(tmp_path / "good")) == good_bytes
    eng2 = _engine(seed=11, **over)
    path, _ = eng2.load_checkpoint(str(tmp_path))  # via latest
    assert path is not None and path.endswith("good")
    _state_equal(good_master, eng2.state.master_params)
    _state_equal(good_opt, eng2.state.opt_state)
    assert eng2.global_steps == 2


def test_torture_kill_during_async_save(tmp_path):
    """The async arm of the same guarantee: a writer killed mid-save
    leaves the previous checkpoint as the loadable truth."""
    over = {"checkpoint": {"io_retry_attempts": 1}}
    eng = _engine(**over)
    _train(eng, steps=2)
    eng.save_checkpoint(str(tmp_path), tag="good")
    good_master = jax.tree.map(
        lambda x: np.array(jax.device_get(x)), eng.state.master_params)
    _train(eng, steps=1, seed=7)
    os.environ["DS_CKPT_FAULT"] = "manifest:1+"
    try:
        eng.save_checkpoint(str(tmp_path), tag="doomed", async_write=True)
        err = eng._ckpt_writer.drain()
    finally:
        os.environ.pop("DS_CKPT_FAULT", None)
    assert err is not None  # poisoned THAT save only
    eng2 = _engine(seed=11)
    path, _ = eng2.load_checkpoint(str(tmp_path))
    assert path.endswith("good")
    _state_equal(good_master, eng2.state.master_params)


# ---------------------------------------------------------------------------
# SIGTERM preemption
# ---------------------------------------------------------------------------
def test_preemption_sigterm_resume_identical(tmp_path):
    """End-to-end: SIGTERM mid-run → final sync save + close → restart →
    loss trajectory identical to an uninterrupted run."""
    ref = _engine(seed=0)
    batches = list(random_batches(ref.train_batch_size, HIDDEN,
                                  num_batches=5, seed=0))
    ref_losses = [float(ref.train_batch(b)) for b in batches]

    eng = _engine(seed=0)
    handler = resilience.install_preemption_handler(
        eng, str(tmp_path), exit_after=False)
    for b in batches[:3]:
        eng.train_batch(b)
    os.kill(os.getpid(), signal.SIGTERM)  # delivered between bytecodes
    assert handler.fired
    handler.uninstall()
    # the hook saved at the PREEMPTED step (3), not an interval boundary
    eng2 = _engine(seed=42)
    path, _ = eng2.load_checkpoint(str(tmp_path))
    assert path is not None and eng2.global_steps == 3
    resumed = [float(eng2.train_batch(b)) for b in batches[3:]]
    assert resumed == ref_losses[3:]


def test_sigterm_config_installs_handler(tmp_path):
    over = {"checkpoint": {"sigterm_save": True,
                           "save_dir": str(tmp_path)}}
    eng = _engine(**over)
    h = eng._preemption_handler
    assert h is not None and h.installed
    assert signal.getsignal(signal.SIGTERM) == h._handle
    eng.close()  # uninstalls
    assert signal.getsignal(signal.SIGTERM) != h._handle


# ---------------------------------------------------------------------------
# telemetry evidence
# ---------------------------------------------------------------------------
def test_async_overlap_visible_in_tracer(tmp_path):
    """With injected write latency, the checkpoint/async_write span must
    extend past its checkpoint/save span (the write ran in the
    background) and a subsequent train/dispatch span must start inside
    the write window — overlap proven from tracer timestamps."""
    over = {"telemetry": {"enabled": True,
                          "output_path": str(tmp_path / "tel"),
                          "compile_events": False, "memory": False}}
    eng = _engine(**over)
    _train(eng, steps=1)
    os.environ["DS_CKPT_DELAY_S"] = "0.2"
    try:
        eng.save_checkpoint(str(tmp_path / "ck"), async_write=True)
        _train(eng, steps=2, seed=5)
        assert eng._ckpt_writer.drain() is None
    finally:
        os.environ.pop("DS_CKPT_DELAY_S", None)
    ev = [e for e in eng.telemetry.tracer.events() if e.get("ph") == "X"]

    def spans(name):
        return [(e["ts"], e["ts"] + e["dur"]) for e in ev
                if e["name"] == name]
    (s0, s1), = spans("checkpoint/save")
    (w0, w1), = spans("checkpoint/async_write")
    assert w1 > s1 + 0.1e6, "write did not run past the save call"
    dispatch = [t for t in spans("train/dispatch") if t[0] > s1]
    assert dispatch and dispatch[0][0] < w1, \
        "no training step overlapped the background write"
    eng.close()


def test_ckpt_scalars_flow_to_summarize(tmp_path, capsys):
    """ckpt_save_s / ckpt_async_overlap_s ride the periodic sync into
    events.jsonl and surface as the summarize checkpoint row."""
    from deepspeed_tpu.telemetry.cli import summarize
    over = {"steps_per_print": 2,
            "telemetry": {"enabled": True,
                          "output_path": str(tmp_path / "tel"),
                          "compile_events": False, "memory": False}}
    eng = _engine(**over)
    _train(eng, steps=1)
    eng.save_checkpoint(str(tmp_path / "ck"), async_write=True)
    assert eng._ckpt_writer.drain() is None
    _train(eng, steps=3, seed=5)  # crosses the steps_per_print sync
    eng.close()
    report = summarize(str(tmp_path / "tel" / "events.jsonl"))
    capsys.readouterr()
    assert report["ckpt_save_s"] is not None
    assert report["ckpt_async_overlap_s"] is not None
    assert report["ckpt_async_overlap_s"] > 0


# ---------------------------------------------------------------------------
# misc semantics
# ---------------------------------------------------------------------------
def test_sync_save_drains_pending_async(tmp_path):
    """Ordering: a sync save issued while an async one is in flight must
    land AFTER it — `latest` ends on the sync tag, never a stale one."""
    eng = _engine()
    _train(eng, steps=1)
    os.environ["DS_CKPT_DELAY_S"] = "0.2"
    try:
        eng.save_checkpoint(str(tmp_path), tag="a", async_write=True)
    finally:
        os.environ.pop("DS_CKPT_DELAY_S", None)
    eng.save_checkpoint(str(tmp_path), tag="b", async_write=False)
    assert not eng._ckpt_writer.in_flight()
    assert (tmp_path / "a" / "meta.json").is_file()
    assert (tmp_path / "b" / "meta.json").is_file()
    assert (tmp_path / "latest").read_text().strip() == "b"


def test_close_drains_async_save(tmp_path):
    eng = _engine()
    _train(eng, steps=1)
    os.environ["DS_CKPT_DELAY_S"] = "0.2"
    try:
        eng.save_checkpoint(str(tmp_path), tag="t", async_write=True)
    finally:
        os.environ.pop("DS_CKPT_DELAY_S", None)
    eng.close()
    assert (tmp_path / "t" / "meta.json").is_file()


def test_fsync_on_by_default(tmp_path, monkeypatch):
    """Production saves fsync every file + the dir (power-loss
    durability); DS_CKPT_FSYNC=0 (the conftest's test-speed knob on this
    image's slow 9p filesystem) suppresses it.  Pin both arms so the
    default can't silently rot."""
    import deepspeed_tpu.runtime.checkpointing as ckpt_mod
    calls = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: calls.append(fd)
                        or real_fsync(fd))
    eng = _engine()
    _train(eng, steps=1)
    monkeypatch.setenv("DS_CKPT_FSYNC", "0")
    eng.save_checkpoint(str(tmp_path), tag="nosync")
    assert not calls
    monkeypatch.delenv("DS_CKPT_FSYNC")  # production default: ON
    assert ckpt_mod._fsync_enabled()
    eng.save_checkpoint(str(tmp_path), tag="sync")
    assert len(calls) > 5  # every leaf + manifests + meta + latest + dir


def test_legacy_checkpoint_without_crc_still_loads(tmp_path):
    """Pre-integrity checkpoints (no crc32/nbytes/digests) load on
    trust — format evolution must not orphan old runs."""
    eng = _engine()
    _train(eng, steps=2)
    eng.save_checkpoint(str(tmp_path), tag="t")
    ck = tmp_path / "t"
    meta = json.load(open(ck / "meta.json"))
    meta.pop("manifest_digests", None)
    meta.pop("format_version", None)
    json.dump(meta, open(ck / "meta.json", "w"))
    for plane in ("model", "optim"):
        mp = ck / plane / "manifest.json"
        m = json.load(open(mp))
        for e in m.values():
            e.pop("crc32", None)
            e.pop("nbytes", None)
        json.dump(m, open(mp, "w"))
    eng2 = _engine(seed=9)
    path, _ = eng2.load_checkpoint(str(tmp_path), tag="t")
    assert path is not None
    _state_equal(eng.state.master_params, eng2.state.master_params)


def test_stacked_handler_uninstall_does_not_clobber(tmp_path):
    """Two engines with SIGTERM hooks: closing/uninstalling the FIRST
    must not clobber the second's active handler (blind restore would
    silently revert SIGTERM to the default kill — found by the verify
    drive)."""
    e1 = _engine(seed=1)
    e2 = _engine(seed=2)
    h1 = resilience.install_preemption_handler(
        e1, str(tmp_path / "a"), exit_after=False)
    h2 = resilience.install_preemption_handler(
        e2, str(tmp_path / "b"), exit_after=False)
    _train(e1, 1)
    _train(e2, 1)
    h1.uninstall()  # sandwiched: must go inert, not restore its prev
    assert signal.getsignal(signal.SIGTERM) == h2._handle
    os.kill(os.getpid(), signal.SIGTERM)
    assert h2.fired and not h1.fired
    assert (tmp_path / "b" / "latest").is_file()   # e2's hook saved
    assert not (tmp_path / "a").exists()           # e1's did not
    h2.uninstall()
    # h2 restored ITS prev (the inert h1, which chains through); a
    # further SIGTERM fires neither hook and saves nothing new
    before = os.listdir(tmp_path)
    os.kill(os.getpid(), signal.SIGTERM)
    assert not h1.fired and os.listdir(tmp_path) == before


def test_sigterm_mid_step_defers_to_boundary(tmp_path):
    """A SIGTERM that interrupts train_batch mid-update must NOT save
    immediately (it could checkpoint a torn, half-applied optimizer
    state with valid CRCs — code-review finding): the handler parks and
    the save runs at the step boundary."""
    eng = _engine(seed=0)
    handler = resilience.install_preemption_handler(
        eng, str(tmp_path), exit_after=False)
    _train(eng, steps=1)
    eng._in_step = True  # simulate the signal landing inside train_batch
    handler._handle(signal.SIGTERM, None)
    assert not handler.fired
    assert eng._deferred_preempt is handler
    assert not (tmp_path / "latest").exists()  # nothing saved mid-step
    eng._in_step = False
    _train(eng, steps=1, seed=3)  # finally-block completes the save
    assert handler.fired
    assert (tmp_path / "latest").is_file()
    eng2 = _engine(seed=9)
    path, _ = eng2.load_checkpoint(str(tmp_path))
    # saved at the boundary AFTER the interrupted step finished
    assert eng2.global_steps == 2
    handler.uninstall()


def test_same_tag_resave_survives_failed_publish(tmp_path):
    """Re-saving an EXISTING tag must never destroy the only copy: the
    old checkpoint is parked aside (swap) and restored when the publish
    rename fails — previously it was rmtree'd before the rename
    (code-review finding)."""
    over = {"checkpoint": {"io_retry_attempts": 1}}
    eng = _engine(**over)
    _train(eng, steps=2)
    eng.save_checkpoint(str(tmp_path), tag="best")
    good = _dir_bytes(str(tmp_path / "best"))
    _train(eng, steps=1, seed=7)
    os.environ["DS_CKPT_FAULT"] = "rename:1+"
    try:
        with pytest.raises(Exception):
            eng.save_checkpoint(str(tmp_path), tag="best")
    finally:
        os.environ.pop("DS_CKPT_FAULT", None)
    reset_fault_injection()
    # the OLD 'best' was restored bitwise and still loads
    assert _dir_bytes(str(tmp_path / "best")) == good
    eng2 = _engine(seed=9, **over)
    path, _ = eng2.load_checkpoint(str(tmp_path), tag="best")
    assert path is not None and eng2.global_steps == 2
    # the parked copy was named *.tmp, so the next save sweeps any debris
    eng.save_checkpoint(str(tmp_path), tag="best")
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


def test_close_surfaces_lost_async_save(tmp_path):
    """A save that fails while close() drains must still land in
    last_ckpt_error — not vanish with the daemon thread (code-review
    finding: drain() inside close() used to clear the error before the
    tick could pop it)."""
    eng = _engine()
    _train(eng, steps=1)
    os.environ["DS_CKPT_FAULT"] = "meta:1+"
    try:
        eng.save_checkpoint(str(tmp_path), tag="t", async_write=True)
        eng.close()
    finally:
        os.environ.pop("DS_CKPT_FAULT", None)
    assert eng.last_ckpt_error is not None


def test_sweep_restores_stranded_park_dir(tmp_path):
    """A crash between the park and publish renames of a same-tag
    re-save leaves ONLY <tag>.replaced.tmp (the old good copy) and
    <tag>.tmp on disk; the next save's sweep must RESTORE the park dir,
    not delete it (code-review finding: it was treated as an orphan)."""
    import shutil
    eng = _engine()
    _train(eng, steps=2)
    eng.save_checkpoint(str(tmp_path), tag="best")
    good = _dir_bytes(str(tmp_path / "best"))
    # simulate the crash window: tag parked, publish never happened
    shutil.move(str(tmp_path / "best"), str(tmp_path / "best.replaced.tmp"))
    (tmp_path / "best.tmp").mkdir()
    (tmp_path / "best.tmp" / "junk.npy").write_bytes(b"partial")
    _train(eng, steps=1, seed=5)
    eng.save_checkpoint(str(tmp_path), tag="other")
    assert _dir_bytes(str(tmp_path / "best")) == good  # restored bitwise
    assert not (tmp_path / "best.replaced.tmp").exists()
    assert not (tmp_path / "best.tmp").exists()        # debris swept
    eng2 = _engine(seed=9)
    path, _ = eng2.load_checkpoint(str(tmp_path), tag="best")
    assert path is not None and eng2.global_steps == 2


def test_sync_save_surfaces_drained_async_failure(tmp_path):
    """An async save failing WHILE a subsequent sync save drains the
    writer must land in last_ckpt_error, not vanish with the drain
    (code-review finding: drain() cleared the error before the pre-step
    tick could pop it)."""
    eng = _engine()
    _train(eng, steps=1)
    gate = threading.Event()

    def boom():
        gate.wait(5.0)
        raise OSError("lost async save")
    eng._ckpt_writer.submit(CheckpointJob(
        "doomed", str(tmp_path / "doomed.tmp"),
        str(tmp_path / "doomed"), boom))
    threading.Timer(0.2, gate.set).start()
    # the sync save finds the writer in flight, drains it, and must
    # surface the drained failure on the engine
    eng.save_checkpoint(str(tmp_path), tag="ok", async_write=False)
    assert isinstance(eng.last_ckpt_error, OSError)
    assert (tmp_path / "latest").read_text().strip() == "ok"


# ---------------------------------------------------------------------------
# elastic-supervisor interplay (ISSUE 6 satellites)
# ---------------------------------------------------------------------------
def test_sigterm_during_elastic_restart_window_no_double_save(tmp_path):
    """The elastic supervisor's kill discipline is SIGTERM (the
    preemption save fires) then an escalated second SIGTERM when the
    worker is slow to die.  The escalation landing in the restart
    window must chain to the previous handler cleanly — exactly ONE
    save on disk, no second save mutating the just-written tag, no
    torn handler chain."""
    chained = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: chained.append(s))
    try:
        eng = _engine(seed=0)
        handler = resilience.install_preemption_handler(
            eng, str(tmp_path), exit_after=False)
        _train(eng, steps=2)
        os.kill(os.getpid(), signal.SIGTERM)   # supervisor's TERM
        assert handler.fired
        assert chained == [signal.SIGTERM]     # saved, THEN chained prev
        latest = (tmp_path / "latest").read_text().strip()
        saved = sorted(os.listdir(tmp_path))
        meta = tmp_path / latest / "meta.json"
        mtime = os.stat(meta).st_mtime_ns
        os.kill(os.getpid(), signal.SIGTERM)   # escalation in the window
        assert chained == [signal.SIGTERM] * 2  # chained, never swallowed
        assert sorted(os.listdir(tmp_path)) == saved  # no new tag/tmp
        assert os.stat(meta).st_mtime_ns == mtime     # no re-save either
        handler.uninstall()
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_sigterm_escalation_mid_step_defers_one_save(tmp_path):
    """Both the supervisor's TERM and its escalation landing while
    train_batch is mid-update (``_in_step``): the handler parks twice,
    saves NOTHING mid-step (a torn half-applied state would have valid
    CRCs), and the step boundary completes exactly one save."""
    eng = _engine(seed=0)
    handler = resilience.install_preemption_handler(
        eng, str(tmp_path), exit_after=False)
    _train(eng, steps=1)
    eng._in_step = True
    handler._handle(signal.SIGTERM, None)
    handler._handle(signal.SIGTERM, None)  # escalation, still mid-step
    assert not handler.fired
    assert not (tmp_path / "latest").exists()  # nothing saved mid-step
    eng._in_step = False
    _train(eng, steps=1, seed=3)  # finally-block completes ONE save
    assert handler.fired
    eng2 = _engine(seed=9)
    path, _ = eng2.load_checkpoint(str(tmp_path))
    assert path is not None and eng2.global_steps == 2
    handler.uninstall()


def test_legacy_checkpoint_without_data_plane_loads_fresh_iter(tmp_path):
    """Checkpoints from before the data-iterator plane existed (ISSUE 6)
    still load: model/optimizer restore exactly, the iterator starts
    FRESH with one loud warning — pinned alongside the no-CRC legacy
    test above (format evolution must not orphan old runs)."""
    import logging

    from deepspeed_tpu.runtime.dataloader import (DeepSpeedDataLoader,
                                                  RepeatingLoader)
    from deepspeed_tpu.utils.logging import logger as ds_logger

    def mk(seed):
        eng = _engine(seed=seed)
        xs = np.random.default_rng(0).standard_normal(
            (32, HIDDEN)).astype(np.float32)
        eng.training_dataloader = RepeatingLoader(DeepSpeedDataLoader(
            [(xs[i], 0.5 * xs[i]) for i in range(32)],
            batch_size=eng.train_batch_size, shuffle=True, seed=5))
        return eng

    eng = mk(0)
    losses = [float(eng.train_batch()) for _ in range(2)]
    eng.save_checkpoint(str(tmp_path), tag="t")
    eng.close()
    # strip the data plane + its digest: the pre-ISSUE-6 on-disk layout
    import shutil
    shutil.rmtree(tmp_path / "t" / "data")
    meta = json.load(open(tmp_path / "t" / "meta.json"))
    del meta["manifest_digests"]["data"]
    json.dump(meta, open(tmp_path / "t" / "meta.json", "w"))

    eng2 = mk(9)
    records = []

    class Rec(logging.Handler):
        def emit(self, record):
            records.append(record)

    h = Rec(level=logging.WARNING)
    ds_logger.addHandler(h)
    try:
        path, _ = eng2.load_checkpoint(str(tmp_path), tag="t")
    finally:
        ds_logger.removeHandler(h)
    assert path is not None and eng2.global_steps == 2
    assert any("predates the data-iterator plane" in r.getMessage()
               for r in records)
    _state_equal(eng.state.master_params, eng2.state.master_params)
    # fresh iterator: draws epoch 0's first batch (a replay, loudly
    # warned about — NOT a crash)
    float(eng2.train_batch())
    assert losses  # reference leg really trained
    eng2.close()
