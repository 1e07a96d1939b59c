"""Stall-proofing of the host-tier bulk pulls.

One monolithic ``jax.device_get`` of a multi-GB leaf is a single native
call that a failing host link can stall *forever* — un-interruptible by
signals, holding the device. The answer is piece-wise pulls with a per-piece daemon-thread watchdog
(``runtime/offload.py: chunked_device_get``), mirroring how the
reference staggers its pinned-buffer copies tile by tile (reference:
csrc/adam/cpu_adam.cpp:64-113). These tests simulate the stall and
assert the failure is a clean RuntimeError that leaves the process
healthy.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.runtime.offload as offload
from deepspeed_tpu.runtime.offload import (HostOffloadOptimizer,
                                           chunked_device_get)


# ---------------------------------------------------------------------
# correctness: chunked pull == plain pull
# ---------------------------------------------------------------------
@pytest.mark.parametrize("shape,dtype", [
    ((), jnp.float32),
    ((7,), jnp.float32),
    ((100, 50), jnp.float32),
    ((33, 16), jnp.bfloat16),
    ((64, 3), jnp.int32),
])
def test_chunked_get_matches_plain(shape, dtype):
    x = jnp.arange(int(np.prod(shape)) or 1, dtype=jnp.float32)
    x = x.reshape(shape).astype(dtype)
    # chunk_mb tiny enough to force many pieces on the 2-D cases
    got = chunked_device_get(x, chunk_mb=0.002, piece_timeout=30)
    want = np.asarray(jax.device_get(x))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def test_chunked_get_numpy_passthrough():
    x = np.arange(12.0, dtype=np.float32).reshape(3, 4)
    got = chunked_device_get(x, chunk_mb=0.001, piece_timeout=5)
    np.testing.assert_array_equal(got, x)


def test_chunked_get_watchdog_disabled():
    x = jnp.ones((8, 8))
    got = chunked_device_get(x, chunk_mb=0.001, piece_timeout=0)
    np.testing.assert_array_equal(got, np.ones((8, 8), np.float32))


def test_chunked_get_actually_chunks(monkeypatch):
    """The piece loop must issue multiple bounded native calls — that
    bound IS the wedge protection."""
    calls = []
    real_get = jax.device_get

    def spy(x):
        calls.append(tuple(x.shape))
        return real_get(x)

    monkeypatch.setattr(offload.jax, "device_get", spy)
    x = jnp.ones((100, 128))  # 51.2 KB fp32
    chunked_device_get(x, chunk_mb=0.01, piece_timeout=30)  # ~10 KB pieces
    assert len(calls) >= 4
    assert all(int(np.prod(s)) * 4 <= 16 << 10 for s in calls)


def test_chunked_get_bounds_pieces_for_wide_leaves(monkeypatch):
    """Flat element-range chunking: a (2, huge) leaf must NOT produce
    half-leaf pieces — every piece stays <= the chunk size, so the
    per-piece timeout measures PROGRESS even on leaves with few rows
    (the slow-vs-stalled distinction)."""
    calls = []
    real_get = jax.device_get

    def spy(x):
        calls.append(int(np.prod(x.shape)))
        return real_get(x)

    monkeypatch.setattr(offload.jax, "device_get", spy)
    x = jnp.ones((2, 16384))  # 128 KB fp32, only 2 rows
    got = chunked_device_get(x, chunk_mb=0.01, piece_timeout=30)
    assert all(n * 4 <= 16 << 10 for n in calls)
    assert len(calls) >= 8
    np.testing.assert_array_equal(got, np.ones((2, 16384), np.float32))


# ---------------------------------------------------------------------
# the stall: a piece that never completes raises cleanly and quickly
# ---------------------------------------------------------------------
def test_stalled_piece_raises_cleanly(monkeypatch):
    release = threading.Event()
    real_get = jax.device_get

    def stalled(x):
        release.wait()  # simulate the un-interruptible native stall
        return real_get(x)

    monkeypatch.setattr(offload.jax, "device_get", stalled)
    x = jnp.ones((100, 128))
    t0 = time.perf_counter()
    try:
        with pytest.raises(RuntimeError, match="did not complete"):
            chunked_device_get(x, chunk_mb=0.01, piece_timeout=0.3)
        elapsed = time.perf_counter() - t0
        # one piece-timeout, not shape[0] of them, and nowhere near a hang
        assert elapsed < 5.0
    finally:
        release.set()  # let the abandoned daemon thread exit
    monkeypatch.undo()
    # process stays healthy: a subsequent pull works (the "next probe")
    got = chunked_device_get(jnp.ones((4, 4)), piece_timeout=10)
    np.testing.assert_array_equal(got, np.ones((4, 4), np.float32))


def test_stalled_master_pull_fails_construction(monkeypatch):
    """End-to-end: HostOffloadOptimizer construction on a stalled link is
    a RuntimeError (the engine attempt chain catches it and falls through
    to the xla tier), not a hang."""
    release = threading.Event()
    real_get = jax.device_get

    def stalled(x):
        release.wait()
        return real_get(x)

    master = {"w": jnp.ones((600, 1024)),  # 2.4 MB: big enough to probe
              "b": jnp.zeros((1024,))}
    monkeypatch.setattr(offload.jax, "device_get", stalled)
    try:
        with pytest.raises(RuntimeError):
            HostOffloadOptimizer(
                master, lr=1e-3, betas=(0.9, 0.999), eps=1e-8,
                weight_decay=0.0)
    finally:
        release.set()


# ---------------------------------------------------------------------
# slow-but-completing probe: warn by default, hard error on request
# ---------------------------------------------------------------------
def _slow_link(monkeypatch, delay=0.05):
    real_get = jax.device_get

    def slow(x):
        time.sleep(delay)
        return real_get(x)

    monkeypatch.setattr(offload.jax, "device_get", slow)


def test_slow_probe_warns_by_default(monkeypatch):
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    _slow_link(monkeypatch)
    monkeypatch.delenv("DS_OFFLOAD_SLOW_LINK", raising=False)
    master = {"w": jnp.ones((600, 1024))}
    records = []

    class Rec(logging.Handler):
        def emit(self, record):
            records.append(record)

    h = Rec(level=logging.WARNING)
    ds_logger.addHandler(h)
    try:
        # must NOT raise; must log the loud warning
        HostOffloadOptimizer._probe_transfer_path(
            master, min_mbps=1e9, probe_timeout=30)
    finally:
        ds_logger.removeHandler(h)
    assert any("MB/s" in r.getMessage() for r in records)


def test_slow_probe_errors_when_strict(monkeypatch):
    _slow_link(monkeypatch)
    monkeypatch.setenv("DS_OFFLOAD_SLOW_LINK", "error")
    master = {"w": jnp.ones((600, 1024))}
    with pytest.raises(RuntimeError, match="measured"):
        HostOffloadOptimizer._probe_transfer_path(
            master, min_mbps=1e9, probe_timeout=30)


def test_probe_propagates_pull_errors(monkeypatch):
    """A dead link raising from device_get must FAIL the probe, not be
    swallowed into a fast-looking measurement."""
    def broken(x):
        raise ValueError("link is dead")

    monkeypatch.setattr(offload.jax, "device_get", broken)
    master = {"w": jnp.ones((600, 1024))}
    with pytest.raises(ValueError, match="link is dead"):
        HostOffloadOptimizer._probe_transfer_path(
            master, min_mbps=1, probe_timeout=30)


def test_steady_state_grad_pull_stall_raises(monkeypatch):
    """Steady-state guard: the per-step grad pull is watchdogged too —
    the probe certifies the link once, this holds for every step after."""
    release = threading.Event()
    real_get = jax.device_get

    def stalled(x):
        release.wait()
        return real_get(x)

    monkeypatch.setattr(offload.jax, "device_get", stalled)
    monkeypatch.setenv("DS_OFFLOAD_PULL_TIMEOUT", "0.3")
    try:
        with pytest.raises(RuntimeError, match="grad pull"):
            offload.guarded_tree_pull({"g": jnp.ones((32, 32))})
    finally:
        release.set()
    monkeypatch.undo()
    got = offload.guarded_tree_pull(
        {"g": jnp.ones((4, 4), jnp.bfloat16), "n": np.int32(3)})
    # dtype-preserving: the DPU stash must stay at 1x the grads' bytes
    assert got["g"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got["g"], np.float32), np.ones((4, 4), np.float32))


def test_prefetch_puller_order_and_errors(monkeypatch):
    """One worker, flatten-order prefetch: values match, device errors
    propagate to the consuming call, duplicate leaf objects are handled."""
    x = jnp.arange(16.0).reshape(4, 4)
    tree = {"a": x, "b": jnp.ones((2,)), "dup": x}
    puller = offload._PrefetchPuller(tree)
    out = jax.tree.map(puller, tree)
    np.testing.assert_array_equal(out["a"], np.asarray(x))
    np.testing.assert_array_equal(out["dup"], np.asarray(x))

    def broken(x):
        raise ValueError("link is dead")

    monkeypatch.setattr(offload.jax, "device_get", broken)
    g = jnp.ones((3,))
    h = jnp.ones((5,))
    puller = offload._PrefetchPuller({"g": g, "h": h})
    with pytest.raises(ValueError, match="link is dead"):
        puller(g)
    # later slots are poisoned with the SAME error, immediately (no
    # per-leaf piece-timeout burn)
    with pytest.raises(ValueError, match="link is dead"):
        puller(h)


def test_prefetch_puller_close_releases_skipped_leaves():
    """The consumer may legitimately skip trailing leaves (the Adam loop
    never requests non-fp32 ones).  close() must release the parked
    worker — otherwise each step leaks a daemon thread holding a
    reference to the whole grad tree — and fail any un-pulled slot a
    late (buggy) request touches instead of hanging."""
    leaves = [jnp.full((4,), float(i)) for i in range(8)]
    before = set(threading.enumerate())
    puller = offload._PrefetchPuller(leaves)
    workers = set(threading.enumerate()) - before  # THIS puller's thread
    assert workers, "no worker thread observed"
    out0 = puller(leaves[0])  # consume ONE leaf; skip the rest
    np.testing.assert_array_equal(out0, np.zeros((4,), np.float32))
    puller.close()
    deadline = time.perf_counter() + 5.0
    while any(t.is_alive() for t in workers) and \
            time.perf_counter() < deadline:
        time.sleep(0.02)
    assert not any(t.is_alive() for t in workers), "worker thread leaked"
    # a late request for a never-pulled leaf fails, not hangs
    with pytest.raises(RuntimeError, match="closed"):
        puller(leaves[-1])


def test_prefetch_puller_bounded_lookahead(monkeypatch):
    """The worker must stay <= LOOKAHEAD leaves past the consumer's need
    — the prefetch buffer is a few leaves, not a full grad tree."""
    pulled = []
    real_get = jax.device_get

    def spy(x):
        pulled.append(x.shape)
        return real_get(x)

    monkeypatch.setattr(offload.jax, "device_get", spy)
    leaves = [jnp.full((4,), float(i)) for i in range(8)]
    puller = offload._PrefetchPuller(leaves)
    time.sleep(0.4)  # give the worker time to run ahead if it could
    assert len(pulled) <= offload._PrefetchPuller.LOOKAHEAD + 1
    out = [puller(g) for g in leaves]
    for i, o in enumerate(out):
        np.testing.assert_array_equal(o, np.full((4,), float(i), np.float32))
    assert len(pulled) == 8


def test_poisoned_optimizer_refuses(monkeypatch):
    """A mid-step pull failure leaves master/moments partially updated:
    the optimizer must refuse further steps AND refuse to serialize that
    state; a checkpoint restore clears the poison."""
    opt = HostOffloadOptimizer(
        {"w": jnp.ones((8, 4)), "b": jnp.zeros((4,))},
        lr=1e-3, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0)
    healthy_state = opt.state_tree()
    healthy_master = jax.tree.map(np.copy, opt.master)

    def broken(x):
        raise ValueError("link is dead")

    monkeypatch.setattr(offload.jax, "device_get", broken)
    with pytest.raises(ValueError, match="link is dead"):
        opt.step({"w": jnp.ones((8, 4)), "b": jnp.ones((4,))})
    monkeypatch.undo()
    with pytest.raises(RuntimeError, match="poisoned"):
        opt.step({"w": np.ones((8, 4), np.float32),
                  "b": np.ones((4,), np.float32)})
    with pytest.raises(RuntimeError, match="refusing to serialize"):
        opt.state_tree()
    opt.load_state_tree(healthy_master, healthy_state)
    opt.step({"w": np.ones((8, 4), np.float32),
              "b": np.ones((4,), np.float32)})  # healthy again
    assert opt.state_tree()["step"] >= 1


def _pull_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("ds-offload-pull")]


def test_watchdog_reuses_one_persistent_worker():
    """No thread spawn per pulled piece (was ~100 spawns/step for a 6 GB
    master at 64 MB chunks): many chunked pulls ride ONE daemon worker.
    Counted by the worker's thread name, not process-wide active_count()
    — unrelated pools must not flake this."""
    # warm: create the worker
    chunked_device_get(jnp.ones((64, 64)), chunk_mb=0.001,
                       piece_timeout=30)
    worker = offload._PULL_POOL.worker
    assert worker is not None
    before = set(_pull_threads())
    assert before, "no pull worker thread observed"
    for _ in range(3):
        chunked_device_get(jnp.ones((100, 128)), chunk_mb=0.01,
                           piece_timeout=30)  # ~13 pieces each
    assert offload._PULL_POOL.worker is worker, "worker was replaced"
    # no NEW pull threads across ~40 pieces (an abandoned predecessor
    # from an earlier stall test may still be draining out of `before`,
    # which is why this is a no-new-threads check, not a count of 1)
    assert not (set(_pull_threads()) - before), (
        "watchdogged pulls must not spawn threads")


def test_watchdog_timeout_abandons_worker(monkeypatch):
    """A timed-out pull abandons the wedged worker (later pulls must not
    queue behind its stalled native call) and the next pull lazily gets
    a fresh one — the per-spawn semantics, paid only on failure."""
    chunked_device_get(jnp.ones((4, 4)), piece_timeout=10)  # ensure one
    wedged = offload._PULL_POOL.worker
    release = threading.Event()
    real_get = jax.device_get

    def stalled(x):
        release.wait()
        return real_get(x)

    monkeypatch.setattr(offload.jax, "device_get", stalled)
    try:
        with pytest.raises(RuntimeError, match="did not complete"):
            chunked_device_get(jnp.ones((32, 32)), chunk_mb=0.001,
                               piece_timeout=0.3)
    finally:
        release.set()  # let the abandoned worker drain and exit
    monkeypatch.undo()
    assert offload._PULL_POOL.worker is not wedged  # abandoned
    got = chunked_device_get(jnp.ones((4, 4)), piece_timeout=10)
    np.testing.assert_array_equal(got, np.ones((4, 4), np.float32))
    assert offload._PULL_POOL.worker is not None
    assert offload._PULL_POOL.worker is not wedged


def test_watchdog_retries_after_abandoned_worker():
    """The sentinel race: a pull landing on a worker that a concurrent
    timeout just stopped must retry transparently on a fresh worker —
    never surface a spurious 'stalled' error on a healthy link."""
    chunked_device_get(jnp.ones((4, 4)), piece_timeout=10)  # ensure one
    worker = offload._PULL_POOL.worker
    worker.stop()  # simulate the concurrent-timeout abandonment
    got = chunked_device_get(jnp.ones((4, 4)), piece_timeout=10)
    np.testing.assert_array_equal(got, np.ones((4, 4), np.float32))
    assert offload._PULL_POOL.worker is not None
    assert offload._PULL_POOL.worker is not worker


def test_fast_probe_passes(monkeypatch):
    monkeypatch.setenv("DS_OFFLOAD_SLOW_LINK", "error")
    master = {"w": jnp.ones((600, 1024))}
    HostOffloadOptimizer._probe_transfer_path(
        master, min_mbps=0.001, probe_timeout=30)


def test_sharded_tier_preserves_passthrough_dtypes():
    """Int/bool buffers must ride the sharded tier UNCAST (the single-
    controller to_host rule): blocks keep their dtype, Adam skips them,
    assemble/canonical/load round-trip them exactly — including wide
    int64 values an fp32 hop would corrupt."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.runtime.offload import ShardedHostOffloadOptimizer

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("data",))
    wide = np.int32(2**24 + 1)  # exact in int32, corrupts via fp32
    master = {
        "w": jax.device_put(np.arange(32, dtype=np.float32).reshape(8, 4),
                            NamedSharding(mesh, P("data", None))),
        "counter": jax.device_put(np.array([wide, 7], np.int32),
                                  NamedSharding(mesh, P())),
        "flag": jax.device_put(np.array([True, False]),
                               NamedSharding(mesh, P())),
    }
    opt = ShardedHostOffloadOptimizer(
        master, lr=1e-2, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
        compute_dtype=jnp.bfloat16)

    # blocks keep their own dtype (leaf order: sorted dict keys)
    blocks = {k: [g["block"] for g in leaf]
              for k, leaf in zip(sorted(master), opt._local)}
    assert all(b.dtype == np.float32 for b in blocks["w"])
    assert all(b.dtype == np.int32 for b in blocks["counter"])
    assert all(b.dtype == np.bool_ for b in blocks["flag"])
    assert blocks["counter"][0][0] == wide

    # compute params: floats → bf16, passthrough buffers uncast
    cp = opt.compute_params()
    assert cp["w"].dtype == jnp.bfloat16
    assert cp["counter"].dtype == jnp.int32
    assert cp["flag"].dtype == jnp.bool_
    assert int(cp["counter"][0]) == wide

    # a step leaves passthrough buffers bit-identical
    grads = {
        "w": jax.device_put(np.ones((8, 4), np.float32),
                            NamedSharding(mesh, P("data", None))),
        "counter": jax.device_put(np.zeros(2, np.int32),
                                  NamedSharding(mesh, P())),
        "flag": jax.device_put(np.zeros(2, np.bool_),
                               NamedSharding(mesh, P())),
    }
    out = opt.step(grads)
    assert out["counter"].dtype == jnp.int32
    assert int(out["counter"][0]) == wide
    assert out["w"].dtype == jnp.bfloat16
    # Adam actually ran on the float leaf ("w" is leaf 2 in sorted order)
    w_blocks = [g["block"] for g in opt._local[2]]
    assert not np.allclose(np.concatenate([b.ravel() for b in w_blocks]),
                           np.arange(32, dtype=np.float32))

    # canonical save form + load round-trip keep the exact wide int
    m, st = opt.canonical_state()
    assert m["counter"].dtype == jnp.int32
    assert int(m["counter"][0]) == wide
    opt.load_state_tree(m, st)
    assert opt._local[0][0]["block"][0] == wide  # "counter" is leaf 0

    tmpl_m, _ = opt.canonical_templates()
    assert tmpl_m["counter"].dtype == jnp.int32
    assert tmpl_m["flag"].dtype == jnp.bool_
