"""Asynchronous input pipeline (DevicePrefetcher + engine wiring).

The step loop used to pay ``next(data_iter)`` → collate →
``_shard_batch`` serially before every dispatch; the prefetcher moves
that chain onto a daemon worker behind a bounded queue.  Contracts
these tests pin:

  - bitwise equivalence with the inline path (``DS_PREFETCH=0``):
    identical losses AND state trees over a seeded loader, standard and
    host-offload engine tiers, and with PLD (whose theta is overwritten
    at consumption time so prefetched batches stay valid across
    ``global_steps`` changes);
  - real concurrency, proven from tracer timestamps with an injected
    worker delay (``DS_PREFETCH_DELAY_S``): batch i+1's collate+put
    overlaps batch i's consumption window, and ``prefetch_wait`` ≈ 0 in
    steady state;
  - StopIteration propagates cleanly at epoch boundaries, worker
    failures poison the iterator with the ORIGINAL exception, shutdown
    is idempotent and ``engine.close()`` drains the worker;
  - ``_shard_batch`` issues ONE batched list-form ``jax.device_put``
    for all numpy leaves, and the multi-process arm raises the
    descriptive ValueError on mismatched jax.Array shardings.
"""
import sys
import threading
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, "tests")

import deepspeed_tpu.runtime.engine as engine_mod
from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.runtime.dataloader import (DeepSpeedDataLoader,
                                              RepeatingLoader)
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.prefetch import (DevicePlacedBatch,
                                            DevicePrefetcher)
from deepspeed_tpu.telemetry.tracing import TraceRecorder

from simple_model import SimpleModel, base_config

HIDDEN = 16


def _dataset(n, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal((n, HIDDEN)).astype(np.float32)
    return [(xs[i], 0.5 * xs[i]) for i in range(n)]


def _engine(monkeypatch=None, prefetch_on=True, n_batches=4, seed=3,
            cfg_over=None, model=None, dataset=None, world_size=8,
            mesh=None):
    cfg = base_config(micro_bs=2, grad_acc=1)
    cfg.update(cfg_over or {})
    dscfg = DeepSpeedConfig(cfg, world_size=world_size)
    if mesh is None:
        mesh = build_mesh() if world_size == 8 else build_mesh(
            dp=1, devices=jax.devices()[:1])
    bs = dscfg.train_batch_size
    if monkeypatch is not None:
        if prefetch_on:
            monkeypatch.delenv("DS_PREFETCH", raising=False)
        else:
            monkeypatch.setenv("DS_PREFETCH", "0")
    eng = DeepSpeedEngine(
        model or SimpleModel(hidden_dim=HIDDEN), dscfg, mesh=mesh,
        seed=seed,
        training_data=(dataset if dataset is not None
                       else _dataset(bs * n_batches)))
    assert eng._prefetch_enabled == prefetch_on
    return eng


def _train(engine, steps):
    return [float(np.asarray(engine.train_batch())) for _ in range(steps)]


def _assert_state_bitwise(e_a, e_b):
    la = jax.tree.leaves(e_a.state)
    lb = jax.tree.leaves(e_b.state)
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"state leaf {i}")


# ---------------------------------------------------------------------
# bitwise equivalence: prefetched vs inline (DS_PREFETCH=0)
# ---------------------------------------------------------------------
def test_prefetch_bitwise_equals_inline(monkeypatch):
    """The acceptance contract (standard tier): N steps over the same
    seeded loader produce identical losses and state trees — the env
    escape hatch IS the inline reference, so it is exercised too."""
    e_on = _engine(monkeypatch, prefetch_on=True)
    e_off = _engine(monkeypatch, prefetch_on=False)
    assert isinstance(e_on._training_iter(), DevicePrefetcher)
    assert not isinstance(e_off._training_iter(), DevicePrefetcher)
    l_on = _train(e_on, 4)
    l_off = _train(e_off, 4)
    assert l_on == l_off
    _assert_state_bitwise(e_on, e_off)
    e_on.close()
    e_off.close()


def test_prefetch_bitwise_offload_tier(monkeypatch):
    """Same contract on the host-offload engine tier (its step path
    composes the input pipeline with the optimizer pipeline)."""
    over = {"zero_optimization": {"stage": 2, "cpu_offload": True,
                                  "offload_impl": "host"},
            "train_micro_batch_size_per_gpu": 4}
    e_on = _engine(monkeypatch, prefetch_on=True, cfg_over=over,
                   world_size=1)
    e_off = _engine(monkeypatch, prefetch_on=False, cfg_over=over,
                    world_size=1)
    l_on = _train(e_on, 3)
    l_off = _train(e_off, 3)
    assert l_on == l_off
    _assert_state_bitwise(e_on, e_off)
    e_on.close()
    e_off.close()


class _PLDModel(SimpleModel):
    """Consumes the engine-injected pld_theta leaf so the theta VALUE
    affects the loss — a stale (placement-time) theta would break the
    bitwise contract below."""

    def loss_fn(self, params, batch, rng, train=True):
        import jax.numpy as jnp
        x, y = batch["x"], batch["y"]
        theta = batch.get("pld_theta")
        base = super().loss_fn(params, (x, y), rng, train)
        if theta is not None:
            return base * jnp.mean(theta.astype(jnp.float32))
        return base


def test_prefetch_pld_theta_overwritten_at_consumption(monkeypatch):
    """PLD + prefetch: batches are placed AHEAD of the step that
    consumes them, so the theta leaf is a placeholder until
    consumption-time overwrite — losses/state must still match the
    inline path exactly (which injects theta fresh each step)."""
    bs = 2 * 8
    ds = [{"x": x, "y": y} for x, y in _dataset(bs * 4)]
    over = {"progressive_layer_drop": {"enabled": True, "theta": 0.5,
                                       "gamma": 0.05}}
    e_on = _engine(monkeypatch, prefetch_on=True, cfg_over=over,
                   model=_PLDModel(hidden_dim=HIDDEN), dataset=ds)
    e_off = _engine(monkeypatch, prefetch_on=False, cfg_over=over,
                    model=_PLDModel(hidden_dim=HIDDEN), dataset=ds)
    # depth-2 queue: batch for step t is placed while global_steps is
    # still t-1 (or t-2) — exactly the staleness the overwrite fixes
    l_on = _train(e_on, 4)
    l_off = _train(e_off, 4)
    assert l_on == l_off
    _assert_state_bitwise(e_on, e_off)
    # theta actually moved over the run (the schedule was live)
    e_on.progressive_layer_drop.update_state(e_on.global_steps)
    assert e_on.progressive_layer_drop.get_theta() < 1.0
    e_on.close()
    e_off.close()


# ---------------------------------------------------------------------
# the concurrency proof: tracer timestamps + injected worker delay
# ---------------------------------------------------------------------
def test_prefetch_overlap_proven_by_tracer(monkeypatch):
    """With a 30ms injected worker delay (DS_PREFETCH_DELAY_S) and a
    50ms consumer, steady-state ``prefetch_wait`` ≈ 0 — batch i+1's
    collate+put ran during batch i's consumption window, read straight
    off tracer timestamps."""
    monkeypatch.setenv("DS_PREFETCH_DELAY_S", "0.03")
    tracer = TraceRecorder()

    def span_fn(name, cat="runtime", **args):
        return tracer.span(name, cat, **args)

    src = iter([np.full((4,), float(i), np.float32) for i in range(6)])
    pf = DevicePrefetcher(src, place_fn=lambda b: jax.device_put(b),
                          depth=2, span_fn=span_fn)
    waits = []
    try:
        for _ in range(6):
            t0 = time.perf_counter()
            batch = next(pf)
            waits.append(time.perf_counter() - t0)
            assert isinstance(batch, jax.Array)
            with tracer.span("consume", "test"):
                time.sleep(0.05)
    finally:
        pf.close()
    # the first pull pays the pipeline fill; steady state is hidden
    assert waits[0] >= 0.02, waits
    assert max(waits[2:]) < 0.02, waits

    def intervals(name):
        return [(e["ts"], e["ts"] + e["dur"]) for e in tracer.events()
                if e.get("name") == name and e.get("ph") == "X"]

    place = intervals("data/prefetch_place")
    consume = intervals("consume")
    assert len(place) == 6 and len(consume) == 6
    overlaps = [min(p1, c1) - max(p0, c0)
                for p0, p1 in place for c0, c1 in consume]
    assert max(overlaps) > 0.02 * 1e6, (
        "no place × consume overlap observed in the trace")
    s = pf.stats()
    assert s["consumed"] == 6
    assert s["hits"] >= 4  # steady state: batch already resident


def test_prefetch_wait_span_emitted(monkeypatch):
    tracer = TraceRecorder()
    pf = DevicePrefetcher(iter([np.zeros(2)]),
                          span_fn=lambda n, cat="x", **a:
                          tracer.span(n, cat, **a))
    next(pf)
    pf.close()
    names = {e["name"] for e in tracer.events()}
    assert "data/prefetch_wait" in names
    assert "data/prefetch_place" in names


# ---------------------------------------------------------------------
# lifecycle: epoch boundary, poison, close, depth bound
# ---------------------------------------------------------------------
def test_stop_iteration_propagates_after_draining():
    pf = DevicePrefetcher(iter([np.zeros(2), np.ones(2)]), depth=4)
    assert np.asarray(next(pf)).sum() == 0
    assert np.asarray(next(pf)).sum() == 2
    with pytest.raises(StopIteration):
        next(pf)
    with pytest.raises(StopIteration):  # stays exhausted
        next(pf)


def test_engine_epoch_boundary_stop_iteration(monkeypatch):
    """A finite (non-repeating) training loader: the engine's wrapped
    iterator raises StopIteration at the epoch boundary, same as the
    inline path."""
    e = _engine(monkeypatch, prefetch_on=True, n_batches=2)
    _train(e, 2)
    with pytest.raises(StopIteration):
        e.train_batch()
    e.close()


def test_worker_source_failure_poisons_with_original_error():
    def gen():
        yield np.zeros(2)
        raise ValueError("collate died")

    pf = DevicePrefetcher(gen(), depth=2)
    next(pf)  # the batch produced before the failure drains first
    with pytest.raises(ValueError, match="collate died"):
        next(pf)
    with pytest.raises(ValueError, match="collate died"):  # poisoned
        next(pf)


def test_worker_place_failure_poisons():
    seen = {"n": 0}

    def place(b):
        seen["n"] += 1
        if seen["n"] > 1:
            raise RuntimeError("h2d link died")
        return b

    pf = DevicePrefetcher(iter([np.zeros(2)] * 4), place_fn=place,
                          depth=2)
    next(pf)
    with pytest.raises(RuntimeError, match="h2d link died"):
        next(pf)


def test_close_idempotent_and_releases_worker():
    before = set(threading.enumerate())
    pf = DevicePrefetcher(iter([np.zeros(2)] * 8), depth=2)
    workers = set(threading.enumerate()) - before
    next(pf)
    pf.close()
    pf.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        next(pf)
    deadline = time.perf_counter() + 5.0
    while any(t.is_alive() for t in workers) and \
            time.perf_counter() < deadline:
        time.sleep(0.01)
    assert not any(t.is_alive() for t in workers), "worker leaked"


def test_engine_close_drains_prefetcher(monkeypatch):
    e = _engine(monkeypatch, prefetch_on=True)
    _train(e, 1)
    pf = e._train_prefetcher
    assert pf is not None and not pf.closed
    e.close()
    assert pf.closed


def test_depth_bounds_lookahead():
    class Counting:
        def __init__(self):
            self.count = 0

        def __next__(self):
            self.count += 1
            return np.zeros(2)

    src = Counting()
    pf = DevicePrefetcher(src, depth=2)
    deadline = time.perf_counter() + 5.0
    while src.count < 2 and time.perf_counter() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)  # worker must now be parked at the bound
    assert src.count == 2, src.count
    next(pf)
    deadline = time.perf_counter() + 5.0
    while src.count < 3 and time.perf_counter() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)
    assert src.count == 3, src.count
    pf.close()


def test_depth_validation():
    with pytest.raises(ValueError, match="depth"):
        DevicePrefetcher(iter([]), depth=0)
    with pytest.raises(DeepSpeedConfigError, match="depth"):
        DeepSpeedConfig(base_config(data_prefetch={"depth": 0}),
                        world_size=8)
    with pytest.raises(DeepSpeedConfigError, match="depth"):
        DeepSpeedConfig(base_config(data_prefetch={"depth": True}),
                        world_size=8)
    cfg = DeepSpeedConfig(base_config(), world_size=8)
    assert cfg.data_prefetch_config.enabled is True  # default ON
    assert cfg.data_prefetch_config.depth == 2


# ---------------------------------------------------------------------
# engine adoption: external prefetcher, eval, placed-batch tag
# ---------------------------------------------------------------------
def test_train_batch_adopts_external_prefetcher(monkeypatch):
    bs = 2 * 8
    ds = _dataset(bs * 3)
    e_pf = _engine(monkeypatch, prefetch_on=False, dataset=ds)
    e_ref = _engine(monkeypatch, prefetch_on=False, dataset=ds)
    loader = DeepSpeedDataLoader(ds, batch_size=bs)
    pf = e_pf.prefetch(iter(loader))
    l_pf = [float(np.asarray(e_pf.train_batch(data_iter=pf)))
            for _ in range(3)]
    l_ref = _train(e_ref, 3)
    assert l_pf == l_ref
    # adopted: stats tracked and close() owns it
    assert e_pf._train_prefetcher is pf
    e_pf.close()
    assert pf.closed
    e_ref.close()


def test_eval_batch_adopts_prefetched(monkeypatch):
    e = _engine(monkeypatch, prefetch_on=False)
    batch = _dataset(16, seed=9)
    xs = np.stack([b[0] for b in batch])
    ys = np.stack([b[1] for b in batch])
    direct = float(np.asarray(e.eval_batch(batch=(xs, ys))))
    pf = e.prefetch(iter([(xs, ys)]), for_eval=True)
    via_pf = float(np.asarray(e.eval_batch(data_iter=pf)))
    assert direct == via_pf
    pf.close()
    e.close()


def test_dropped_engine_stays_collectable(monkeypatch):
    """The worker thread is a GC root: it must hold the engine WEAKLY,
    so an engine dropped without close() is still collected (its flush
    finalizer fires) and the finalizer drains the parked worker."""
    import gc
    import weakref

    e = _engine(monkeypatch, prefetch_on=True)
    _train(e, 1)
    pf = e._train_prefetcher
    assert pf is not None
    ref = weakref.ref(e)
    del e
    gc.collect()
    assert ref() is None, "engine pinned by the prefetch worker"
    deadline = time.perf_counter() + 5.0
    while not pf.closed and time.perf_counter() < deadline:
        gc.collect()
        time.sleep(0.02)
    assert pf.closed, "finalizer did not drain the worker"


def test_engine_close_drains_eval_prefetchers(monkeypatch):
    """An engine-built eval prefetcher abandoned mid-consumption must be
    drained by engine.close() — otherwise its parked worker pins
    ``depth`` device-resident batches forever."""
    e = _engine(monkeypatch, prefetch_on=False)
    batch = _dataset(16, seed=9)
    xs = np.stack([b[0] for b in batch])
    ys = np.stack([b[1] for b in batch])
    pf = e.prefetch(iter([(xs, ys)] * 6), for_eval=True)
    e.eval_batch(data_iter=pf)  # consume one, abandon the rest
    assert not pf.closed
    e.close()
    assert pf.closed


def test_placed_batch_kind_mismatch_is_descriptive(monkeypatch):
    """A prefetcher built with the wrong for_eval flag must fail with a
    descriptive error at the consumption site, not a deep shape error
    (or a silently wrong loss) inside the compiled step."""
    e = _engine(monkeypatch, prefetch_on=False)
    batch = _dataset(16, seed=9)
    xs = np.stack([b[0] for b in batch])
    ys = np.stack([b[1] for b in batch])
    pf_train = e.prefetch(iter([(xs, ys)]))
    with pytest.raises(ValueError, match="for_eval=True"):
        e.eval_batch(data_iter=pf_train)
    pf_eval = e.prefetch(iter([(xs, ys)]), for_eval=True)
    with pytest.raises(ValueError, match="train placement"):
        e.train_batch(data_iter=pf_eval)
    e.close()


def test_adopted_prefetcher_replaced_still_drains(monkeypatch):
    """Mixed usage: a caller-built training prefetcher adopted via
    data_iter=, then a no-arg train_batch() that builds the engine's
    own — the replaced one must still be closed by engine.close(), and
    the stats baseline must reset (no negative interval deltas)."""
    bs = 2 * 8
    ds = _dataset(bs * 4)
    e = _engine(monkeypatch, prefetch_on=True, dataset=ds)
    external = e.prefetch(iter(DeepSpeedDataLoader(ds, batch_size=bs)))
    e.train_batch(data_iter=external)
    assert e._train_prefetcher is external
    e.train_batch()  # no-arg: engine builds + binds its own
    assert e._train_prefetcher is not external
    assert e._prefetch_prev_stats is None  # baseline reset on rebind
    e.close()
    assert external.closed
    assert e._train_prefetcher.closed


def test_prefetcher_list_pruned(monkeypatch):
    """Per-eval prefetchers must not accumulate forever: closed entries
    are pruned from the engine's list on the next prefetch()."""
    e = _engine(monkeypatch, prefetch_on=False)
    for _ in range(5):
        pf = e.prefetch(iter([]), for_eval=True)
        pf.close()
    assert len(e._prefetchers) <= 2
    e.close()


def test_placed_batch_is_explicit_tag(monkeypatch):
    """A user batch containing jax Arrays must still go through the
    engine's reshape/validation — only the DevicePlacedBatch TAG skips
    it."""
    e = _engine(monkeypatch, prefetch_on=False)
    placed = e._place_train_batch(next(iter(
        DeepSpeedDataLoader(_dataset(16), batch_size=16))))
    assert isinstance(placed, DevicePlacedBatch)
    loss = float(np.asarray(e.train_batch(placed)))
    assert np.isfinite(loss)
    e.close()


# ---------------------------------------------------------------------
# _shard_batch satellites: batched put + multi-process error arm
# ---------------------------------------------------------------------
def test_shard_batch_issues_one_batched_put(monkeypatch):
    e = _engine(monkeypatch, prefetch_on=False)
    bs = e.train_batch_size
    x = np.zeros((bs, HIDDEN), np.float32)
    y = np.ones((bs, HIDDEN), np.float32)
    calls = []
    real_put = jax.device_put

    def spy(v, device=None, **kw):
        calls.append(v)
        return real_put(v, device, **kw)

    monkeypatch.setattr(engine_mod.jax, "device_put", spy)
    sharded = e._shard_batch((x, y))
    monkeypatch.undo()
    assert len(calls) == 1, f"{len(calls)} device_put calls (want 1)"
    assert isinstance(calls[0], list) and len(calls[0]) == 2
    for leaf in jax.tree.leaves(sharded):
        assert leaf.shape[:2] == (1, bs)
    e.close()


def test_shard_batch_device_leaf_passthrough(monkeypatch):
    """jax.Array leaves keep the pay-zero-transfer contract (a repeating
    batch device_put ONCE costs nothing per step)."""
    e = _engine(monkeypatch, prefetch_on=False)
    bs = e.train_batch_size
    x = jax.device_put(np.zeros((bs, HIDDEN), np.float32))
    sharded = e._shard_batch((x, np.ones((bs, HIDDEN), np.float32)))
    assert jax.tree.leaves(sharded)[0].shape == (1, bs, HIDDEN)
    e.close()


def test_shard_batch_multiprocess_error_arm(monkeypatch):
    """nproc > 1 with a mismatched-sharding jax.Array must raise the
    descriptive ValueError, not a deep XLA error."""
    e = _engine(monkeypatch, prefetch_on=False)
    rows = e.train_batch_size // 2  # per-process slice at nproc=2
    x = jax.device_put(np.zeros((rows, HIDDEN), np.float32))
    monkeypatch.setattr(engine_mod.jax, "process_count", lambda: 2)
    with pytest.raises(ValueError,
                       match="multi-process _shard_batch needs "
                             "process-local"):
        e._shard_batch({"x": x})
    monkeypatch.undo()
    e.close()


# ---------------------------------------------------------------------
# telemetry: wait span + hit-ratio scalar + gauge + summarize row
# ---------------------------------------------------------------------
def test_prefetch_telemetry_artifacts(monkeypatch, tmp_path):
    import json as _json
    from deepspeed_tpu.telemetry.cli import summarize

    e = _engine(monkeypatch, prefetch_on=True,
                cfg_over={"steps_per_print": 1,
                          "telemetry": {"enabled": True,
                                        "output_path": str(tmp_path)}})
    _train(e, 3)
    depth_gauge = e.telemetry.registry.gauge("data_prefetch_queue_depth")
    assert depth_gauge.value() is not None
    e.close()

    prom = (tmp_path / "metrics.prom").read_text()
    assert "data_prefetch_queue_depth" in prom
    syncs = [_json.loads(l) for l in
             (tmp_path / "events.jsonl").read_text().splitlines()
             if _json.loads(l).get("kind") == "sync"]
    assert any("prefetch_hit_ratio" in (s.get("scalars") or {})
               for s in syncs)
    rep = summarize(str(tmp_path / "events.jsonl"))
    assert rep["prefetch_hit_ratio"] is not None


def test_summarize_prefetch_row(tmp_path, capsys):
    import json as _json
    from deepspeed_tpu.telemetry.cli import summarize
    p = tmp_path / "events.jsonl"
    lines = [{"kind": "sync", "step": 10 * (i + 1), "interval_s": 1.0,
              "steps": 10, "step_avg_s": 0.1,
              "scalars": {"prefetch_hit_ratio": r,
                          "prefetch_wait_s": 0.001}}
             for i, r in enumerate((0.8, 1.0))]
    p.write_text("\n".join(_json.dumps(l) for l in lines) + "\n")
    rep = summarize(str(p))
    assert rep["prefetch_hit_ratio"] == pytest.approx(0.9)
    assert rep["prefetch_wait_s"] == pytest.approx(0.001)
    assert "input prefetch" in capsys.readouterr().out


# ---------------------------------------------------------------------
# the engine's own loader: a slow collate moves off the step path
# ---------------------------------------------------------------------
@pytest.mark.parametrize("prefetch_on", [True, False])
def test_engine_moves_a_slow_collate_off_the_step_path(prefetch_on):
    """A collate that sleeps 50 ms goes through the engine's own loader
    (``collate_fn=``).  With ``data_prefetch`` on, every batch is
    collated on the worker and the step's exposed input stall
    (``stats()``: ``wait_s`` per consumed batch) stays under the
    collate, with batches already resident when asked; with the config
    off there is no prefetcher and the step's own thread pays every
    collate."""
    delay_s, steps = 0.05, 3
    collated_on = []

    def slow_collate(samples):
        collated_on.append(threading.current_thread())
        time.sleep(delay_s)
        xs, ys = zip(*samples)
        return np.stack(xs), np.stack(ys)

    cfg = base_config(micro_bs=2, grad_acc=1)
    cfg["data_prefetch"] = {"enabled": prefetch_on, "depth": 2}
    dscfg = DeepSpeedConfig(cfg, world_size=1)
    eng = DeepSpeedEngine(
        SimpleModel(hidden_dim=HIDDEN), dscfg,
        mesh=build_mesh(dp=1, devices=jax.devices()[:1]), seed=3,
        training_data=_dataset(dscfg.train_batch_size * 8),
        collate_fn=slow_collate)
    assert eng._prefetch_enabled == prefetch_on
    _train(eng, 1)                       # compile, fill the pipeline
    pf = eng._train_prefetcher
    s0 = pf.stats() if prefetch_on else None
    mark = len(collated_on)
    assert all(np.isfinite(_train(eng, steps)))
    here = threading.current_thread()
    if prefetch_on:
        s1 = pf.stats()
        assert s1["consumed"] - s0["consumed"] == steps
        assert s1["hits"] > s0["hits"]
        assert (s1["wait_s"] - s0["wait_s"]) / steps < delay_s
        assert here not in collated_on
    else:
        assert pf is None
        assert collated_on[mark:] == [here] * steps
    eng.close()
