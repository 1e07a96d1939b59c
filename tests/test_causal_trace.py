"""Causal tracing, per-request serving traces, and the fault flight
recorder (ISSUE 10; docs/observability.md).

Covers the acceptance contract:
  - CPU-provable causal chain: under ``DS_STAGE_DELAY_S`` injected
    delay, trace.json contains flow events linking a prefetch place
    span to the consuming step span, and a serve request's admission to
    its decode ticks — asserted from the PARSED trace JSON (flow ids +
    span enclosure), not timestamps alone;
  - an injected sticky fault produces a ``flightrec_*.json`` whose
    ``diagnose`` output names the degraded stage and the original
    exception;
  - per-request serving records reconstruct TTFT / queue-wait p50/p99
    matching the registry histograms;
  - trace-context lifecycle at the fault boundaries: poison ends a
    request's trace with an error span (no leaked flows), degradation
    to inline keeps emitting the same span names, and export flushes
    in-flight flows.
"""
import glob
import json
import os

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.telemetry.cli import _percentile, diagnose, summarize
from deepspeed_tpu.telemetry.hub import write_flight_record
from deepspeed_tpu.telemetry.tracing import TraceContext, TraceRecorder
from deepspeed_tpu.runtime.stages import Stage, reset_fault_injection

from simple_model import SimpleModel, base_config

HIDDEN = 16


@pytest.fixture(autouse=True)
def _clean_faults():
    reset_fault_injection()
    yield
    reset_fault_injection()


def _load_trace(tel_dir):
    doc = json.loads(open(os.path.join(str(tel_dir), "trace.json")).read())
    return doc["traceEvents"]


def _enclosing_spans(evs, flow_ev):
    """Names of the complete spans (ph X) whose [ts, ts+dur] on the
    flow event's thread contain the flow event — the slice a Chrome
    flow arrow binds to."""
    return {e["name"] for e in evs
            if e["ph"] == "X" and e["tid"] == flow_ev["tid"]
            and e["ts"] <= flow_ev["ts"] <= e["ts"] + e["dur"]}


# ---------------------------------------------------------------------------
# TraceContext + flow-event primitives
# ---------------------------------------------------------------------------

def test_trace_context_ids_unique_and_child_lineage():
    a, b = TraceContext.new(), TraceContext.new()
    assert a.trace_id != b.trace_id
    c = a.child()
    assert c.trace_id == a.trace_id          # same flow
    assert c.parent_id == a.span_id
    assert c.span_id not in (a.span_id, b.trace_id)


def test_flow_events_emitted_with_shared_identity(tmp_path):
    tr = TraceRecorder()
    ctx = TraceContext.new()
    with tr.span("producer", cat="data"):
        tr.flow_start("link", ctx, cat="data")
    with tr.span("middle"):
        tr.flow_step("link", ctx, cat="data")
    with tr.span("consumer", cat="train"):
        tr.flow_end("link", ctx, cat="data")
    tr.export(str(tmp_path / "trace.json"))
    evs = json.loads(open(tmp_path / "trace.json").read())["traceEvents"]
    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    assert [e["ph"] for e in flows] == ["s", "t", "f"]
    # Chrome binds a flow by (cat, id, name): all three must agree
    assert len({(e["name"], e["cat"], e["id"]) for e in flows}) == 1
    assert flows[0]["id"] == ctx.trace_id
    end = flows[-1]
    assert end["bp"] == "e"
    for e in flows:
        assert "ph" in e and "ts" in e and "name" in e  # trace contract


def test_export_flushes_in_flight_flows(tmp_path):
    """A flow open at shutdown (work in flight when the run died) is
    terminated by export — no dangling arrows, and the terminator is
    marked as a flush, not a real consumption."""
    tr = TraceRecorder()
    ctx = TraceContext.new()
    tr.flow_start("inflight", ctx)
    tr.export(str(tmp_path / "trace.json"))
    evs = json.loads(open(tmp_path / "trace.json").read())["traceEvents"]
    ends = [e for e in evs if e["ph"] == "f" and e["id"] == ctx.trace_id]
    assert len(ends) == 1
    assert ends[0]["args"]["flushed"] is True
    # flushing is once: a second export must not duplicate terminators
    tr.export(str(tmp_path / "trace2.json"))
    evs2 = json.loads(open(tmp_path / "trace2.json").read())["traceEvents"]
    assert len([e for e in evs2 if e["ph"] == "f"
                and e["id"] == ctx.trace_id]) == 1


def test_flow_terminators_survive_buffer_cap(tmp_path):
    """Regression: once the event buffer caps, a flow whose 's' was
    admitted must still get its 'f' (terminators force past the cap,
    bounded by admitted starts) — otherwise diagnose reports phantom
    in-flight work on a healthy run."""
    tr = TraceRecorder(max_events=4)
    ctx = TraceContext.new()
    tr.flow_start("link", ctx)
    for i in range(10):
        tr.span(f"filler{i}").end()    # fill the buffer past the cap
    tr.flow_end("link", ctx)           # must not be dropped
    evs = tr.events()
    assert any(e["ph"] == "f" and e["id"] == ctx.trace_id for e in evs)
    assert tr.dropped > 0
    tr.export(str(tmp_path / "trace.json"))
    doc = json.loads(open(tmp_path / "trace.json").read())
    starts = {e["id"] for e in doc["traceEvents"] if e.get("ph") == "s"}
    ends = {e["id"] for e in doc["traceEvents"] if e.get("ph") == "f"}
    assert starts <= ends              # no dangling starts


def test_async_span_pairs_for_overlapping_intervals():
    tr = TraceRecorder()
    a = tr.async_begin("req", 1, cat="serve", rid=1)
    b = tr.async_begin("req", 2, cat="serve", rid=2)  # overlaps a
    a.end(reason="length")
    a.end()                            # idempotent
    b.end()
    evs = tr.events()
    assert [(e["ph"], e["id"]) for e in evs] == [
        ("b", 1), ("b", 2), ("e", 1), ("e", 2)]
    assert evs[2]["args"]["reason"] == "length"


# ---------------------------------------------------------------------------
# engine: prefetch place span -> consuming step span (acceptance)
# ---------------------------------------------------------------------------

def _make_engine(tel_dir, steps_per_print=10 ** 9, **tel_extra):
    cfg = base_config(micro_bs=2, grad_acc=1, stage=0)
    cfg["steps_per_print"] = steps_per_print
    cfg["telemetry"] = {"enabled": True, "output_path": str(tel_dir),
                        **tel_extra}
    eng, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN), config=cfg)
    return eng


def _batches(eng, n, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        x = rng.standard_normal((int(eng.train_batch_size),
                                 HIDDEN)).astype(np.float32)
        yield (x, 0.5 * x)


def test_prefetch_flow_links_place_span_to_step_span(tmp_path,
                                                     monkeypatch):
    """THE train-side causal chain, CPU-provable: with injected
    placement delay the worker's place spans and the consumer's
    dispatch spans are far apart in time and on different threads, and
    the flow events still link them pairwise by id."""
    monkeypatch.setenv("DS_STAGE_DELAY_S", "prefetch:0.02")
    eng = _make_engine(tmp_path)
    it = eng.prefetch(_batches(eng, 5))
    for _ in range(5):
        eng.train_batch(data_iter=it)
    eng.close()
    evs = _load_trace(tmp_path)
    starts = [e for e in evs if e["ph"] == "s"
              and e["name"] == "data/batch"]
    ends = [e for e in evs if e["ph"] == "f"
            and e["name"] == "data/batch"]
    assert len(starts) == 5 and len(ends) == 5
    # ids pair the producer side to the consumer side (the causal
    # assertion — parsed structure, not timestamps)
    assert {e["id"] for e in starts} == {e["id"] for e in ends}
    for s in starts:
        assert "data/prefetch_place" in _enclosing_spans(evs, s)
    for f in ends:
        assert "train/dispatch" in _enclosing_spans(evs, f)
    # produced on the worker thread, consumed on the caller's
    assert {e["tid"] for e in starts} != {e["tid"] for e in ends}


def test_closed_prefetcher_releases_stage_depth_sampler(tmp_path):
    """Regression: a closed prefetcher must not stay pinned by the
    engine-lifetime shared Stage record through its bound qsize — later
    stage events would sample a dead channel's depth and the source
    iterator would be retained for the rest of the run."""
    eng = _make_engine(tmp_path)
    stage = eng._stage_records["prefetch"]
    pf_eval = eng.prefetch(_batches(eng, 1), for_eval=True)
    assert stage.depth_fn is not None
    pf_eval.close()
    assert stage.depth_fn is None      # released with its owner
    pf_train = eng.prefetch(_batches(eng, 1))
    assert stage.depth_fn is not None  # next owner reinstalls
    pf_train.close()
    eng.close()


def test_eval_prefetched_batches_close_their_flows(tmp_path):
    """Regression: eval-placed batches must terminate their flows too —
    an eval loop must not grow the recorder's open-flow set by one
    entry per batch (each would flush as a synthetic terminator at
    export, eating the event budget)."""
    eng = _make_engine(tmp_path)
    it = eng.prefetch(_batches(eng, 3), for_eval=True)
    for _ in range(3):
        eng.eval_batch(data_iter=it)
    assert not eng.telemetry.tracer._open_flows, (
        "eval batches leaked open flows")
    eng.close()
    evs = _load_trace(tmp_path)
    ends = [e for e in evs if e["ph"] == "f"
            and e["name"] == "data/batch"]
    assert len(ends) == 3
    assert not any((e.get("args") or {}).get("flushed") for e in ends)
    for f in ends:
        assert "eval/dispatch" in _enclosing_spans(evs, f)


def test_ckpt_flow_links_save_to_async_write(tmp_path):
    eng = _make_engine(tmp_path / "tel")
    for b in _batches(eng, 1):
        eng.train_batch(b)
    eng.save_checkpoint(str(tmp_path / "ckpt"), tag="t1",
                        async_write=True)
    eng.close()
    evs = _load_trace(tmp_path / "tel")
    starts = [e for e in evs if e["ph"] == "s"
              and e["name"] == "checkpoint/job"]
    ends = [e for e in evs if e["ph"] == "f"
            and e["name"] == "checkpoint/job"]
    assert len(starts) == 1 and len(ends) == 1
    assert starts[0]["id"] == ends[0]["id"]
    assert "checkpoint/snapshot" in _enclosing_spans(evs, starts[0])
    assert "checkpoint/async_write" in _enclosing_spans(evs, ends[0])


def test_degraded_prefetch_keeps_span_names_and_closes_flows(
        tmp_path, monkeypatch):
    """Satellite: degradation-to-inline keeps emitting the SAME span
    names (a degraded run's trace answers the same queries) and every
    batch flow still closes — no leaks across the fault boundary."""
    monkeypatch.setenv("DS_STAGE_FAULT", "prefetch:place:1+")
    eng = _make_engine(tmp_path)
    it = eng.prefetch(_batches(eng, 4))
    for _ in range(4):
        eng.train_batch(data_iter=it)
    assert eng._stage_records["prefetch"].degraded
    eng.close()
    evs = _load_trace(tmp_path)
    places = [e for e in evs if e["ph"] == "X"
              and e["name"] == "data/prefetch_place"]
    inline = [e for e in places if (e.get("args") or {}).get("inline")]
    assert inline, "degraded path emitted no inline place spans"
    starts = {e["id"] for e in evs if e["ph"] == "s"
              and e["name"] == "data/batch"}
    ends = {e["id"] for e in evs if e["ph"] == "f"
            and e["name"] == "data/batch"}
    assert len(starts) == 4 and starts == ends
    # the degradation itself dumped a flight record
    assert glob.glob(os.path.join(str(tmp_path), "flightrec_*.json"))


def test_flow_end_adds_zero_device_syncs(tmp_path, monkeypatch):
    """The causal-linking overhead contract, on the CONSUMER path: a
    train_batch consuming a prefetched batch (which terminates the
    batch's flow inside its dispatch span) performs zero device syncs —
    flow events are host-side appends riding existing span points.
    (The producer-side flow rides the worker thread, whose in-span
    drain was always there; here the worker is drained first so the
    counter sees only the consumer.)"""
    import time as _time
    eng = _make_engine(tmp_path)
    eng.train_batch(next(_batches(eng, 1)))     # compile outside window
    it = eng.prefetch(_batches(eng, 2), depth=2)
    deadline = _time.monotonic() + 30
    while it.qsize() < 2 and _time.monotonic() < deadline:
        _time.sleep(0.01)
    assert it.qsize() == 2                      # worker fully drained

    class Counter:
        count = 0
    real_bur = jax.block_until_ready
    real_dg = jax.device_get
    real_asarray = np.asarray

    def wrap(real):
        def inner(*a, **k):
            Counter.count += 1
            return real(*a, **k)
        return inner

    def asarray(obj, *a, **k):
        if isinstance(obj, jax.Array):
            Counter.count += 1
        return real_asarray(obj, *a, **k)
    monkeypatch.setattr(jax, "block_until_ready", wrap(real_bur))
    monkeypatch.setattr(jax, "device_get", wrap(real_dg))
    monkeypatch.setattr(np, "asarray", asarray)
    for _ in range(2):
        eng.train_batch(data_iter=it)
    assert Counter.count == 0, (
        "flow-event emission added device syncs to the consume path")
    monkeypatch.undo()
    eng.close()
    evs = _load_trace(tmp_path)
    assert len([e for e in evs if e["ph"] == "f"
                and e["name"] == "data/batch"]) == 2


# ---------------------------------------------------------------------------
# flight recorder + diagnose (acceptance)
# ---------------------------------------------------------------------------

def test_sticky_fault_flightrec_diagnose_names_stage_and_error(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("DS_STAGE_FAULT", "prefetch:place:1+")
    eng = _make_engine(tmp_path)
    it = eng.prefetch(_batches(eng, 3))
    for _ in range(3):
        eng.train_batch(data_iter=it)
    eng.close()
    recs = glob.glob(os.path.join(str(tmp_path), "flightrec_*.json"))
    assert recs
    fr = json.loads(open(recs[0]).read())
    assert fr["version"] == 1
    st = fr["stages"]["prefetch"]
    assert st["degraded"] is True
    kinds = [e["kind"] for e in st["events"]]
    assert "failure" in kinds and "degraded" in kinds
    rep = diagnose(str(tmp_path))
    out = capsys.readouterr().out
    assert rep["degraded_stages"] == ["prefetch"]
    assert rep["first_failure_stage"] == "prefetch"
    assert "InjectedStageFault" in rep["error"]
    assert "prefetch" in out and "InjectedStageFault" in out


def test_dump_flight_record_on_demand_and_step_failure(tmp_path):
    eng = _make_engine(tmp_path)
    for b in _batches(eng, 1):
        eng.train_batch(b)
    path = eng.dump_flight_record(reason="operator request")
    assert path and os.path.isfile(path)
    doc = json.loads(open(path).read())
    assert doc["reason"] == "operator request"
    assert set(doc["stages"]) == {"prefetch", "offload_h2d",
                                  "disk_read", "disk_write",
                                  "ckpt_writer"}
    # a failing train_batch dumps once (and only once)
    with pytest.raises((ValueError, IndexError, TypeError)):
        eng.train_batch(np.float32(0.0))  # bogus batch: placement fails
    assert eng._flightrec_poison_dumped
    eng.close()


def test_stage_ring_is_bounded_and_samples_depth():
    st = Stage("s")
    st.depth_fn = lambda: 7
    for i in range(600):
        st.record_event("ok", point="p", i=i)
    assert len(st.events) == 256        # FLIGHT_RING_SIZE bound
    ev = list(st.events)[-1]
    assert ev["depth"] == 7 and ev["i"] == 599
    snap = st.flight_snapshot()
    assert snap["degraded"] is False
    assert len(snap["events"]) == 256


def test_write_flight_record_torn_safe(tmp_path):
    st = Stage("x")
    st.record_event("failure", error="boom")
    p = write_flight_record(str(tmp_path), {"x": st}, 3, "unit",
                            error=RuntimeError("orig"))
    doc = json.loads(open(p).read())
    assert doc["error"] == "RuntimeError('orig')"
    assert not glob.glob(os.path.join(str(tmp_path), "*.tmp"))


def test_supervisor_give_up_dumps_flight_record(tmp_path):
    from deepspeed_tpu.launcher.elastic import (ElasticGiveUpError,
                                                ElasticSupervisor,
                                                RestartPolicy)

    class P:
        def poll(self):
            return 1

    sup = ElasticSupervisor(
        {"localhost": [0]},
        launch_fn=lambda active, attempt: [("localhost", P())],
        policy=RestartPolicy(max_restarts=0),
        heartbeat_dir=str(tmp_path))
    with pytest.raises(ElasticGiveUpError):
        sup.run()
    p = os.path.join(str(tmp_path), "flightrec_supervisor.json")
    assert os.path.isfile(p)
    doc = json.loads(open(p).read())
    assert "ElasticGiveUpError" in doc["reason"]
    kinds = [e["kind"] for e in doc["stages"]["supervisor"]["events"]]
    assert kinds.count("launch") == 1 and "give_up" in kinds


# ---------------------------------------------------------------------------
# serving: request flow + per-request records (acceptance)
# ---------------------------------------------------------------------------

def _serve_engine(tmp_path, slots=2, **serving_extra):
    from deepspeed_tpu.inference import ServeEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    tiny = GPT2Config(vocab_size=128, n_positions=64, d_model=32,
                      n_layer=2, n_head=4)
    cfg = {"serving": {"slots": slots, "max_seq_len": 32,
                       "prefill_len": 8, **serving_extra},
           "telemetry": {"enabled": True, "output_path": str(tmp_path)}}
    return ServeEngine(GPT2Model(tiny), cfg)


def test_serve_flow_links_admit_to_decode_ticks(tmp_path, monkeypatch):
    """THE serve-side causal chain: each request's flow starts inside
    its prefill (admission) span and steps through every decode tick it
    rides — under injected per-tick delay, asserted structurally."""
    monkeypatch.setenv("DS_STAGE_DELAY_S", "serve:0.005")
    eng = _serve_engine(tmp_path)
    reqs = [eng.submit([1, 2, 3], max_new_tokens=4) for _ in range(3)]
    eng.run_until_idle()
    for r in reqs:
        assert r.result(timeout=30)
    eng.close()
    evs = _load_trace(tmp_path)
    starts = [e for e in evs if e["ph"] == "s"
              and e["name"] == "serve/request"]
    steps = [e for e in evs if e["ph"] == "t"
             and e["name"] == "serve/request"]
    ends = [e for e in evs if e["ph"] == "f"
            and e["name"] == "serve/request"]
    assert len(starts) == 3 and len(ends) == 3
    assert {e["id"] for e in starts} == {e["id"] for e in ends}
    # every decode-tick step belongs to an admitted request's flow
    assert steps and {e["id"] for e in steps} <= {e["id"]
                                                 for e in starts}
    for s in starts:
        assert "serve/prefill" in _enclosing_spans(evs, s)
    for t in steps:
        assert "serve/decode_step" in _enclosing_spans(evs, t)
    for f in ends:
        assert "serve/finish" in _enclosing_spans(evs, f)
    # root lifetimes are ASYNC (b/e) pairs — concurrent requests
    # overlap, which complete (X) slices would mis-render; pairs match
    # by (cat, id, name) and carry the rid
    roots_b = [e for e in evs if e["ph"] == "b"
               and e["name"] == "serve/request"]
    roots_e = [e for e in evs if e["ph"] == "e"
               and e["name"] == "serve/request"]
    assert {e["args"]["rid"] for e in roots_b} == {r.rid for r in reqs}
    assert {e["id"] for e in roots_b} == {e["id"] for e in roots_e}
    waits_b = [e for e in evs if e["ph"] == "b"
               and e["name"] == "serve/queue_wait"]
    waits_e = [e for e in evs if e["ph"] == "e"
               and e["name"] == "serve/queue_wait"]
    assert len(waits_b) == 3
    assert {e["id"] for e in waits_b} == {e["id"] for e in waits_e}


def test_serve_records_reconstruct_registry_histograms(tmp_path):
    """Acceptance: the per-request completion records in events.jsonl
    reconstruct TTFT and queue-wait p50/p99 matching the registry
    histograms (same raw observations, same interpolation)."""
    eng = _serve_engine(tmp_path, slots=2)
    reqs = [eng.submit([1 + i, 2, 3], max_new_tokens=3)
            for i in range(6)]
    eng.run_until_idle()
    for r in reqs:
        r.result(timeout=30)
    reg = eng.telemetry.registry
    eng.close()
    recs = [json.loads(l) for l in
            open(os.path.join(str(tmp_path), "events.jsonl"))]
    srs = [r for r in recs if r["kind"] == "serve_request"]
    assert len(srs) == 6
    for r in srs:
        assert r["error"] is None and r["finish_reason"] == "length"
        assert r["queue_wait_s"] >= 0 and r["ttft_s"] > 0
        assert r["decode_tokens"] == 2
        assert "trace_id" in r
    for name, field in (("serve_ttft_seconds", "ttft_s"),
                        ("serve_queue_wait_seconds", "queue_wait_s")):
        res = reg.histogram(name).reservoir()
        assert res is not None and res.count == 6
        vals = sorted(float(r[field]) for r in srs)
        for q in (0.50, 0.99):
            assert res.percentile(q) == pytest.approx(
                _percentile(vals, q), rel=1e-9)
    # summarize's split row reports the same reconstruction
    rep = summarize(os.path.join(str(tmp_path), "events.jsonl"))
    assert rep["serve_requests"] == 6
    assert rep["serve_ttft_p50_s"] == pytest.approx(
        reg.histogram("serve_ttft_seconds").reservoir().percentile(0.5),
        rel=1e-9)
    assert rep["serve_queue_wait_p99_s"] == pytest.approx(
        reg.histogram("serve_queue_wait_seconds").reservoir()
        .percentile(0.99), rel=1e-9)
    assert rep["serve_decode_p50_s"] is not None


def test_serve_poison_ends_traces_with_error_span_no_leaks(tmp_path):
    """Satellite: trace context survives Channel poison — every
    in-flight request's trace ends with an error span and a terminated
    flow, and the flight recorder captures the pool's last moments."""
    eng = _serve_engine(tmp_path, slots=2)
    r_ok = eng.submit([1, 2], max_new_tokens=2)
    eng.run_until_idle()
    r_ok.result(timeout=30)

    boom = RuntimeError("decode exploded")

    def bad_decode(*a, **k):
        raise boom
    reqs = [eng.submit([3, 4], max_new_tokens=4) for _ in range(2)]
    eng._decode_fn = bad_decode
    with pytest.raises(RuntimeError, match="decode exploded"):
        eng.run_until_idle()
    for r in reqs:
        with pytest.raises(RuntimeError, match="decode exploded"):
            r.result(timeout=30)
    recs = glob.glob(os.path.join(str(tmp_path), "flightrec_*.json"))
    assert recs, "poison did not dump a flight record"
    fr = json.loads(open(max(recs)).read())
    assert fr["reason"] == "serve poison"
    assert "decode exploded" in fr["error"]
    assert "poison" in [e["kind"] for e in fr["stages"]["serve"]["events"]]
    eng.close()
    evs = _load_trace(tmp_path)
    errors = [e for e in evs if e["ph"] == "X"
              and e["name"] == "serve/error"]
    assert {e["args"]["rid"] for e in errors} == {r.rid for r in reqs}
    starts = {e["id"] for e in evs if e["ph"] == "s"
              and e["name"] == "serve/request"}
    ends = {e["id"] for e in evs if e["ph"] == "f"
            and e["name"] == "serve/request"}
    assert starts == ends, "poisoned requests leaked open flows"
    # the failed requests' completion records carry the original error
    jrecs = [json.loads(l) for l in
             open(os.path.join(str(tmp_path), "events.jsonl"))]
    failed = [r for r in jrecs if r["kind"] == "serve_request"
              and r.get("error")]
    assert len(failed) == 2
    assert all("decode exploded" in r["error"] for r in failed)
    rep = summarize(os.path.join(str(tmp_path), "events.jsonl"))
    assert rep["serve_requests_failed"] == 2


# ---------------------------------------------------------------------------
# anomaly trigger (opt-in, one-shot, bounded)
# ---------------------------------------------------------------------------

def test_anomaly_ratio_config_validation():
    from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1}, 1)
    assert cfg.telemetry_config.anomaly_ratio == 0.0   # default off
    for bad in (1.0, -2, True, "3"):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                             "telemetry": {"anomaly_ratio": bad}}, 1)
    ok = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                          "telemetry": {"anomaly_ratio": 3.0}}, 1)
    assert ok.telemetry_config.anomaly_ratio == 3.0


def test_anomaly_trigger_one_shot_capture_and_dump(tmp_path,
                                                   monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path, **k: calls.append(("start", path)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    eng = _make_engine(tmp_path, anomaly_ratio=2.0)
    for avg in [0.1] * 6:
        eng._anomaly_check(avg)           # healthy baseline
    assert not eng._anomaly_fired and not calls
    eng._anomaly_check(0.5)               # 5x the trailing median
    assert eng._anomaly_fired
    assert [c[0] for c in calls] == ["start"]
    assert "anomaly_profile" in calls[0][1]
    recs = glob.glob(os.path.join(str(tmp_path), "flightrec_*.json"))
    assert recs
    assert "anomaly" in json.loads(open(recs[0]).read())["reason"]
    # bounded: the capture closes at the NEXT sync ...
    eng._anomaly_check(0.5)
    assert [c[0] for c in calls] == ["start", "stop"]
    # ... and one-shot: a later anomalous interval must not re-fire
    eng._anomaly_check(5.0)
    assert [c[0] for c in calls] == ["start", "stop"]
    eng.close()
    assert [c[0] for c in calls] == ["start", "stop"]


def test_anomaly_straggler_arm_capture_survives_its_own_sync(
        tmp_path, monkeypatch):
    """Regression: the straggler arm fires AFTER the sync's anomaly
    check (which is also where a previous capture closes) — its capture
    must stay open until the NEXT sync, not be stopped microseconds
    after it starts by the same sync."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    eng = _make_engine(tmp_path, anomaly_ratio=2.0)
    # one telemetry sync: check runs first, then the straggler arm
    # fires (the ordering _telemetry_sync now guarantees)
    eng._anomaly_check(0.1)
    eng._fire_anomaly("this host flagged as straggler (hostX/0)")
    assert calls == ["start"]          # still capturing after the sync
    eng._anomaly_check(0.1)            # next sync closes the window
    assert calls == ["start", "stop"]
    eng.close()
    assert calls == ["start", "stop"]


def test_anomaly_capture_defers_to_pending_profiler_window(
        tmp_path, monkeypatch):
    """Regression: with a user-configured profiler window still PENDING
    (start_step not reached), the anomaly trigger must not open its own
    capture — the window's later start_trace would raise 'Profile has
    already been started' and kill train_batch.  The flight dump still
    happens."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path, **k: calls.append(path))
    cfg = base_config(micro_bs=2, grad_acc=1, stage=0)
    cfg["telemetry"] = {"enabled": True, "output_path": str(tmp_path),
                        "anomaly_ratio": 2.0}
    cfg["profiler"] = {"enabled": True, "start_step": 100,
                       "num_steps": 3,
                       "output_path": str(tmp_path / "xplane")}
    eng, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN), config=cfg)
    for avg in [0.1] * 6:
        eng._anomaly_check(avg)
    eng._anomaly_check(0.9)            # anomalous: fires the one-shot
    assert eng._anomaly_fired
    assert not eng._anomaly_profiling and not calls
    assert glob.glob(os.path.join(str(tmp_path), "flightrec_*.json"))
    eng.close()


def test_serve_close_failed_records_match_counter(tmp_path):
    """Regression: requests still queued at close() get failed records
    AND the serve_requests_failed_total counter — summarize's
    record-derived count and the scraped counter must agree."""
    eng = _serve_engine(tmp_path, slots=2)
    reqs = [eng.submit([1, 2], max_new_tokens=2) for _ in range(3)]
    reg = eng.telemetry.registry
    eng.close()                        # never stepped: all still queued
    for r in reqs:
        with pytest.raises(RuntimeError, match="ServeEngine closed"):
            r.result(timeout=5)
    assert reg.counter("serve_requests_failed_total").value() == 3
    recs = [json.loads(l) for l in
            open(os.path.join(str(tmp_path), "events.jsonl"))]
    failed = [r for r in recs if r["kind"] == "serve_request"
              and r.get("error")]
    assert len(failed) == 3


def test_anomaly_trigger_off_by_default(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path, **k: calls.append(path))
    eng = _make_engine(tmp_path)           # anomaly_ratio defaults 0
    for avg in [0.1] * 6 + [9.9]:
        eng._anomaly_check(avg)
    assert not eng._anomaly_fired and not calls
    eng.close()
