"""Unified telemetry subsystem (deepspeed_tpu/telemetry/).

Covers the acceptance contract:
  - telemetry-enabled ``train_batch`` adds ZERO device syncs per step
    (spans close lazily at the periodic steps_per_print sync);
  - the exported trace file is valid Chrome trace-event JSON (loadable
    by ``json.loads``, every event carrying ph/ts/name);
  - ``recompiles_total`` increments when a jitted program retraces
    (shape-change test) and the Prometheus exporter output parses
    line-by-line.
"""
import json
import os
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry import (CompileMonitor, MetricsRegistry,
                                     TelemetryHub, TraceRecorder,
                                     prometheus_text)
from deepspeed_tpu.telemetry.cli import summarize

from simple_model import SimpleModel, base_config


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "help text")
    c.inc()
    c.inc(2, route="train")
    assert c.value() == 1
    assert c.value(route="train") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("hbm_bytes")
    g.set(5, device="0")
    g.set(7, device="0")  # last write wins
    assert g.value(device="0") == 7
    h = reg.histogram("lat_seconds")
    for v in range(1, 101):
        h.observe(v / 100)
    res = h.reservoir()
    assert res.count == 100 and res.min == 0.01 and res.max == 1.0
    assert abs(res.percentile(0.5) - 0.5) < 0.05
    assert abs(res.percentile(0.99) - 0.99) < 0.05
    # idempotent re-registration; kind mismatch is an error
    assert reg.counter("requests_total") is c
    with pytest.raises(ValueError):
        reg.gauge("requests_total")


def test_histogram_reservoir_is_bounded():
    reg = MetricsRegistry()
    h = reg.histogram("x", reservoir_size=64)
    for v in range(10_000):
        h.observe(float(v))
    res = h.reservoir()
    assert len(res.samples) == 64        # bounded memory
    assert res.count == 10_000           # exact count survives
    assert res.percentile(0.5) > 1000    # samples span the stream


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_trace_recorder_span_and_export(tmp_path):
    tr = TraceRecorder()
    with tr.span("outer", cat="test", step=3):
        with tr.span("inner"):
            pass
    tr.complete("marker", time.perf_counter() - 0.5, 0.25, stalled=True)
    h = tr.begin("lazy")
    h.end(steps=5)
    h.end()  # idempotent
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"outer", "inner", "marker", "lazy"} <= names
    for e in evs:
        assert "ph" in e and "ts" in e and "name" in e
    lazy = next(e for e in evs if e["name"] == "lazy")
    assert lazy["args"]["steps"] == 5
    outer = next(e for e in evs if e["name"] == "outer")
    inner = next(e for e in evs if e["name"] == "inner")
    assert outer["ts"] <= inner["ts"]
    assert outer["dur"] >= inner["dur"]
    # a span filed after the fact lies on the recorder's clock
    marker = next(e for e in evs if e["name"] == "marker")
    assert marker["ph"] == "X" and marker["args"] == {"stalled": True}
    assert marker["dur"] == pytest.approx(0.25e6)
    assert marker["ts"] + marker["dur"] < outer["ts"]


def _fixed_recorder():
    """A recorder on a clock that counts, holding one event of every
    kind."""
    import itertools
    tr = TraceRecorder(process_name="golden", pid=7)
    ticks = itertools.count()
    tr._now_us = lambda: next(ticks) * 1000.0 + 0.1234
    tr._origin_unix_ns = 1_700_000_000_000_000_000
    tr.span("outer", cat="test", step=3, where="a").end(n=1)
    tr.span("bare").end()
    req = tr.async_begin("serve/request", 11, cat="serve", rid=11)
    req.end(tokens=5)
    tr.flow_start("batch", 21, n=2)
    tr.flow_step("batch", 21)
    tr.flow_end("batch", 21, cat="flow", ok=True)
    tr.flow_start("left_open", 22)
    tr._origin = 0.0
    tr.complete("process_stall", 0.5, 0.25, cat="stall", cause="gc")
    return tr


GOLDEN_TRACE = (
    '{"traceEvents": [{"name": "process_name", "ph": "M", "pid": 7, "tid": '
    '0, "ts": 0, "args": {"name": "golden"}}, {"name": "outer", "cat": '
    '"test", "ph": "X", "pid": 7, "tid": 0, "ts": 0.123, "dur": 1000.0, '
    '"args": {"step": 3, "where": "a", "n": 1}}, {"name": "bare", "cat": '
    '"runtime", "ph": "X", "pid": 7, "tid": 0, "ts": 2000.123, "dur": '
    '1000.0}, {"name": "serve/request", "cat": "serve", "ph": "b", "id": 11, '
    '"pid": 7, "tid": 0, "ts": 4000.123, "args": {"rid": 11}}, {"name": '
    '"serve/request", "cat": "serve", "ph": "e", "id": 11, "pid": 7, "tid": '
    '0, "ts": 5000.123, "args": {"tokens": 5}}, {"name": "batch", "cat": '
    '"flow", "ph": "s", "id": 21, "pid": 7, "tid": 0, "ts": 6000.123, '
    '"args": {"n": 2}}, {"name": "batch", "cat": "flow", "ph": "t", "id": '
    '21, "pid": 7, "tid": 0, "ts": 7000.123}, {"name": "batch", "cat": '
    '"flow", "ph": "f", "id": 21, "pid": 7, "tid": 0, "ts": 8000.123, "bp": '
    '"e", "args": {"ok": true}}, {"name": "left_open", "cat": "flow", "ph": '
    '"s", "id": 22, "pid": 7, "tid": 0, "ts": 9000.123}, {"name": '
    '"process_stall", "cat": "stall", "ph": "X", "pid": 7, "tid": 0, "ts": '
    '500000.0, "dur": 250000.0, "args": {"cause": "gc"}}, {"name": '
    '"left_open", "cat": "flow", "ph": "f", "id": 22, "pid": 7, "tid": 0, '
    '"ts": 10000.123, "bp": "e", "args": {"flushed": true}}], '
    '"displayTimeUnit": "ms", "otherData": {"origin_unix_ns": '
    '1700000000000000000}}')


def test_trace_json_is_byte_for_byte_what_the_dict_recorder_wrote(tmp_path):
    """The recorder keeps tuples (PR 54); ``trace.json`` is the file the
    recorder of dicts wrote, key order and all (the string below was
    written by the parent commit's recorder from the same calls)."""
    path = _fixed_recorder().export(str(tmp_path / "trace.json"))
    assert open(path).read() == GOLDEN_TRACE


def test_a_full_recorder_leaves_the_collector_one_list_to_walk():
    """Kept events are tuples of scalars, which the collector stops
    tracking once it has seen what they hold (the pairs, then the args,
    then the event: three passes at most): then none of a buffer's events
    is a tracked object (a dict each was, so a full collection walked the
    whole buffer; PERF.md section 6, PR 54)."""
    import gc
    tr = TraceRecorder(max_events=2000)
    for i in range(2000):
        tr.span("serve/tick", cat="serve", tick=i, active=3).end(produced=1)
    for _ in range(3):
        gc.collect()
    kept = tr._events
    assert len(kept) == 2000
    assert not any(gc.is_tracked(ev) for ev in kept)
    assert not any(gc.is_tracked(ev[-1]) for ev in kept)
    assert tr.events()[5]["args"] == {"tick": 5, "active": 3, "produced": 1}


def test_trace_recorder_bounds_events():
    tr = TraceRecorder(max_events=10)
    for i in range(25):
        tr.span(f"e{i}").end()
    assert len(tr.events()) == 10
    assert tr.dropped == 15


# ---------------------------------------------------------------------------
# prometheus exporter — parses line-by-line (acceptance)
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^(?:# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^{}]*\})? \S+)$")


def test_prometheus_text_parses_line_by_line():
    reg = MetricsRegistry()
    reg.counter("recompiles_total", "retraces").inc(3, program="train_step")
    reg.gauge("device_bytes_in_use").set(1.5e9, device="0")
    h = reg.histogram("train_step_seconds", "synced step time")
    h.observe(0.25)
    h.observe(0.75)
    text = prometheus_text(reg)
    lines = text.strip().splitlines()
    assert lines, "exporter produced no output"
    for line in lines:
        assert _PROM_LINE.match(line), f"unparseable line: {line!r}"
    assert 'recompiles_total{program="train_step"} 3.0' in lines
    assert any(l.startswith("train_step_seconds{quantile=") for l in lines)
    assert "train_step_seconds_count 2.0" in lines


# ---------------------------------------------------------------------------
# compile monitor — recompiles_total increments on retrace (acceptance)
# ---------------------------------------------------------------------------

def test_recompiles_total_increments_on_shape_change():
    reg = MetricsRegistry()
    cm = CompileMonitor(reg, storm_threshold=100)
    f = jax.jit(lambda x: x * 2)
    assert cm.track("prog", f)
    f(jnp.ones((2,)))
    cm.sample()
    assert reg.counter("recompiles_total").value(program="prog") == 0
    f(jnp.ones((3,)))  # new shape -> retrace
    cm.sample()
    assert reg.counter("recompiles_total").value(program="prog") == 1
    cm.sample()  # idempotent between retraces
    assert reg.counter("recompiles_total").value(program="prog") == 1
    # the exporter carries the label through, line-parseable
    text = prometheus_text(reg)
    assert 'recompiles_total{program="prog"} 1.0' in text.splitlines()


def test_compile_monitor_jax_monitoring_listener():
    reg = MetricsRegistry()
    cm = CompileMonitor(reg)
    installed = cm.install()
    try:
        if not installed:
            pytest.skip("jax.monitoring unavailable in this jax")
        before = reg.counter("jax_compiles_total").value()
        jax.jit(lambda x: x + 1)(jnp.ones((4,)))  # fresh program compiles
        assert reg.counter("jax_compiles_total").value() > before
    finally:
        cm.uninstall()


def test_compile_monitor_storm_warning(monkeypatch):
    from deepspeed_tpu.telemetry import compile_monitor as cm_mod
    warnings = []
    monkeypatch.setattr(
        cm_mod.logger, "warning",
        lambda msg, *args: warnings.append(msg % args if args else msg))
    reg = MetricsRegistry()
    cm = CompileMonitor(reg, storm_threshold=2)
    f = jax.jit(lambda x: x * 1.5)
    cm.track("stormy", f)
    for n in range(1, 5):
        f(jnp.ones((n,)))
    cm.sample()
    assert any("recompile storm" in w and "stormy" in w for w in warnings)
    warnings.clear()
    cm.sample()  # warned once per program, not per sample
    assert not warnings


def test_track_skips_non_jitted_drivers():
    reg = MetricsRegistry()
    cm = CompileMonitor(reg)
    assert not cm.track("python_driver", lambda s, b: (s, b))


# ---------------------------------------------------------------------------
# memory
# ---------------------------------------------------------------------------

def test_collect_memory_stats_structured():
    from deepspeed_tpu.runtime.utils import (collect_memory_stats,
                                             format_memory_status,
                                             memory_status)
    stats = collect_memory_stats()
    assert isinstance(stats["devices"], list)
    assert "host_rss_bytes" in stats
    if stats["host_rss_bytes"] is not None:
        assert stats["host_rss_bytes"] > 0
    # the log line and the dict share one collection path
    line = format_memory_status(stats, "probe")
    assert line.startswith("MEMORY probe:")
    assert memory_status("probe").startswith("MEMORY probe:")


def test_memory_sampler_sets_gauges():
    from deepspeed_tpu.telemetry.memory import MemorySampler
    reg = MetricsRegistry()
    ms = MemorySampler(reg)
    stats = ms.sample()
    if stats["host_rss_bytes"] is not None:
        assert reg.gauge("host_rss_bytes").value() == \
            stats["host_rss_bytes"]
    # CPU test meshes expose no allocator stats; devices list may be
    # empty, but the call must never throw or sync


# ---------------------------------------------------------------------------
# summarize CLI
# ---------------------------------------------------------------------------

def test_summarize_cli(tmp_path, capsys):
    path = tmp_path / "events.jsonl"
    with open(path, "w") as f:
        for i in range(6):
            f.write(json.dumps({"kind": "step", "ts": i, "step": i + 1,
                                "dispatch_s": 0.001}) + "\n")
        f.write(json.dumps({"kind": "sync", "ts": 6, "step": 3,
                            "interval_s": 0.6, "steps": 3,
                            "step_avg_s": 0.2,
                            "samples_per_sec": 160.0}) + "\n")
        f.write(json.dumps({"kind": "sync", "ts": 9, "step": 6,
                            "interval_s": 1.2, "steps": 3,
                            "step_avg_s": 0.4,
                            "samples_per_sec": 80.0}) + "\n")
        f.write(json.dumps({"kind": "memory", "ts": 9, "step": 6,
                            "stats": {"devices": [
                                {"id": 0, "peak_bytes_in_use": 2 ** 30}],
                                "host_rss_bytes": 2 ** 28}}) + "\n")
        f.write("not json\n")
    rep = summarize(str(path))
    assert rep["steps"] == 6
    assert rep["step_time_source"] == "synced intervals"
    assert abs(rep["p50_s"] - 0.3) < 1e-9     # [.2 x3, .4 x3] weighted
    assert rep["samples_per_sec"] == pytest.approx(120.0)
    assert rep["peak_hbm_bytes"] == 2 ** 30
    assert rep["bad_lines"] == 1

    from deepspeed_tpu.telemetry.cli import main
    assert main(["summarize", str(path)]) == 0
    out = capsys.readouterr().out
    assert "p50" in out and "peak HBM" in out
    assert main(["summarize", str(tmp_path / "missing.jsonl")]) == 2


def test_summarize_dispatch_only_is_labelled(tmp_path):
    path = tmp_path / "events.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "step", "step": 1,
                            "dispatch_s": 0.001}) + "\n")
    rep = summarize(str(path))
    assert "DISPATCH-ONLY" in rep["step_time_source"]


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

HIDDEN = 16


def _make_engine(tmp_path, telemetry: bool, steps_per_print=10 ** 9):
    import deepspeed_tpu
    cfg = base_config(micro_bs=2, grad_acc=1, stage=0)
    cfg["steps_per_print"] = steps_per_print
    if telemetry:
        cfg["telemetry"] = {"enabled": True, "output_path": str(tmp_path)}
    eng, *_ = deepspeed_tpu.initialize(model=SimpleModel(hidden_dim=HIDDEN),
                                       config=cfg)
    return eng


def _batch(eng, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((int(eng.train_batch_size),
                             HIDDEN)).astype(np.float32)
    return (x, 0.5 * x)


@pytest.fixture(scope="module")
def engine_pair(tmp_path_factory):
    tel_dir = tmp_path_factory.mktemp("telemetry_out")
    eng_off = _make_engine(tel_dir / "unused", telemetry=False)
    eng_on = _make_engine(tel_dir, telemetry=True)
    # warm up: compile both step programs outside the counted window
    for eng in (eng_off, eng_on):
        eng.train_batch(_batch(eng))
        eng.train_batch(_batch(eng, seed=1))
    yield eng_off, eng_on, tel_dir
    eng_on.close()
    eng_off.close()


class _SyncCounter:
    """Counts device-draining calls: jax.block_until_ready,
    jax.device_get, jax.effects_barrier, and np.asarray on jax Arrays
    (materialization).  Installed around a window of train_batch calls."""

    def __init__(self, monkeypatch):
        self.count = 0
        real_bur = jax.block_until_ready
        real_dg = jax.device_get
        real_eb = jax.effects_barrier
        real_asarray = np.asarray

        def wrap(real):
            def inner(*a, **k):
                self.count += 1
                return real(*a, **k)
            return inner

        def asarray(obj, *a, **k):
            if isinstance(obj, jax.Array):
                self.count += 1
            return real_asarray(obj, *a, **k)

        monkeypatch.setattr(jax, "block_until_ready", wrap(real_bur))
        monkeypatch.setattr(jax, "device_get", wrap(real_dg))
        monkeypatch.setattr(jax, "effects_barrier", wrap(real_eb))
        monkeypatch.setattr(np, "asarray", asarray)


def test_train_batch_adds_zero_device_syncs(engine_pair, monkeypatch):
    """THE overhead contract: with steps_per_print not yet reached,
    telemetry-enabled steps perform exactly as many device syncs as
    telemetry-disabled ones (zero — spans are host-side stamps that
    close lazily; the drain happens only at the periodic sync)."""
    eng_off, eng_on, _ = engine_pair
    counts = {}
    for name, eng in (("off", eng_off), ("on", eng_on)):
        with pytest.MonkeyPatch.context() as mp:
            sc = _SyncCounter(mp)
            for i in range(4):
                eng.train_batch(_batch(eng, seed=10 + i))
            counts[name] = sc.count
    assert counts["on"] == counts["off"], counts
    assert counts["on"] == 0, (
        "train_batch itself must not sync between steps_per_print "
        f"boundaries; counted {counts['on']}")


def test_engine_trace_prom_and_events(engine_pair):
    """Runs AFTER the zero-sync test (same module-scoped engines):
    trigger the periodic sync, close, and validate every artifact."""
    _, eng_on, tel_dir = engine_pair
    # steps_per_print is read per call — flip it so the boundary fires
    eng_on.config.steps_per_print = 1
    eng_on.train_batch(_batch(eng_on, seed=99))
    eng_on.train_batch(_batch(eng_on, seed=100))
    eng_on.close()
    eng_on.close()  # idempotent

    # Chrome trace-event JSON: json.loads-able, ph/ts/name on every event
    doc = json.loads(open(os.path.join(tel_dir, "trace.json")).read())
    evs = doc["traceEvents"]
    assert evs
    for e in evs:
        assert "ph" in e and "ts" in e and "name" in e, e
    names = {e["name"] for e in evs}
    assert "train/dispatch" in names
    assert "train/shard_batch" in names
    assert "train/steps_interval" in names   # lazy close at the sync

    # prometheus scrape file parses line-by-line
    for line in open(os.path.join(tel_dir, "metrics.prom")):
        line = line.strip()
        if line:
            assert _PROM_LINE.match(line), line

    # JSONL stream: step + sync + metrics records, summarize runs
    kinds = set()
    with open(os.path.join(tel_dir, "events.jsonl")) as f:
        for raw in f:
            kinds.add(json.loads(raw)["kind"])
    assert {"step", "sync", "metrics"} <= kinds
    rep = summarize(os.path.join(tel_dir, "events.jsonl"))
    assert rep["steps"] >= 8
    assert rep["p50_s"] is not None


def test_engine_tracks_train_step_program(engine_pair):
    _, eng_on, _ = engine_pair
    assert "train_step" in eng_on.telemetry.compile_monitor \
        .tracked_programs()


def test_telemetry_config_block_defaults_and_validation():
    from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1}, 1)
    assert not cfg.telemetry_config.enabled
    assert cfg.telemetry_config.trace
    assert cfg.telemetry_config.compile_events
    assert cfg.telemetry_config.memory
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "telemetry": {"enabled": True,
                                       "recompile_storm_threshold": 0}}, 1)
    with pytest.raises(DeepSpeedConfigError):
        # bool is an int subclass; it must not slip through as 1
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "telemetry": {"enabled": True,
                                       "recompile_storm_threshold": True}},
                        1)


def test_prometheus_hostile_label_values_escaped():
    """Satellite: label values containing backslash, double-quote, and
    newline must escape per the exposition format (and still parse
    line-by-line — a newline smuggled into a label would tear the
    format)."""
    reg = MetricsRegistry()
    hostile = 'pa\\th"quoted"\nline2'
    reg.counter("hostile_total", "h").inc(1, label=hostile)
    text = prometheus_text(reg)
    lines = text.strip().splitlines()
    for line in lines:
        assert _PROM_LINE.match(line), f"unparseable line: {line!r}"
    sample = next(l for l in lines if l.startswith("hostile_total{"))
    assert '\\\\' in sample          # backslash doubled
    assert '\\"' in sample           # quote escaped
    assert '\\n' in sample and "\n" not in sample  # newline literalized


def test_prometheus_help_fallback_and_escaping():
    """Satellite: every metric emits a # HELP line — gauges/summaries
    registered without help text fall back to their name, and help text
    with newlines/backslashes is escaped (one line per record)."""
    reg = MetricsRegistry()
    reg.gauge("helpless_gauge").set(1.0)           # no help text
    reg.histogram("helpless_seconds").observe(0.5)  # no help text
    reg.counter("multi_total", "line one\nline two \\ slash").inc()
    text = prometheus_text(reg)
    lines = text.strip().splitlines()
    assert "# HELP helpless_gauge helpless_gauge" in lines
    assert "# HELP helpless_seconds helpless_seconds" in lines
    assert ("# HELP multi_total line one\\nline two \\\\ slash"
            in lines)
    for line in lines:
        assert _PROM_LINE.match(line), f"unparseable line: {line!r}"


def test_summarize_and_diagnose_tolerate_torn_tail(tmp_path, capsys):
    """Satellite: a killed run's truncated final events.jsonl line is
    skipped AND counted — never silently dropped."""
    from deepspeed_tpu.telemetry.cli import diagnose
    path = tmp_path / "events.jsonl"
    with open(path, "w") as f:
        for i in range(4):
            f.write(json.dumps({"kind": "step", "step": i + 1,
                                "dispatch_s": 0.001}) + "\n")
        # the torn tail: a write killed mid-record, no trailing newline
        f.write('{"kind": "sync", "step": 4, "interval_')
    rep = summarize(str(path))
    assert rep["steps"] == 4
    assert rep["bad_lines"] == 1
    out = capsys.readouterr().out
    assert "skipped 1 unparseable" in out
    drep = diagnose(str(tmp_path))
    assert drep["skipped_lines"] == 1
    assert drep["last_step"] == 4
    dout = capsys.readouterr().out
    assert "skipped 1 malformed/torn" in dout


def test_heartbeat_ages_and_summarize_liveness_row(tmp_path, capsys):
    """Satellite: heartbeat staleness is operator-visible — beat_ages
    over real heartbeat fixtures, the heartbeat_age_s gauge path, and
    the summarize liveness row built from a metrics snapshot."""
    from deepspeed_tpu.telemetry.heartbeat import (HeartbeatWriter,
                                                   beat_ages,
                                                   read_heartbeats)
    hb_dir = tmp_path / "hb"
    w0 = HeartbeatWriter(str(hb_dir), process_index=0, host="hostA")
    w1 = HeartbeatWriter(str(hb_dir), process_index=1, host="hostB")
    w0.beat(3)
    w1.beat(3)
    beats = read_heartbeats(str(hb_dir))
    now = beats["hostA/0"]["time"]
    ages = beat_ages(beats, now=now + 7.5)
    assert set(ages) == {"hostA/0", "hostB/1"}
    assert ages["hostA/0"] == pytest.approx(7.5, abs=1.0)
    # clock skew clamps at zero, never negative
    assert beat_ages(beats, now=now - 100)["hostA/0"] == 0.0

    # the gauge lands in the metrics snapshot -> summarize liveness row
    reg = MetricsRegistry()
    g = reg.gauge("heartbeat_age_s", "beat age")
    for key, age in ages.items():
        g.set(age, host=key)
    reg.counter("straggler_detected_total", "s").inc()
    path = tmp_path / "events.jsonl"
    hub_like = json.dumps({"kind": "metrics", "step": 3,
                           "metrics": reg.snapshot()})
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "step", "step": 1,
                            "dispatch_s": 0.001}) + "\n")
        f.write(hub_like + "\n")
    rep = summarize(str(path))
    assert rep["liveness_hosts"] == 2
    assert rep["liveness_max_age_s"] == pytest.approx(
        max(ages.values()), rel=1e-6)
    out = capsys.readouterr().out
    assert "liveness" in out and "2 host(s)" in out


def test_hub_close_idempotent(tmp_path):
    hub = TelemetryHub(str(tmp_path), compile_events=False, memory=False)
    hub.record_step(1, 0.01)
    hub.on_sync(1, interval_s=0.01, steps=1)
    hub.close()
    hub.close()
    hub.on_sync(2)  # post-close: silently ignored
    assert os.path.isfile(tmp_path / "trace.json")
    assert os.path.isfile(tmp_path / "metrics.prom")


def test_summarize_offload_attribution_split(tmp_path, capsys):
    """The H2D-tier attribution scalars (offload_h2d_s /
    offload_cpu_adam_s) get summarize rows like the disk tier's
    read/write split — emitted-but-never-consumed was a jaxlint JL102
    finding."""
    p = tmp_path / "events.jsonl"
    lines = [{"kind": "sync", "step": 10 * (i + 1), "interval_s": 1.0,
              "steps": 10, "step_avg_s": 0.1,
              "scalars": {"offload_overlap_ratio": r,
                          "offload_h2d_s": 0.12,
                          "offload_cpu_adam_s": 0.30}}
             for i, r in enumerate((0.6, 0.8))]
    p.write_text("\n".join(json.dumps(l) for l in lines) + "\n")
    rep = summarize(str(p))
    assert rep["offload_overlap_ratio"] == pytest.approx(0.7)
    assert rep["offload_h2d_s"] == pytest.approx(0.12)
    assert rep["offload_cpu_adam_s"] == pytest.approx(0.30)
    out = capsys.readouterr().out
    assert "offload H2D overlap" in out
    assert "H2D" in out and "Adam" in out
