"""The device's queue in the host's books (docs/observability.md "The
device's queue"; ``telemetry/device_queue.py``, ``ServeEngine.aux_log``,
``.device_seconds``, ``.queue_dry_seconds``, ``.setup_log``) and the
watcher of process freezes (``telemetry/hub.py::StallWatch``).

A CPU run proves the arithmetic, never a time: the engine cases run a
real toy engine against ``_Device``, a stand-in for the chip that runs
what it is sent in order on a clock the test owns (the engine's
``time.perf_counter`` and ``jax.block_until_ready`` stubbed so that each
wait moves that clock to the end of the program waited for).
"""
import json
import sys
import time
import types
from concurrent.futures import wait

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import ServeEngine, engine as engine_mod
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.models.olmoe import OlmoeConfig, OlmoeModel
from deepspeed_tpu.telemetry.device_queue import OUTSIDE, DeviceQueueBook
from deepspeed_tpu.telemetry.hub import Beat, StallWatch, TelemetryHub

DECODE_S, PREFILL_S = 0.020, 0.070
SLOTS = 2
#: keys of a record in the device's queue book
QUEUE_KEYS = {"program", "bucket", "sent_t", "ready_t", "at_once", "ahead",
              "run_s", "dry_s", "dry_phase"}


def _gpt2():
    return GPT2Model(GPT2Config(vocab_size=128, n_positions=64, d_model=32,
                                n_layer=2, n_head=4, remat=None,
                                attn_impl="dense"))


def _olmoe():
    return OlmoeModel(OlmoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=16,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=4, num_experts_per_tok=2, max_position_embeddings=64,
        attn_impl="dense"))


_params = {}


def _engine(model_fn=_gpt2, slots=SLOTS, telemetry=None, **serving):
    model = model_fn()
    if model_fn not in _params:
        _params[model_fn] = model.init(jax.random.PRNGKey(0))
    cfg = {"serving": {"slots": slots, "page_len": 4, "max_seq_len": 32,
                       "prefill_len": 8, "prefix_cache": False, **serving}}
    if telemetry is not None:
        cfg["telemetry"] = {"enabled": True, "output_path": str(telemetry)}
    return ServeEngine(model, cfg, params=_params[model_fn])


def _prompt(i: int, n: int = 3):
    return [int(t) for t in
            np.random.default_rng(100 + i).integers(1, 128, n)]


class _Device:
    """What the chip is to the host: programs run in the order they were
    sent, one at a time, ``DECODE_S`` a decode tick and ``PREFILL_S`` a
    prefill; a wait for an output returns when its program has ended.
    Reading the clock costs nothing, so every number is exact."""

    def __init__(self, eng, monkeypatch):
        self.t, self.free_at = 100.0, 0.0
        self.ends = {}          # id(output) -> (output, its program's end)
        self.waits = []         # ("decode" | "prefill", output) per wait
        monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
            perf_counter=lambda: self.t, sleep=self.sleep))
        real = jax.block_until_ready

        def block_until_ready(x):
            if id(x) in self.ends:
                self.waits.append(("decode" if x.shape else "prefill", x))
                self.t = max(self.t, self.ends[id(x)][1])
            return real(x)

        monkeypatch.setattr(jax, "block_until_ready", block_until_ready)
        eng._ready_now = lambda x: self.ends[id(x)][1] <= self.t
        eng._decode_fn = self._sending(eng._decode_fn, DECODE_S)
        eng._prefill_fn = self._sending(eng._prefill_fn, PREFILL_S)

    def sleep(self, seconds):
        self.t += seconds

    def _sending(self, program, seconds):
        def call(*operands):
            out = program(*operands)
            self.free_at = max(self.t, self.free_at) + seconds
            self.ends[id(out[1])] = (out[1], self.free_at)
            return out
        return call


def _records(eng):
    return [(kind, rec) for _, kind, rec in eng.aux_log]


def _serve_three(eng, dev):
    """Two requests fill the two slots and the engine runs ahead; the
    shorter ends, and the third is admitted behind the tick in flight,
    its prefill taking the last slot: the next tick goes behind it."""
    a = eng.submit(_prompt(0), max_new_tokens=9)
    b = eng.submit(_prompt(1), max_new_tokens=4)
    for _ in range(3):
        eng.step()
    # the shorter has ended, found at the retirement of the tick before
    # the one now in flight, which was sent ahead without it
    assert eng._inflight is not None and len(eng.scheduler.active) == 1
    c = eng.submit(_prompt(2), max_new_tokens=6)
    dev.sleep(0.003)                # the caller's own time, device busy
    eng.run_until_idle()
    return a, b, c


# ---------------------------------------------------------------------------
# the book alone, on a clock the test owns
# ---------------------------------------------------------------------------

class _Clock:
    t = 10.0

    def __call__(self):
        return self.t


def test_book_counts_a_programs_own_seconds_from_consecutive_waits():
    clock = _Clock()
    mirrored = []
    book = DeviceQueueBook(clock, on_device=lambda *a: mirrored.append(a))
    first = book.sent("serve_decode", "sync", "out0")
    clock.t = 10.5
    second = book.sent("serve_prefill", "512", "out1")      # behind it
    assert (first["ahead"], second["ahead"]) == (0, 1)
    assert book.ahead_of(second) == [(first, "out0")]
    clock.t = 11.0
    book.ready(first, at_once=False)
    clock.t = 11.25
    book.ready(second, at_once=False)
    # the first ran from its send, the second from the first's end
    assert first["run_s"] == 1.0 and second["run_s"] == 0.25
    assert book.device_seconds == {("serve_decode", "sync"): 1.0,
                                   ("serve_prefill", "512"): 0.25}
    assert mirrored == [("serve_decode", "sync", 1.0),
                        ("serve_prefill", "512", 0.25)]
    assert not book.pending and book.last_ready_t == 11.25


def test_book_leaves_out_a_wait_that_returned_at_once():
    clock = _Clock()
    book = DeviceQueueBook(clock)
    rec = book.sent("serve_decode", "ahead", None)
    clock.t = 12.0          # the host was busy; the program ended unseen
    book.ready(rec, at_once=True)
    assert rec["run_s"] == 2.0 and rec["at_once"] is True
    assert book.device_seconds == {}


def test_book_gives_dry_seconds_to_the_phase_at_the_middle():
    from deepspeed_tpu.telemetry.device_queue import Phase

    class _Span:
        def end(self):
            pass

    clock = _Clock()
    mirrored = []
    book = DeviceQueueBook(clock, on_dry=lambda *a: mirrored.append(a))
    rec = book.sent("serve_decode", "sync", None)
    assert rec["dry_s"] == 0.0      # nothing known before the first send
    with Phase(book, "tick", _Span()):
        with Phase(book, "token_pull", _Span()):
            clock.t = 11.0
            book.ready(rec, at_once=False)
        with Phase(book, "emit", _Span()):      # 11.0 .. 11.1
            clock.t = 11.1
        assert book.phase == "tick"
    assert book.phase == OUTSIDE                # 11.1 .. 11.9: the caller
    clock.t = 11.9
    with Phase(book, "tick", _Span()), Phase(book, "admit", _Span()):
        clock.t = 12.0
        nxt = book.sent("serve_prefill", "8", None)
    # dry 11.0 .. 12.0; at 11.5 no span was open
    assert nxt["dry_s"] == 1.0 and nxt["dry_phase"] == OUTSIDE
    assert book.dry_seconds == {OUTSIDE: 1.0} == dict(mirrored)
    clock.t = 12.5
    queued = book.sent("serve_decode", "behind", None)
    assert queued["ahead"] == 1 and queued["dry_s"] == 0.0


# ---------------------------------------------------------------------------
# the engine's records
# ---------------------------------------------------------------------------

@pytest.fixture
def served(monkeypatch):
    eng = _engine()
    dev = _Device(eng, monkeypatch)
    reqs = _serve_three(eng, dev)
    yield eng, dev, reqs
    eng.close()


def test_records_hold_every_program_in_the_order_the_device_ran_it(served):
    eng, dev, _ = served
    recs = _records(eng)
    assert all(set(rec) == QUEUE_KEYS for _, rec in recs)
    assert {kind for kind, _ in recs} == {"prefill", "decode"}
    assert {rec["program"] for kind, rec in recs if kind == "decode"} \
        == {"serve_decode"}
    assert {(rec["program"], rec["bucket"]) for kind, rec in recs
            if kind == "prefill"} == {("serve_prefill", "8")}
    # one record a call, filed at retirement; by send they are in the
    # device's order, and so are the ends the host saw
    by_send = sorted((rec for _, rec in recs), key=lambda r: r["sent_t"])
    assert len(by_send) == sum(eng.prefill_calls.values()) \
        + sum(eng.ahead_stats[arm] for arm in ("ahead", "behind", "sync"))
    before = None
    for rec in by_send:
        start = rec["sent_t"] if before is None \
            else max(rec["sent_t"], before)
        assert rec["ready_t"] >= start
        assert rec["run_s"] == pytest.approx(rec["ready_t"] - start)
        before = rec["ready_t"]
    # the arms, by what was unretired at the send
    arms = {rec["bucket"]: rec["ahead"] for kind, rec in recs
            if kind == "decode"}
    assert arms == {"sync": 0, "ahead": 1, "behind": 2}
    counted = {arm: sum(1 for k, r in recs if r["bucket"] == arm)
               for arm in ("ahead", "behind", "sync")}
    assert counted == {arm: eng.ahead_stats[arm] for arm in counted}
    assert counted["behind"] == 1 and counted["ahead"] >= 3
    # the prefill behind the tick in flight was filed BEFORE that tick
    # (the tick is retired where it always was) and ended after it
    i = next(i for i, (k, r) in enumerate(recs)
             if k == "prefill" and r["ahead"] == 1)
    behind_tick = recs[i + 1][1]
    assert recs[i + 1][0] == "decode" and behind_tick["bucket"] == "ahead"
    assert behind_tick["sent_t"] < recs[i][1]["sent_t"]
    assert behind_tick["ready_t"] < recs[i][1]["ready_t"]


def test_device_seconds_are_the_programs_own_times(served):
    eng, dev, _ = served
    recs = [rec for _, rec in _records(eng)]
    # nothing finished unseen, so every program ran exactly its time
    assert not any(rec["at_once"] for rec in recs)
    for rec in recs:
        own = PREFILL_S if rec["program"] == "serve_prefill" else DECODE_S
        assert rec["run_s"] == pytest.approx(own)
    prefills = sum(eng.prefill_calls.values())
    assert eng.device_seconds[("serve_prefill", "8")] \
        == pytest.approx(prefills * PREFILL_S)
    for arm in ("ahead", "behind", "sync"):
        assert eng.device_seconds[("serve_decode", arm)] \
            == pytest.approx(eng.ahead_stats[arm] * DECODE_S)
    assert eng.device_seconds is eng.book.device_seconds


def test_ttft_is_queue_wait_plus_prefill_wait_plus_prefill(served):
    eng, dev, (a, b, c) = served
    for req in (a, b, c):
        queue_wait = req.admit_t - req.submit_t
        assert queue_wait + req.prefill_wait_s + req.prefill_s \
            == pytest.approx(req.token_times[0], abs=1e-12)
        # the prefill's own interval, whatever it was queued behind
        assert req.prefill_s == pytest.approx(PREFILL_S)
    # nothing was in flight when the first two were admitted
    assert a.prefill_wait_s == pytest.approx(0.0)
    # the third was admitted 3 ms of the caller's time into a tick in
    # flight: it waited the rest of that tick, then its own prefill
    assert c.prefill_wait_s == pytest.approx(DECODE_S - 0.003)
    assert c.token_times[0] == pytest.approx(
        (c.admit_t - c.submit_t) + DECODE_S - 0.003 + PREFILL_S)


def test_no_wait_is_added_but_one_an_admission_behind_a_tick(served):
    eng, dev, _ = served
    ticks = sum(eng.ahead_stats[arm] for arm in ("ahead", "behind", "sync"))
    prefills = sum(eng.prefill_calls.values())
    kinds = [kind for kind, _ in dev.waits]
    # a wait a program, and one more for the admission behind the tick in
    # flight: on that tick's token array, before the prefill's own
    assert kinds.count("prefill") == prefills == 3
    assert kinds.count("decode") == ticks + 1
    i = max(i for i, kind in enumerate(kinds) if kind == "prefill")
    assert kinds[i - 1] == "decode"
    tick_tokens = dev.waits[i - 1][1]
    # the same array is waited for again where the tick is retired
    later = [x for kind, x in dev.waits[i + 1:] if kind == "decode"]
    assert later[0] is tick_tokens
    assert sum(1 for _, x in dev.waits if x is tick_tokens) == 2
    others = {id(x) for kind, x in dev.waits
              if kind == "decode" and x is not tick_tokens}
    assert len(others) == ticks - 1     # every other tick: one wait


def test_queue_is_never_dry_while_every_slot_is_taken(served):
    eng, dev, _ = served
    full = [rec for _, rec in _records(eng)
            if rec["bucket"] in ("ahead", "behind")]
    assert full and all(rec["dry_s"] == 0.0 for rec in full)
    # the prefill behind the tick in flight found the queue busy too
    assert [rec["dry_s"] for _, rec in _records(eng)
            if rec["program"] == "serve_prefill" and rec["ahead"]] == [0.0]


def test_dry_seconds_go_to_the_callers_time_between_two_steps(monkeypatch):
    eng = _engine(slots=4)          # a slot stays free: synchronous ticks
    dev = _Device(eng, monkeypatch)
    try:
        eng.submit(_prompt(0), max_new_tokens=8)
        eng.step()
        eng.step()
        before = dict(eng.queue_dry_seconds)
        dev.sleep(0.5)              # the caller, between two steps
        eng.step()
        rec = eng.aux_log[-1][2]
        assert rec["bucket"] == "sync" and rec["ahead"] == 0
        assert rec["dry_s"] == pytest.approx(0.5)
        assert rec["dry_phase"] == OUTSIDE
        assert eng.queue_dry_seconds[OUTSIDE] \
            == pytest.approx(before.get(OUTSIDE, 0.0) + 0.5)
        assert eng.queue_dry_seconds is eng.book.dry_seconds
    finally:
        eng.close()


def _run_on_device(monkeypatch, telemetry):
    with monkeypatch.context() as patch:
        eng = _engine(telemetry=telemetry)
        dev = _Device(eng, patch)
        reqs = _serve_three(eng, dev)
        out = {"records": _records(eng),
               "device_seconds": dict(eng.device_seconds),
               "dry_seconds": dict(eng.queue_dry_seconds),
               "requests": [(r.prefill_s, r.prefill_wait_s) for r in reqs]}
        if telemetry is not None:
            reg = eng.telemetry.registry
            out["device_ctr"] = {
                (dict(k)["program"], dict(k)["bucket"]): v for k, v in
                reg.counter("serve_device_seconds_total").series()}
            out["dry_ctr"] = {
                dict(k)["phase"]: v for k, v in
                reg.counter("serve_queue_dry_seconds_total").series()}
        eng.close()
    return out


def test_telemetry_on_and_off_keep_the_same_books(monkeypatch, tmp_path):
    off = _run_on_device(monkeypatch, None)
    on = _run_on_device(monkeypatch, tmp_path)
    assert on["records"] == off["records"]
    assert on["requests"] == off["requests"]
    assert on["device_seconds"] == off["device_seconds"]
    # the registry mirrors the plain attributes
    assert on["device_ctr"] == pytest.approx(on["device_seconds"])
    assert on["dry_ctr"] == pytest.approx(on["dry_seconds"])
    with open(tmp_path / "events.jsonl") as f:
        done = [json.loads(line) for line in f
                if '"serve_request"' in line]
    assert len(done) == 3
    for rec in done:
        assert rec["queue_wait_s"] + rec["prefill_wait_s"] \
            + rec["prefill_s"] == pytest.approx(rec["ttft_s"])


def test_aux_log_keeps_a_serving_aux_models_counters_by_name():
    """What the benchmark's family modules index: 3-tuples, ``kind`` of
    ``decode`` / ``prefill``, the model's counters by name in ``vals``
    (beside the queue's)."""
    eng = _engine(_olmoe)
    try:
        eng.submit(_prompt(0, 5), max_new_tokens=4)
        eng.run_until_idle()
        log0 = len(eng.aux_log)
        eng.submit(_prompt(1, 5), max_new_tokens=3)
        eng.run_until_idle()
        calls = list(eng.aux_log)[log0:]
        assert [kind for _, kind, _ in calls] == ["prefill"] + ["decode"] * 2
        for t, kind, vals in calls:
            assert vals["ready_t"] <= t
            assert set(vals) == QUEUE_KEYS | set(eng.model.serving_aux)
            assert vals["moe_rows"] > 0 and vals["moe_experts_hit"] > 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# set-up by phase
# ---------------------------------------------------------------------------

def test_setup_log_stamps_construction_the_ladder_and_first_calls(tmp_path):
    model = GPT2Model(GPT2Config(vocab_size=128, n_positions=1100,
                                 d_model=32, n_layer=1, n_head=4,
                                 remat=None, attn_impl="dense"))
    eng = ServeEngine(model, {
        "serving": {"slots": 2, "page_len": 64, "max_seq_len": 1100,
                    "prefill_len": 1024, "prefix_cache": False},
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    try:
        eng.submit(_prompt(0), max_new_tokens=3)
        eng.submit(_prompt(1, 600), max_new_tokens=2)
        eng.run_until_idle()
        # the quarter is built last and no call waited for it
        wait(eng._prefill_build.values())
        phases = [phase for phase, _, _ in eng.setup_log]
        assert phases[:3] == ["params", "cache", "feed"]
        for want in ("lower:512", "compile:512", "lower:1024",
                     "compile:1024", "first_call:serve_prefill:512",
                     "first_call:serve_prefill:1024",
                     "first_call:serve_decode"):
            assert phases.count(want) == 1, want
        assert phases.index("lower:512") < phases.index("compile:512") \
            < phases.index("lower:1024") < phases.index("compile:1024") \
            < phases.index("lower:256") < phases.index("compile:256")
        assert "rungs_wait:256" not in phases
        assert all(seconds >= 0 and started > 0
                   for _, started, seconds in eng.setup_log)
        gauge = eng.telemetry.registry.gauge("serve_setup_seconds")
        assert {dict(k)["phase"]: v for k, v in gauge.series()} \
            == {phase: seconds for phase, _, seconds in eng.setup_log}
    finally:
        eng.close()
    with open(tmp_path / "trace.json") as f:
        names = [e["name"] for e in json.load(f)["traceEvents"]]
    assert names.count("serve/setup_lower") == 3
    assert names.count("serve/setup_first_call") == 3
    assert {"serve/setup_params", "serve/setup_cache", "serve/setup_compile",
            "serve/prefill_wait", "serve/prefill_run"} <= set(names)


def test_setup_compiles_the_page_copy_a_prefix_hit_can_bring():
    """With the prefix cache on, construction runs ``serve_copy_page`` once
    (phase ``copy_page``): the first copy-on-write of a timed window finds
    it compiled.  Without the cache nothing copies a page and nothing is
    compiled for it."""
    model = GPT2Model(GPT2Config(vocab_size=128, n_positions=256,
                                 d_model=32, n_layer=1, n_head=4,
                                 remat=None, attn_impl="dense"))
    for prefix_cache, want in ((True, 1), (False, 0)):
        eng = ServeEngine(model, {"serving": {
            "slots": 2, "page_len": 16, "max_seq_len": 256,
            "prefill_len": 64, "prefix_cache": prefix_cache}})
        try:
            phases = [phase for phase, _, _ in eng.setup_log]
            assert phases.count("copy_page") == want
            assert eng._copy_fn._cache_size() == want
            eng._copy_page(0, 0)
            assert eng._copy_fn._cache_size() == 1
        finally:
            eng.close()


def test_a_refused_configuration_leaves_no_hub_open(tmp_path):
    with pytest.raises(ValueError, match="exceeds"):
        ServeEngine(_gpt2(), {
            "serving": {"slots": 2, "max_seq_len": 4096},
            "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    with open(tmp_path / "events.jsonl") as f:      # closed: flushed
        assert '"metrics"' in f.read()


# ---------------------------------------------------------------------------
# a freeze of the process
# ---------------------------------------------------------------------------

def _sample(t, cpu, gens=(0, 0, 0), switches=0, faults=0, compiles=0.0):
    return Beat(t, cpu, list(gens), switches, faults, compiles)


@pytest.mark.parametrize("cause, now", [
    ("compile", _sample(1.4, 0.4, compiles=1.0, gens=(3, 1, 1))),
    ("gc", _sample(1.4, 0.4, gens=(9, 2, 1), faults=2)),
    ("paging", _sample(1.4, 0.01, faults=2)),
    ("busy", _sample(1.4, 0.39, gens=(5, 0, 0))),
    ("descheduled", _sample(1.4, 0.01, switches=7)),
])
def test_stall_watch_sorts_a_silence_by_cause(tmp_path, cause, now):
    hub = TelemetryHub(str(tmp_path), compile_events=False, memory=False)
    watch = StallWatch(hub, phase_fn=lambda: "emit")
    try:
        watch._record(_sample(1.0, 0.0), now)
        ctr = hub.registry.counter("process_stalls_total")
        assert ctr.value(cause=cause) == 1
        (span,) = [e for e in hub.tracer.events()
                   if e["name"] == "process_stall"]
        assert span["dur"] == pytest.approx(0.4e6)
        assert span["args"]["cause"] == cause
        assert span["args"]["phase"] == "emit"
    finally:
        hub.close()
    with open(tmp_path / "events.jsonl") as f:
        (event,) = [json.loads(line) for line in f
                    if '"process_stall"' in line]
    assert event["cause"] == cause and event["wall_s"] == pytest.approx(0.4)
    assert event["involuntary_switches"] == now.switches
    assert event["major_faults"] == now.faults
    assert event["gc_collections"] == now.collections
    assert event["compiles"] == int(now.compiles)


def test_stall_watch_sees_the_interpreter_lock_held(tmp_path):
    """A thread that keeps the interpreter lock for 0.3 s silences the
    watcher: one ``process_stall``, CPU burnt through it.  How much CPU is
    the host's to give: alone the spin burns all 0.3 s, under six workers
    of the suite it read 0.176 s.  So the stall is held to its wall time,
    its cause and its phase, and its CPU to the share that MAKES the cause
    ``busy`` (half the wall time; less is ``descheduled``)."""
    hub = TelemetryHub(str(tmp_path), compile_events=False, memory=False)
    hub.watch_stalls(lambda: "decode_prep")
    hub.watch_stalls()                      # once: no second thread
    interval = sys.getswitchinterval()
    try:
        time.sleep(0.1)                     # the watcher is beating
        sys.setswitchinterval(5.0)          # nobody takes the lock from us
        end = time.perf_counter() + 0.3
        while time.perf_counter() < end:
            pass
    finally:
        sys.setswitchinterval(interval)
    time.sleep(0.1)                         # the beat comes back
    hub.close()
    assert not hub._stall_watch._thread.is_alive()
    with open(tmp_path / "events.jsonl") as f:
        stalls = [json.loads(line) for line in f
                  if '"process_stall"' in line]
    assert len(stalls) == 1
    stall = stalls[0]
    assert stall["wall_s"] > 0.25 and stall["cause"] == "busy"
    assert stall["cpu_s"] >= stall["wall_s"] / 2
    assert stall["phase"] == "decode_prep"
    assert hub.registry.counter("process_stalls_total").value(
        cause="busy") == 1


def test_stall_watch_runs_with_telemetry_on_only(tmp_path):
    import threading

    def watchers():
        return [t for t in threading.enumerate()
                if t.name == "telemetry_stall_watch"]

    before = len(watchers())
    off = _engine()
    assert len(watchers()) == before
    on = _engine(telemetry=tmp_path)
    assert len(watchers()) == before + 1
    on.close()
    off.close()
    assert len(watchers()) == before
