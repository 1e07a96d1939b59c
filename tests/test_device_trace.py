"""The program's reader of a device trace (``telemetry/device_trace.py``,
``python -m deepspeed_tpu.telemetry device``) and the no-chip half of it,
``utils/hlo.py::scopes``.

The reduction is pure Python over a plain event list, so its arithmetic is
held here on hand-written lists: a CPU run proves sums, never a time.  What
a chip's capture holds (the ``op_name`` in the event's metadata) is in the
module's docstring, read from the chip by PR 54; the capture taken here, on
the CPU, holds no device plane, and is read for its host annotations alone.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.telemetry import cli, device_trace as dt
from deepspeed_tpu.telemetry.device_trace import (OUTSIDE, SHORT_GAPS,
                                                  Reduction)
from deepspeed_tpu.utils import hlo

US = 1_000


def _op(label, start_us, dur_us, scope=None):
    return [label, start_us * US, dur_us * US, scope]


def _planes():
    """Two chips, 1,000 us: chip 0 runs a decode tick (a ``while`` around
    its body: a fusion of ``layer/attn/absorb``, a ``ds_`` kernel, an
    unscoped copy) and a prefill (one fusion, one collective), chip 1 the
    tick alone, shifted.  The host runs a tick's spans and a window."""
    tick0 = [
        _op("while.3 while", 100, 300, "layer"),
        _op("fusion.7 fusion", 110, 90, "layer/attn/absorb"),
        _op("ds_latent_decode_attn.2 custom-call:tpu_custom_call", 200, 100,
            "layer/attn/ds_latent_decode_attn"),
        _op("copy.9 copy", 310, 50),
        _op("fusion.8 fusion", 420, 80, "lm_head"),
    ]
    prefill0 = [
        _op("fusion.1 fusion", 600, 150, "layer/moe"),
        _op("all-reduce.4 all-reduce", 750, 50, "layer/moe"),
    ]
    tick1 = [_op(label, s // US + 20, d // US, scope)
             for label, s, d, scope in tick0]
    host = [
        ["bench/traced_window", 0, 1000 * US],
        ["serve/tick", 50 * US, 480 * US],
        ["serve/decode_step", 60 * US, 300 * US],
        ["serve/decode_dispatch", 70 * US, 20 * US],
        ["serve/token_pull", 400 * US, 100 * US],
        ["serve/tick", 550 * US, 300 * US],
        ["bench/step", 40 * US, 500 * US],      # no span of the program's
    ]
    return {
        "/device:TPU:0": {
            "XLA Ops": tick0 + prefill0,
            "XLA Modules": [["jit_serve_decode(11)", 100 * US, 400 * US],
                            ["jit_serve_prefill(22)", 600 * US, 200 * US]]},
        "/device:TPU:1": {
            "XLA Ops": tick1,
            "XLA Modules": [["jit_serve_decode(11)", 120 * US, 400 * US]]},
        "/host:CPU": {"python": host},
    }


@pytest.fixture
def red():
    return Reduction(_planes(), window="bench/traced_window", depth=2)


def test_self_time_is_duration_less_what_the_nested_events_cover():
    events = _planes()["/device:TPU:0"]["XLA Ops"]
    selfs = dict(zip((e[0] for e in events), dt.self_times(events)))
    # the while holds 90 + 100 + 50 us of its 300
    assert selfs["while.3 while"] == 60 * US
    assert selfs["fusion.7 fusion"] == 90 * US
    assert selfs["copy.9 copy"] == 50 * US
    assert selfs["all-reduce.4 all-reduce"] == 50 * US


def test_busy_time_and_idle_share_are_the_benchmarks(red):
    # chip 0: 300 + 80 + 200 us, chip 1: 300 + 80
    assert red.busy_ns == {"/device:TPU:0": 580 * US,
                           "/device:TPU:1": 380 * US}
    assert red.busy_s == pytest.approx(480e-6)
    assert red.idle_share_pct == pytest.approx(52.0)
    assert red.window_s == pytest.approx(1e-3)


@pytest.mark.parametrize("by", ["scope", "op", "instruction"])
def test_every_table_sums_to_busy_time(red, by):
    assert sum(red.device_scope_seconds(by).values()) \
        == pytest.approx(red.busy_s)


def test_device_seconds_by_program_scope_and_kind(red):
    table = red.device_scope_seconds("scope")
    # mean over two chips: the tick runs on both, the prefill on one
    assert table[("serve_decode", "layer/attn",
                  "kernel:ds_latent_decode_attn")] == pytest.approx(100e-6)
    assert table[("serve_decode", "layer/attn", "xla")] \
        == pytest.approx(90e-6)
    assert table[("serve_decode", "layer", "xla")] == pytest.approx(60e-6)
    assert table[("serve_decode", "(unscoped)", "copy")] \
        == pytest.approx(50e-6)
    assert table[("serve_decode", "lm_head", "xla")] == pytest.approx(80e-6)
    assert table[("serve_prefill", "layer/moe", "xla")] \
        == pytest.approx(75e-6)
    assert table[("serve_prefill", "layer/moe", "collective")] \
        == pytest.approx(25e-6)
    assert len(table) == 7


def test_scopes_are_cut_to_the_depth_asked_for(red):
    deep = red.device_scope_seconds("scope", depth=3)
    assert ("serve_decode", "layer/attn/absorb", "xla") in deep
    flat = red.device_scope_seconds("scope", depth=1)
    assert flat[("serve_decode", "layer", "xla")] == pytest.approx(150e-6)


def test_the_ledgers_operation_families_are_split_by_scope(red):
    table = red.device_scope_seconds("op")
    assert table[("fusion fusion", "layer/attn")] == pytest.approx(90e-6)
    assert table[("fusion fusion", "lm_head")] == pytest.approx(80e-6)
    assert table[("fusion fusion", "layer/moe")] == pytest.approx(75e-6)
    assert table[("copy copy", "(unscoped)")] == pytest.approx(50e-6)


def test_unscoped_time_is_listed_by_instruction(red):
    assert red.unscoped() == [
        ["serve_decode", "copy.9 copy", "(unscoped)",
         pytest.approx(50e-6)]]


def test_runs_of_a_program_give_milliseconds_a_run(red):
    assert red.program_runs() == {"serve_decode": 1, "serve_prefill": 1}
    rows = {tuple(r["key"]): r for r in red.summary()["device_scope_seconds"]}
    row = rows[("serve_prefill", "layer/moe", "xla")]
    assert row["ms_a_run"] == pytest.approx(0.075)
    assert row["busy_pct"] == pytest.approx(100 * 75 / 480)
    assert red.summary()["kernel_share_pct"] == pytest.approx(100 * 100 / 480)


def test_an_idle_gap_is_split_over_the_spans_it_overlaps(red):
    idle = red.device_idle_seconds()
    # chip 0 is idle 0-100, 400-420, 500-600, 800-1000 us
    # 0-100: 50 us before any span, 10 of serve/tick, 10 of decode_step,
    # 20 of decode_dispatch, 10 of decode_step again
    assert idle["serve/decode_dispatch"]["seconds"] == pytest.approx(20e-6)
    assert idle["serve/decode_step"]["seconds"] == pytest.approx(20e-6)
    assert idle["serve/decode_step"]["gaps"] == 2
    # 400-420 and 500-530 lie in serve/token_pull (innermost), 530-550
    # outside every span, 550-600 and 800-850 in the second serve/tick
    assert idle["serve/token_pull"]["seconds"] == pytest.approx(20e-6)
    assert idle["serve/tick"]["seconds"] == pytest.approx(
        (10 + 30 + 50 + 50) * 1e-6)
    assert idle["serve/tick"]["longest_s"] == pytest.approx(50e-6)
    # bench/step is the caller's: no span of the program's
    assert idle[OUTSIDE]["seconds"] == pytest.approx((50 + 20 + 150) * 1e-6)
    assert idle[OUTSIDE]["longest_s"] == pytest.approx(150e-6)
    assert sum(v["seconds"] for v in idle.values()) == pytest.approx(
        (1000 - 580) * 1e-6)


def test_gaps_under_20_us_are_counted_together():
    planes = _planes()
    planes["/device:TPU:0"]["XLA Ops"].append(_op("fusion.9 fusion", 515, 80))
    idle = Reduction(planes, window="bench/traced_window"
                     ).device_idle_seconds()
    # 500-515 is now a gap of 15 us, 595-600 one of 5 us
    assert idle[SHORT_GAPS] == {"seconds": pytest.approx(20e-6), "gaps": 2,
                                "longest_s": pytest.approx(15e-6)}


def test_the_whole_capture_is_the_window_where_none_is_named():
    red = Reduction(_planes())
    assert (red.t0, red.t1) == (100 * US, 800 * US)
    assert red.busy_ns["/device:TPU:0"] == 580 * US


def test_a_window_the_capture_lacks_is_an_error():
    with pytest.raises(ValueError, match="no bench/other"):
        Reduction(_planes(), window="bench/other")


def test_dispatch_lag_of_a_list_built_with_a_known_offset():
    """Each tick is sent 3 ms before its run starts on a device clock that
    reads 1.5 ms early: the lag reads 1.5 ms, every run paired with its
    own dispatch though one run starts BEFORE the next tick's dispatch."""
    sent = [i * 20_000 * US for i in range(1, 41)]
    runs = [t + 3_000 * US - 1_500 * US for t in sent]
    runs[7] += 400 * US
    planes = {
        "/device:TPU:0": {
            "XLA Ops": [_op("fusion.1 fusion", r // US, 10_000) for r in runs],
            "XLA Modules": [["jit_serve_decode(5)", r, 10_000 * US]
                            for r in runs]},
        "/host:CPU": {"python": [["serve/decode_dispatch", t, 500 * US]
                                 for t in sent]}}
    lag = Reduction(planes).dispatch_lag()["serve/decode_dispatch"]
    # the first dispatch lies before the capture's first device event
    assert lag["n"] == 39
    assert lag["min_ms"] == pytest.approx(1.5)
    assert lag["median_ms"] == pytest.approx(1.5)
    assert lag["p99_ms"] == pytest.approx(1.5 + 0.4 * 0.62, abs=0.01)


def test_queue_dry_seconds_are_cut_to_the_window():
    records = [{"sent_t": 10.0, "dry_s": 0.5, "dry_phase": "admit"},
               {"sent_t": 11.0, "dry_s": 0.0, "dry_phase": ""},
               {"sent_t": 12.2, "dry_s": 0.4, "dry_phase": "outside_step"},
               {"sent_t": 20.0, "dry_s": 1.0, "dry_phase": "admit"}]
    assert dt.queue_dry_seconds(records, 9.8, 12.0) == {
        "admit": pytest.approx(0.2), "outside_step": pytest.approx(0.2)}


# -- the file's side: what an XSpace's metadata table holds -----------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def test_op_names_are_read_from_the_planes_event_metadata():
    """A hand-built XSpace: one device plane whose ``event_metadata`` names
    two operations of two programs, one without an ``op_name``."""
    stat_names = _field(5, _field(1, 26) + _field(2, _field(1, 26)
                                                  + _field(2, b"tf_op"))) \
        + _field(5, _field(1, 35) + _field(2, _field(1, 35)
                                           + _field(2, b"program_id")))

    def event(ident, name, op_name, program):
        stats = _field(5, _field(1, 35) + _field(3, program))
        if op_name:
            stats += _field(5, _field(1, 26) + _field(5, op_name))
        return _field(4, _field(1, ident) + _field(
            2, _field(1, ident) + _field(2, name) + stats))

    plane = _field(2, b"/device:TPU:0") + stat_names \
        + event(1, b"%fusion.1 = f32[8] fusion(%p)",
                b"jit(serve_decode)/layer/attn/absorb/dot_general:", 11) \
        + event(2, b"%fusion.1 = f32[8] fusion(%p)",
                b"jit(serve_prefill)/layer/moe/mul:", 22) \
        + event(3, b"%copy.2 = f32[8] copy(%p)", b"", 11)
    other = _field(2, b"/host:CPU") + event(9, b"python", b"x/y:", 1)
    raw = _field(1, plane) + _field(1, other)
    assert dt.op_names(raw) == {"/device:TPU:0": {
        ("11", "%fusion.1 = f32[8] fusion(%p)"):
            "jit(serve_decode)/layer/attn/absorb/dot_general",
        ("22", "%fusion.1 = f32[8] fusion(%p)"):
            "jit(serve_prefill)/layer/moe/mul"}}


def test_labels_and_kinds_are_the_benchmarks():
    text = ('%ds_moe_down.12 = bf16[8,16]{1,0} custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call"')
    assert dt.label_of(text) == "ds_moe_down.12 custom-call:tpu_custom_call"
    assert dt.kind_of(dt.label_of(text)) == "kernel:ds_moe_down"
    assert dt.kind_of("ds_moe_up_relu2.3 custom-call:tpu_custom_call") \
        == "kernel:ds_moe_up_relu2"
    assert dt.kind_of("all-reduce-start.1 all-reduce-start") == "collective"
    assert dt.kind_of("copy-done.8 copy-done") == "copy"
    assert dt.kind_of("slice-done.4 async-done") == "xla"
    assert dt.op_family("convert_reduce_fusion.12 fusion") \
        == "convert_reduce_fusion fusion"
    assert dt.program_of("jit_serve_decode(6601560532864832973)") \
        == "serve_decode"


# -- the no-chip half: scopes of a compiled program's text ------------------

def test_scope_path_strips_what_jax_adds():
    path = hlo.scope_path
    assert path("jit(f)/transpose(jvp())/while/body/closed_call/layer/layer/"
                "checkpoint/rematted_computation/attn/tanh") == "layer/attn"
    assert path("jit(f)/jvp(embed)/dot_general") == "embed"
    assert path("jit(step)/transpose(jvp(lm_head))/add_any") == "lm_head"
    assert path("jit(<lambda>)/while/body/closed_call/layer/attn/"
                "btd,dke->btke/dot_general") == "layer/attn"
    assert path("jit(f)/layer/attn/jit(_where)/select_n") == "layer/attn"
    assert path("jit(f)/layer/moe/cond/branch_1_fun/mul") == "layer/moe"
    assert path("jit(<lambda>)/while/body/dynamic_slice") == ""
    assert path("p['attn']['q_w'][0]") == ""
    assert hlo.cut("layer/attn/absorb", 2) == "layer/attn"
    assert hlo.cut("", 2) == hlo.UNSCOPED
    assert hlo.cut("mixed:layer/attn/absorb+layer/attn/latent_q", 2) \
        == "layer/attn"
    assert hlo.cut("mixed:layer/attn+layer/mlp", 2) \
        == "mixed:layer/attn+layer/mlp"


@pytest.fixture(scope="module")
def scanned_text():
    def step(w, x):
        def loss(w, x):
            with jax.named_scope("embed"):
                h = x @ w["e"]

            def body(h, wl):
                @jax.checkpoint
                def block(h, wl):
                    with jax.named_scope("attn"):
                        h = jnp.tanh(h @ wl)
                    with jax.named_scope("mlp"):
                        return h + jnp.sin(h @ wl)
                with jax.named_scope("layer"):
                    return block(h, wl), None

            h, _ = jax.lax.scan(body, h, w["l"])
            with jax.named_scope("lm_head"):
                return jnp.sum(h * h)
        return jax.value_and_grad(loss)(w, x)

    w = {"e": jnp.ones((64, 64)), "l": jnp.ones((5, 64, 64))}
    return jax.jit(step).lower(w, jnp.ones((8, 64))).compile().as_text()


def test_scopes_of_two_named_scopes_a_scan_and_a_checkpoint(scanned_text):
    found = hlo.scopes(scanned_text)
    by_scope = {}
    for s in found:
        by_scope.setdefault(s.scope, []).append(s)
    assert {"embed", "lm_head", "layer/attn", "layer/mlp"} <= set(by_scope)
    # the scan's trip count is multiplied in, forward and backward
    assert {s.times for s in by_scope["layer/attn"]} == {5}
    assert {s.times for s in by_scope["embed"]} == {1}
    # forward (``jvp``, inside ``checkpoint``) and backward (``transpose``,
    # ``rematted_computation``) of a block lie under the same scope
    attn = [s.op_name for s in by_scope["layer/attn"]]
    assert any("transpose(" in n for n in attn)
    assert any("rematted_computation" in n for n in attn)
    assert any("transpose(" not in n for n in attn)
    # no wrapper is left in a scope
    for scope in by_scope:
        assert not set(scope.split("/")) & {
            "while", "body", "checkpoint", "closed_call", "jvp", "jit(step)"}
    # the loop's own slicing of what it scans over has no scope
    assert any(s.op_name.endswith(("dynamic_slice", "dynamic_update_slice"))
               for s in by_scope[""])


MIXED_TEXT = """HloModule jit_f

%fused_computation (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %mul.1 = f32[8]{0} multiply(%p0, %p1), metadata={op_name="jit(f)/layer/attn/mul"}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %p1), metadata={op_name="jit(f)/layer/mlp/add"}
}

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(%p0), metadata={op_name="jit(f)/layer/mlp/neg"}
  ROOT %exp.1 = f32[8]{0} exponential(%neg.1), metadata={op_name="jit(f)/layer/mlp/exp"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, backend_config={"estimated_cycles":"100"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%i, %fusion.2)
}

%cond (arg: (s32[], f32[8])) -> pred[] {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8], b: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = f32[8]{0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%a, %b), kind=kLoop, calls=%fused_computation, backend_config={"estimated_cycles":"700"}
  %copy.3 = f32[8]{0} copy(%fusion.1), backend_config={"estimated_cycles":"200"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{0}) tuple(%zero, %copy.3)
  %while.1 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8]{0} get-tuple-element(%while.1), index=1
}
"""


def test_a_fusion_without_metadata_takes_what_its_instructions_share():
    found = {s.instruction: s for s in hlo.scopes(MIXED_TEXT)}
    assert found["fusion.1"].scope == "mixed:layer/attn+layer/mlp"
    assert found["fusion.1"].cycles == 700
    # the body's fusion: both of its instructions are the mlp's; the TPU's
    # text states no trip count, the condition's one bound is read
    assert found["fusion.2"].scope == "layer/mlp"
    assert found["fusion.2"].times == 4
    assert found["copy.3"].scope == "" and found["copy.3"].op_name == ""
    # what a fusion holds is no operation of its own
    assert "mul.1" not in found and "neg.1" not in found
    assert hlo.scope_cycles(MIXED_TEXT) == {
        "mixed:layer/attn+layer/mlp": 700, "(unscoped)": 200,
        "layer/mlp": 400}
    assert hlo.scope_cycles(MIXED_TEXT, depth=1) == {
        "layer": 1100, "(unscoped)": 200}


def test_less_metadata_keeps_the_instructions_alone():
    bare = hlo.less_metadata(MIXED_TEXT)
    assert "metadata" not in bare and "op_name" not in bare
    assert "%mul.1 = f32[8]{0} multiply(%p0, %p1)\n" in bare
    renamed = MIXED_TEXT.replace("layer/attn", "layer/other")
    assert renamed != MIXED_TEXT
    assert hlo.less_metadata(renamed) == bare


# -- a capture taken here: the spans are in the file, telemetry off ---------

def test_a_capture_around_a_toy_engine_holds_the_loops_spans(tmp_path):
    from deepspeed_tpu.inference import ServeEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    model = GPT2Model(GPT2Config(vocab_size=128, n_positions=64, d_model=32,
                                 n_layer=2, n_head=4, remat=None,
                                 attn_impl="dense"))
    eng = ServeEngine(model, {"serving": {
        "slots": 2, "page_len": 4, "max_seq_len": 32, "prefill_len": 8,
        "prefix_cache": False}})
    assert eng.telemetry is None
    try:
        eng.submit(list(range(1, 6)), max_new_tokens=12)
        eng.step()                              # compiles, outside the capture
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("test/window"):
                for _ in range(3):
                    eng.step()
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    planes = dt.read(str(tmp_path), window="test/window")
    names = [e[0] for line in planes["/host:CPU"].values() for e in line]
    assert names.count("test/window") == 1
    assert names.count("serve/tick") == 3
    assert names.count("serve/decode_dispatch") == 3
    # nothing but the program's span families and the window is kept
    assert all(n == "test/window" or n.startswith(dt.SPAN_FAMILIES)
               for n in names)
    (window,) = [e for line in planes["/host:CPU"].values() for e in line
                 if e[0] == "test/window"]
    ticks = [e for line in planes["/host:CPU"].values() for e in line
             if e[0] == "serve/tick"]
    assert all(window[1] <= s and s + d <= window[1] + window[2]
               for _, s, d in ticks)
    # the CPU's capture holds no device plane to reduce
    assert not [p for p in planes if dt.DEVICE_PLANE.match(p)]
    assert dt.op_names(open(dt.newest_xplane(str(tmp_path)), "rb").read()) \
        == {}


# -- the door ----------------------------------------------------------------

@pytest.fixture
def fixture_list(tmp_path):
    path = tmp_path / "events.json"
    path.write_text(json.dumps(_planes()))
    return str(path)


def test_the_cli_prints_the_three_tables(fixture_list, capsys):
    assert cli.main(["device", fixture_list, "--window",
                     "bench/traced_window"]) == 0
    out = capsys.readouterr().out
    assert "busy 0.0005 s on 2 chip(s), idle 52.000 %" in out
    assert "serve_decode x1, serve_prefill x1" in out
    assert "serve_decode  layer/attn  kernel:ds_latent_decode_attn" in out
    assert "serve_decode  copy.9 copy  (unscoped)" in out
    assert "(outside spans)" in out and "serve/token_pull" in out
    assert "serve/decode_dispatch -> its program's run: n 1" in out


def test_the_cli_json_parses_and_takes_by_and_depth(fixture_list, capsys):
    assert cli.main(["device", fixture_list, "--window", "bench/traced_window",
                     "--by", "op", "--depth", "3", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["by"] == "op" and doc["devices"] == 2
    keys = [tuple(r["key"]) for r in doc["device_scope_seconds"]]
    assert ("fusion fusion", "layer/attn/absorb") in keys
    assert doc["busy_s"] == pytest.approx(480e-6)
    assert sum(v["seconds"] for v in doc["device_idle_seconds"].values()) \
        == pytest.approx(420e-6)


def test_the_cli_says_what_it_cannot_open(tmp_path, capsys):
    assert cli.main(["device", str(tmp_path)]) == 2
    assert "no .xplane.pb" in capsys.readouterr().err
