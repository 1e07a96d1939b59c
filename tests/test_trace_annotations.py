"""The program's spans on the profiler's clock (docs/observability.md,
"Span naming"): every ``tracing.span`` is a ``jax.profiler``
annotation, with telemetry on or off, so an xplane session shows the
serve tick's phases and the train step's spans beside the device trace;
jitted programs carry one stable name each.

All the profiler cases live in THIS file and share ONE session (the
module-scoped ``session`` fixture): a process takes one profiler
session at a time and the suite runs ``--dist loadfile``.
"""
import glob
import inspect
import json
import os
import re

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.telemetry import tracing

from simple_model import SimpleModel, base_config

TINY = GPT2Config(vocab_size=128, n_positions=64, d_model=32, n_layer=2,
                  n_head=4, remat=None, attn_impl="dense")
SLOTS = 4
HIDDEN = 16
#: a decode-only tick, in order (``serve/decode_step`` holds the
#: dispatch and the pull, as it always has)
TICK_TREE = [(1, "serve/admit"), (1, "serve/decode_prep"),
             (1, "serve/decode_step"), (2, "serve/decode_dispatch"),
             (2, "serve/token_pull"), (1, "serve/emit")]
#: the same spans while every slot is taken: the NEXT tick's prep and
#: dispatch go before the pull of the one in flight, inside its span
AHEAD_TREE = [(1, "serve/admit"), (1, "serve/decode_step"),
              (2, "serve/decode_prep"), (2, "serve/decode_dispatch"),
              (2, "serve/token_pull"), (1, "serve/emit")]


def _serve_engine(tmp, telemetry: bool) -> ServeEngine:
    cfg = {"serving": {"slots": SLOTS, "max_seq_len": 64,
                       "prefill_len": 16, "page_len": 8}}
    if telemetry:
        cfg["telemetry"] = {"enabled": True, "output_path": str(tmp)}
    return ServeEngine(GPT2Model(TINY), cfg)


def _train_engine():
    import deepspeed_tpu
    cfg = base_config(micro_bs=2, grad_acc=1, stage=0)
    cfg["steps_per_print"] = 1          # every step reports: a sync each
    eng, *_ = deepspeed_tpu.initialize(model=SimpleModel(hidden_dim=HIDDEN),
                                       config=cfg)
    return eng


def _batch(eng, seed=0):
    x = np.random.default_rng(seed).standard_normal(
        (int(eng.train_batch_size), HIDDEN)).astype(np.float32)
    return (x, 0.5 * x)


def _submit(eng, n, seed=0):
    rng = np.random.default_rng(seed)
    return [eng.submit(list(rng.integers(1, 128, 5)), max_new_tokens=40)
            for _ in range(n)]


def _tree(events, lo, hi, prefix):
    """Pre-order [(depth, name)] of the ``prefix`` events inside
    [lo, hi], nesting by time containment; depth 0 is the outermost."""
    evs = sorted((e for e in events if e[0].startswith(prefix)
                  and lo <= e[1] and e[1] + e[2] <= hi),
                 key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for name, start, dur in evs:
        while stack and start >= stack[-1]:
            stack.pop()
        out.append((len(stack), name))
        stack.append(start + dur)
    return out


def _split_ticks(tree):
    """[(depth, name)] -> one child list per ``serve/tick``."""
    ticks = []
    for depth, name in tree:
        if name == "serve/tick":
            ticks.append([])
        elif ticks:
            ticks[-1].append((depth, name))
    return ticks


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """Three decode-only ticks of a toy ServeEngine with telemetry off,
    two train_batch calls, three ticks with telemetry on — inside ONE
    xplane session, each under a ``test/...`` marker annotation."""
    out = tmp_path_factory.mktemp("trace_annotations")
    off = _serve_engine(out / "unused", telemetry=False)
    on = _serve_engine(out / "tel", telemetry=True)
    train = _train_engine()
    # compile and admit outside the session: the traced ticks decode only
    for eng in (off, on):
        _submit(eng, 2)
        eng.step()
        eng.step()
    for i in range(2):
        train.train_batch(_batch(train, seed=i))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # annotations only: small and quick
    jax.profiler.start_trace(str(out / "xplane"), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test/serve_off"):
            for _ in range(3):
                off.step()
        with jax.profiler.TraceAnnotation("test/train"):
            for i in range(2):
                train.train_batch(_batch(train, seed=10 + i))
        with jax.profiler.TraceAnnotation("test/serve_on"):
            for _ in range(3):
                on.step()
    finally:
        jax.profiler.stop_trace()
    on.close()                          # writes trace.json
    path = glob.glob(str(out / "xplane" / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    lines = [[(e.name, int(e.start_ns), int(e.duration_ns))
              for e in line.events] for line in host.lines]
    # the thread that ran the engines carries the markers
    events = next(ln for ln in lines
                  if any(n == "test/serve_off" for n, _, _ in ln))
    windows = {n: (s, s + d) for n, s, d in events if n.startswith("test/")}
    with open(out / "tel" / "trace.json") as f:
        trace_json = json.load(f)
    yield {"events": events, "windows": windows, "trace_json": trace_json,
           "off": off, "train": train}
    off.close()
    train.close()


def test_tick_phases_are_annotations_with_telemetry_off(session):
    lo, hi = session["windows"]["test/serve_off"]
    ticks = _split_ticks(_tree(session["events"], lo, hi, "serve/"))
    assert len(ticks) == 3
    for children in ticks:
        assert children == TICK_TREE


def test_train_spans_are_annotations_with_telemetry_off(session):
    lo, hi = session["windows"]["test/train"]
    names = [n for _, n in _tree(session["events"], lo, hi, "train/")]
    assert names.count("train/shard_batch") == 2
    assert names.count("train/dispatch") == 2
    # every step reports here: the report and the metrics pull inside it
    assert names.count("train/sync") == 4
    per_step = names[:len(names) // 2]
    assert per_step == ["train/shard_batch", "train/dispatch",
                        "train/sync", "train/sync"]


def test_trace_json_and_xplane_hold_the_same_spans(session):
    """Telemetry on: the recorder's complete events and the profiler's
    annotations are one set of spans, same names, same nesting."""
    lo, hi = session["windows"]["test/serve_on"]
    xplane = _split_ticks(_tree(session["events"], lo, hi, "serve/"))
    doc = session["trace_json"]
    assert doc["otherData"]["origin_unix_ns"] > 1.5e18
    spans = [(e["name"], e["ts"], e["dur"]) for e in doc["traceEvents"]
             if e["ph"] == "X" and e["name"].startswith("serve/")]
    recorded = _split_ticks(_tree(spans, 0, float("inf"), "serve/"))
    assert len(xplane) == 3
    assert recorded[-3:] == xplane == [TICK_TREE] * 3
    tick = [e for e in doc["traceEvents"] if e["name"] == "serve/tick"][-1]
    assert {"tick", "active", "queued", "pages_free", "produced",
            "admitted"} <= set(tick["args"])
    # the decode kernel's reach rides the prepare span: 2 requests, a
    # 5-token prompt and five ticks each, own two pages of 8 each; the
    # toy model decodes dense, so a block is a page, of 4 slots x 8
    prep = [e for e in doc["traceEvents"]
            if e["name"] == "serve/decode_prep"][-1]["args"]
    assert prep["active"] == 2
    assert prep["live_pages"] == 4
    assert prep["page_blocks"] == "4/32"


def _count_spans(monkeypatch, fn):
    """Spans entered by ``fn()``, counted at the one helper every span
    of both engines goes through."""
    names = []
    real = tracing.span

    def counting(tracer, name, *a, **k):
        names.append(name)
        return real(tracer, name, *a, **k)

    monkeypatch.setattr(tracing, "span", counting)
    fn()
    monkeypatch.setattr(tracing, "span", real)
    return names


def test_annotations_per_tick_do_not_grow_with_live_slots(session,
                                                          monkeypatch):
    """The budget: at most 8 annotations a decode-only tick with
    telemetry off, none of them per request — 2 live slots or all."""
    eng = session["off"]
    assert len(eng.scheduler.active) == 2
    few = _count_spans(monkeypatch, eng.step)
    _submit(eng, SLOTS - 2, seed=1)
    admitting = _count_spans(monkeypatch, eng.step)
    assert len(eng.scheduler.active) == SLOTS
    full = _count_spans(monkeypatch, eng.step)
    assert few == ["serve/tick"] + [n for _, n in TICK_TREE]
    assert full == ["serve/tick"] + [n for _, n in AHEAD_TREE]
    assert len(full) <= 8
    # three more for each admission (its prefill, cut into the wait for
    # what was queued ahead of it and its own run), and the step that
    # fills the slots sends two ticks: its own, and the first one ahead
    assert len(admitting) == len(full) + 3 * (SLOTS - 2) + 2
    assert admitting.count("serve/prefill") == SLOTS - 2 \
        == admitting.count("serve/prefill_wait") \
        == admitting.count("serve/prefill_run")


def test_annotations_per_train_step(session, monkeypatch):
    eng = session["train"]
    names = _count_spans(monkeypatch,
                         lambda: eng.train_batch(_batch(eng, seed=20)))
    assert len(names) <= 4, names
    eng.config.steps_per_print = 10 ** 9      # between reports: no sync
    names = _count_spans(monkeypatch,
                         lambda: eng.train_batch(_batch(eng, seed=21)))
    assert names == ["train/shard_batch", "train/dispatch"]


# ---------------------------------------------------------------------------
# program names
# ---------------------------------------------------------------------------

def test_serving_programs_have_distinct_stable_names(tmp_path):
    eng = ServeEngine(GPT2Model(TINY), {
        "serving": {"slots": SLOTS, "max_seq_len": 64, "prefill_len": 16,
                    "page_len": 8, "speculate_k": 2,
                    "draft": {"d_model": 32, "n_layer": 1, "n_head": 4}},
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    names = [fn.__name__ for fn in eng.programs()]
    assert len(set(names)) == len(names)
    assert all(n.startswith("serve_") for n in names)
    assert {"serve_decode", "serve_prefill", "serve_copy_page",
            "serve_verify", "serve_draft_propose"} <= set(names)
    assert eng.telemetry.compile_monitor.tracked_programs() == names
    # the profiler's XLA Modules line shows jit_<name>
    assert eng._decode_fn.__wrapped__.__name__ == "serve_decode"
    eng.close()


def test_step_builders_yield_distinct_names(session):
    """No two ``_build_*_step`` builders jit a function of the same
    name (``jit_sm`` twice, ``jit_train_step`` for the offload tier,
    were the accidents), read from the builders' own source; the live
    engine's ``track_program`` labels are those names."""
    jitted = {}
    for attr, fn in inspect.getmembers(DeepSpeedEngine, inspect.isfunction):
        if not re.fullmatch(r"_build_\w+_step", attr):
            continue
        src = inspect.getsource(fn)
        found = re.findall(r'jax\.jit\(\s*(?:_named\(f?"([\w{}]+)"|(\w+))',
                           src)
        assert len(found) == 1, (attr, found)
        jitted[attr] = found[0][0] or found[0][1]
    assert len(jitted) >= 8
    assert len(set(jitted.values())) == len(jitted), jitted
    assert all(re.match(r"(train|eval)_step", n) for n in jitted.values())
    eng = session["train"]
    names = [fn.__name__ for fn in eng.step_programs()]
    assert names == ["train_step", "eval_step"]
    assert jitted["_build_train_step"] == "train_step"
