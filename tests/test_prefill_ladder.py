"""The prefill ladder (docs/serving.md "The prefill ladder",
``inference/engine.py::prefill_ladder``): ``serve_prefill`` is built at
``serving.prefill_len``, at its half, below the half at ONE further half
whatever its length (the quarter) and past it at each further half of at
least 1,024 tokens; a call runs the smallest rung that holds its tokens
and is built.  Proved here by equality and by count, never
by speed: the same prompts through an engine with its ladder and through
the same engine held to its top rung give the same streams, ``kv_len``,
pages and (MiMo-V2) window rings; the counters say which rung ran; a rung
under 1,024 tokens below the half is built last and no call waits for it
while a longer one is built; a call waits only where no rung that holds
it is built; and once the ladder's thread is through, no length compiles
anything.  CPU, tiny widths, seeded weights.
"""
import threading
from concurrent.futures import wait

import jax
import numpy as np
import pytest
from jax import monitoring

from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.inference.engine import (LADDER_FLOOR, late_rungs,
                                            prefill_ladder)
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.models.mimo_v2 import MimoV2Config, MimoV2Model

TOP = 1024
LENGTHS = (5, 250, 256, 257, 512, 513, 600, 1024)
RUNG = {n: 256 if n <= 256 else 512 if n <= 512 else 1024 for n in LENGTHS}
CALLS = {r: sum(1 for n in LENGTHS if RUNG[n] == r) for r in (256, 512, 1024)}
NEW = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.mark.parametrize("prefill_len,ladder", [
    (32, (32,)), (128, (128,)), (256, (256,)), (512, (256, 512)),
    # a ladder of two rungs gets its quarter, whatever its length
    (1024, (256, 512, 1024)), (2048, (512, 1024, 2048)),
    (3072, (768, 1536, 3072)),
    # past the quarter a rung is built only from 1,024 tokens up
    (4096, (1024, 2048, 4096)), (8192, (1024, 2048, 4096, 8192)),
    (5120, (1280, 2560, 5120)),
    # a half is a rung only as a whole multiple of 256
    (768, (768,)), (1000, (1000,)), (1280, (1280,)), (1536, (768, 1536))])
def test_the_ladder_of_a_prefill_len(prefill_len, ladder):
    assert prefill_ladder(prefill_len) == ladder
    assert ladder[-1] == prefill_len
    assert all(r % 256 == 0 and 2 * r == up
               for r, up in zip(ladder, ladder[1:]))
    # below the quarter every rung is at least the floor long
    assert all(r >= LADDER_FLOOR == 1024 for r in ladder[:-3])
    # no rung is left out: the one under the lowest would be no multiple
    # of 256, or a fourth rung or further under the floor
    low = ladder[0]
    assert low % 512 or (len(ladder) > 2 and low // 2 < LADDER_FLOOR)
    # the rungs built last and never waited for: a quarter under the floor
    late = late_rungs(ladder)
    assert late == tuple(r for r in ladder[:-2] if r < 1024)
    assert late in ((), ladder[:1]) and (not late or len(ladder) == 3)


def _gpt2(top=TOP):
    return GPT2Model(GPT2Config(vocab_size=128, n_positions=top + 8,
                                d_model=32, n_layer=2, n_head=4, remat=None,
                                attn_impl="dense"))


def _mimo(top=TOP):
    return MimoV2Model(MimoV2Config(
        vocab_size=128, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=3,
        hybrid_layer_pattern=(0, 1, 0), moe_layer_freq=(0, 1, 1),
        num_attention_heads=4, num_key_value_heads=2, head_dim=24,
        v_head_dim=16, swa_num_attention_heads=4, swa_num_key_value_heads=2,
        swa_head_dim=24, swa_v_head_dim=16, sliding_window=8,
        n_routed_experts=8, num_experts_per_tok=2, experts_held=(0, 8),
        max_position_embeddings=top + 8, attn_impl="dense"))


MODELS = {"gpt2": _gpt2, "mimo_v2": _mimo}


def _engine(family, one_rung=False, serving=(), top=TOP, built=True,
            **config):
    """``built``: the ladder's thread is through before the first prompt,
    so that which rung a call runs does not hang on the thread's pace."""
    model = MODELS[family](top)
    eng = ServeEngine(model, {
        "serving": {"slots": 2, "page_len": 16, "max_seq_len": top + 8,
                    "prefill_len": top, "prefix_cache": False,
                    **dict(serving)}, **config},
        params=model.init(jax.random.PRNGKey(0)))
    if built:
        wait(eng._prefill_build.values())
    if one_rung:
        # the engine as it was before the ladder: held to its top rung
        # from here, in the test; the program has no such option
        eng.prefill_buckets = (top,)
        eng.prefill_calls = {top: 0}
    return eng


def _prompt(n):
    return [int(t) for t in np.random.default_rng(n).integers(1, 128, n)]


def _leaves(eng, req):
    """What the request holds on the device: its pages of each pool and,
    for a model with window state, its slot's rings."""
    out = {k: np.asarray(eng.cache[k])[:, np.asarray(req.pages)]
           for k in ("k", "v")}
    for name, leaf in eng.cache.get("state", {}).items():
        out[name] = np.asarray(leaf)[:, req.slot]
    return out


def _serve_one(eng, n):
    """One prompt of ``n`` tokens, alone: its stream, ``kv_len`` after its
    first step (the prefill and a tick) and at the end, and what it holds
    on the device after that step."""
    req = eng.submit(_prompt(n), max_new_tokens=NEW)
    eng.step()
    kv_after_step = req.kv_len
    leaves = _leaves(eng, req)
    eng.run_until_idle()
    return dict(tokens=list(req.tokens), reason=req.finish_reason,
                kv_len=(kv_after_step, req.kv_len), leaves=leaves)


@pytest.fixture(scope="module", params=sorted(MODELS))
def served(request):
    """Every length through the ladder and through the top rung alone."""
    family = request.param
    out = {}
    for one_rung in (False, True):
        eng = _engine(family, one_rung)
        try:
            out[one_rung] = {n: _serve_one(eng, n) for n in LENGTHS}
            out[one_rung]["calls"] = dict(eng.prefill_calls)
            out[one_rung]["jit_cache"] = eng._prefill_fn._cache_size()
            out[one_rung]["programs"] = [f.__name__ for f in eng.programs()]
        finally:
            eng.close()
    return out


@pytest.mark.parametrize("n", LENGTHS)
def test_a_rung_gives_what_the_top_rung_gives(served, n):
    got, want = served[False][n], served[True][n]
    assert got["tokens"] == want["tokens"] and len(got["tokens"]) == NEW
    assert got["reason"] == want["reason"] == "length"
    # the prompt's keys and the first tick's, one more a token after
    assert got["kv_len"] == want["kv_len"] == (n + 1, n + NEW - 1)
    assert sorted(got["leaves"]) == sorted(want["leaves"])
    for name, leaf in got["leaves"].items():
        # float32 on the CPU: the same sums in programs of two lengths
        np.testing.assert_allclose(leaf, want["leaves"][name], atol=2e-5,
                                   err_msg=name)


def test_the_rungs_are_one_program_under_one_name(served):
    assert served[False]["calls"] == CALLS == {256: 3, 512: 2, 1024: 3}
    assert served[True]["calls"] == {TOP: len(LENGTHS)}
    assert served[False]["programs"] == served[True]["programs"]
    assert served[False]["programs"].count("serve_prefill") == 1
    # the rungs are executables built ahead from the one jitted function,
    # whose own cache stays empty; held to one rung it is called as ever
    assert (served[False]["jit_cache"], served[True]["jit_cache"]) == (0, 1)


@pytest.mark.parametrize("prefill_len", [32, 128, 256])
def test_under_512_an_engine_builds_one_prefill_program(prefill_len):
    model = _gpt2()
    eng = ServeEngine(model, {"serving": {
        "slots": 2, "page_len": 16, "max_seq_len": TOP,
        "prefill_len": prefill_len}})
    try:
        assert eng.prefill_buckets == (prefill_len,)
        for n in (3, prefill_len):
            eng.submit(_prompt(n), max_new_tokens=2)
        eng.run_until_idle()
        assert eng.prefill_calls == {prefill_len: 2}
        assert eng.prefill_pad_tokens == prefill_len - 3
        assert eng._prefill_fn._cache_size() == 1
        assert eng._prefill_build == {}
    finally:
        eng.close()


def _build_order(eng):
    """The ladder's thread, once through: its futures and its ``lower``
    phases, each in the order they came."""
    return dict(order=list(eng._prefill_build),
                lowered=[phase for phase, _, _ in eng.setup_log
                         if phase.startswith("lower:")])


def _counted(tmp_path_factory, lengths, top):
    """A ladder engine with telemetry on: its construction, first prefill
    (the first of ``lengths``) and what is left of the ladder's thread,
    then every other length, with the backend compiles each step caused
    (``jax.monitoring``, what the benchmark's windows count)."""
    compiles = []

    def listen(event, duration, **kw):
        if event == COMPILE_EVENT:
            compiles.append(event)

    monitoring.register_event_duration_secs_listener(listen)
    eng = _engine("gpt2", top=top, telemetry={
        "enabled": True,
        "output_path": str(tmp_path_factory.mktemp("ladder_tel"))})
    rows = {}
    try:
        reg = eng.telemetry.registry
        before = 0
        for n in lengths:
            req = eng.submit(_prompt(n), max_new_tokens=NEW)
            eng.run_until_idle()
            wait(eng._prefill_build.values())
            eng.telemetry.compile_monitor.sample()
            rows[n] = dict(
                tokens=list(req.tokens),
                compiles=len(compiles) - before,
                recompiles=reg.counter("recompiles_total", "").value(
                    program="serve_prefill"),
                rungs_ready=sorted(r for r, built in
                                   eng._prefill_build.items()
                                   if built.exception() is None))
            before = len(compiles)
        rows["calls"] = dict(eng.prefill_calls)
        rows["tokens"] = (eng.prefill_tokens, eng.prefill_pad_tokens)
        rows["pad_counter"] = reg.counter(
            "serve_prefill_pad_tokens_total", "").value()
        rows["by_bucket"] = {
            r: reg.counter("serve_prefills_total", "").value(bucket=str(r))
            for r in eng.prefill_buckets}
        rows["buckets"] = eng.prefill_buckets
        rows.update(_build_order(eng))
        rows["pending"] = (
            eng.prefill_rung_pending,
            reg.counter("serve_prefill_rung_pending_total", "").value())
    finally:
        eng.close()
        monitoring.unregister_event_duration_listener(listen)
    return rows


@pytest.fixture(scope="module")
def counted(tmp_path_factory):
    return _counted(tmp_path_factory, LENGTHS, TOP)


def test_every_rung_is_built_ahead_from_construction_on(counted):
    first = counted[LENGTHS[0]]
    assert first["rungs_ready"] == [256, 512, 1024]
    # from construction to the first request's end: the three rungs and
    # the decode tick, at least
    assert first["compiles"] >= 4


@pytest.mark.parametrize("n", LENGTHS[1:])
def test_once_the_ladder_is_built_no_length_compiles(counted, n):
    assert counted[n]["compiles"] == 0
    assert counted[n]["recompiles"] == counted[LENGTHS[0]]["recompiles"] == 0


def test_the_counters_say_which_rung_ran(counted):
    assert counted["buckets"] == (256, 512, 1024)
    assert counted["calls"] == CALLS
    assert counted["by_bucket"] == {r: float(c) for r, c in CALLS.items()}
    wanted = sum(LENGTHS)
    pad = sum(RUNG[n] - n for n in LENGTHS)
    assert counted["tokens"] == (wanted, pad)
    assert counted["pad_counter"] == pad
    # every prompt found all three rungs built: none ran a longer rung
    # than it had to
    assert counted["pending"] == (0, 0.0)
    # against the one bucket: 8 x 1,024 less the tokens wanted; and the
    # three prompts of the quarter would have padded 256 more each at 512
    assert pad < len(LENGTHS) * TOP - wanted
    assert sum(max(RUNG[n], 512) - n for n in LENGTHS) - pad == 3 * 256


@pytest.mark.parametrize("family", sorted(MODELS))
def test_a_full_engines_admission_still_goes_behind_its_prefill(family):
    """More requests than slots, prompts of both rungs: each admission
    that takes the last slot sends the next tick behind its prefill,
    whichever rung that prefill ran (``_send_behind_prefill`` takes the
    token where the program left it), and the streams are the top
    rung's."""
    requests = [(_prompt(n), new) for n, new in
                ((300, 9), (20, 4), (700, 6), (512, 2), (513, 7))]
    out = {}
    for one_rung in (False, True):
        eng = _engine(family, one_rung)
        try:
            reqs = [eng.submit(p, max_new_tokens=new) for p, new in requests]
            eng.run_until_idle()
            assert eng._inflight is None and not eng.pool.refs
            out[one_rung] = (
                [(list(r.tokens), r.finish_reason, r.kv_len) for r in reqs],
                dict(eng.ahead_stats), dict(eng.prefill_calls))
        finally:
            eng.close()
    assert out[False][0] == out[True][0]
    assert out[False][1] == out[True][1] and out[False][1]["behind"] >= 2
    assert out[False][2] == {256: 1, 512: 2, 1024: 2}


ARMS = {
    # the slot cache: serve_prefill takes (length, slot) and no page row
    "slot_cache": dict(serving={"page_len": 0}),
    # a prefix hit: the second prompt's delta is its last 40 tokens
    "prefix_delta": dict(serving={"prefix_cache": True}),
    # tenant adapters: two more operands ahead of the slot's tail
    "lora": dict(serving={"lora": {"rank": 2, "alpha": 4.0,
                                   "max_adapters": 4,
                                   "hbm_adapter_slots": 2,
                                   "targets": ["qkv_w"]}}),
    # sampling: a key as the last operand
    "sampling": dict(serving={"temperature": 0.7}),
}


@pytest.mark.parametrize("arm", sorted(ARMS))
def test_every_admission_arm_takes_the_ladder(arm):
    """The rungs are built on the operands each arm hands
    ``serve_prefill`` (``_build_prefill_rungs``): a mismatch would fail
    the first prefill.  Same streams as the top rung alone, but under
    sampling, where a tie in float32 may fall the other way."""
    shared = _prompt(600)
    requests = [(_prompt(30), 0), (shared, 1), (shared[:560] + _prompt(40), 1),
                (_prompt(400), 2)]
    out = {}
    for one_rung in (False, True):
        eng = _engine("gpt2", one_rung, **ARMS[arm])
        try:
            reqs = [eng.submit(p, max_new_tokens=NEW,
                               **({"adapter_id": t} if arm == "lora" else {}))
                    for p, t in requests]
            eng.run_until_idle()
            assert all(r.finish_reason == "length" and r.error is None
                       for r in reqs)
            out[one_rung] = ([list(r.tokens) for r in reqs],
                             dict(eng.prefill_calls))
        finally:
            eng.close()
    if arm != "sampling":
        assert out[False][0] == out[True][0]
    # 30 and (a prefix hit's delta) 40 tokens run the quarter, 400 the half
    delta = arm == "prefix_delta"
    assert out[False][1] == {256: 2 if delta else 1, 512: 1,
                             1024: 1 if delta else 2}
    assert out[True][1] == {TOP: 4}


def test_a_chunk_runs_the_rung_that_holds_it():
    """Chunked prefill takes the ladder as is: a chunk of 64 tokens runs
    the lowest rung, not the whole ``prefill_len``; same stream."""
    out = {}
    for one_rung in (False, True):
        eng = _engine("gpt2", one_rung, serving={"prefill_chunk_len": 64})
        try:
            req = eng.submit(_prompt(200), max_new_tokens=NEW)
            eng.run_until_idle()
            out[one_rung] = (list(req.tokens), req.kv_len,
                             dict(eng.prefill_calls))
        finally:
            eng.close()
    assert out[False][:2] == out[True][:2]
    assert out[False][2] == {256: 4, 512: 0, 1024: 0}
    assert out[True][2] == {TOP: 4}


# -- a ladder of three rungs (prefill_len 4,096: the family cells' ladder) --

TOP3 = 4096
LENGTHS3 = (7, 1000, 1024, 1025, 2048, 2049, 3000, 4096)
RUNG3 = {7: 1024, 1000: 1024, 1024: 1024, 1025: 2048, 2048: 2048,
         2049: 4096, 3000: 4096, 4096: 4096}


@pytest.fixture(scope="module")
def three_rungs(tmp_path_factory):
    """Every length through a ladder of three rungs, counted as
    ``counted`` counts, and through the same engine held to its top
    rung."""
    rows = _counted(tmp_path_factory, LENGTHS3, TOP3)
    eng = _engine("gpt2", one_rung=True, top=TOP3)
    try:
        rows["top_rung_tokens"] = {}
        for n in LENGTHS3:
            req = eng.submit(_prompt(n), max_new_tokens=NEW)
            eng.run_until_idle()
            rows["top_rung_tokens"][n] = list(req.tokens)
        rows["top_rung_pad"] = eng.prefill_pad_tokens
    finally:
        eng.close()
    return rows


def test_three_rungs_are_built_ahead_from_construction_on(three_rungs):
    assert three_rungs["buckets"] == (1024, 2048, 4096)
    first = three_rungs[LENGTHS3[0]]
    assert first["rungs_ready"] == [1024, 2048, 4096]
    # three rungs and the decode tick, at least
    assert first["compiles"] >= 4


@pytest.mark.parametrize("n", LENGTHS3)
def test_a_length_runs_the_smallest_of_three_rungs_that_holds_it(
        three_rungs, n):
    got = three_rungs[n]
    assert got["tokens"] == three_rungs["top_rung_tokens"][n]
    assert len(got["tokens"]) == NEW
    assert got["recompiles"] == 0
    if n != LENGTHS3[0]:
        assert got["compiles"] == 0


def test_three_rungs_count_their_calls_and_their_padding(three_rungs):
    calls = {r: sum(1 for n in LENGTHS3 if RUNG3[n] == r)
             for r in (1024, 2048, 4096)}
    assert calls == {1024: 3, 2048: 2, 4096: 3}
    assert three_rungs["calls"] == calls
    assert three_rungs["by_bucket"] == {r: float(c) for r, c in calls.items()}
    wanted = sum(LENGTHS3)
    pad = sum(RUNG3[n] - n for n in LENGTHS3)
    assert three_rungs["tokens"] == (wanted, pad)
    assert three_rungs["pad_counter"] == pad
    # the two rungs of before (2,048 and 4,096) would have padded the
    # three shortest prompts by 1,024 tokens more each
    assert three_rungs["top_rung_pad"] == len(LENGTHS3) * TOP3 - wanted
    two = sum((2048 if n <= 2048 else 4096) - n for n in LENGTHS3)
    assert two - pad == 3 * 1024


# -- what a call waits for, and what it never waits for ---------------------

QUARTER, HALF = 256, 512


@pytest.fixture
def held_back(monkeypatch):
    """``held_back(rung)``: the ladder's thread stops before ``rung``
    until the gate it returns opens (one rung a test)."""
    gate = threading.Event()
    build = ServeEngine._build_prefill_rung

    def hold(rung):
        def held(self, r, *operands):
            if r == rung:
                assert gate.wait(120)
            return build(self, r, *operands)

        monkeypatch.setattr(ServeEngine, "_build_prefill_rung", held)
        return gate

    yield hold
    gate.set()


def _waits(eng):
    return [phase for phase, _, _ in eng.setup_log
            if phase.startswith("rungs_wait")]


@pytest.mark.parametrize("top,order", [
    (1024, [512, 1024, 256]), (2048, [1024, 2048, 512]),
    (4096, [1024, 2048, 4096])])
def test_the_threads_order_is_half_whole_quarter(top, order, counted,
                                                 three_rungs):
    """The rungs the ladder had are built first, ascending as ever; a
    quarter under 1,024 tokens last."""
    if top == 2048:
        eng = _engine("gpt2", top=top)
        try:
            rows = _build_order(eng)
        finally:
            eng.close()
    else:
        rows = counted if top == TOP else three_rungs
    assert rows["order"] == order
    assert rows["lowered"] == [f"lower:{r}" for r in order]
    ladder = prefill_ladder(top)
    assert sorted(order) == list(ladder)
    assert order[len(order) - len(late_rungs(ladder)):] == \
        list(late_rungs(ladder))


def test_a_call_that_fits_the_quarter_runs_the_half_while_it_is_building(
        held_back, tmp_path):
    """With the quarter's build held back, a call that fits it runs the
    half without waiting, is charged the half's padding and counts one
    ``prefill_rung_pending``; once the quarter is there the same prompt
    takes it, and gives the same stream."""
    gate = held_back(QUARTER)
    eng = _engine("gpt2", built=False, telemetry={
        "enabled": True, "output_path": str(tmp_path)})
    try:
        reg = eng.telemetry.registry
        wait([eng._prefill_build[HALF], eng._prefill_build[TOP]])
        assert not eng._prefill_build[QUARTER].done()
        first = eng.submit(_prompt(5), max_new_tokens=NEW)
        eng.run_until_idle()
        assert not eng._prefill_build[QUARTER].done() and _waits(eng) == []
        assert eng.prefill_calls == {QUARTER: 0, HALF: 1, TOP: 0}
        assert eng.prefill_rung_pending == 1
        assert (eng.prefill_tokens, eng.prefill_pad_tokens) == (5, HALF - 5)
        # a prompt only the half and the whole hold is not pending on it
        eng.submit(_prompt(300), max_new_tokens=NEW)
        eng.run_until_idle()
        assert eng.prefill_rung_pending == 1 and _waits(eng) == []

        gate.set()
        wait([eng._prefill_build[QUARTER]])
        again = eng.submit(_prompt(5), max_new_tokens=NEW)
        eng.run_until_idle()
        assert eng.prefill_calls == {QUARTER: 1, HALF: 2, TOP: 0}
        assert eng.prefill_rung_pending == 1 and _waits(eng) == []
        assert eng.prefill_pad_tokens == \
            (HALF - 5) + (HALF - 300) + (QUARTER - 5)
        assert list(again.tokens) == list(first.tokens)
        assert len(first.tokens) == NEW
        assert reg.counter(
            "serve_prefill_rung_pending_total", "").value() == 1
        assert {r: reg.counter("serve_prefills_total", "").value(
            bucket=str(r)) for r in (QUARTER, HALF)} == {QUARTER: 1.0,
                                                         HALF: 2.0}
        assert reg.counter("serve_prefill_pad_tokens_total",
                           "").value() == eng.prefill_pad_tokens
    finally:
        gate.set()
        eng.close()


def test_a_call_waits_only_when_no_rung_that_holds_it_is_built(held_back):
    """Nothing is built: a prompt that fits the quarter waits for the
    HALF, the first rung the thread reaches that holds it (never for the
    quarter, which is built last), runs it, and is counted pending."""
    gate = held_back(HALF)
    eng = _engine("gpt2", built=False)
    try:
        assert not any(b.done() for b in eng._prefill_build.values())
        threading.Timer(0.3, gate.set).start()
        req = eng.submit(_prompt(5), max_new_tokens=NEW)
        eng.run_until_idle()
        assert len(req.tokens) == NEW and req.finish_reason == "length"
        assert _waits(eng) == [f"rungs_wait:{HALF}"]
        assert eng.prefill_calls[HALF] == 1 and eng.prefill_calls[QUARTER] == 0
        assert eng.prefill_rung_pending == 1
    finally:
        gate.set()
        eng.close()


def test_a_call_of_the_half_returns_while_the_top_rung_is_building(
        held_back):
    gate = held_back(TOP)
    eng = _engine("gpt2", built=False)
    try:
        low = eng.submit(_prompt(5), max_new_tokens=NEW)
        eng.run_until_idle()
        assert len(low.tokens) == NEW and low.finish_reason == "length"
        assert eng._prefill_build[HALF].done()
        assert not eng._prefill_build[TOP].done()
        assert not eng._prefill_build[QUARTER].done()   # behind the top
        # a call of the top rung waits for that rung
        threading.Timer(0.3, gate.set).start()
        top = eng.submit(_prompt(600), max_new_tokens=NEW)
        eng.run_until_idle()
        assert len(top.tokens) == NEW and top.finish_reason == "length"
        waits = _waits(eng)
        assert waits.count(f"rungs_wait:{TOP}") == 1
        assert waits.count(f"rungs_wait:{HALF}") <= 1
        assert f"rungs_wait:{QUARTER}" not in waits
        assert eng.prefill_calls == {QUARTER: 0, HALF: 1, TOP: 1}
        assert eng.prefill_rung_pending == 1
    finally:
        gate.set()
        eng.close()


def test_close_waits_for_every_rung(held_back):
    gate = held_back(TOP)
    eng = _engine("gpt2", built=False)
    threading.Timer(0.3, gate.set).start()
    eng.close()
    assert all(built.done() for built in eng._prefill_build.values())
    assert sorted(eng._prefill_build) == [QUARTER, HALF, TOP]
