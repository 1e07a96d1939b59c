"""The tick sent ahead (docs/serving.md "A tick", ``ServeEngine._run_ahead``):
while every slot is taken, decode tick n+1 goes onto the device's queue,
fed tick n's tokens as they lie there, before the host pulls and books
tick n.  Proved here by equality and by order, never by speed: the same
requests through an engine whose slots are full (ahead arm) and through
one with a free slot (synchronous arm) give the same streams, finish
reasons and ``kv_len``; and a log of the calls shows which order ran.
CPU, tiny widths, seeded weights.
"""
import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel
from deepspeed_tpu.models.olmoe import OlmoeConfig, OlmoeModel

#: the five methods the benchmark's stall watch wraps by name
#: (benchmark/lib/olmoe_family.py::_StallWatch.PHASES)
PHASES = ("_admit", "_decode_prepare", "_decode_dispatch", "_pull_tokens",
          "_emit_tokens")
FULL = 2            # slots of the engine that runs ahead: they fill
ROOMY = 8           # slots of the one that never fills: synchronous
PAGE = 4
MAX_LEN = 32


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


def _nemotron_h():
    return NemotronHModel(NemotronHConfig(
        vocab_size=128, hidden_size=32, hybrid_override_pattern="ME*",
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
        ssm_state_size=8, chunk_size=4, n_routed_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=16, moe_latent_size=16,
        moe_shared_expert_intermediate_size=16, max_position_embeddings=64,
        experts_held=(0, 8), attn_impl="dense"))


MODELS = {"gpt2": _gpt2, "olmoe": _olmoe, "nemotron_h": _nemotron_h}
_params, _engines = {}, {}


def _engine(family: str, slots: int, pages: int = 0):
    """One engine a (family, slots, pool), kept for the module: a case
    leaves it idle and its pool whole, as it found it."""
    key = (family, slots, pages)
    if key not in _engines:
        _engines[key] = _fresh_engine(family, slots, pages)
    return _engines[key]


def _fresh_engine(family: str, slots: int, pages: int = 0, **config):
    model = MODELS[family]()
    if family not in _params:
        _params[family] = model.init(jax.random.PRNGKey(0))
    return ServeEngine(model, {
        "serving": {"slots": slots, "page_len": PAGE, "pages": pages,
                    "max_seq_len": MAX_LEN, "prefill_len": 8,
                    "prefix_cache": False}, **config},
        params=_params[family])


@pytest.fixture(scope="module", autouse=True)
def _close_engines():
    yield
    for eng in _engines.values():
        eng.close()
    _engines.clear()
    _params.clear()


def _prompt(i: int, n: int = 3):
    return [int(t) for t in
            np.random.default_rng(100 + i).integers(1, 128, n)]


def _serve(eng, requests):
    """Submit (prompt, max_new_tokens, eos_id) triples, serve them, and
    hand back what the caller may compare, plus the arm counts."""
    before = dict(eng.ahead_stats)
    reqs = [eng.submit(p, max_new_tokens=n, eos_id=eos)
            for p, n, eos in requests]
    eng.run_until_idle()
    assert eng._inflight is None
    assert not eng.pool.refs and eng.pool.free_count == eng.pool.pages - 1
    stats = {k: eng.ahead_stats[k] - before[k] for k in before}
    # a finished request keeps the kv_len it ended with
    return ([(list(r.tokens), r.finish_reason, r.kv_len) for r in reqs],
            stats)


def _length(family):
    # more requests than the full engine has slots, of unequal lengths:
    # every finish is predicted, and each freed slot's admission lands
    # between two ticks sent ahead: the second of them goes BEHIND the
    # admission's prefill, its first token merged on the device
    return dict(requests=[(_prompt(i), n, None)
                          for i, n in enumerate((9, 4, 6, 2, 7))],
                behind=3)


def _eos_first(family):
    # the third request's eos is the token its prefill emits: the tick
    # sent behind that prefill has run its row by the time the host sees
    # the token, and the slot it leaves takes the fourth request in the
    # same step, in the old order (one tick behind a prefill a step)
    streams, _ = _serve(_engine(family, ROOMY), [(_prompt(2), 2, None)])
    return dict(requests=[(_prompt(0), 9, None), (_prompt(1), 3, None),
                          (_prompt(2), 9, streams[0][0][0]),
                          (_prompt(3), 4, None)],
                wasted=1, behind=1,
                reasons=["length", "length", "eos", "length"])


def _eos_late(family):
    # a token that one stream emits for the first time some ticks in
    # becomes that request's eos: the full engine finds it at retirement,
    # one row after it sent that slot ahead again
    streams, _ = _serve(_engine(family, ROOMY),
                        [(_prompt(i), 9, None) for i in range(8)])
    i, eos = next((i, t[j]) for i, (t, _, _) in enumerate(streams)
                  for j in range(2, 7) if t[j] not in t[:j])
    return dict(requests=[(_prompt(i), 9, eos), (_prompt(i + 1), 9, None),
                          (_prompt(i + 2), 5, None)],
                wasted=1, reasons=["eos", "length", "length"])


def _page_boundary(family):
    # prompts of 3 on pages of 4: the first tick sent ahead crosses a
    # page, and every fourth one after it
    return dict(requests=[(_prompt(i), 14, None) for i in range(2)])


def _seq_capacity(family):
    # runs into max_seq_len: kv_capacity by count, predicted like length
    return dict(requests=[(_prompt(0), 64, None), (_prompt(1), 40, None)],
                reasons=["kv_capacity", "kv_capacity"])


def _dry_pool(family):
    # five pages for two requests that want four each: the third page of
    # slot 1 is asked for on a tick sent ahead and the pool is dry; it
    # sits that tick out and ends as the synchronous engine ends it
    return dict(requests=[(_prompt(i), 12, None) for i in range(2)],
                pages=1 + 5, roomy=FULL + 1,
                reasons=["length", "kv_capacity"])


CASES = [(f, c) for c in (_length, _eos_late, _page_boundary, _eos_first)
         for f in MODELS] + [("gpt2", _seq_capacity), ("gpt2", _dry_pool)]


@pytest.mark.parametrize(
    "family,case", CASES, ids=[f"{f}-{c.__name__[1:]}" for f, c in CASES])
def test_ahead_and_synchronous_engines_emit_the_same(family, case):
    spec = case(family)
    pages = spec.get("pages", 0)
    full = _engine(family, FULL, pages)
    roomy = _engine(family, spec.get("roomy", ROOMY), pages)
    ahead, a_stats = _serve(full, spec["requests"])
    sync, s_stats = _serve(roomy, spec["requests"])
    assert ahead == sync
    if "reasons" in spec:
        assert [r for _, r, _ in ahead] == spec["reasons"]
    # the arms that ran: the full engine sent ticks ahead, the other none
    assert a_stats["ahead"] > 0
    assert s_stats["ahead"] == 0 and s_stats["sync"] > 0
    assert a_stats["wasted_rows"] == spec.get("wasted", 0)
    assert s_stats["wasted_rows"] == 0
    # ticks sent behind the prefill that took the last slot
    if "behind" in spec:
        assert a_stats["behind"] == spec["behind"]
    assert s_stats["behind"] == 0
    for eng in (full, roomy):
        assert eng._decode_fn._cache_size() == 1
        assert eng._feed_fn._cache_size() == 1


def _full_engine_mid_flight(**config):
    """A fresh full engine stepped until a tick is in flight."""
    eng = _fresh_engine("gpt2", FULL, **config)
    reqs = [eng.submit(_prompt(i), max_new_tokens=12) for i in range(FULL)]
    eng.step()
    eng.step()
    assert eng._inflight is not None
    return eng, reqs


@pytest.mark.parametrize("how", ["run_until_idle", "close", "poison"])
def test_a_tick_in_flight_is_retired_from_outside_the_tick(how):
    eng, reqs = _full_engine_mid_flight()
    booked = [len(r.tokens) for r in reqs]
    if how == "run_until_idle":
        eng.run_until_idle()
        assert [len(r.tokens) for r in reqs] == [12, 12]
    elif how == "close":
        eng.close()                 # does not hang on the queued tick
        # the tokens the device had made are booked, and nothing more
        assert [len(r.tokens) for r in reqs] == [n + 1 for n in booked]
    else:
        err = RuntimeError("boom")
        eng._poison(err)
        assert all(r.error is err and r.done.is_set() for r in reqs)
        assert [len(r.tokens) for r in reqs] == booked
        assert not eng.pool.refs
    assert eng._inflight is None
    eng.close()


@pytest.mark.parametrize("how", ["export_pages", "adopt_request"])
def test_migration_with_a_tick_in_flight(how):
    """A migration source ends while the engine is full, and a migrated
    request is adopted into the slot it freed: both with a tick in
    flight, both as a synchronous engine does them.  (Fresh engines:
    whole pages are compared, dead rows and all.)"""
    roomy = _fresh_engine("gpt2", ROOMY)
    want = roomy.submit(_prompt(7), max_new_tokens=3, detach_kv=True)
    whole = roomy.submit(_prompt(7), max_new_tokens=6)
    first = roomy.submit(_prompt(7), max_new_tokens=1, detach_kv=True)
    roomy.run_until_idle()
    pages = roomy.export_pages(want)
    eng = _fresh_engine("gpt2", FULL)
    got = eng.submit(_prompt(7), max_new_tokens=3, detach_kv=True)
    other = eng.submit(_prompt(8), max_new_tokens=12)
    while not got.done.is_set():
        eng.step()
    assert eng._inflight is not None and not other.done.is_set()
    n = len(other.tokens)
    if how == "export_pages":
        assert eng.export_pages(got) == pages
    else:
        adopted = eng.adopt_request(_prompt(7), first.tokens[0], 6, None,
                                    roomy.export_pages(first))
    # either one retired the tick in flight first
    assert eng._inflight is None and len(other.tokens) == n + 1
    assert got.tokens == want.tokens
    eng.run_until_idle()
    if how == "adopt_request":
        assert adopted.tokens == whole.tokens
        assert eng.ahead_stats["ahead"] > 2     # and it ran ahead again
    for e, r in ((eng, got), (roomy, want), (roomy, first)):
        e.release_detached(r)
    for e in (eng, roomy):
        assert not e.pool.refs
        e.close()


def test_the_order_of_dispatch_and_pull_follows_the_slots(tmp_path):
    """In the manner of tests/test_prefetch.py: the calls are logged, not
    timed.  Full slots: the dispatch of tick n+1 precedes the pull of
    tick n.  A free slot: it follows the emit of tick n.  The five methods
    the benchmark wraps are reached through the instance, so a ``setattr``
    stand-in sees every call; the counters agree with the log."""
    eng, reqs = _full_engine_mid_flight(
        telemetry={"enabled": True, "output_path": str(tmp_path)})
    eng.run_until_idle()
    base = dict(eng.ahead_stats)
    log = []
    serial = {"_decode_dispatch": 0, "_pull_tokens": 0, "_emit_tokens": 0}

    def stand_in(name, fn):
        def call(*a, **k):
            if name in serial:
                log.append((name, serial[name]))
                serial[name] += 1
            else:
                log.append((name, None))
            return fn(*a, **k)
        return call

    for name in PHASES:
        assert callable(getattr(ServeEngine, name))
        setattr(eng, name, stand_in(name, getattr(eng, name)))
    program = eng._decode_fn
    calls = []

    def logged_program(*a):
        calls.append(len(log))
        return program(*a)

    eng._decode_fn = logged_program

    def order(a, b):
        return log.index(a) < log.index(b)

    # full: 2 requests in 2 slots, 6 tokens each (5 decode ticks)
    for i in range(FULL):
        eng.submit(_prompt(i), max_new_tokens=6)
    eng.run_until_idle()
    n_full = serial["_decode_dispatch"]
    assert n_full == 5
    for n in range(n_full - 1):
        assert order(("_decode_dispatch", n + 1), ("_pull_tokens", n))
        assert order(("_pull_tokens", n), ("_emit_tokens", n))
    full_stats = {k: eng.ahead_stats[k] - base[k] for k in base}
    assert full_stats == {"ahead": 4, "behind": 0, "sync": 1,
                          "wasted_rows": 0}

    # a free slot: 1 request in 2 slots
    eng.submit(_prompt(3), max_new_tokens=6)
    eng.run_until_idle()
    assert serial["_decode_dispatch"] == n_full + 5
    for n in range(n_full, n_full + 4):
        assert order(("_emit_tokens", n), ("_decode_dispatch", n + 1))
    assert eng.ahead_stats["ahead"] - base["ahead"] == 4
    assert eng.ahead_stats["sync"] - base["sync"] == 1 + 5

    # every phase went through the instance's attribute, the program was
    # called once a dispatch, and pulls and emits pair off with dispatches
    assert {name for name, _ in log} == set(PHASES)
    assert len(calls) == serial["_decode_dispatch"] \
        == serial["_pull_tokens"] == serial["_emit_tokens"]
    reg = eng.telemetry.registry
    ticks = reg.counter("serve_ticks_total")
    assert ticks.value(arm="ahead") == eng.ahead_stats["ahead"]
    assert ticks.value(arm="sync") == eng.ahead_stats["sync"]
    assert reg.counter("serve_ahead_wasted_rows_total").value() == 0
    for name in PHASES:
        delattr(eng, name)
    eng._decode_fn = program
    eng.close()
