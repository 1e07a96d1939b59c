"""Hidden dropout's masks come from a counter hash of the site's key and
the element's global position (``ops/dropout.py``): the rate, the
independence of the streams the models really derive, what a disabled
site traces, the mesh, and recomputation."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.models.bert import BertConfig, BertModel
from deepspeed_tpu.ops.dropout import (dropout, keep_mask, keep_threshold,
                                       traced_sites)
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.utils.hlo import RngFusion, rng_fusions

SHAPE = (16, 256, 1024)         # 2^22 elements
N = math.prod(SHAPE)
SIGMAS = 4.0
STEP_KEY = jax.random.fold_in(jax.random.PRNGKey(7), 11)  # engine: rng, step


def _mask(key, rate):
    return np.asarray(keep_mask(key, rate, SHAPE))


def _layer_keys(step_key, i):
    """(attention-output site, FFN-output site) of layer ``i``, as
    ``BertModel.encode`` and ``DeepSpeedTransformerLayer`` derive them."""
    _, r1, r2 = jax.random.split(jax.random.fold_in(step_key, i), 3)
    return r1, r2


def _within(share, expected):
    sd = math.sqrt(expected * (1.0 - expected) / N)
    assert abs(share - expected) <= SIGMAS * sd, (share, expected, sd)


@pytest.mark.parametrize("rate", [0.1, 0.5])
def test_kept_share_is_one_minus_rate(rate):
    _within(_mask(_layer_keys(STEP_KEY, 0)[0], rate).mean(), 1.0 - rate)


@pytest.mark.parametrize("rate,threshold", [(0.1, 429496730),
                                            (0.5, 2147483648),
                                            (1.0, 4294967295)])
def test_threshold_is_the_rounded_rate(rate, threshold):
    assert int(keep_threshold(rate)) == threshold == min(
        round(rate * 2 ** 32), 2 ** 32 - 1)


def _pairs():
    a1, a2 = _layer_keys(STEP_KEY, 0)
    b1, _ = _layer_keys(STEP_KEY, 1)
    next_step = jax.random.fold_in(jax.random.PRNGKey(7), 12)
    return {
        "two_sites_of_a_layer": (a1, a2),
        "two_layers": (a1, b1),
        "two_steps": (a1, _layer_keys(next_step, 0)[0]),
        "embedding_and_layer": (jax.random.fold_in(STEP_KEY, 997), a1),
    }


@pytest.mark.parametrize("rate", [0.1, 0.5])
@pytest.mark.parametrize("pair", ["two_sites_of_a_layer", "two_layers",
                                  "two_steps", "embedding_and_layer"])
def test_streams_are_independent(pair, rate):
    one, other = _pairs()[pair]
    agree = (_mask(one, rate) == _mask(other, rate)).mean()
    _within(agree, (1.0 - rate) ** 2 + rate ** 2)


@pytest.mark.parametrize("rate", [0.1, 0.5])
@pytest.mark.parametrize("axis", [1, 2], ids=["one_row", "one_column"])
def test_a_mask_is_independent_of_itself_shifted(axis, rate):
    mask = _mask(_layer_keys(STEP_KEY, 0)[1], rate)
    agree = (mask == np.roll(mask, 1, axis=axis)).mean()
    _within(agree, (1.0 - rate) ** 2 + rate ** 2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_mean_of_dropped_ones_is_one(dtype):
    rate = 0.1
    y = np.asarray(dropout(jnp.ones(SHAPE, dtype), rate, STEP_KEY),
                   np.float32)
    assert y.dtype == np.float32 and set(np.unique(y)) == {
        0.0, np.float32(jnp.asarray(1.0 / (1.0 - rate), dtype))}
    # sd of the mean: scale * sqrt(rate (1 - rate) / N); the scale itself
    # is rounded to the array's dtype (bf16: 1.109375 for 1.1111)
    rounding = abs(float(jnp.asarray(1 / 0.9, dtype)) * 0.9 - 1.0)
    assert abs(y.mean() - 1.0) <= rounding + SIGMAS * math.sqrt(
        rate / (1.0 - rate) / N)


@pytest.mark.parametrize("case", ["rate_zero", "no_key"])
def test_a_disabled_site_returns_its_input_and_traces_nothing(case):
    rate, key = {"rate_zero": (0.0, STEP_KEY), "no_key": (0.1, None)}[case]
    x = jnp.ones((4, 8))
    assert dropout(x, rate, key) is x
    jaxpr = jax.make_jaxpr(lambda x: dropout(x, rate, key))(x)
    assert not jaxpr.eqns


def test_the_mask_is_the_same_on_a_two_device_data_mesh():
    """[B, T, D] cut in two on ``data``: each half gets the rows of the
    whole array's mask, because a position is counted in the unsharded
    array."""
    mesh = build_mesh(dp=2, devices=jax.devices()[:2])
    x = jnp.ones((8, 32, 128), jnp.float32)
    whole = np.asarray(jax.jit(lambda x: dropout(x, 0.1, STEP_KEY))(x))
    placed = jax.device_put(x, NamedSharding(mesh, P("data")))
    cut = jax.jit(lambda x: dropout(x, 0.1, STEP_KEY))(placed)
    assert cut.sharding.spec[0] == "data"
    halves = [np.asarray(s.data) for s in sorted(
        cut.addressable_shards, key=lambda s: s.index[0].start)]
    assert [h.shape for h in halves] == [(4, 32, 128)] * 2
    np.testing.assert_array_equal(np.concatenate(halves), whole)
    assert 0.85 < (whole != 0).mean() < 0.95


TINY = BertConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                  num_attention_heads=4, intermediate_size=64,
                  max_position_embeddings=32)


def _batch(rows=4, seq=16):
    r = np.random.default_rng(0)
    ids = r.integers(0, TINY.vocab_size, (rows, seq), dtype=np.int32)
    labels = np.where(r.random((rows, seq)) < 0.3, ids, -100).astype(np.int32)
    return {"input_ids": jnp.asarray(ids),
            "attention_mask": jnp.ones((rows, seq), jnp.int32),
            "token_type_ids": jnp.zeros((rows, seq), jnp.int32),
            "labels": jnp.asarray(labels),
            "next_sentence_label": jnp.zeros((rows,), jnp.int32)}


def test_the_recomputed_forward_regenerates_the_first_mask_bit_for_bit():
    """Under ``jax.checkpoint`` the backward runs the forward again: the
    gradient of a dropped product is mask * scale * weight, so one bit of
    difference between the two masks would show."""
    x = jnp.linspace(0.5, 1.5, 64 * 256).reshape(64, 256)
    w = jnp.linspace(-1.0, 1.0, 64 * 256).reshape(64, 256)

    def loss(x, key):
        return jnp.sum(dropout(x * 3.0, 0.1, key) * w)

    plain = jax.jit(jax.grad(loss))(x, STEP_KEY)
    again = jax.jit(jax.grad(jax.checkpoint(loss)))(x, STEP_KEY)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(again))
    kept = np.asarray(keep_mask(STEP_KEY, 0.1, x.shape))
    np.testing.assert_array_equal(np.asarray(plain) != 0, kept & (
        np.asarray(w) != 0))


def test_bert_gradients_are_the_same_with_and_without_recomputation():
    """``remat='block'`` against ``remat=None`` on two layers: the two
    programs fuse their float32 sums differently (differences in the last
    place), while one flipped mask element of 2,048 a site would move a
    gradient by a thousandth of its size."""
    grads = []
    for remat in ("block", None):
        model = BertModel(dataclasses.replace(TINY, remat=remat))
        params = model.init(jax.random.PRNGKey(1))
        grads.append(jax.jit(jax.grad(
            lambda p: model.loss_fn(p, _batch(), STEP_KEY, train=True)))(
                params))
    for a, b in zip(*map(jax.tree.leaves, grads)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=2e-6 * np.abs(b).max())
    # and dropout is on: the loss differs from the one without it
    model = BertModel(TINY)
    params = model.init(jax.random.PRNGKey(1))
    assert float(model.loss_fn(params, _batch(), STEP_KEY, train=True)) != \
        float(model.loss_fn(params, _batch(), STEP_KEY, train=False))


@pytest.mark.parametrize("remat", ["block", None])
@pytest.mark.parametrize("scan_layers", [True, False])
def test_sites_are_counted_once_a_layer(scan_layers, remat):
    """Embedding + two sites a layer: 5 for two layers (49 for
    BERT-large's 24), scanned or unrolled, recomputed or not; 0 in eval,
    and the gradient's program is not what is counted."""
    model = BertModel(dataclasses.replace(TINY, scan_layers=scan_layers,
                                          remat=remat))
    params = model.init(jax.random.PRNGKey(1))
    for train, sites in ((True, 1 + 2 * TINY.num_hidden_layers), (False, 0)):
        forward = jax.make_jaxpr(lambda p: model.loss_fn(
            p, _batch(), STEP_KEY, train=train))(params)
        assert traced_sites(forward.jaxpr) == sites


def test_the_engine_sets_the_gauge_while_it_traces_the_step(tmp_path):
    import deepspeed_tpu
    model = BertModel(TINY)
    mesh = build_mesh(dp=1, devices=jax.devices()[:1])
    engine, *_ = deepspeed_tpu.initialize(
        model=model, mesh=mesh, seed=0, config={
            "train_micro_batch_size_per_gpu": 4,
            "gradient_accumulation_steps": 2,
            "steps_per_print": 10 ** 9,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    try:
        batch = jax.tree.map(lambda a: np.concatenate([np.asarray(a)] * 2),
                             _batch())
        engine.train_batch(batch)
        # two micro-batches of a step, 5 sites each
        gauge = engine.telemetry.registry.gauge("train_dropout_sites")
        assert gauge.value(generator="hash") == 10
    finally:
        engine.close()


# ---------------------------------------------------------------------------
# utils/hlo.py::rng_fusions, on the CPU's text and on a written one
# ---------------------------------------------------------------------------
def _cpu_text(fn):
    x = jnp.ones((256, 1024), jnp.bfloat16)
    return jax.jit(fn).lower(x, STEP_KEY).compile().as_text()


@pytest.mark.parametrize("generator", ["threefry", "hash"])
def test_rng_fusions_tells_a_threefry_draw_from_the_hash(generator):
    """The CPU's compiler rolls threefry's twenty rotations into a loop of
    five with four in its body (the TPU's unrolls them into the fusion
    that reads the mask: ``tests/test_chip_training.py``), so here a body
    of four rounds is what is looked for; the hash has three shifts."""
    def drawn(x, key):
        keep = jax.random.bernoulli(key, 0.9, x.shape)
        return jnp.where(keep, x / 0.9, 0.0).astype(x.dtype)

    text = _cpu_text(drawn if generator == "threefry"
                     else lambda x, key: dropout(x, 0.1, key))
    found = rng_fusions(text, elements=256 * 1024, rounds=4)
    if generator == "hash":
        assert found == []
        return
    assert found and all(f.elements == 256 * 1024 and f.times == 5
                         and f.cycles is None for f in found)
    # what the key derivations of a step hold stays under the size
    assert rng_fusions(text, elements=256 * 1024 + 1, rounds=1) == []


def _written_program(rounds: int, elements: int, nested: bool) -> str:
    """A program in the TPU compiler's notation: a loop of 24 around a
    fusion of ``rounds`` shift-and-xor pairs over ``elements`` words."""
    array = f"u32[{elements}]{{0:T(1024)}}"
    rot = "\n".join(
        f"  %srl.{i} = {array} shift-right-logical(%p, %p)\n"
        f"  %xor.{i} = {array} xor(%srl.{i}, %p)" for i in range(rounds))
    inner = (f"%fused_computation.1 (p: {array}) -> {array} {{\n"
             f"  %p = {array} parameter(0)\n{rot}\n"
             f"  ROOT %out = {array} add(%xor.0, %p)\n}}\n")
    outer = (f"%fused_computation.2 (q: {array}) -> {array} {{\n"
             f"  %q = {array} parameter(0)\n"
             f"  ROOT %fusion.7 = {array} fusion(%q), kind=kLoop, "
             f"calls=%fused_computation.1\n}}\n")
    callee = "%fused_computation.2" if nested else "%fused_computation.1"
    return inner + outer + (
        f"%body (t: (s32[], {array})) -> (s32[], {array}) {{\n"
        f"  %t = (s32[], {array}) parameter(0)\n"
        f"  %x = {array} get-tuple-element(%t), index=1\n"
        f"  %convert_reduce_fusion.3 = {array} fusion(%x), kind=kOutput, "
        f"calls={callee}, backend_config={{\"window_config\":"
        f"{{\"estimated_cycles\":\"3030000\"}}}}\n"
        f"  %i = s32[] get-tuple-element(%t), index=0\n"
        f"  ROOT %r = (s32[], {array}) tuple(%i, %convert_reduce_fusion.3)\n"
        f"}}\n"
        f"%cond (t: (s32[], {array})) -> pred[] {{\n"
        f"  %t = (s32[], {array}) parameter(0)\n"
        f"  %i = s32[] get-tuple-element(%t), index=0\n"
        f"  %n = s32[] constant(24)\n"
        f"  ROOT %lt = pred[] compare(%i, %n), direction=LT\n}}\n"
        f"ENTRY %main (a: (s32[], {array})) -> (s32[], {array}) {{\n"
        f"  %a = (s32[], {array}) parameter(0)\n"
        f"  ROOT %while.1 = (s32[], {array}) while(%a), condition=%cond, "
        f"body=%body\n}}\n")


@pytest.mark.parametrize("rounds,elements,nested,listed", [
    (22, 1 << 24, False, True),     # threefry in a matmul's epilogue
    (22, 1 << 24, True, True),      # ... in a fusion nested in it
    (3, 1 << 24, False, False),     # a site's hash
    (22, 6, False, False),          # a key derivation
], ids=["threefry", "threefry_nested", "hash", "fold_in"])
def test_rng_fusions_on_a_written_program(rounds, elements, nested, listed):
    found = rng_fusions(_written_program(rounds, elements, nested))
    assert found == ([RngFusion("convert_reduce_fusion.3", elements, 24,
                                3030000)] if listed else [])
